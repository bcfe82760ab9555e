"""p50 of the program's ``serve.snapshot.store`` span: handing the coded
shards to their hosts, in ms. Moves ``tokens_per_s``."""

import statistics


def read(ev):
    d = ev.span_durations("serve.snapshot.store")
    return statistics.median(d) * 1e3 if d else None
