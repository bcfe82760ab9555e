"""p50 of the program's ``serve.snapshot.to_host`` span: the copy of the
N coded shards from the device to the host, in ms. Moves
``tokens_per_s``."""

import statistics


def read(ev):
    d = ev.span_durations("serve.snapshot.to_host")
    return statistics.median(d) * 1e3 if d else None
