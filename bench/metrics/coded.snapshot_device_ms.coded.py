"""p50 of the program's ``serve.snapshot.device`` span: the snapshot's
limbs and its device encode, up to the coded array being ready, in ms.
Moves ``tokens_per_s``."""

import statistics


def read(ev):
    d = ev.span_durations("serve.snapshot.device")
    return statistics.median(d) * 1e3 if d else None
