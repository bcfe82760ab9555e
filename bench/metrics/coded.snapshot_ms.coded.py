"""p50 of the harness's host-clock span around ``CodedServeGuard.snapshot``
(limbs, encode, copy to the host, store), in ms. Moves ``tokens_per_s``."""

import statistics


def read(ev):
    d = ev.span_durations("bench.snapshot")
    return statistics.median(d) * 1e3 if d else None
