"""p50 of the device time of one run of the serving engine's decode tick
(the program ``jit_tick``) inside the traced window, on the cell's chip,
in ms. Moves ``tokens_per_s``."""

import statistics

PROGRAM = "jit_tick"


def read(ev):
    if ev.trace is None or not ev.trace.devices:
        return None
    runs = ev.trace.module_runs(lambda n: n.split("(", 1)[0] == PROGRAM)[0]
    return statistics.median(runs) * 1e3 if runs else None
