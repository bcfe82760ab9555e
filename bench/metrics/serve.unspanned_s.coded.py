"""Seconds of the traced window in which no ``serve.*`` host span of the
program is open: the window less the union of the trace's ``serve.*``
host intervals (nested and overlapping spans counted once). Time the
serve loop spends outside every span it names. Moves ``tokens_per_s``."""

PREFIX = "serve."


def read(ev):
    if ev.trace is None:
        return None
    lo, hi = ev.trace.window
    spans = sorted((max(s, lo), min(e, hi)) for n, s, e in ev.trace.host
                   if n.startswith(PREFIX) and e > lo and s < hi)
    if not spans:
        return None
    covered, end = 0.0, lo
    for s, e in spans:
        s = max(s, end)
        if e > s:
            covered += e - s
            end = e
    return (hi - lo - covered) / 1e9
