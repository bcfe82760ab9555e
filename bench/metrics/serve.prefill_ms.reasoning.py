"""p50 of the program's ``serve.prefill`` span: one request's prompt
through the compiled prefill of its bucket, up to its first token, in ms.
Moves ``tokens_per_s``."""

import statistics


def read(ev):
    d = ev.span_durations("serve.prefill")
    return statistics.median(d) * 1e3 if d else None
