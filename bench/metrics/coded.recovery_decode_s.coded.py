"""The program's ``serve.recovery.decode`` span of the window's recovery
(the first, as ``coded.recovery_s.coded`` reads the first): the Lagrange
decode of K surviving shards on the host, in s. Moves ``tokens_per_s``."""


def read(ev):
    d = ev.span_durations("serve.recovery.decode")
    return d[0] if d else None
