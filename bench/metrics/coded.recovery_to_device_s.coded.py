"""The program's ``serve.recovery.to_device`` span of the window's
recovery (the first, as ``coded.recovery_s.coded`` reads the first): the
rebuilt shards copied to the device and unsharded into the engine's
state, in s. Moves ``tokens_per_s``."""


def read(ev):
    d = ev.span_durations("serve.recovery.to_device")
    return d[0] if d else None
