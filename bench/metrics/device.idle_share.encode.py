"""Share of the traced window in which no operation ran on the device, in %,
averaged over the chips the cell uses."""


def read(ev):
    if ev.trace is None:
        return None
    return ev.trace.idle_share() * 100.0
