"""Device time of the collective-permutes per encode call, mean over the
chips, in ms. Moves ``encode_gbps``."""

COLLECTIVE = "collective-permute"


def read(ev):
    if ev.trace is None or not ev.facts.get("calls_traced"):
        return None
    runs = ev.trace.op_runs(lambda n: n.rsplit("/", 1)[-1].startswith(COLLECTIVE))
    if not any(runs):
        return None
    per_dev = [sum(r) for r in runs]
    return sum(per_dev) / len(per_dev) / ev.facts["calls_traced"] * 1e3
