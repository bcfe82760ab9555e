"""The guard's own ``recovery_us`` of the window's recovery (reconstruct
from K shards and unshard, ending in ``block_until_ready``), in s. Moves
``tokens_per_s``."""


def read(ev):
    us = ev.facts.get("recovery_us") or []
    return us[0] / 1e6 if us else None
