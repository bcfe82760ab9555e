"""The decode tick's share of the HBM roofline, in %: the bytes the window's
ticks need (``bench/counts_mla_moe.chunk_bytes`` over the program's
``serve.decode_chunk`` spans: every weight once a tick, the held experts
included, and the latent cache rows the occupied slots attend, from the
span's ``ticks``, ``occupied`` and ``cache_rows``) at the chip's HBM peak,
over the device time of the ``jit_tick`` runs, per tick. Moves
``tokens_per_s``."""

from bench.counts_mla_moe import chunk_bytes

PROGRAM = "jit_tick"


def read(ev):
    if ev.trace is None or not ev.trace.devices:
        return None
    runs = ev.trace.module_runs(lambda n: n.split("(", 1)[0] == PROGRAM)[0]
    chunks = [a for n, _, a in ev.spans
              if n == "serve.decode_chunk" and "cache_rows" in a]
    if not runs or not chunks or "hbm_bytes_per_s" not in ev.peaks:
        return None
    slots = int(ev.traffic["pool"]["slots"])
    need = sum(chunk_bytes(ev.config, slots, a["ticks"], a["occupied"], a["cache_rows"])
               for a in chunks)
    ticks = sum(a["ticks"] for a in chunks)
    per_tick = sum(runs) / len(runs)
    return need / ticks / ev.peaks["hbm_bytes_per_s"] / per_tick * 100.0
