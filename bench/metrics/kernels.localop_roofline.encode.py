"""The LocalOp kernels' share of their roofline, in %: the words the IR's
LocalOps read and write per device per call (slots in + out, 4 bytes each)
over the HBM peak, against the device time of the LocalOp's Pallas kernels
per call, mean over chips. The work is counted from the IR, not from the
kernels' limbs. Moves ``encode_gbps``.

The kernels are the encode program's custom calls (Pallas), matched by the
names the trace prints today (see PERF.md)."""


def is_kernel(name: str) -> bool:
    op = name.rsplit("/", 1)[-1]
    return "gf_matmul" in op or "butterfly" in op or op.startswith("custom-call")


def read(ev):
    calls = ev.facts.get("calls_traced")
    if ev.trace is None or not calls:
        return None
    runs = ev.trace.op_runs(is_kernel)
    if not any(runs):
        return None
    per_call = sum(sum(r) for r in runs) / len(runs) / calls
    need = ev.facts["localop_words_per_word"] * ev.facts["packet_words"] * 4
    return need / ev.peaks["hbm_bytes_per_s"] / per_call * 100.0
