"""The whole encode call's share of the chip's peak, in %: the least time a
call can take on one chip (its packet read and its output written at the
HBM peak, or its words on the wire at the interconnect peak, whichever is
longer) over the device time of the call's program, mean over chips.
Bounds ``kernels.localop_roofline.encode`` from above as a step share: a
change that moves work out of the kernels still shows here. Moves
``encode_gbps``.

The call's program is the one that ran once per call in the traced window
(its name, today ``jit__lambda_``, is not stable)."""


def read(ev):
    calls = ev.facts.get("calls_traced")
    if ev.trace is None or not calls:
        return None
    names = ev.trace.module_counts()
    program = [n for n, c in names.items() if c == calls]
    if len(program) != 1:
        return None
    runs = ev.trace.module_runs(lambda n: n == program[0])
    per_call = sum(sum(r) for r in runs) / sum(len(r) for r in runs)
    words = ev.facts["packet_words"]
    t_hbm = 2 * words * 4 / ev.peaks["hbm_bytes_per_s"]
    t_ici = ev.facts["wire_words_per_word"] * words * 4 / ev.peaks["ici_bytes_per_s"]
    return max(t_hbm, t_ici) / per_call * 100.0
