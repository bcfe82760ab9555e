"""How unevenly the routed pairs fall on the experts held here: the busiest
held expert's pairs over the mean held expert's pairs, each summed over
the window's ``serve.decode_chunk`` spans (their ``moe_pairs_max`` and
``moe_pairs`` attributes, read at the chunk's sync). 1 is even. Moves
``tokens_per_s``."""


def read(ev):
    chunks = [a for n, _, a in ev.spans if n == "serve.decode_chunk" and "moe_pairs" in a]
    pairs = sum(a["moe_pairs"] for a in chunks)
    if not pairs:
        return None
    held = int(ev.config["n_routed_experts"])
    return sum(a["moe_pairs_max"] for a in chunks) / (pairs / held)
