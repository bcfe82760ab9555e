"""Work counted from shapes for a DeepSeek-V3-style decoder (MLA attention,
leading dense layers, routed experts held in a share, a shared expert):
its parameters and the bytes one decode tick needs.

The counts are of the work the model needs, not of what the program does
today: a tick needs every weight once (the held experts each counted as
read every tick: with 96 slots × 8 of 256 each is hit with probability
≈ 95%), the embedding rows of its tokens, and the latent cache rows the
slots attend (positions 0..pos of each), not every slot's whole
``max_len`` rows the program reads now. So a program that reads less
cannot push a share of this count past 100%.
"""

from __future__ import annotations

BF16, F32 = 2, 4


def dims(config: dict) -> dict:
    return {
        "L": int(config["num_hidden_layers"]), "dense": int(config["first_k_dense_replace"]),
        "d": int(config["hidden_size"]), "H": int(config["num_attention_heads"]),
        "ff": int(config["intermediate_size"]), "eff": int(config["moe_intermediate_size"]),
        "qr": int(config["q_lora_rank"]), "kvr": int(config["kv_lora_rank"]),
        "dn": int(config["qk_nope_head_dim"]), "dr": int(config["qk_rope_head_dim"]),
        "dv": int(config["v_head_dim"]), "V": int(config["vocab_size"]),
        "E": int(config["router_experts"]), "held": int(config["n_routed_experts"]),
        "shared": int(config["n_shared_experts"]),
    }


def layer_params(config: dict) -> dict:
    """Parameters of one layer of each kind, split by part (norms included)."""
    m = dims(config)
    d, H = m["d"], m["H"]
    attn = (d * m["qr"] + m["qr"] + m["qr"] * H * (m["dn"] + m["dr"])
            + d * m["kvr"] + m["kvr"] + m["kvr"] * H * (m["dn"] + m["dv"])
            + d * m["dr"] + H * m["dv"] * d)
    norms = 2 * d
    return {
        "attn": attn,
        "norms": norms,
        "dense_mlp": 3 * d * m["ff"],
        "experts": m["held"] * 3 * d * m["eff"],
        "shared": m["shared"] * 3 * d * m["eff"],
        "router": d * m["E"] + m["E"],  # float32, with the selection bias
    }


def param_count(config: dict, vocab: int | None = None) -> int:
    """Every parameter (``vocab`` rows of embedding and of the head)."""
    m = dims(config)
    p = layer_params(config)
    V = m["V"] if vocab is None else vocab
    dense = p["attn"] + p["norms"] + p["dense_mlp"]
    moe = p["attn"] + p["norms"] + p["experts"] + p["shared"] + p["router"]
    return 2 * V * m["d"] + m["dense"] * dense + (m["L"] - m["dense"]) * moe + m["d"]


def weight_bytes(config: dict, vocab: int | None = None) -> int:
    """Bytes of the weights as the program holds them: bfloat16, the router
    and the selection bias float32."""
    m = dims(config)
    router = (m["L"] - m["dense"]) * layer_params(config)["router"]
    return (param_count(config, vocab) - router) * BF16 + router * F32


def latent_row_bytes(config: dict) -> int:
    """Bytes of one position's latent cache row over every layer (bf16)."""
    m = dims(config)
    return m["L"] * (m["kvr"] + m["dr"]) * BF16


def tick_weight_bytes(config: dict, slots: int) -> int:
    """Weight bytes one decode tick needs: all but the embedding table, of
    which only the slots' rows."""
    m = dims(config)
    return weight_bytes(config) - m["V"] * m["d"] * BF16 + slots * m["d"] * BF16


def chunk_bytes(config: dict, slots: int, ticks: int, occupied: int, cache_rows: int) -> int:
    """Bytes the ``ticks`` ticks of a decode chunk need: each reads the
    weights, and each occupied slot attends its rows 0..pos (``cache_rows``
    is the sum of the occupied slots' positions at the chunk's start)."""
    rows = sum(cache_rows + occupied * (t + 1) for t in range(ticks))
    return ticks * tick_weight_bytes(config, slots) + rows * latent_row_bytes(config)
