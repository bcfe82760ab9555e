"""Tiny stand-ins for the cells, small enough for a CPU run of the harness."""

TINY_MODEL = {
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "vocab_size": 512,
    "program_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                          "head_dim": 16, "d_ff": 128, "vocab_size": 512,
                          "vocab_padded": 512},
}

CODED = {
    "arrivals": {"pattern": "backlog", "requests": 2048},
    "prompt": {"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 4, "max": 60},
    "output": {"dist": "lognormal", "median": 12, "sigma": 0.5, "min": 4, "max": 32},
    "block": 4,
    "pool": {"slots": 2, "max_len": 128, "buckets": [32, 64], "sync_every": 4},
    "check": {"served_tokens": 40, "ref_len": 96},
}

#: the encode at a payload a CPU holds
ENCODE = {"packet_words": 4096}


def overrides(kind: str) -> dict:
    if kind == "coded":
        return {"config": TINY_MODEL, "traffic": CODED}
    if kind == "encode":
        return {"config": ENCODE}
    raise KeyError(kind)
