"""Work counted from shapes: parameters, the coded pool's shard words and the
encode's LocalOp words.

The parameter arithmetic is copied from the program's
``launch/roofline.param_counts`` (dense decoder part), with the norm scales
counted so that the total equals the model's leaf sizes, and the published
vocabulary rather than the program's padding.
"""

from __future__ import annotations

import math


def dense_dims(config: dict) -> dict:
    """The sizes of a dense GQA decoder from its published config keys."""
    return {
        "L": int(config["num_hidden_layers"]),
        "d": int(config["hidden_size"]),
        "H": int(config["num_attention_heads"]),
        "Hkv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "ff": int(config["intermediate_size"]),
        "V": int(config["vocab_size"]),
        "tied": bool(config["tie_word_embeddings"]),
        "qk_norm": config.get("model_type") == "qwen3",
    }


def param_count(config: dict, vocab: int | None = None) -> int:
    """Every parameter of the decoder (``vocab`` rows of embedding)."""
    m = dense_dims(config)
    d, H, Hkv, hd, ff, L = m["d"], m["H"], m["Hkv"], m["hd"], m["ff"], m["L"]
    V = m["V"] if vocab is None else vocab
    emb = V * d * (1 if m["tied"] else 2)
    attn = d * (H + 2 * Hkv) * hd + H * hd * d
    mlp = 3 * d * ff
    norms = 2 * d + (2 * hd if m["qk_norm"] else 0)
    return emb + L * (attn + mlp + norms) + d  # + the final norm


def pool_state_limbs(config: dict, slots: int, max_len: int, max_new: int) -> int:
    """16-bit limbs of the serving engine's decode state: the bf16 KV cache
    (one limb each) and the per-slot state (int32 and uint32 leaves two limbs
    per element, bool leaves packed two per limb)."""
    m = dense_dims(config)
    cache = 2 * m["L"] * slots * max_len * m["Hkv"] * m["hd"]
    # last_tok, pos, gen_count, max_gen (S,); gen_buf (S, max_new); rng (S, 2)
    i32 = slots * (4 + max_new + 2)
    return cache + 2 * i32 + math.ceil(slots / 2)


def shard_words(total_limbs: int, K: int) -> int:
    """Words per shard when the limbs are cut into K equal shards."""
    return -(-total_limbs // K)


def prepare_shoot_counts(K: int, p: int) -> dict:
    """Rounds, ppermutes and LocalOps of the universal prepare-and-shoot
    encode, and per device the words each LocalOp reads and writes (slots
    in + out) and the words on the wire, per word of packet, from the IR."""
    from repro.core.ir import CommRound, LocalOp, ir_permute_count
    from repro.core.schedule import plan_prepare_shoot

    ir = plan_prepare_shoot(K, p).to_ir(None)
    local = [s for s in ir.steps if isinstance(s, LocalOp)]
    rounds = [s for s in ir.steps if isinstance(s, CommRound)]
    return {
        "rounds": len(rounds),
        "ppermutes": ir_permute_count(ir),
        "local_ops": len(local),
        "localop_words_per_word": sum(len(op.in_slots) + len(op.out_slots) for op in local),
        "wire_words_per_word": sum(max(t.elems for t in r.transfers) for r in rounds
                                   if r.transfers),
    }
