"""Plain reference of DeepSeek-V3's decoder, from the published description
(hf deepseek-ai/DeepSeek-V3: config.json and modeling_deepseek.py):
RMSNorm; multi-head latent attention with its low-rank q path (w_dq, norm,
w_uq) and kv path (w_dkv, norm, then w_uk and w_uv), a rotary key shared by
the heads, YaRN frequencies and the softmax scale (1/sqrt(qk head dim)) ·
mscale²; the first ``first_k_dense_replace`` layers with a SwiGLU FFN, the
rest with routed experts (noaux_tc: sigmoid scores, a selection bias, the
best ``topk_group`` of ``n_group`` groups by the sum of their two best
biased scores, the top ``num_experts_per_tok`` inside them, gates the
unbiased scores normalised and scaled by ``routed_scaling_factor``) plus a
shared expert; a final RMSNorm and an untied head.

Float32 throughout, every product at ``Precision.HIGHEST``; no cache, no
batching, no kernels; the keys and values are decompressed and attended
(not the absorbed form the program decodes with). It imports nothing of the
program: it reads the benchmark's own weights by their names. It runs one
layer at a time (one compiled program per kind of layer, called per
layer), attention in blocks of queries, so that 4096 positions × 128 heads
fit beside the weights.

Departures from the published model, the same in the program:

* one chip's share of an expert-parallel deployment: the layer computes
  only the experts it holds (``n_routed_experts`` of them from
  ``experts_held_first``) for the tokens routed to them; the router still
  scores all ``router_experts``; what the other experts would add is left
  out, and that partial result goes on to the next layer;
* a slice of the vocabulary (``vocab_size`` rows): ids are drawn from it
  and the logits are over it;
* the rotary part rotates the two halves of its dimensions where
  DeepSeek's inference code rotates adjacent pairs: the same map up to a
  fixed permutation of the rope columns of w_uq and w_kr, which random
  weights do not tell apart;
* no multi-token-prediction module (the engine has no speculative decode).

``control`` switches the control on: ``"fp8"`` (float8 e4m3) or ``"int8"``
rounds every matrix product's weights (per output channel) and inputs (per
token), the router's included, as ``dense_decoder.py`` does.
"""

from __future__ import annotations

import functools
import math

import numpy as np

#: queries per attention block: (heads, block, T) f32 scores
Q_BLOCK = 256


def _dims(config: dict) -> dict:
    return {
        "L": int(config["num_hidden_layers"]), "d": int(config["hidden_size"]),
        "H": int(config["num_attention_heads"]), "dense": int(config["first_k_dense_replace"]),
        "ff": int(config["intermediate_size"]), "eff": int(config["moe_intermediate_size"]),
        "qr": int(config["q_lora_rank"]), "kvr": int(config["kv_lora_rank"]),
        "dn": int(config["qk_nope_head_dim"]), "dr": int(config["qk_rope_head_dim"]),
        "dv": int(config["v_head_dim"]), "V": int(config["vocab_size"]),
        "E": int(config["router_experts"]), "held": int(config["n_routed_experts"]),
        "first": int(config["experts_held_first"]), "k": int(config["num_experts_per_tok"]),
        "shared": int(config["n_shared_experts"]), "n_group": int(config["n_group"]),
        "topk_group": int(config["topk_group"]), "eps": float(config["rms_norm_eps"]),
        "theta": float(config["rope_theta"]), "scale": float(config["routed_scaling_factor"]),
        "norm": bool(config["norm_topk_prob"]),
    }


def _items(config: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads", "first_k_dense_replace",
            "intermediate_size", "moe_intermediate_size", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "vocab_size", "router_experts",
            "n_routed_experts", "experts_held_first", "num_experts_per_tok", "n_shared_experts",
            "n_group", "topk_group", "rms_norm_eps", "rope_theta", "routed_scaling_factor",
            "norm_topk_prob")
    rs = config["rope_scaling"]
    return tuple((k, config[k]) for k in keys) + (("rope_scaling", tuple(sorted(rs.items()))),)


def check_weights(weights, config: dict) -> None:
    """Raise unless every weight's shape agrees with the configuration's keys."""
    m = _dims(config)
    d, H, dn, dr, dv = m["d"], m["H"], m["dn"], m["dr"], m["dv"]
    attn = {"w_dq": (d, m["qr"]), "w_uq": (m["qr"], H * (dn + dr)), "w_dkv": (d, m["kvr"]),
            "w_uk": (m["kvr"], H * dn), "w_uv": (m["kvr"], H * dv), "w_kr": (d, dr),
            "wo": (H * dv, d)}
    mlp = {"w_gate": (d, m["ff"]), "w_up": (d, m["ff"]), "w_down": (m["ff"], d)}
    sff = m["shared"] * m["eff"]
    moe = {"router": (d, m["E"]), "select_bias": (m["E"],),
           "w_gate": (m["held"], d, m["eff"]), "w_up": (m["held"], d, m["eff"]),
           "w_down": (m["held"], m["eff"], d)}
    shared = {"w_gate": (d, sff), "w_up": (d, sff), "w_down": (sff, d)}
    want = {}
    for i in range(m["dense"]):
        want |= {f"prefix_{i}/attn/{k}": s for k, s in attn.items()}
        want |= {f"prefix_{i}/mlp/{k}": s for k, s in mlp.items()}
    n_moe = m["L"] - m["dense"]
    want |= {f"body/b0/attn/{k}": (n_moe, *s) for k, s in attn.items()}
    want |= {f"body/b0/moe/{k}": (n_moe, *s) for k, s in moe.items()}
    want |= {f"body/b0/moe/shared/{k}": (n_moe, *s) for k, s in shared.items()}
    for name, shape in want.items():
        node = weights
        for part in name.split("/"):
            node = node[part]
        if tuple(node.shape) != shape:
            raise ValueError(f"{config['name']}: {name} has shape {tuple(node.shape)}, "
                             f"the configuration's keys give {shape}")
    if weights["embed"].shape[0] < m["V"] or weights["lm_head"].shape[1] < m["V"]:
        raise ValueError(f"{config['name']}: fewer vocabulary rows than vocab_size")
    if m["E"] % m["n_group"] or not 0 <= m["first"] <= m["E"] - m["held"]:
        raise ValueError(f"{config['name']}: experts, groups and the held share disagree")


def yarn_inv_freq(config: dict) -> np.ndarray:
    """DeepSeekV3YarnRotaryEmbedding's frequencies, in float64."""
    rs = config["rope_scaling"]
    dim, base = int(config["qk_rope_head_dim"]), float(config["rope_theta"])
    factor, orig = float(rs["factor"]), float(rs["original_max_position_embeddings"])
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    freq_inter = freq_extra / factor

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def softmax_scale(config: dict) -> float:
    """q_head_dim ** -0.5 · mscale², mscale = 0.1 · mscale_all_dim · ln(factor) + 1."""
    rs = config["rope_scaling"]
    scale = (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])) ** -0.5
    factor, all_dim = float(rs["factor"]), float(rs.get("mscale_all_dim", 0))
    if all_dim and factor > 1:
        mscale = 0.1 * all_dim * math.log(factor) + 1.0
        scale *= mscale * mscale
    return scale


def _fake_quant(x, axis, mode):
    """Round ``x`` to the control's 8-bit grid along ``axis`` and back."""
    import jax.numpy as jnp

    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if mode == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if mode == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control mode {mode!r}")


def _mm(x, w, mode):
    """x (T, a) @ w (a, b) in float32, or at the control's precision."""
    import jax
    import jax.numpy as jnp

    if mode != "f32":
        x = _fake_quant(x, axis=1, mode=mode)
        w = _fake_quant(w, axis=0, mode=mode)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _rms(x, scale, eps):
    import jax.numpy as jnp

    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def route(logits, bias, m):
    """noaux_tc: (T, E) logits → (gates (T, k), experts (T, k))."""
    import jax
    import jax.numpy as jnp

    T, E = logits.shape
    scores = jax.nn.sigmoid(logits)
    for_choice = scores + bias[None, :]
    if m["n_group"] > 1:
        grouped = for_choice.reshape(T, m["n_group"], E // m["n_group"])
        group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)
        group_idx = jax.lax.top_k(group_scores, m["topk_group"])[1]
        group_mask = jnp.zeros((T, m["n_group"]), bool).at[
            jnp.arange(T)[:, None], group_idx].set(True)
        score_mask = jnp.repeat(group_mask, E // m["n_group"], axis=1)
        for_choice = jnp.where(score_mask, for_choice, -jnp.inf)
    topk_idx = jax.lax.top_k(for_choice, m["k"])[1]
    weights = jnp.take_along_axis(scores, topk_idx, axis=1)
    if m["norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * m["scale"], topk_idx


@functools.cache
def _programs(config_items: tuple, T: int, mode: str):
    """Jitted per-layer functions at sequence length ``T``."""
    import jax
    import jax.numpy as jnp

    config = dict(config_items)
    config["rope_scaling"] = dict(config["rope_scaling"])
    m = _dims(config)
    d, H, dn, dr, dv, eps = m["d"], m["H"], m["dn"], m["dr"], m["dv"], m["eps"]
    hi = jax.lax.Precision.HIGHEST
    f = lambda a: a.astype(jnp.float32)  # noqa: E731
    half = dr // 2
    ang = np.arange(T, dtype=np.float64)[:, None] * yarn_inv_freq(config)[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    scale = softmax_scale(config)
    nb = -(-T // Q_BLOCK)

    def rope(x):  # (T, h, dr): the two halves rotate together
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def swiglu(w, x):
        return _mm(jax.nn.silu(_mm(x, f(w["w_gate"]), mode)) * _mm(x, f(w["w_up"]), mode),
                   f(w["w_down"]), mode)

    def attention(at, x):
        q = _mm(_rms(_mm(x, f(at["w_dq"]), mode), f(at["q_norm"]["scale"]), eps),
                f(at["w_uq"]), mode).reshape(T, H, dn + dr)
        q_nope, q_rope = q[..., :dn], rope(q[..., dn:])
        ckv = _rms(_mm(x, f(at["w_dkv"]), mode), f(at["kv_norm"]["scale"]), eps)
        k_rope = rope(_mm(x, f(at["w_kr"]), mode)[:, None, :])[:, 0]  # (T, dr)
        k_nope = _mm(ckv, f(at["w_uk"]), mode).reshape(T, H, dn)
        v = _mm(ckv, f(at["w_uv"]), mode).reshape(T, H, dv)
        pad = nb * Q_BLOCK - T
        qn = jnp.pad(q_nope, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, H, dn)
        qr = jnp.pad(q_rope, ((0, pad), (0, 0), (0, 0))).reshape(nb, Q_BLOCK, H, dr)

        def block(args):
            i, qn_b, qr_b = args
            s = (jnp.einsum("qhd,khd->hqk", qn_b, k_nope, precision=hi)
                 + jnp.einsum("qhd,kd->hqk", qr_b, k_rope, precision=hi)) * scale
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(qpos[None, :, None] >= jnp.arange(T)[None, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=hi)

        o = jax.lax.map(block, (jnp.arange(nb), qn, qr)).reshape(nb * Q_BLOCK, H * dv)[:T]
        return _mm(o, f(at["wo"]), mode)

    def dense_layer(lw, x):
        x = x + attention(lw["attn"], _rms(x, f(lw["ln1"]["scale"]), eps))
        return x + swiglu(lw["mlp"], _rms(x, f(lw["ln2"]["scale"]), eps))

    def moe_layer(body, i, x):
        lw = jax.tree.map(lambda a: a[i], body)
        x = x + attention(lw["attn"], _rms(x, f(lw["ln1"]["scale"]), eps))
        h = _rms(x, f(lw["ln2"]["scale"]), eps)
        moe = lw["moe"]
        gates, experts = route(_mm(h, f(moe["router"]), mode), f(moe["select_bias"]), m)

        def expert(acc, args):  # the held experts, one at a time, over every token
            e, wg, wu, wd = args
            w = jnp.sum(jnp.where(experts == m["first"] + e, gates, 0.0), axis=1)
            y = _mm(jax.nn.silu(_mm(h, f(wg), mode)) * _mm(h, f(wu), mode), f(wd), mode)
            return acc + w[:, None] * y, None

        routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                                 (jnp.arange(m["held"]), moe["w_gate"], moe["w_up"],
                                  moe["w_down"]))
        return x + routed + swiglu(moe["shared"], h)

    def embed(table, tokens):
        return jnp.take(table[:m["V"]], tokens, axis=0).astype(jnp.float32)

    def head(ln_f, lm_head, x):
        return _mm(_rms(x, f(ln_f["scale"]), eps), f(lm_head[:, :m["V"]]), mode)

    return (jax.jit(embed), jax.jit(dense_layer), jax.jit(moe_layer), jax.jit(head))


def forward(weights, config: dict, tokens, mode: str = "f32"):
    """Logits (T, vocab_size) float32 of one sequence, a layer at a time."""
    import jax.numpy as jnp

    check_weights(weights, config)
    m = _dims(config)
    embed, dense_layer, moe_layer, head = _programs(_items(config), len(tokens), mode)
    x = embed(weights["embed"], jnp.asarray(tokens))
    for i in range(m["dense"]):
        x = dense_layer(weights[f"prefix_{i}"], x)
    for i in range(m["L"] - m["dense"]):
        x = moe_layer(weights["body"]["b0"], jnp.int32(i), x)
    return head(weights["ln_f"], weights["lm_head"], x)


def served_gaps(weights, config: dict, prompt, served, T: int, control: str | None = None):
    """For each served token: how far its reference logit lies below the
    reference's best, over the largest |logit| at that position.

    With ``control``, the tokens are the ones the control puts first at each
    position of the same sequence (prompt and served tokens), read under the
    float32 reference instead: the control's reading."""
    import jax.numpy as jnp

    plen, g = len(prompt), len(served)
    if plen + g > T:
        raise ValueError(f"sequence of {plen + g} exceeds the reference length {T}")
    seq = np.zeros((T,), np.int32)
    seq[:plen] = prompt
    seq[plen:plen + g] = served
    ref = forward(weights, config, seq)[plen - 1:plen - 1 + g]
    if control is None:
        chosen = jnp.asarray(np.asarray(served, np.int32))
    else:
        lg = forward(weights, config, seq, control)[plen - 1:plen - 1 + g]
        chosen = jnp.argmax(lg, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    scale = jnp.max(jnp.abs(ref), axis=-1)
    return np.asarray((best - got) / scale, np.float64)
