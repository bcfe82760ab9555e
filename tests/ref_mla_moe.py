"""Plain reference of DeepSeek-V3's decoder for the tier-1 tests, from the
published description (hf deepseek-ai/DeepSeek-V3: config.json and
modeling_deepseek.py), at the sizes of a program ``ModelConfig``.

RMSNorm; multi-head latent attention with its low-rank q path (w_dq, norm,
w_uq) and kv path (w_dkv, norm, then w_uk and w_uv), a rotary key shared by
the heads, YaRN frequencies and the softmax scale (1/sqrt(qk head dim)) ·
mscale²; the leading dense layers with a SwiGLU FFN, the rest with routed
experts (noaux_tc: sigmoid scores, a selection bias, the best
``topk_group`` of ``n_group`` groups by the sum of their two best biased
scores, the top k inside them, gates the unbiased scores normalised and
scaled by ``routed_scaling_factor``) plus a shared expert; a final RMSNorm
and an untied head.

Float32, every product at ``Precision.HIGHEST``; no cache, no batching, no
kernels; the keys and values are decompressed and attended, not the
absorbed form the program decodes with. It uses nothing of the program but
the sizes in its config, and reads the program's parameter tree by name.

Departures from the published model, the same in the program:

* a held share of the experts (``held_first``, ``n_held``): only their part
  of the routed sum is computed; the router scores all experts;
* a vocabulary of ``vocab_size`` rows (the program pads it; the padding is
  cut here);
* the rotary part rotates the two halves of its dimensions where DeepSeek's
  inference code rotates adjacent pairs (the same map up to a fixed
  permutation of the rope columns of w_uq and w_kr);
* no multi-token-prediction module.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def seeded_params(model, seed: int):
    """The model's parameter tree with the benchmark's kind of values: every
    matrix, the router and the selection bias truncated normal (std 0.02,
    cut at 2 std), every norm scale 1, in the model's dtypes."""
    shapes = model.param_specs()[0]
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    key = jax.random.key(seed)
    out = []
    for i, (path, s) in enumerate(leaves):
        if getattr(path[-1], "key", None) == "scale":
            out.append(jnp.ones(s.shape, s.dtype))
        else:
            w = 0.02 * jax.random.truncated_normal(jax.random.fold_in(key, i), -2.0, 2.0, s.shape)
            out.append(w.astype(s.dtype))
    return jax.tree.unflatten(treedef, out)


def yarn_inv_freq(dim: int, base: float, yarn) -> np.ndarray:
    """DeepseekV3YarnRotaryEmbedding's inv_freq, in float64."""
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return freq_extra
    freq_inter = freq_extra / yarn.factor

    def correction_dim(rotations):
        return (dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(correction_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    inv_freq_mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def softmax_scale(cfg) -> float:
    """q_head_dim ** -0.5, times mscale² (mscale = 0.1 ln factor + 1) with YaRN."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if cfg.yarn is not None and cfg.yarn.factor > 1:
        mscale = 0.1 * math.log(cfg.yarn.factor) + 1.0
        scale *= mscale * mscale
    return scale


def _rms(x, scale, eps=1e-6):
    return x * (1.0 / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)) * scale


def _f(a):
    return jnp.asarray(a, jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, _f(w), precision=HI)


def route(logits, bias, mc):
    """noaux_tc on (T, E) logits → (gates (T, k), experts (T, k))."""
    T, E = logits.shape
    scores = jax.nn.sigmoid(logits)
    for_choice = scores + bias[None, :]
    if mc.n_group > 1:
        grouped = for_choice.reshape(T, mc.n_group, E // mc.n_group)
        group_scores = jax.lax.top_k(grouped, 2)[0].sum(-1)
        group_idx = jax.lax.top_k(group_scores, mc.topk_group)[1]
        group_mask = jnp.zeros((T, mc.n_group), bool).at[jnp.arange(T)[:, None], group_idx].set(True)
        for_choice = jnp.where(jnp.repeat(group_mask, E // mc.n_group, axis=1), for_choice, -jnp.inf)
    topk_idx = jax.lax.top_k(for_choice, mc.top_k)[1]
    weights = jnp.take_along_axis(scores, topk_idx, axis=1)
    if mc.norm_topk_prob:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * mc.routed_scaling_factor, topk_idx


def _swiglu(w, x):
    return _mm(jax.nn.silu(_mm(x, w["w_gate"])) * _mm(x, w["w_up"]), w["w_down"])


def moe_layer(moe, h, mc, held_first: int | None = None, n_held: int | None = None,
              shared: bool = True):
    """The routed sum over the held experts, every token through each (0
    where not chosen), plus the shared expert; ``moe`` holds the weights of
    the ``n_held`` experts from ``held_first``."""
    held_first = mc.held_first if held_first is None else held_first
    n_held = mc.held if n_held is None else n_held
    gates, experts = route(_mm(h, moe["router"]), _f(moe["select_bias"]), mc)
    out = jnp.zeros_like(h)
    for e in range(n_held):
        w = jnp.sum(jnp.where(experts == held_first + e, gates, 0.0), axis=1)
        y = _mm(jax.nn.silu(_mm(h, moe["w_gate"][e])) * _mm(h, moe["w_up"][e]), moe["w_down"][e])
        out = out + w[:, None] * y
    if shared and "shared" in moe:
        out = out + _swiglu(moe["shared"], h)
    return out


def attention(at, x, cfg, positions):
    m, H = cfg.mla, cfg.n_heads
    T = x.shape[0]
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ang = np.asarray(positions, np.float64)[:, None] * yarn_inv_freq(dr, cfg.rope_theta, cfg.yarn)
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]

    def rope(v):
        v1, v2 = v[..., : dr // 2], v[..., dr // 2:]
        return jnp.concatenate([v1 * cos - v2 * sin, v2 * cos + v1 * sin], axis=-1)

    q = _mm(_rms(_mm(x, at["w_dq"]), _f(at["q_norm"]["scale"])), at["w_uq"]).reshape(T, H, dn + dr)
    ckv = _rms(_mm(x, at["w_dkv"]), _f(at["kv_norm"]["scale"]))
    k_nope = _mm(ckv, at["w_uk"]).reshape(T, H, dn)
    v = _mm(ckv, at["w_uv"]).reshape(T, H, dv)
    k_rope = rope(_mm(x, at["w_kr"])[:, None, :])[:, 0]
    s = (jnp.einsum("qhd,khd->hqk", q[..., :dn], k_nope, precision=HI)
         + jnp.einsum("qhd,kd->hqk", rope(q[..., dn:]), k_rope, precision=HI)) * softmax_scale(cfg)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision=HI)
    return _mm(o.reshape(T, H * dv), at["wo"])


def forward(params, cfg, tokens):
    """Logits (T, vocab_size) float32 of one sequence."""
    T = len(tokens)
    x = _f(params["embed"])[jnp.asarray(tokens)]
    pos = np.arange(T)

    def block(lw, x, moe: bool):
        x = x + attention(lw["attn"], _rms(x, _f(lw["ln1"]["scale"])), cfg, pos)
        h = _rms(x, _f(lw["ln2"]["scale"]))
        return x + (moe_layer(lw["moe"], h, cfg.moe) if moe else _swiglu(lw["mlp"], h))

    for i in range(cfg.moe.first_dense):
        x = block(params[f"prefix_{i}"], x, moe=False)
    body = params["body"]["b0"]
    for i in range(cfg.n_layers - cfg.moe.first_dense):
        x = block(jax.tree.map(lambda a: a[i], body), x, moe=True)
    return _mm(_rms(x, _f(params["ln_f"]["scale"])), params["lm_head"])[:, : cfg.vocab_size]
