"""Shared model layers (pure JAX, pytree params, scan-friendly).

Conventions:
* params are nested dicts of jnp arrays; every builder has an ``init`` and
  an ``apply``-style function; shapes carry logical dim names via the
  parallel ``*_specs`` functions (for the dry-run's NamedShardings).
* activations: bf16 by default; softmax / norms / router in f32.
* attention is chunked (online-softmax over KV blocks, lax.scan) so 32k
  prefill compiles with bounded memory — no S×S score tensor.
* ``Ctx`` threads (mesh, rules) for with_sharding_constraint annotations;
  ctx=None (single host tests) skips them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.dist.sharding import ShardingRules, constrain


@dataclasses.dataclass(frozen=True)
class Ctx:
    mesh: Any = None
    rules: ShardingRules | None = None

    def cons(self, x, dims):
        if self.mesh is None:
            return x
        return constrain(x, self.mesh, self.rules, dims)

    def flag(self, name: str) -> bool:
        return self.rules is not None and self.rules.has(name)


NO_CTX = Ctx()


def truncnorm_init(key, shape, dtype, scale=0.02):
    return (scale * jax.random.truncated_normal(key, -2.0, 2.0, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------


def rmsnorm_init(d, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(params, x, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d, dtype=jnp.bfloat16):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(params, x, eps=1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def yarn_mscale(factor: float) -> float:
    """YaRN's attention temperature term, ``0.1 ln(factor) + 1``."""
    return 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0


def rope_freqs(head_dim, theta, yarn=None) -> np.ndarray:
    """The head_dim/2 rotary frequencies; with ``yarn`` (a ``YarnConfig``)
    YaRN's blend as DeepSeek-V3 publishes it: frequencies that turn more
    than ``beta_fast`` times over the original context are kept, those
    that turn fewer than ``beta_slow`` times are divided by ``factor``, and
    a linear ramp over the dimensions blends the two between."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2.0 / head_dim))
    if yarn is None:
        return freqs

    def dim_of(rotations):
        return (head_dim * math.log(yarn.original_max_position / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(yarn.beta_fast)), 0)
    high = min(math.ceil(dim_of(yarn.beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float32) - low) / max(high - low, 1e-3), 0, 1)
    return (freqs / yarn.factor * ramp + freqs * (1 - ramp)).astype(np.float32)


def rope_angles(positions, head_dim, theta, yarn=None):
    """positions: (...,) int32 → (cos, sin): (..., head_dim/2) f32."""
    freqs = rope_freqs(head_dim, theta, yarn)
    ang = positions.astype(jnp.float32)[..., None] * freqs[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: (..., S, H, D); cos/sin: (..., S, 1, D/2) or broadcastable."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# chunked causal attention (online softmax — no S×S tensor)
# ---------------------------------------------------------------------------


def _attn_chunk(q, k, v, scale, mask):
    """q: (B,Hq,Tq,D) k/v: (B,Hkv,Tk,D); GQA via head grouping. mask: (Tq,Tk)
    or None. Returns (out_unnorm f32, row_max f32, row_sum f32)."""
    B, Hq, Tq, D = q.shape
    Hkv = k.shape[1]
    G = Hq // Hkv
    qg = q.reshape(B, Hkv, G, Tq, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if mask is not None:
        s = jnp.where(mask[None, None, None], s, -1e30)
    m = jnp.max(s, axis=-1)  # (B,Hkv,G,Tq)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32))
    return o, m, l


def chunked_causal_attention(q, k, v, *, chunk_q=1024, chunk_k=1024, causal=True,
                             q_offset=0, scale=None):
    """q: (B,Hq,Sq,D), k/v: (B,Hkv,Sk,D) → (B,Hq,Sq,D) in q.dtype.

    Online-softmax over KV chunks inside a scan over Q chunks. ``q_offset``
    is the absolute position of q[0] (for prefill continuation / decode);
    ``scale`` multiplies the scores (default 1/sqrt(D)).
    """
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D) if scale is None else scale
    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    # pad to multiples
    pq = (-Sq) % cq
    pk = (-Sk) % ck
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq, nk = qp.shape[2] // cq, kp.shape[2] // ck

    q_pos = jnp.arange(cq)
    k_pos = jnp.arange(ck)

    def q_step(_, iq):
        qc = jax.lax.dynamic_slice_in_dim(qp, iq * cq, cq, axis=2)

        def k_step(carry, ik):
            o, m, l = carry
            kc = jax.lax.dynamic_slice_in_dim(kp, ik * ck, ck, axis=2)
            vc = jax.lax.dynamic_slice_in_dim(vp, ik * ck, ck, axis=2)
            abs_k = ik * ck + k_pos
            valid = abs_k < Sk  # mask KV PADDING (ragged Sk) in every mode
            if causal:
                abs_q = q_offset + iq * cq + q_pos
                mask = (abs_q[:, None] >= abs_k[None, :]) & valid[None, :]
            else:
                mask = jnp.broadcast_to(valid[None, :], (cq, ck))
            oc, mc, lc = _attn_chunk(qc, kc, vc, scale, mask)
            m_new = jnp.maximum(m, mc)
            alpha = jnp.exp(m - m_new)
            beta = jnp.exp(mc - m_new)
            o = o * alpha[..., None] + oc * beta[..., None]
            l = l * alpha + lc * beta
            return (o, m_new, l), None

        o0 = jnp.zeros((B, Hkv, G, cq, D), jnp.float32)
        m0 = jnp.full((B, Hkv, G, cq), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        (o, m, l), _ = jax.lax.scan(k_step, (o0, m0, l0), jnp.arange(nk))
        out = o / jnp.maximum(l[..., None], 1e-30)
        return None, out.reshape(B, Hq, cq, D).astype(q.dtype)

    _, outs = jax.lax.scan(q_step, None, jnp.arange(nq))
    # outs: (nq, B, Hq, cq, D) → (B, Hq, Sq, D)
    out = jnp.moveaxis(outs, 0, 2).reshape(B, Hq, nq * cq, D)
    return out[:, :, :Sq]


def decode_attention(q, k_cache, v_cache, kv_len_mask):
    """q: (B,Hq,1,D); caches: (B,Hkv,Smax,D); kv_len_mask: (B,Smax) bool.
    Plain softmax over the cache (linear in Smax)."""
    B, Hq, _, D = q.shape
    Hkv = k_cache.shape[1]
    G = Hq // Hkv
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qg.astype(jnp.float32), k_cache.astype(jnp.float32))
    s = s * scale
    s = jnp.where(kv_len_mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, Hq, 1, D).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def attention_init(key, cfg, dtype=jnp.bfloat16):
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": truncnorm_init(ks[0], (d, H * hd), dtype),
        "wk": truncnorm_init(ks[1], (d, Hkv * hd), dtype),
        "wv": truncnorm_init(ks[2], (d, Hkv * hd), dtype),
        "wo": truncnorm_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def attention_specs(cfg):
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": ("d_model", "heads"),
        "wk": ("d_model", "kv_heads"),
        "wv": ("d_model", "kv_heads"),
        "wo": ("heads", "d_model"),
    }
    if cfg.qkv_bias:
        s |= {"bq": ("heads",), "bk": ("kv_heads",), "bv": ("kv_heads",)}
    if cfg.qk_norm:
        s |= {"q_norm": {"scale": ("head_dim",)}, "k_norm": {"scale": ("head_dim",)}}
    return s


def _qkv(params, x, cfg, positions, rope=True):
    B, S, d = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if cfg.qkv_bias:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(B, S, H, hd)
    k = k.reshape(B, S, Hkv, hd)
    v = v.reshape(B, S, Hkv, hd)
    if cfg.qk_norm:
        q = rmsnorm(params["q_norm"], q)
        k = rmsnorm(params["k_norm"], k)
    if rope:
        cos, sin = rope_angles(positions, hd, cfg.rope_theta)
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    return q, k, v


def attention_fwd(params, x, cfg, ctx=NO_CTX, positions=None, rope=True, causal=True):
    """Training/prefill full-sequence attention. Returns (y, (k, v))."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q, k, v = _qkv(params, x, cfg, positions, rope)
    if ctx.flag("attn_heads"):
        # head-sharded attention internals (Megatron-style): gather seq once,
        # keep the chunk scans slice-local — avoids GSPMD involuntary reshard
        q = ctx.cons(q, ("batch", None, "heads", None))
        k = ctx.cons(k, ("batch", None, "kv_heads", None))
        v = ctx.cons(v, ("batch", None, "kv_heads", None))
    else:
        q = ctx.cons(q, ("batch", "seq", "heads", None))
        k = ctx.cons(k, ("batch", "seq", "kv_heads", None))
    o = chunked_causal_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
        causal=causal,
    )
    o = o.transpose(0, 2, 1, 3).reshape(B, S, -1)
    y = o @ params["wo"]
    return ctx.cons(y, ("batch", "seq", "d_model")), (k, v)


def attention_decode(params, x, cfg, cache, pos, ctx=NO_CTX, rope=True):
    """x: (B,1,d); cache: {"k": (B,Smax,Hkv,hd), "v": ..., } pos: (B,) int32.
    Returns (y, new_cache)."""
    B = x.shape[0]
    q, k, v = _qkv(params, x, cfg, pos[:, None], rope)
    kc = _scatter_time(cache["k"], k, pos)
    vc = _scatter_time(cache["v"], v, pos)
    Smax = kc.shape[1]
    mask = jnp.arange(Smax)[None, :] <= pos[:, None]
    o = decode_attention(
        q.transpose(0, 2, 1, 3), kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3), mask
    )
    y = o.transpose(0, 2, 1, 3).reshape(B, 1, -1) @ params["wo"]
    return y, {"k": kc, "v": vc}


def _scatter_time(cache, new, pos):
    """cache: (B, Smax, ...), new: (B, 1, ...), pos: (B,) → write at [b, pos[b]]."""
    B = cache.shape[0]
    t = jnp.arange(cache.shape[1])
    sel = (t[None, :] == pos[:, None]).reshape(
        (B, cache.shape[1]) + (1,) * (cache.ndim - 2)
    )
    return jnp.where(sel, new.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def swiglu_init(key, d, d_ff, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 3)
    return {
        "w_gate": truncnorm_init(ks[0], (d, d_ff), dtype),
        "w_up": truncnorm_init(ks[1], (d, d_ff), dtype),
        "w_down": truncnorm_init(ks[2], (d_ff, d), dtype),
    }


def swiglu_specs():
    return {
        "w_gate": ("d_model", "d_ff"),
        "w_up": ("d_model", "d_ff"),
        "w_down": ("d_ff", "d_model"),
    }


def swiglu(params, x, ctx=NO_CTX):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    h = ctx.cons(h, ("batch", "seq", "d_ff"))
    return ctx.cons(h @ params["w_down"], ("batch", "seq", "d_model"))


def gelu_mlp_init(key, d, d_ff, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 2)
    return {
        "w_up": truncnorm_init(ks[0], (d, d_ff), dtype),
        "b_up": jnp.zeros((d_ff,), dtype),
        "w_down": truncnorm_init(ks[1], (d_ff, d), dtype),
        "b_down": jnp.zeros((d,), dtype),
    }


def gelu_mlp_specs():
    return {
        "w_up": ("d_model", "d_ff"),
        "b_up": ("d_ff",),
        "w_down": ("d_ff", "d_model"),
        "b_down": ("d_model",),
    }


def gelu_mlp(params, x, ctx=NO_CTX):
    h = jax.nn.gelu(x @ params["w_up"] + params["b_up"])
    h = ctx.cons(h, ("batch", "seq", "d_ff"))
    return ctx.cons(h @ params["w_down"] + params["b_down"], ("batch", "seq", "d_model"))


# ---------------------------------------------------------------------------
# MoE: routing, then the held experts' part of the result
# ---------------------------------------------------------------------------
# Two dispatches share one router:
# * ``moe_dropless`` (serving: decode and prefill): the (token, expert)
#   pairs whose expert is held here, sorted by expert, through grouped
#   products (``jax.lax.ragged_dot``) — no capacity, no dropped token,
#   FLOPs in the held pairs. It is the only path for a held share.
# * ``moe_block``'s capacity path (the training forward and the dry-run):
#   per-expert buffers of capacity C, which expert-sharded GSPMD
#   partitions; tokens past C are dropped.


def moe_init(key, cfg, dtype=jnp.bfloat16):
    mc = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "router": truncnorm_init(ks[0], (d, mc.n_experts), jnp.float32, scale=0.006),
        "w_gate": truncnorm_init(ks[1], (mc.held, d, mc.expert_ff), dtype),
        "w_up": truncnorm_init(ks[2], (mc.held, d, mc.expert_ff), dtype),
        "w_down": truncnorm_init(ks[3], (mc.held, mc.expert_ff, d), dtype),
    }
    if mc.scoring == "sigmoid":
        # DeepSeek-V3's e_score_correction_bias: steers selection only
        p["select_bias"] = jnp.zeros((mc.n_experts,), jnp.float32)
    if mc.shared_ff:
        p["shared"] = swiglu_init(ks[4], d, mc.shared_ff, dtype)
    return p


def moe_specs(cfg):
    # expert weights use the dedicated "expert_d" logical name so profiles
    # can exclude them from FSDP while keeping dense params sharded
    s = {
        "router": ("d_model", "experts"),
        "w_gate": ("experts", "expert_d", "moe_ff"),
        "w_up": ("experts", "expert_d", "moe_ff"),
        "w_down": ("experts", "moe_ff", "expert_d"),
    }
    if cfg.moe.scoring == "sigmoid":
        s["select_bias"] = (None,)
    if cfg.moe.shared_ff:
        s["shared"] = swiglu_specs()
    return s


def moe_route(logits, mc, select_bias=None):
    """Router logits (T, E) f32 → (gates (T, k) f32, experts (T, k) int32).

    ``softmax``: softmax over all E, then the top k. ``sigmoid`` (DeepSeek-V3
    noaux_tc): s = sigmoid(logits); experts are chosen by s + select_bias:
    each of ``n_group`` groups scores the sum of its two best biased scores,
    the best ``topk_group`` groups stay, and the top k of their experts are
    taken; the gates are the unbiased s of those. Ties go to the lower
    index (``lax.top_k``). Then the optional normalisation to sum 1, and
    the ``routed_scaling_factor``."""
    k = mc.top_k
    if mc.scoring == "softmax":
        gates, eidx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    elif mc.scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        choice = s + select_bias
        if mc.n_group > 1:
            T, E = choice.shape
            groups = choice.reshape(T, mc.n_group, E // mc.n_group)
            group_score = jax.lax.top_k(groups, 2)[0].sum(-1)  # (T, n_group)
            _, best = jax.lax.top_k(group_score, mc.topk_group)
            kept = jnp.any(best[:, :, None] == jnp.arange(mc.n_group), axis=1)
            choice = jnp.where(jnp.repeat(kept, E // mc.n_group, axis=1), choice, -jnp.inf)
        _, eidx = jax.lax.top_k(choice, k)
        gates = jnp.take_along_axis(s, eidx, axis=1)
    else:
        raise ValueError(f"unknown MoE scoring {mc.scoring!r}")
    if mc.norm_topk_prob:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * mc.routed_scaling_factor, eidx.astype(jnp.int32)


def _router_logits(params, xt):
    return xt.astype(jnp.float32) @ params["router"].astype(jnp.float32)


def moe_dropless(params, x, cfg, ctx=NO_CTX):
    """Routed experts without a capacity: returns ``(out (B,S,d), aux,
    pairs (held,) int32)``, ``pairs`` the (token, expert) pairs each held
    expert computed, ``aux`` as :func:`moe_block`'s.

    The router scores all ``n_experts``; the pairs whose expert lies in the
    held share are sorted by expert and run through three grouped products
    (``jax.lax.ragged_dot``), so the expert FLOPs follow the held pairs, and
    every such pair is computed. Pairs of experts held elsewhere add
    nothing here. The shared expert runs on every token."""
    mc = cfg.moe
    B, S, d = x.shape
    T, k, H = B * S, mc.top_k, mc.held
    xt = x.reshape(T, d)
    logits = _router_logits(params, xt)
    gates, eidx = moe_route(logits, mc, params.get("select_bias"))
    local = eidx.reshape(-1) - mc.held_first
    held = (local >= 0) & (local < H)
    key = jnp.where(held, local, H)  # pairs held elsewhere sort last
    pairs = jnp.zeros((H + 1,), jnp.int32).at[key].add(1)[:H]
    # a token picks an expert at most once, so at most min(k, H) of its
    # pairs are held here: the sorted prefix of that length holds them all
    M = T * min(k, H)
    order = jnp.argsort(key, stable=True)[:M]
    tok = order // k
    xs = xt[tok]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, params["w_gate"], pairs)) * jax.lax.ragged_dot(
        xs, params["w_up"], pairs
    )
    y = jax.lax.ragged_dot(h.astype(x.dtype), params["w_down"], pairs)
    live = jnp.arange(M) < pairs.sum()
    g = jnp.where(live, gates.reshape(-1)[order], 0.0)
    contrib = jnp.where(live[:, None], y.astype(jnp.float32) * g[:, None], 0.0)
    out = jnp.zeros((T, d), jnp.float32).at[tok].add(contrib)
    out = out.astype(x.dtype).reshape(B, S, d)
    if mc.shared_ff:
        out = out + swiglu(params["shared"], x, ctx)
    aux = _balance_aux(logits, eidx, mc)
    return ctx.cons(out, ("batch", "seq", "d_model")), aux, pairs


def moe_forward(params, x, cfg, ctx=NO_CTX):
    """The training forward's expert layer, ``(out, aux)``: the capacity
    path where every expert is held here, else the dropless one."""
    if cfg.moe.held == cfg.moe.n_experts:
        return moe_block(params, x, cfg, ctx)
    out, aux, _ = moe_dropless(params, x, cfg, ctx)
    return out, aux


def _balance_aux(logits, eidx, mc):
    """The Switch load-balance loss E · Σ_e f_e · p_e over the router's E
    experts: f_e the share of pairs routed to e, p_e the mean routing
    probability — the softmax, or for sigmoid scoring the scores normalised
    over the experts (DeepSeek-V3's sequence-wise form)."""
    E = mc.n_experts
    if mc.scoring == "sigmoid":
        s = jax.nn.sigmoid(logits)
        me = (s / s.sum(-1, keepdims=True)).mean(0)
    else:
        me = jax.nn.softmax(logits, axis=-1).mean(0)
    ce = jnp.zeros((E,), jnp.float32).at[eidx.reshape(-1)].add(1.0) / eidx.size
    return E * jnp.sum(me * ce)


def moe_block(params, x, cfg, ctx=NO_CTX):
    """Top-k routed experts with capacity-factor sort-based dispatch (the
    training forward). Returns ``(out, aux)``.

    Gathers/scatters (O(T·k·d) bytes, ~0 FLOPs) move tokens into per-expert
    buffers of capacity C = ceil(T·k/E · capacity_factor); expert matmuls
    are dense (E, C, d)×(E, d, f) einsums — compiled FLOPs stay proportional
    to ACTIVE parameters (MODEL_FLOPS ratio in the roofline stays honest).
    Overflowing tokens are dropped (standard GShard/Switch semantics), so
    serving uses :func:`moe_dropless`; a held share has only that path.
    ``aux`` is :func:`_balance_aux`."""
    mc = cfg.moe
    if mc.held != mc.n_experts:
        raise ValueError(f"{cfg.name}: a held share of experts serves only dropless")
    B, S, d = x.shape
    T = B * S
    E, k = mc.n_experts, mc.top_k
    xt = x.reshape(T, d)
    logits = _router_logits(params, xt)
    gate_vals, eidx = moe_route(logits, mc, params.get("select_bias"))

    C = int(math.ceil(T * k / E * mc.capacity_factor))
    C = max(C, 4)
    # flatten (token, slot) pairs and sort by expert id (stable)
    flat_e = eidx.reshape(-1)  # (T*k,)
    flat_t = jnp.repeat(jnp.arange(T), k)
    flat_g = gate_vals.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    # position within expert group
    same = jnp.concatenate([jnp.zeros((1,), jnp.int32), (se[1:] == se[:-1]).astype(jnp.int32)])
    seg_pos = _segment_rank(same)
    keep = seg_pos < C
    buf_idx = se * C + jnp.where(keep, seg_pos, 0)
    if ctx.flag("moe_gather"):
        # gather-form dispatch/combine (§Perf lever): scatters with computed
        # indices force GSPMD to replicate+all-reduce the buffers; both maps
        # are re-expressed as gathers with an explicit inverse permutation.
        # dispatch: slot (e, c) pulls its token (slot_token built by scatter
        # over (T*k,)-index space — 8-byte rows, negligible vs (·, d) arrays)
        slot_token = (
            jnp.full((E * C + 1,), T, jnp.int32)
            .at[jnp.where(keep, buf_idx, E * C)]
            .set(st.astype(jnp.int32))
        )[: E * C]
        xt_pad = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)])
        eb = xt_pad[slot_token].reshape(E, C, d)
    else:
        # scatter-form dispatch (baseline)
        buf = jnp.zeros((E * C, d), x.dtype)
        vals = jnp.where(keep[:, None], xt[st], 0).astype(x.dtype)
        buf = buf.at[buf_idx].add(vals)  # collisions only among dropped → add of 0s
        eb = buf.reshape(E, C, d)
    eb = ctx.cons(eb, ("experts", None, "d_model"))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", eb, params["w_gate"])) * jnp.einsum(
        "ecd,edf->ecf", eb, params["w_up"]
    )
    h = ctx.cons(h, ("experts", None, "moe_ff"))
    out_b = jnp.einsum("ecf,efd->ecd", h, params["w_down"]).reshape(E * C, d)
    if ctx.flag("moe_gather"):
        # combine: every token has exactly k (possibly zeroed) contributions;
        # invert the expert-sort and segment-sum groups of k — gather + dense
        # reduce, no scatter-add of (T, d) partials.
        contrib = out_b[buf_idx] * (sg * keep.astype(sg.dtype))[:, None]
        inv = jnp.argsort(st, stable=True)  # groups the k slots of each token
        out = contrib[inv].reshape(T, k, d).astype(jnp.float32).sum(axis=1)
    else:
        contrib = out_b[buf_idx] * (sg * keep.astype(sg.dtype))[:, None]
        out = jnp.zeros((T, d), jnp.float32).at[st].add(contrib.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(B, S, d)
    if mc.shared_ff:
        out = out + swiglu(params["shared"], x, ctx)
    return ctx.cons(out, ("batch", "seq", "d_model")), _balance_aux(logits, eidx, mc)


def _segment_rank(same_as_prev):
    """Given 0/1 'same as previous' flags of a sorted array, return the rank
    of each element within its run (vectorized prefix trick)."""
    n = same_as_prev.shape[0]
    idx = jnp.arange(n)
    # start-of-run positions: cummax of idx*(1-same)
    starts = jax.lax.associative_scan(jnp.maximum, jnp.where(same_as_prev == 0, idx, 0))
    return idx - starts
