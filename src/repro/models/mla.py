"""Multi-head Latent Attention (DeepSeek-V2/V3) with compressed KV cache.

Train/prefill: decompress the latent c_kv to full K/V and run chunked
attention. Decode: ABSORBED form — q_nope is folded through W_uk so scores
are taken directly against the cached 512-dim latent (plus the shared rope
key), and the output is reconstructed through W_uv. The cache holds only
(c_kv: kv_lora_rank, k_rope: qk_rope_head_dim) per token — MLA's point.

With ``cfg.yarn`` the rotary frequencies are YaRN's and the softmax scale
is (1/sqrt(qk head dim)) · mscale², as DeepSeek-V3 publishes. The rotary
part rotates the two halves of its 64 dimensions (``apply_rope``), where
DeepSeek's inference code rotates adjacent pairs: the same map up to a
fixed permutation of the rope columns of ``w_uq`` and ``w_kr``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import (
    NO_CTX,
    _scatter_time,
    apply_rope,
    chunked_causal_attention,
    rmsnorm,
    rmsnorm_init,
    rope_angles,
    truncnorm_init,
    yarn_mscale,
)


def mla_init(key, cfg, dtype=jnp.bfloat16):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 8)
    qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "w_dq": truncnorm_init(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": rmsnorm_init(m.q_lora_rank, dtype),
        "w_uq": truncnorm_init(ks[1], (m.q_lora_rank, H * qk_head), dtype),
        "w_dkv": truncnorm_init(ks[2], (d, m.kv_lora_rank), dtype),
        "kv_norm": rmsnorm_init(m.kv_lora_rank, dtype),
        "w_uk": truncnorm_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype),
        "w_uv": truncnorm_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype),
        "w_kr": truncnorm_init(ks[5], (d, m.qk_rope_head_dim), dtype),  # shared 1 head
        "wo": truncnorm_init(ks[6], (H * m.v_head_dim, d), dtype),
    }


def mla_specs(cfg):
    return {
        "w_dq": ("d_model", None),
        "q_norm": {"scale": (None,)},
        "w_uq": (None, "heads"),
        "w_dkv": ("d_model", None),
        "kv_norm": {"scale": (None,)},
        "w_uk": (None, "heads"),
        "w_uv": (None, "heads"),
        "w_kr": ("d_model", None),
        "wo": ("heads", "d_model"),
    }


def _mla_qkr(params, x, cfg, positions):
    """Shared q computation + rope pieces. Returns q_nope (B,S,H,dn),
    q_rope (B,S,H,dr), c_kv (B,S,r), k_rope (B,S,1,dr)."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q = rmsnorm(params["q_norm"], x @ params["w_dq"]) @ params["w_uq"]
    q = q.reshape(B, S, H, m.qk_nope_head_dim + m.qk_rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    c_kv = rmsnorm(params["kv_norm"], x @ params["w_dkv"])
    k_rope = (x @ params["w_kr"]).reshape(B, S, 1, m.qk_rope_head_dim)
    cos, sin = rope_angles(positions, m.qk_rope_head_dim, cfg.rope_theta, cfg.yarn)
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope, cos, sin)
    return q_nope, q_rope, c_kv, k_rope


def softmax_scale(cfg) -> float:
    m = cfg.mla
    scale = 1.0 / math.sqrt(m.qk_nope_head_dim + m.qk_rope_head_dim)
    if cfg.yarn is not None:
        scale *= yarn_mscale(cfg.yarn.factor) ** 2
    return scale


def mla_fwd(params, x, cfg, ctx=NO_CTX, positions=None):
    """Full-sequence (train/prefill). Returns (y, (c_kv, k_rope)) for caching."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    q_nope, q_rope, c_kv, k_rope = _mla_qkr(params, x, cfg, positions)
    # decompress K/V
    k_nope = (c_kv @ params["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (c_kv @ params["w_uv"]).reshape(B, S, H, m.v_head_dim)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, S, H, m.qk_rope_head_dim))], axis=-1
    )
    if ctx.flag("attn_heads"):
        q_full = ctx.cons(q_full, ("batch", None, "heads", None))
        k_full = ctx.cons(k_full, ("batch", None, "heads", None))
    else:
        q_full = ctx.cons(q_full, ("batch", "seq", "heads", None))
        k_full = ctx.cons(k_full, ("batch", "seq", "heads", None))
    # pad v to qk head dim for the shared chunked kernel, then slice
    o = chunked_causal_attention(
        q_full.transpose(0, 2, 1, 3),
        k_full.transpose(0, 2, 1, 3),
        jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, q_full.shape[-1] - m.v_head_dim))).transpose(0, 2, 1, 3),
        scale=softmax_scale(cfg),
        # blocks of 256 queries: at 128 heads a block's f32 scores are
        # 128 MB, not 512, beside the decode cache a prefill writes into
        chunk_q=256,
    )
    o = o.transpose(0, 2, 1, 3)[..., : m.v_head_dim].reshape(B, S, -1)
    y = o @ params["wo"]
    return ctx.cons(y, ("batch", "seq", "d_model")), (c_kv, k_rope[:, :, 0, :])


def mla_decode(params, x, cfg, cache, pos, ctx=NO_CTX):
    """Absorbed decode. cache: {"c_kv": (B,Smax,r), "k_rope": (B,Smax,dr)}."""
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    q_nope, q_rope, c_kv_new, k_rope_new = _mla_qkr(params, x, cfg, pos[:, None])
    ckv = _scatter_time(cache["c_kv"], c_kv_new, pos)  # (B,Smax,r)
    krp = _scatter_time(cache["k_rope"], k_rope_new[:, :, 0, :], pos)
    Smax = ckv.shape[1]
    # absorb: q_lat[h] = q_nope[h] @ W_uk[h]^T → score vs latent directly
    w_uk = params["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], w_uk)  # (B,H,r)
    s = jnp.einsum(
        "bhr,bsr->bhs", q_lat.astype(jnp.float32), ckv.astype(jnp.float32)
    ) + jnp.einsum(
        "bhd,bsd->bhs", q_rope[:, 0].astype(jnp.float32), krp.astype(jnp.float32)
    )
    s = s * softmax_scale(cfg)
    mask = jnp.arange(Smax)[None, :] <= pos[:, None]
    s = jnp.where(mask[:, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsr->bhr", p, ckv.astype(jnp.float32))  # (B,H,r)
    w_uv = params["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, w_uv).astype(x.dtype)
    y = o.reshape(B, 1, -1) @ params["wo"]
    return y, {"c_kv": ckv, "k_rope": krp}


def mla_cache_init(cfg, batch, s_max, dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, s_max, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, s_max, m.qk_rope_head_dim), dtype),
    }
