"""Model assembly: decoder-only LM / MoE / SSM / hybrid / enc-dec / VLM.

A model is a layer PATTERN: an optional non-repeated prefix plus a repeated
body period, scanned with ``jax.lax.scan`` (params stacked over repeats) so
the HLO stays one-layer-sized regardless of depth — essential for the 40-cell
dry-run compile budget.

Public surface (used by train/, serve/, launch/):
    build_model(cfg)        → Model
    model.init(rng)         → params
    model.param_specs()     → (ShapeDtypeStruct pytree, logical-dims pytree)
    model.forward(params, batch, ctx)          → logits (train/prefill)
    model.loss(params, batch, ctx)             → (loss, metrics)
    model.init_cache(batch) / model.cache_specs(batch)
    model.prefill(params, batch, ctx)          → (logits, cache)
    model.decode_step(params, cache, tokens, pos, ctx) → (logits, cache)
    model.prefill_into_cache(params, cache, tokens, slot, ctx)
                            → (logits, cache)   # one-pass KV fill of a slot
    model.supports_prefill  → bool              # False for recurrent/enc-dec
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from . import layers as L
from . import mla as MLA
from . import ssm as SSM


# ---------------------------------------------------------------------------
# layer-kind registry
# ---------------------------------------------------------------------------
# kind → (init, specs, fwd, decode, cache_init, cache_specs); a decode
# returns (x, cache, pairs): pairs (held,) int32 from an expert layer, else None


def _dense_init(key, cfg, dtype, d_ff=None):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "ln1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.attention_init(k1, cfg, dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "mlp": L.swiglu_init(k2, cfg.d_model, d_ff or cfg.d_ff, dtype),
    }


def _dense_specs(cfg):
    return {
        "ln1": {"scale": ("d_model",)},
        "attn": L.attention_specs(cfg),
        "ln2": {"scale": ("d_model",)},
        "mlp": L.swiglu_specs(),
    }


def _dense_fwd(params, x, cfg, ctx, aux):
    h, _ = L.attention_fwd(params["attn"], L.rmsnorm(params["ln1"], x), cfg, ctx)
    x = x + h
    x = x + L.swiglu(params["mlp"], L.rmsnorm(params["ln2"], x), ctx)
    return x, aux


def _dense_decode(params, x, cfg, cache, pos, ctx):
    h, cache2 = L.attention_decode(
        params["attn"], L.rmsnorm(params["ln1"], x), cfg, cache, pos, ctx
    )
    x = x + h
    x = x + L.swiglu(params["mlp"], L.rmsnorm(params["ln2"], x), ctx)
    return x, cache2, None


def _dense_prefill(params, x, cfg, ctx, aux):
    """Full-sequence forward that also returns this layer's cache content
    (the K/V rows for positions [0, S)) — the decode path's cache is filled
    in ONE pass instead of a per-token refeed."""
    h, (k, v) = L.attention_fwd(params["attn"], L.rmsnorm(params["ln1"], x), cfg, ctx)
    x = x + h
    x = x + L.swiglu(params["mlp"], L.rmsnorm(params["ln2"], x), ctx)
    return x, aux, {"k": k, "v": v}


def _kv_cache_init(cfg, batch, s_max, dtype):
    return {
        "k": jnp.zeros((batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype),
        "v": jnp.zeros((batch, s_max, cfg.n_kv_heads, cfg.head_dim), dtype),
    }


def _kv_cache_dims():
    return {
        "k": ("batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("batch", "kv_seq", "kv_heads", "head_dim"),
    }


def _moe_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": L.rmsnorm_init(cfg.d_model, dtype),
        "attn": L.attention_init(k1, cfg, dtype),
        "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        "moe": L.moe_init(k2, cfg, dtype),
    }
    if cfg.moe.dense_residual_ff:
        p["dense_mlp"] = L.swiglu_init(
            jax.random.fold_in(key, 7), cfg.d_model, cfg.moe.dense_residual_ff, dtype
        )
    return p


def _moe_specs(cfg):
    s = {
        "ln1": {"scale": ("d_model",)},
        "attn": L.attention_specs(cfg),
        "ln2": {"scale": ("d_model",)},
        "moe": L.moe_specs(cfg),
    }
    if cfg.moe.dense_residual_ff:
        s["dense_mlp"] = L.swiglu_specs()
    return s


def _moe_fwd(params, x, cfg, ctx, aux):
    h, _ = L.attention_fwd(params["attn"], L.rmsnorm(params["ln1"], x), cfg, ctx)
    x = x + h
    xn = L.rmsnorm(params["ln2"], x)
    mo, a = L.moe_forward(params["moe"], xn, cfg, ctx)
    if cfg.moe.dense_residual_ff:
        mo = mo + L.swiglu(params["dense_mlp"], xn, ctx)
    return x + mo, aux + a


def _moe_decode(params, x, cfg, cache, pos, ctx):
    h, cache2 = L.attention_decode(
        params["attn"], L.rmsnorm(params["ln1"], x), cfg, cache, pos, ctx
    )
    x = x + h
    xn = L.rmsnorm(params["ln2"], x)
    mo, _, pairs = L.moe_dropless(params["moe"], xn, cfg, ctx)
    if cfg.moe.dense_residual_ff:
        mo = mo + L.swiglu(params["dense_mlp"], xn, ctx)
    return x + mo, cache2, pairs


def _moe_prefill(params, x, cfg, ctx, aux):
    h, (k, v) = L.attention_fwd(params["attn"], L.rmsnorm(params["ln1"], x), cfg, ctx)
    x = x + h
    xn = L.rmsnorm(params["ln2"], x)
    mo, a, _ = L.moe_dropless(params["moe"], xn, cfg, ctx)
    if cfg.moe.dense_residual_ff:
        mo = mo + L.swiglu(params["dense_mlp"], xn, ctx)
    return x + mo, aux + a, {"k": k, "v": v}


def _mla_block_init(moe: bool):
    def init(key, cfg, dtype):
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": L.rmsnorm_init(cfg.d_model, dtype),
            "attn": MLA.mla_init(k1, cfg, dtype),
            "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        }
        if moe:
            p["moe"] = L.moe_init(k2, cfg, dtype)
        else:
            p["mlp"] = L.swiglu_init(k2, cfg.d_model, cfg.moe.dense_ff or cfg.d_ff, dtype)
        return p

    return init


def _mla_block_specs(moe: bool):
    def specs(cfg):
        s = {
            "ln1": {"scale": ("d_model",)},
            "attn": MLA.mla_specs(cfg),
            "ln2": {"scale": ("d_model",)},
        }
        if moe:
            s["moe"] = L.moe_specs(cfg)
        else:
            s["mlp"] = L.swiglu_specs()
        return s

    return specs


def _mla_fwd(moe: bool):
    def fwd(params, x, cfg, ctx, aux):
        h, _ = MLA.mla_fwd(params["attn"], L.rmsnorm(params["ln1"], x), cfg, ctx)
        x = x + h
        xn = L.rmsnorm(params["ln2"], x)
        if moe:
            mo, a = L.moe_forward(params["moe"], xn, cfg, ctx)
            return x + mo, aux + a
        return x + L.swiglu(params["mlp"], xn, ctx), aux

    return fwd


def _mla_decode(moe: bool):
    def dec(params, x, cfg, cache, pos, ctx):
        h, cache2 = MLA.mla_decode(
            params["attn"], L.rmsnorm(params["ln1"], x), cfg, cache, pos, ctx
        )
        x = x + h
        xn = L.rmsnorm(params["ln2"], x)
        if moe:
            mo, _, pairs = L.moe_dropless(params["moe"], xn, cfg, ctx)
            return x + mo, cache2, pairs
        return x + L.swiglu(params["mlp"], xn, ctx), cache2, None

    return dec


def _mla_prefill(moe: bool):
    def pf(params, x, cfg, ctx, aux):
        h, (c_kv, k_rope) = MLA.mla_fwd(
            params["attn"], L.rmsnorm(params["ln1"], x), cfg, ctx
        )
        x = x + h
        xn = L.rmsnorm(params["ln2"], x)
        content = {"c_kv": c_kv, "k_rope": k_rope}
        if moe:
            mo, a, _ = L.moe_dropless(params["moe"], xn, cfg, ctx)
            return x + mo, aux + a, content
        return x + L.swiglu(params["mlp"], xn, ctx), aux, content

    return pf


def _mamba_block_init(moe: bool):
    def init(key, cfg, dtype):
        k1, k2 = jax.random.split(key)
        p = {
            "ln1": L.rmsnorm_init(cfg.d_model, dtype),
            "mamba": SSM.mamba_init(k1, cfg, dtype),
            "ln2": L.rmsnorm_init(cfg.d_model, dtype),
        }
        if moe:
            p["moe"] = L.moe_init(k2, cfg, dtype)
        else:
            p["mlp"] = L.swiglu_init(k2, cfg.d_model, cfg.d_ff, dtype)
        return p

    return init


def _mamba_block_specs(moe: bool):
    def specs(cfg):
        s = {
            "ln1": {"scale": ("d_model",)},
            "mamba": SSM.mamba_specs(cfg),
            "ln2": {"scale": ("d_model",)},
        }
        s["moe" if moe else "mlp"] = L.moe_specs(cfg) if moe else L.swiglu_specs()
        return s

    return specs


def _mamba_fwd(moe: bool):
    def fwd(params, x, cfg, ctx, aux):
        h, _ = SSM.mamba_fwd(params["mamba"], L.rmsnorm(params["ln1"], x), cfg, ctx)
        x = x + h
        xn = L.rmsnorm(params["ln2"], x)
        if moe:
            mo, a = L.moe_forward(params["moe"], xn, cfg, ctx)
            return x + mo, aux + a
        return x + L.swiglu(params["mlp"], xn, ctx), aux

    return fwd


def _mamba_decode(moe: bool):
    def dec(params, x, cfg, cache, pos, ctx):
        h, st = SSM.mamba_decode(params["mamba"], L.rmsnorm(params["ln1"], x), cfg, cache)
        x = x + h
        xn = L.rmsnorm(params["ln2"], x)
        if moe:
            mo, _, pairs = L.moe_dropless(params["moe"], xn, cfg, ctx)
            return x + mo, st, pairs
        return x + L.swiglu(params["mlp"], xn, ctx), st, None

    return dec


def _rwkv_init(key, cfg, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": L.layernorm_init(cfg.d_model, dtype),
        "tm": SSM.rwkv6_init(k1, cfg, dtype),
        "ln2": L.layernorm_init(cfg.d_model, dtype),
        "cm": SSM.rwkv6_channel_mix_init(k2, cfg, dtype),
    }


def _rwkv_specs(cfg):
    return {
        "ln1": {"scale": ("d_model",), "bias": ("d_model",)},
        "tm": SSM.rwkv6_specs(cfg),
        "ln2": {"scale": ("d_model",), "bias": ("d_model",)},
        "cm": SSM.rwkv6_channel_mix_specs(),
    }


def _rwkv_fwd(params, x, cfg, ctx, aux):
    h, _ = SSM.rwkv6_time_mix(params["tm"], L.layernorm(params["ln1"], x), cfg, ctx)
    x = x + h
    h2, _ = SSM.rwkv6_channel_mix(params["cm"], L.layernorm(params["ln2"], x))
    return x + h2, aux


def _rwkv_decode(params, x, cfg, cache, pos, ctx):
    xn = L.layernorm(params["ln1"], x)
    h, (wkv, tm_prev) = SSM.rwkv6_time_mix(
        params["tm"], xn, cfg, ctx, state=cache["wkv"], x_prev=cache["tm_prev"],
        return_state=True,
    )
    x = x + h
    xn2 = L.layernorm(params["ln2"], x)
    h2, cm_prev = SSM.rwkv6_channel_mix(params["cm"], xn2, x_prev=cache["cm_prev"], return_state=True)
    return x + h2, {"wkv": wkv, "tm_prev": tm_prev, "cm_prev": cm_prev}, None


_KINDS: dict[str, dict[str, Any]] = {
    "dense": dict(init=_dense_init, specs=_dense_specs, fwd=_dense_fwd, decode=_dense_decode, cache="kv", prefill=_dense_prefill),
    "moe": dict(init=_moe_init, specs=_moe_specs, fwd=_moe_fwd, decode=_moe_decode, cache="kv", prefill=_moe_prefill),
    "mla_dense": dict(init=_mla_block_init(False), specs=_mla_block_specs(False), fwd=_mla_fwd(False), decode=_mla_decode(False), cache="mla", prefill=_mla_prefill(False)),
    "mla_moe": dict(init=_mla_block_init(True), specs=_mla_block_specs(True), fwd=_mla_fwd(True), decode=_mla_decode(True), cache="mla", prefill=_mla_prefill(True)),
    # recurrent states have no per-position cache rows a one-pass prefill
    # could write; engines fall back to the per-token refeed for these
    "mamba": dict(init=_mamba_block_init(False), specs=_mamba_block_specs(False), fwd=_mamba_fwd(False), decode=_mamba_decode(False), cache="mamba", prefill=None),
    "mamba_moe": dict(init=_mamba_block_init(True), specs=_mamba_block_specs(True), fwd=_mamba_fwd(True), decode=_mamba_decode(True), cache="mamba", prefill=None),
    "rwkv": dict(init=_rwkv_init, specs=_rwkv_specs, fwd=_rwkv_fwd, decode=_rwkv_decode, cache="rwkv", prefill=None),
}


def layer_pattern(cfg: ModelConfig) -> tuple[list[str], list[str], int]:
    """(prefix kinds, body period kinds, n_repeats)."""
    n = cfg.n_layers
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        return [], ["rwkv"], n
    if cfg.ssm is not None and cfg.ssm.kind == "mamba":
        period = cfg.ssm.attn_layer_period or 8
        kinds = []
        for i in range(period):
            is_attn = (i % period) == cfg.ssm.attn_layer_offset
            is_moe = cfg.moe is not None and (i % cfg.moe.layer_period) == cfg.moe.layer_offset
            if is_attn:
                kinds.append("moe" if is_moe else "dense")
            else:
                kinds.append("mamba_moe" if is_moe else "mamba")
        assert n % period == 0
        return [], kinds, n // period
    if cfg.mla is not None:
        fd = cfg.moe.first_dense if cfg.moe else 0
        return ["mla_dense"] * fd, ["mla_moe"], n - fd
    if cfg.moe is not None:
        return [], ["moe"], n
    return [], ["dense"], n


# ---------------------------------------------------------------------------
# cache constructors
# ---------------------------------------------------------------------------


def _cache_init_for(kind: str, cfg, batch, s_max, dtype):
    c = _KINDS[kind]["cache"]
    if c == "kv":
        return _kv_cache_init(cfg, batch, s_max, dtype)
    if c == "mla":
        return MLA.mla_cache_init(cfg, batch, s_max, dtype)
    if c == "mamba":
        return SSM.mamba_state_init(cfg, batch, dtype)
    if c == "rwkv":
        return SSM.rwkv6_state_init(cfg, batch, dtype)
    raise KeyError(c)


def _write_slot(cache_tree, content_tree, slot):
    """Write per-layer prefill content (1, L, ...) into row ``slot`` of the
    batched cache leaves (B, Smax, ...) — ``slot`` may be a traced scalar."""

    def write(leaf, content):
        starts = (slot,) + (0,) * (leaf.ndim - 1)
        return jax.lax.dynamic_update_slice(leaf, content.astype(leaf.dtype), starts)

    return jax.tree.map(write, cache_tree, content_tree)


def _cache_dims_for(kind: str):
    c = _KINDS[kind]["cache"]
    if c == "kv":
        return _kv_cache_dims()
    if c == "mla":
        return {"c_kv": ("batch", "kv_seq", None), "k_rope": ("batch", "kv_seq", None)}
    if c == "mamba":
        return (("batch", "d_ff", "state"), ("batch", "conv", "d_ff"))
    if c == "rwkv":
        return {
            "wkv": ("batch", "heads", None, None),
            "tm_prev": ("batch", None, "d_model"),
            "cm_prev": ("batch", None, "d_model"),
        }
    raise KeyError(c)


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
        self.prefix, self.body, self.repeats = layer_pattern(cfg)
        self.is_encdec = cfg.encdec is not None
        self.is_vlm = cfg.vlm is not None

    # -- params ------------------------------------------------------------
    def init(self, rng) -> dict:
        cfg, dtype = self.cfg, self.dtype
        keys = jax.random.split(rng, 8)
        params: dict[str, Any] = {
            "embed": L.truncnorm_init(keys[0], (cfg.vocab_padded, cfg.d_model), dtype),
            "ln_f": L.rmsnorm_init(cfg.d_model, dtype),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = L.truncnorm_init(keys[1], (cfg.d_model, cfg.vocab_padded), dtype)
        for i, kind in enumerate(self.prefix):
            params[f"prefix_{i}"] = _KINDS[kind]["init"](jax.random.fold_in(keys[2], i), cfg, dtype)
        body = []
        for r in range(self.repeats):
            blk = {}
            for j, kind in enumerate(self.body):
                blk[f"b{j}"] = _KINDS[kind]["init"](
                    jax.random.fold_in(keys[3], r * len(self.body) + j), cfg, dtype
                )
            body.append(blk)
        params["body"] = jax.tree.map(lambda *xs: jnp.stack(xs), *body)
        if self.is_encdec:
            params["encoder"] = self._encoder_init(keys[4])
        if cfg.mtp:
            params["mtp"] = self._mtp_init(keys[5])
        return params

    def _encoder_init(self, key):
        cfg, dtype = self.cfg, self.dtype
        enc_layers = []
        for i in range(cfg.encdec.n_enc_layers):
            k = jax.random.fold_in(key, i)
            k1, k2 = jax.random.split(k)
            enc_layers.append(
                {
                    "ln1": L.layernorm_init(cfg.d_model, dtype),
                    "attn": L.attention_init(k1, cfg, dtype),
                    "ln2": L.layernorm_init(cfg.d_model, dtype),
                    "mlp": L.gelu_mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
                }
            )
        cross = []
        for i in range(cfg.n_layers):
            k = jax.random.fold_in(jax.random.fold_in(key, 1000), i)
            cross.append({"ln": L.layernorm_init(cfg.d_model, dtype), "attn": L.attention_init(k, cfg, dtype)})
        return {
            "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *enc_layers),
            "ln_post": L.layernorm_init(cfg.d_model, dtype),
            "cross": jax.tree.map(lambda *xs: jnp.stack(xs), *cross),
        }

    def _mtp_init(self, key):
        cfg, dtype = self.cfg, self.dtype
        k1, k2 = jax.random.split(key)
        return {
            "norm_h": L.rmsnorm_init(cfg.d_model, dtype),
            "norm_e": L.rmsnorm_init(cfg.d_model, dtype),
            "proj": L.truncnorm_init(k1, (2 * cfg.d_model, cfg.d_model), dtype),
            "block": _KINDS[self.body[-1]]["init"](k2, cfg, dtype),
        }

    def param_specs(self):
        """(ShapeDtypeStruct pytree, logical-dims pytree) without allocation."""
        shapes = jax.eval_shape(lambda: self.init(jax.random.key(0)))
        dims = self._dims_tree()
        return shapes, dims

    def _dims_tree(self):
        cfg = self.cfg
        dims: dict[str, Any] = {
            "embed": ("vocab", "d_model"),
            "ln_f": {"scale": ("d_model",)},
        }
        if not cfg.tie_embeddings:
            dims["lm_head"] = ("d_model", "vocab")
        for i, kind in enumerate(self.prefix):
            dims[f"prefix_{i}"] = _KINDS[kind]["specs"](cfg)
        body = {}
        for j, kind in enumerate(self.body):
            # leading scan dim → None
            body[f"b{j}"] = jax.tree.map(
                lambda d: (None, *d),
                _KINDS[kind]["specs"](cfg),
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            )
        dims["body"] = body
        if self.is_encdec:
            enc_specs = {
                "ln1": {"scale": ("d_model",), "bias": ("d_model",)},
                "attn": L.attention_specs(cfg),
                "ln2": {"scale": ("d_model",), "bias": ("d_model",)},
                "mlp": L.gelu_mlp_specs(),
            }
            stack = lambda tree: jax.tree.map(
                lambda d: (None, *d), tree,
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            )
            dims["encoder"] = {
                "layers": stack(enc_specs),
                "ln_post": {"scale": ("d_model",), "bias": ("d_model",)},
                "cross": stack({"ln": {"scale": ("d_model",), "bias": ("d_model",)}, "attn": L.attention_specs(cfg)}),
            }
        if cfg.mtp:
            dims["mtp"] = {
                "norm_h": {"scale": ("d_model",)},
                "norm_e": {"scale": ("d_model",)},
                "proj": (None, "d_model"),
                "block": _KINDS[self.body[-1]]["specs"](cfg),
            }
        return dims

    # -- embedding / head ----------------------------------------------------
    def _embed(self, params, tokens):
        return jnp.take(params["embed"], tokens, axis=0)

    def _head(self, params, x):
        w = params["embed"].T if self.cfg.tie_embeddings else params["lm_head"]
        logits = (x @ w).astype(jnp.float32)
        if self.cfg.vocab_padded > self.cfg.vocab_size:
            pad = self.cfg.vocab_padded - self.cfg.vocab_size
            logits = logits - jnp.pad(
                jnp.zeros((self.cfg.vocab_size,), jnp.float32),
                (0, pad),
                constant_values=1e30,
            )
        return logits

    # -- encoder (whisper stub frontend) -------------------------------------
    def _encode_frames(self, params, frames, ctx):
        """frames: (B, F, d) precomputed stub embeddings → encoder output."""
        cfg = self.cfg
        pos = _sinusoidal(frames.shape[1], cfg.d_model).astype(frames.dtype)
        x = frames + pos[None]

        def step(x, lp):
            h, _ = L.attention_fwd(
                lp["attn"], L.layernorm(lp["ln1"], x), cfg, ctx, rope=False, causal=False
            )
            x = x + h
            x = x + L.gelu_mlp(lp["mlp"], L.layernorm(lp["ln2"], x), ctx)
            return x, None

        x, _ = jax.lax.scan(step, x, params["encoder"]["layers"])
        return L.layernorm(params["encoder"]["ln_post"], x)

    # -- trunk ----------------------------------------------------------------
    def _trunk(self, params, x, ctx, enc_out=None):
        """Full-seq forward through prefix + scanned body. Returns (x, aux)."""
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(self.prefix):
            x, aux = _KINDS[kind]["fwd"](params[f"prefix_{i}"], x, cfg, ctx, aux)

        body_fns = [_KINDS[k]["fwd"] for k in self.body]
        cross_params = params["encoder"]["cross"] if self.is_encdec else None

        def body_step(carry, xs):
            x, aux, li = carry
            blk = xs["blk"]
            for j, fn in enumerate(body_fns):
                x, aux = fn(blk[f"b{j}"], x, cfg, ctx, aux)
                if cross_params is not None:
                    cp = jax.tree.map(lambda a, _li=li, _j=j: a[li * len(body_fns) + _j], cross_params)
                    x = x + self._cross_attn(cp, x, enc_out, cfg, ctx)
            return (x, aux, li + 1), None

        if self.is_encdec:
            # index cross params dynamically inside scan
            def body_step2(carry, blk):
                x, aux, li = carry
                for j, fn in enumerate(body_fns):
                    x, aux = fn(blk[f"b{j}"], x, cfg, ctx, aux)
                    cp = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, li * len(body_fns) + j, keepdims=False),
                        cross_params,
                    )
                    x = x + self._cross_attn(cp, x, enc_out, cfg, ctx)
                return (x, aux, li + 1), None

            (x, aux, _), _ = jax.lax.scan(body_step2, (x, aux, 0), params["body"])
        else:
            def body_step3(carry, blk):
                x, aux = carry
                for j, fn in enumerate(body_fns):
                    fn_ = fn
                    if cfg.remat == "block":
                        fn_ = jax.checkpoint(fn, static_argnums=(2, 3))
                    x, aux = fn_(blk[f"b{j}"], x, cfg, ctx, aux)
                return (x, aux), None

            (x, aux), _ = jax.lax.scan(body_step3, (x, aux), params["body"])
        return L.rmsnorm(params["ln_f"], x), aux

    def _cross_attn(self, cp, x, enc_out, cfg, ctx):
        """Decoder cross-attention onto encoder output (whisper)."""
        xn = L.layernorm(cp["ln"], x)
        B, S, _ = x.shape
        H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        q = (xn @ cp["attn"]["wq"]).reshape(B, S, H, hd)
        k = (enc_out @ cp["attn"]["wk"]).reshape(B, -1, Hkv, hd)
        v = (enc_out @ cp["attn"]["wv"]).reshape(B, -1, Hkv, hd)
        o = L.chunked_causal_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=False,
        )
        return o.transpose(0, 2, 1, 3).reshape(B, S, -1) @ cp["attn"]["wo"]

    # -- public forward/loss --------------------------------------------------
    def forward(self, params, batch, ctx=L.NO_CTX):
        """batch: {"tokens": (B,S) int32, optional "frames"/"patches"} → logits."""
        cfg = self.cfg
        x = self._embed(params, batch["tokens"]).astype(self.dtype)
        enc_out = None
        if self.is_encdec:
            enc_out = self._encode_frames(params, batch["frames"].astype(self.dtype), ctx)
            pos = _sinusoidal(x.shape[1], cfg.d_model).astype(x.dtype)
            x = x + pos[None]
        if self.is_vlm:
            x = jnp.concatenate([batch["patches"].astype(self.dtype), x], axis=1)
        x = ctx.cons(x, ("batch", "seq", "d_model"))
        h, aux = self._trunk(params, x, ctx, enc_out)
        if self.is_vlm:
            h = h[:, batch["patches"].shape[1] :]
        logits = self._head(params, h)
        return logits, aux, h

    def loss(self, params, batch, ctx=L.NO_CTX):
        """Causal LM loss (+MoE aux, +MTP when enabled)."""
        cfg = self.cfg
        logits, aux, h = self.forward(params, batch, ctx)
        tokens = batch["tokens"]
        labels = batch["labels"]
        mask = (labels >= 0).astype(jnp.float32)
        ce = _xent(logits[:, :-1], labels[:, 1:], mask[:, 1:])
        metrics = {"ce": ce, "aux": aux}
        total = ce + 0.01 * aux
        if cfg.mtp:
            mtp = params["mtp"]
            # predict t+2: combine h_i with embed(t_{i+1})
            emb_next = self._embed(params, tokens[:, 1:]).astype(self.dtype)
            hcomb = jnp.concatenate(
                [L.rmsnorm(mtp["norm_h"], h[:, :-1]), L.rmsnorm(mtp["norm_e"], emb_next)],
                axis=-1,
            ) @ mtp["proj"]
            hm, _ = _KINDS[self.body[-1]]["fwd"](
                mtp["block"], hcomb, cfg, ctx, jnp.zeros((), jnp.float32)
            )
            mtp_logits = self._head(params, hm)
            mtp_ce = _xent(mtp_logits[:, :-1], labels[:, 2:], mask[:, 2:])
            metrics["mtp_ce"] = mtp_ce
            total = total + 0.3 * mtp_ce
        metrics["loss"] = total
        return total, metrics

    # -- serving ---------------------------------------------------------------
    def init_cache(self, batch: int, s_max: int):
        cfg, dtype = self.cfg, self.dtype
        caches = []
        for r in range(self.repeats):
            blk = {f"b{j}": _cache_init_for(k, cfg, batch, s_max, dtype) for j, k in enumerate(self.body)}
            caches.append(blk)
        cache: dict[str, Any] = {"body": jax.tree.map(lambda *xs: jnp.stack(xs), *caches)}
        for i, kind in enumerate(self.prefix):
            cache[f"prefix_{i}"] = _cache_init_for(kind, cfg, batch, s_max, dtype)
        if self.is_encdec:
            cache["enc_out"] = jnp.zeros((batch, cfg.encdec.n_frames, cfg.d_model), dtype)
        return cache

    def cache_dims(self):
        dims: dict[str, Any] = {}
        body = {}
        for j, kind in enumerate(self.body):
            body[f"b{j}"] = jax.tree.map(
                lambda d: (None, *d),
                _cache_dims_for(kind),
                is_leaf=lambda x: isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x),
            )
        dims["body"] = body
        for i, kind in enumerate(self.prefix):
            dims[f"prefix_{i}"] = _cache_dims_for(kind)
        if self.is_encdec:
            dims["enc_out"] = ("batch", "frames", "d_model")
        return dims

    def decode_step(self, params, cache, tokens, pos, ctx=L.NO_CTX, with_pairs=False):
        """tokens: (B,1) int32; pos: (B,) int32 → (logits (B,1,V), new cache).

        ``with_pairs`` also returns the (token, expert) pairs each held
        expert computed, summed over the layers ((held,) int32; None for a
        model without experts)."""
        cfg = self.cfg
        cache = dict(cache)
        x = self._embed(params, tokens).astype(self.dtype)
        if self.is_encdec:
            ppos = _sinusoidal_at(pos, cfg.d_model).astype(x.dtype)
            x = x + ppos[:, None, :]
        enc_out = cache.get("enc_out") if self.is_encdec else None
        pairs = None
        for i, kind in enumerate(self.prefix):
            x, cache[f"prefix_{i}"], p = _KINDS[kind]["decode"](
                params[f"prefix_{i}"], x, cfg, cache[f"prefix_{i}"], pos, ctx
            )
            pairs = _add_pairs(pairs, p)
        dec_fns = [_KINDS[k]["decode"] for k in self.body]
        cross_params = params["encoder"]["cross"] if self.is_encdec else None

        def step(carry, xs):
            x, li = carry
            blk, bcache = xs
            new_bcache = {}
            step_pairs = None
            for j, fn in enumerate(dec_fns):
                x, new_bcache[f"b{j}"], p = fn(blk[f"b{j}"], x, cfg, bcache[f"b{j}"], pos, ctx)
                step_pairs = _add_pairs(step_pairs, p)
                if cross_params is not None:
                    cp = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(a, li * len(dec_fns) + j, keepdims=False),
                        cross_params,
                    )
                    x = x + self._cross_attn(cp, x, enc_out, cfg, ctx)
            return (x, li + 1), (new_bcache, step_pairs)

        (x, _), (new_body, body_pairs) = jax.lax.scan(
            step, (x, 0), (params["body"], cache["body"])
        )
        cache["body"] = new_body
        logits = self._head(params, L.rmsnorm(params["ln_f"], x))
        if with_pairs:
            if body_pairs is not None:
                pairs = _add_pairs(pairs, body_pairs.sum(0))
            return logits, cache, pairs
        return logits, cache

    def prefill(self, params, batch, ctx=L.NO_CTX):
        """Run the full prompt, returning logits; cache building for decode is
        exercised separately (decode_step), matching the dry-run contract."""
        return self.forward(params, batch, ctx)

    @property
    def supports_prefill(self) -> bool:
        """True iff every layer kind can emit its cache rows from one
        full-sequence pass (attention K/V and MLA latents can; recurrent
        mamba/rwkv states and the enc-dec/VLM frontends cannot)."""
        if self.is_encdec or self.is_vlm:
            return False
        return all(
            _KINDS[k].get("prefill") is not None for k in (*self.prefix, *self.body)
        )

    def prefill_into_cache(self, params, cache, tokens, slot, ctx=L.NO_CTX):
        """One-pass prompt prefill into a decode-slot cache row.

        ``tokens``: (1, L) int32, the prompt right-padded to a length bucket
        L ≤ Smax. Runs the full-sequence trunk once, writing every layer's
        cache content for positions [0, L) into row ``slot`` of the batched
        decode ``cache``, and returns ``(logits (1, L, V_padded), cache)``.
        Rows of the padded tail carry garbage K/V, which the decode path
        never attends (its mask is ``t <= pos`` and the per-token decode
        overwrites position p before attending it).
        """
        if not self.supports_prefill:
            raise NotImplementedError(
                f"{self.cfg.name}: one-pass prefill needs per-position cache "
                "rows in every layer (recurrent/enc-dec/VLM models refeed)"
            )
        cfg = self.cfg
        cache = dict(cache)
        x = self._embed(params, tokens).astype(self.dtype)
        x = ctx.cons(x, ("batch", "seq", "d_model"))
        aux = jnp.zeros((), jnp.float32)
        for i, kind in enumerate(self.prefix):
            x, aux, content = _KINDS[kind]["prefill"](
                params[f"prefix_{i}"], x, cfg, ctx, aux
            )
            cache[f"prefix_{i}"] = _write_slot(cache[f"prefix_{i}"], content, slot)
        pf_fns = [_KINDS[k]["prefill"] for k in self.body]

        def step(carry, xs):
            x, aux = carry
            blk, bcache = xs
            new_bcache = {}
            for j, fn in enumerate(pf_fns):
                x, aux, content = fn(blk[f"b{j}"], x, cfg, ctx, aux)
                new_bcache[f"b{j}"] = _write_slot(bcache[f"b{j}"], content, slot)
            return (x, aux), new_bcache

        (x, _), new_body = jax.lax.scan(step, (x, aux), (params["body"], cache["body"]))
        cache["body"] = new_body
        logits = self._head(params, L.rmsnorm(params["ln_f"], x))
        return logits, cache


def _add_pairs(a, b):
    return b if a is None else a if b is None else a + b


def _xent(logits, labels, mask):
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = (lse - ll) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


@functools.lru_cache(maxsize=8)
def _sin_table(S: int, d: int):
    pos = np.arange(S)[:, None]
    i = np.arange(d // 2)[None, :]
    ang = pos / (10000 ** (2 * i / d))
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1).astype(np.float32)


def _sinusoidal(S: int, d: int):
    return jnp.asarray(_sin_table(S, d))


def _sinusoidal_at(pos, d: int):
    half = d // 2
    i = jnp.arange(half, dtype=jnp.float32)
    ang = pos.astype(jnp.float32)[:, None] / (10000 ** (2 * i / d))[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
