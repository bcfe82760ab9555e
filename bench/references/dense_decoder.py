"""Plain reference of a dense GQA decoder (Qwen3: RMSNorm, per-head q/k
RMSNorm, rotary positions rotating the two halves, grouped-query causal
attention, SwiGLU, tied embeddings), from the published description.

Float32 throughout, every product at ``Precision.HIGHEST``; no cache, no
batching, no kernels. It imports nothing of the program: it reads the
benchmark's own weights by their names and runs one sequence through all
layers, the stacked layer weights scanned one layer at a time so that only
one layer is ever upcast.

``mode`` switches the control on: ``"int8"`` rounds every matrix product's
weights (per output channel) and inputs (per token) to int8, symmetric
absmax, the step a later change would be tempted by on a chip with an int8
path; ``"fp8"`` does the same in float8 e4m3.
"""

from __future__ import annotations

import functools

import numpy as np


def _dims(config):
    return (int(config["num_hidden_layers"]), int(config["hidden_size"]),
            int(config["num_attention_heads"]), int(config["num_key_value_heads"]),
            int(config["head_dim"]), int(config["vocab_size"]),
            float(config["rms_norm_eps"]), float(config["rope_theta"]))


def _fake_quant(x, axis, mode):
    """Round ``x`` to the control's 8-bit grid along ``axis`` (the reduced
    dimension keeps its scale per remaining index) and back to float32."""
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


@functools.cache
def forward_fn(config_items: tuple, T: int, mode: str = "f32"):
    """Jitted ``(weights, tokens (T,) int32) -> logits (T, vocab) float32``."""
    import jax
    import jax.numpy as jnp

    config = dict(config_items)
    L, d, H, Hkv, hd, V, eps, theta = _dims(config)
    G = H // Hkv
    half = hd // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float64) * 2.0 / hd))
    ang = np.arange(T, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    causal = jnp.asarray(np.tril(np.ones((T, T), bool)))
    hi = jax.lax.Precision.HIGHEST

    def rope(x):  # (T, h, hd)
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    def layer(x, lw):
        f = lambda a: a.astype(jnp.float32)  # noqa: E731
        at, mlp = lw["attn"], lw["mlp"]
        h = _rms(x, f(lw["ln1"]["scale"]), eps)
        q = _mm(h, f(at["wq"]), mode).reshape(T, H, hd)
        k = _mm(h, f(at["wk"]), mode).reshape(T, Hkv, hd)
        v = _mm(h, f(at["wv"]), mode).reshape(T, Hkv, hd)
        q = rope(_rms(q, f(at["q_norm"]["scale"]), eps))
        k = rope(_rms(k, f(at["k_norm"]["scale"]), eps))
        qg = q.reshape(T, Hkv, G, hd)
        s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=hi) / np.sqrt(hd)
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=hi).reshape(T, H * hd)
        x = x + _mm(o, f(at["wo"]), mode)
        h = _rms(x, f(lw["ln2"]["scale"]), eps)
        u = jax.nn.silu(_mm(h, f(mlp["w_gate"]), mode)) * _mm(h, f(mlp["w_up"]), mode)
        return x + _mm(u, f(mlp["w_down"]), mode), None

    def fwd(weights, tokens):
        emb = weights["embed"][:V]
        x = jnp.take(emb, tokens, axis=0).astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, weights["body"]["b0"])
        x = _rms(x, weights["ln_f"]["scale"].astype(jnp.float32), eps)
        return _mm(x, emb.astype(jnp.float32).T, mode)

    return jax.jit(fwd)


def _items(config: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "num_attention_heads",
            "num_key_value_heads", "head_dim", "vocab_size", "rms_norm_eps",
            "rope_theta")
    return tuple((k, config[k]) for k in keys)


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
    tok = jnp.asarray(seq)
    ref = forward_fn(_items(config), T, "f32")(weights, tok)[plen - 1:plen - 1 + g]
    if control is None:
        chosen = jnp.asarray(np.asarray(served, np.int32))
    else:
        lg = forward_fn(_items(config), T, control)(weights, tok)[plen - 1:plen - 1 + g]
        chosen = jnp.argmax(lg, axis=-1)
    best = jnp.max(ref, axis=-1)
    got = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    scale = jnp.max(jnp.abs(ref), axis=-1)
    return np.asarray((best - got) / scale, np.float64)
