"""Cost-model validation: jaxpr walker exactness, collective parsing, and
analytic param counts vs PUBLIC model sizes (catches config drift)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get
from repro.launch.dryrun import parse_collectives
from repro.launch.jaxpr_cost import cost_of_fn
from repro.launch.roofline import param_counts


def test_jaxpr_cost_scan_trip_counts():
    def body(c, x):
        return c @ x, ()

    def f(c, xs):
        out, _ = jax.lax.scan(body, c, xs)
        return out

    c = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    xs = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    cost = cost_of_fn(f, c, xs)
    assert cost.flops == pytest.approx(10 * 2 * 64**3, rel=1e-6)


def test_jaxpr_cost_nested_scan():
    def f(c, xs):
        def outer(c, x):
            def inner(c2, x2):
                return c2 @ x2, ()
            o, _ = jax.lax.scan(inner, c, xs)
            return o, ()
        out, _ = jax.lax.scan(outer, c, xs)
        return out

    c = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    xs = jax.ShapeDtypeStruct((5, 32, 32), jnp.float32)
    cost = cost_of_fn(f, c, xs)
    assert cost.flops == pytest.approx(25 * 2 * 32**3, rel=1e-6)


def test_jaxpr_cost_counts_grad_and_remat():
    def layer(w, x):
        return jnp.tanh(x @ w)

    def loss(w, x):
        return jax.checkpoint(layer)(w, x).sum()

    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
    base = cost_of_fn(loss, w, x).flops
    g = cost_of_fn(jax.grad(loss), w, x).flops
    assert g >= 2.5 * base  # fwd + recompute + 2 bwd matmuls


def test_parse_collectives():
    hlo = """
  %ag = bf16[256,1024]{1,0} all-gather(%x), replica_groups={...}
  %ar.1 = f32[128]{0} all-reduce(%y), to_apply=%sum
  %cp = u32[16,16]{1,0} collective-permute(%z), source_target_pairs={{0,1}}
  %ags = bf16[64]{0} all-gather-start(%w)
  %agd = bf16[64]{0} all-gather-done(%ags)
"""
    got = parse_collectives(hlo)
    assert got["all-gather"]["count"] == 2
    assert got["all-gather"]["bytes"] == 256 * 1024 * 2 + 64 * 2
    assert got["all-reduce"]["bytes"] == 128 * 4
    assert got["collective-permute"]["bytes"] == 16 * 16 * 4


# public sizes: (total_B, active_B, rel_tol)
PUBLIC_SIZES = {
    "qwen1.5-32b": (32.5e9, 32.5e9, 0.12),
    "deepseek-coder-33b": (33.3e9, 33.3e9, 0.05),
    "qwen3-1.7b": (1.72e9, 1.72e9, 0.05),
    "internlm2-20b": (19.9e9, 19.9e9, 0.05),
    "arctic-480b": (480e9, 17e9, 0.12),
    "deepseek-v3-671b": (671e9, 37e9, 0.05),
    # one chip of EP32: 7 layers, 8 of 256 experts (all a token can reach
    # here), a 16,384-row padded slice of the vocabulary
    "deepseek-v3-ep32": (4.32e9, 4.32e9, 0.01),
    "rwkv6-3b": (3.0e9, 3.0e9, 0.08),
    "jamba-v0.1-52b": (52e9, 12e9, 0.05),
    "internvl2-26b": (20e9, 20e9, 0.05),  # LLM backbone only (ViT stubbed)
    "whisper-base": (74e6, 74e6, 0.45),  # + vocab padding & cross-attn acct
}


@pytest.mark.parametrize("name", list(ARCHS))
def test_param_counts_match_public(name):
    pc = param_counts(get(name))
    tot, act, tol = PUBLIC_SIZES[name]
    assert pc["total"] == pytest.approx(tot, rel=tol), pc
    assert pc["active"] == pytest.approx(act, rel=tol), pc
