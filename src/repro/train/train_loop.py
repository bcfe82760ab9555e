"""Train/serve step factories with mesh-aware shardings.

``make_train_step`` builds the jit-able step used by both the real trainer
(examples/train_lm.py) and the multi-pod dry-run (launch/dryrun.py): the
SAME function lowers on 1 CPU device or on the 512-chip production mesh —
only the shardings differ.

Gradient accumulation: ``accum > 1`` splits the batch's leading dim into
microbatches and lax.scan's over them (sequential, memory-bounded).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist.sharding import ShardingRules, named_sharding
from repro.models.layers import NO_CTX, Ctx
from repro.models.inputs import batch_dims
from . import optimizer as opt


def make_ctx(mesh=None, rules: ShardingRules | None = None) -> Ctx:
    return Ctx(mesh, rules or ShardingRules()) if mesh is not None else NO_CTX


def make_train_step(model, opt_cfg: opt.OptConfig, mesh=None, rules=None, accum: int = 1):
    ctx = make_ctx(mesh, rules)

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, ctx)
        return loss, metrics

    def train_step(params, opt_state, batch):
        if accum == 1:
            (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch
            )
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((accum, x.shape[0] // accum) + x.shape[1:]), batch
            )

            def mb_step(carry, mb):
                acc_g, acc_l = carry
                (l, m), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                acc_g = jax.tree.map(jnp.add, acc_g, g)
                return (acc_g, acc_l + l), m

            zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), ms = jax.lax.scan(mb_step, (zero_g, 0.0), micro)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum
            metrics = jax.tree.map(lambda m: m[-1], ms)
        new_params, new_state, om = opt.apply_updates(opt_cfg, params, grads, opt_state)
        return new_params, new_state, {**metrics, **om, "loss": loss}

    return train_step


def make_decode_step(model, mesh=None, rules=None, with_pairs=False):
    """``(params, cache, tokens, pos) → (logits, cache)``; with
    ``with_pairs`` also the held experts' pairs (``Model.decode_step``)."""
    ctx = make_ctx(mesh, rules)

    def decode_step(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, ctx, with_pairs=with_pairs)

    return decode_step


def make_prefill_step(model, mesh=None, rules=None, into_cache: bool = False):
    """Prefill step factory.

    ``into_cache=False`` (legacy, dry-run contract): ``(params, batch) →
    logits`` — full forward over the prompt, no cache.

    ``into_cache=True`` (serving contract): ``(params, cache, tokens (1, L),
    slot, plen) → (last_logits (1, V_padded), cache)`` — ONE forward pass
    writes the prompt's per-layer K/V into row ``slot`` of the batched
    decode cache and returns the logits of position ``plen - 1``, i.e. the
    first generated token's distribution. This replaces the per-token
    prompt refeed: jit it once per length bucket L and the prompt costs one
    graph launch instead of ``plen`` decode steps.
    """
    ctx = make_ctx(mesh, rules)

    if into_cache:

        def prefill_cache(params, cache, tokens, slot, plen):
            logits, cache = model.prefill_into_cache(params, cache, tokens, slot, ctx)
            idx = jnp.reshape(jnp.maximum(plen - 1, 0), (1, 1, 1))
            last = jnp.take_along_axis(logits, idx, axis=1)[:, 0]
            return last, cache

        return prefill_cache

    def prefill(params, batch):
        logits, aux, _ = model.forward(params, batch, ctx)
        return logits

    return prefill


# ---------------------------------------------------------------------------
# shardings (dry-run + real placement share these)
# ---------------------------------------------------------------------------


def param_shardings(model, mesh, rules: ShardingRules):
    shapes, dims = model.param_specs()
    return _tree_shard(mesh, rules, shapes, dims)


def _tree_shard(mesh, rules, shapes, dims):
    def is_dims(x):
        return isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x)

    flat_s = jax.tree.flatten(shapes)[0]
    flat_d, treedef = jax.tree.flatten(dims, is_leaf=is_dims)
    assert len(flat_s) == len(flat_d), (len(flat_s), len(flat_d))
    out = [
        named_sharding(mesh, rules, d, s.shape) for s, d in zip(flat_s, flat_d)
    ]
    return jax.tree.unflatten(treedef, out)


def opt_state_shardings(opt_cfg, model, mesh, rules: ShardingRules):
    pshard = _tree_shard(mesh, rules, *model.param_specs())
    return {
        "m": pshard,
        "v": pshard,
        "step": NamedSharding(mesh, P()),
    }


def batch_shardings(model, mesh, rules: ShardingRules, kind="train"):
    dims = batch_dims(model.cfg, kind)
    return {
        k: named_sharding(mesh, rules, d) for k, d in dims.items()
    }


def cache_shardings(model, mesh, rules: ShardingRules, cache_shapes):
    dims = model.cache_dims()
    return _tree_shard(mesh, rules, cache_shapes, dims)
