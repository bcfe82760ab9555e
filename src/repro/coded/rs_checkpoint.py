"""Erasure-coded optimizer/parameter state across the data-parallel axis —
the paper's all-to-all encode as the framework's fault-tolerance fast path
(DESIGN §2, §8; Remark 1 of the paper).

Scheme
------
Every DP replica k holds a distinct state shard x_k (ZeRO-style). Every
``coded_every`` steps the replicas run ONE all-to-all encode of the Cauchy
generator A (universal prepare-and-shoot — C1 = ⌈log_{p+1}K⌉ rounds,
C2 = Θ(√K/p) elements, vs Θ(K/p) for the all-gather a naive scheme needs):
replica k ends up holding the parity packet

    P_k = Σ_r x_r · A[r, k]        (in GF(2^31−1), exact)

in spare HBM. Loss of any set F of ≤ K−|F| nodes destroys {x_k, P_k : k∈F};
the survivors recover every lost x_r bit-exactly by solving the f×f Cauchy
subsystem  Σ_{r∈F} x_r A[r, j] = P_j − Σ_{r∉F} x_r A[r, j]  for any f
surviving parity indices j (every square Cauchy submatrix is invertible).

Bit-exactness over floats: state is bitcast to 16-bit limbs (canonical
elements < 2^16 < q), encoded, and reassembled — no rounding anywhere.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.field import M31, Field
from repro.core.matrices import cauchy_matrix
from repro.core.prepare_shoot import encode_universal
from repro.core.schedule import counted_c2, plan_prepare_shoot


# ---------------------------------------------------------------------------
# bitcast <-> limbs
# ---------------------------------------------------------------------------


@dataclass
class LimbMeta:
    treedef: Any
    shapes: list[tuple[int, ...]]
    dtypes: list[Any]
    sizes_u16: list[int]
    total: int


def state_to_limbs(state) -> tuple[jnp.ndarray, LimbMeta]:
    """Pytree → (S,) uint32 array of 16-bit limbs (canonical mod-q elements).

    A 2-byte leaf gives one limb per element (its bit pattern); a 4-byte
    leaf gives all its low halves, then all its high halves; a 1-byte leaf
    packs two bytes per limb, little-endian."""
    leaves, treedef = jax.tree.flatten(state)
    parts = []
    shapes, dtypes, sizes = [], [], []
    for leaf in leaves:
        arr = jnp.asarray(leaf)
        shapes.append(arr.shape)
        dtypes.append(arr.dtype)
        u16 = _leaf_to_limbs(arr)
        sizes.append(int(u16.size))
        parts.append(u16)
    limbs = jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.uint32)
    return limbs, LimbMeta(treedef, shapes, dtypes, sizes, int(limbs.size))


# Each leaf is converted by one jitted program and never through an array
# whose last dimension is 2: a TPU pads that dimension to 128 lanes, and a
# bf16 cache bitcast to (N, 2) bytes is then 64x its size.


@jax.jit
def _leaf_to_limbs(arr):
    if arr.dtype == jnp.bool_:  # bitcast can't take bool directly
        arr = arr.astype(jnp.uint8)
    flat = arr.reshape(-1)
    size = arr.dtype.itemsize
    if size == 2:
        return jax.lax.bitcast_convert_type(flat, jnp.uint16).astype(jnp.uint32)
    if size == 4:
        w = jax.lax.bitcast_convert_type(flat, jnp.uint32)
        return jnp.concatenate([w & 0xFFFF, w >> 16])
    if size != 1:
        raise TypeError(f"no 16-bit limb layout for {arr.dtype}")
    u8 = jax.lax.bitcast_convert_type(flat, jnp.uint8).astype(jnp.uint32)
    if u8.size % 2:
        u8 = jnp.pad(u8, (0, 1))
    return u8[0::2] | (u8[1::2] << 8)


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _leaf_from_limbs(u16, shape, dtype):
    n = int(np.prod(shape, dtype=np.int64))
    size = dtype.itemsize
    if size == 2:
        flat = jax.lax.bitcast_convert_type(u16.astype(jnp.uint16), dtype)
    elif size == 4:
        flat = jax.lax.bitcast_convert_type(u16[:n] | (u16[n:] << 16), dtype)
    else:
        u8 = jnp.stack([u16 & 0xFF, u16 >> 8], axis=1).reshape(-1)[:n]
        u8 = u8.astype(jnp.uint8)
        flat = u8.astype(jnp.bool_) if dtype == jnp.bool_ else (
            jax.lax.bitcast_convert_type(u8, dtype)
        )
    return flat.reshape(shape)


def limbs_to_state(limbs: jnp.ndarray, meta: LimbMeta):
    out = []
    off = 0
    for shape, dtype, size in zip(meta.shapes, meta.dtypes, meta.sizes_u16):
        out.append(
            _leaf_from_limbs(
                limbs[off : off + size], shape=tuple(shape), dtype=np.dtype(dtype)
            )
        )
        off += size
    return jax.tree.unflatten(meta.treedef, out)


# ---------------------------------------------------------------------------
# parity plan
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParityPlan:
    K: int
    p: int
    q: int
    A: np.ndarray  # (K, K) Cauchy generator
    ps_plan: Any

    @property
    def c1(self) -> int:
        return self.ps_plan.c1

    @property
    def c2(self) -> int:
        return counted_c2(self.ps_plan)


def build_parity_plan(K: int, p: int = 1, q: int = M31) -> ParityPlan:
    f = Field(q)
    A = cauchy_matrix(f, K)
    return ParityPlan(K=K, p=p, q=q, A=A, ps_plan=plan_prepare_shoot(K, p))


def encode_parity(x_limbs: jnp.ndarray, plan: ParityPlan) -> jnp.ndarray:
    """Single-program path (tests / single host): x_limbs (K, S) → (K, S)
    parity packets, via the universal algorithm (host-A Shoup fast path)."""
    return encode_universal(x_limbs, plan.A, p=plan.p, q=plan.q, plan=plan.ps_plan)


def encode_parity_collective(mesh, axis, plan: ParityPlan):
    """Mesh path: returns a jitted (K, S)→(K, S) function whose communication
    is ppermute rounds on the DP axis/axes.

    ``axis`` may be a single mesh-axis name (flat prepare-and-shoot, the
    default) or a tuple of axis names outermost → innermost — the
    topology-aligned path ``launch.profiles.resolve_profile`` selects when
    the DP replicas span a hierarchy (two axes → two-level
    ``hierarchical_encode_jit``, more → recursive ``multilevel_encode_jit``);
    every variant is bit-exact (same modular sums, reassociated)."""
    from repro.dist.collectives import (
        hierarchical_encode_jit,
        multilevel_encode_jit,
        ps_encode_jit,
    )

    if isinstance(axis, (tuple, list)):
        axes = tuple(axis)
        if len(axes) == 1:
            fn, _ = ps_encode_jit(mesh, axes[0], plan.A, p=plan.p, q=plan.q)
        elif len(axes) == 2:
            fn, _ = hierarchical_encode_jit(
                mesh, axes[0], axes[1], plan.A, p=plan.p, q=plan.q
            )
        else:
            fn, _ = multilevel_encode_jit(mesh, axes, plan.A, p=plan.p, q=plan.q)
        return fn
    fn, _ = ps_encode_jit(mesh, axis, plan.A, p=plan.p, q=plan.q)
    return fn


# ---------------------------------------------------------------------------
# recovery
# ---------------------------------------------------------------------------


def recover_lost(
    plan: ParityPlan,
    lost: list[int],
    surviving_x: dict[int, np.ndarray],
    surviving_parity: dict[int, np.ndarray],
) -> dict[int, np.ndarray]:
    """Recover the lost replicas' limb arrays bit-exactly.

    surviving_x/parity: {replica index → (S,) uint32 limbs}. Needs
    |surviving_parity| ≥ |lost| (any subset works — Cauchy guarantee).
    """
    f = Field(plan.q)
    F = sorted(lost)
    J = sorted(surviving_parity)[: len(F)]
    if len(J) < len(F):
        raise ValueError(f"need ≥{len(F)} surviving parity shards, have {len(J)}")
    A = plan.A
    S = next(iter(surviving_parity.values())).shape[0]
    rhs = np.zeros((len(J), S), dtype=np.uint64)
    for ji, j in enumerate(J):
        acc = surviving_parity[j].astype(np.uint64) % f.q
        for r, xr in surviving_x.items():
            acc = f.sub(acc, f.mul(xr, A[r, j]))
        rhs[ji] = acc
    M = A[np.ix_(F, J)].T.astype(np.uint64)  # equations j × unknowns r
    sol = f.solve(M, rhs)  # (f, S)
    return {r: sol[i] for i, r in enumerate(F)}


# ---------------------------------------------------------------------------
# high-level: coded checkpoint of a training-state pytree across K replicas
# ---------------------------------------------------------------------------


def shard_state_limbs(state, K: int) -> tuple[jnp.ndarray, LimbMeta]:
    """Flatten state to limbs and split into K equal shards (pad to K)."""
    limbs, meta = state_to_limbs(state)
    S = -(-int(limbs.size) // K)
    limbs = jnp.pad(limbs, (0, S * K - limbs.size))
    return limbs.reshape(K, S), meta


def unshard_state_limbs(shards: jnp.ndarray, meta: LimbMeta):
    return limbs_to_state(shards.reshape(-1)[: meta.total], meta)
