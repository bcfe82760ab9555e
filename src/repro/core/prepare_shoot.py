"""Array-level (jnp) executor for the universal prepare-and-shoot algorithm.

Vectorized over the processor axis: ``x`` has shape ``(K, *payload)`` and the
whole K-processor algorithm runs as one program. Every ``jnp.roll`` along
axis 0 is exactly one ``ppermute`` in the distributed version
(``dist/collectives.py`` reuses the same round structure 1:1) — this module
is both the single-host reference and the local-semantics oracle for the
mesh collective.

Correctness note: the w-variable initialization applies the *first-coverage
mask* — contribution (slot u, variable l) is kept iff l·m + offset(u) < K —
which makes the algorithm exact for every (K, p) with no Eq. 3 correction
(see schedule.coeff_mask and DESIGN.md §11).

Two coefficient paths:

* ``A`` as a runtime array (any matrix, the *universal* promise): the
  coefficient tensor is gathered from A inside jit and products use the
  uint32-only generic ``mmul``.
* ``A`` as a host numpy array: coefficients and their Shoup duals are baked
  in as compile-time constants (~2 multiplies instead of ~10 uint32 ops per
  product — the beyond-paper fast path, see EXPERIMENTS.md §Perf).
"""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from .field import M31, Field, madd, mmul, shoup_mul, shoup_precompute
from .schedule import (
    PrepareShootPlan,
    coeff_mask,
    plan_prepare_shoot,
    shoot_coeff_indices,
    shoot_coeff_tensor,
)


def _bcast(coef, ndim_payload):
    """Append payload broadcast dims to a coefficient array."""
    return coef.reshape(coef.shape + (1,) * ndim_payload)


def prepare_phase(x: jnp.ndarray, plan: PrepareShootPlan) -> jnp.ndarray:
    """x: (K, *payload) → buf: (K, m, *payload), buf[k, u] = x_{k - offsets[u]}.

    Round t concatenates [self, roll(s_1), .., roll(s_p)] — message size
    (p+1)^{t-1} per port, matching Lemma 3's C2 accounting.
    """
    K = plan.K
    buf = x[:, None]
    for shifts in plan.prepare_shifts:
        parts = [buf]
        for s in shifts:
            parts.append(jnp.roll(buf, s % K, axis=0))  # receive from k - s
        buf = jnp.concatenate(parts, axis=1)
    return buf


def shoot_init(
    buf: jnp.ndarray,
    plan: PrepareShootPlan,
    A: jnp.ndarray | np.ndarray,
    q: int,
) -> jnp.ndarray:
    """w[k, l] = Σ_u buf[k, u] · mask[u,l] · A[(k-off_u)%K, (k+l·m)%K] (mod q).

    This modular contraction is the gf_matmul kernel hot spot; here it is the
    pure-jnp form (kernels/gf_matmul/ops.py provides the Pallas-backed
    drop-in).
    """
    mask = coeff_mask(plan)  # (m, n) bool
    npay = buf.ndim - 2
    if isinstance(A, np.ndarray):  # host path: constants + Shoup
        coef_np = (shoot_coeff_tensor(plan, A) * mask[None, :, :]).astype(np.uint32)
        coef_sh = jnp.asarray(shoup_precompute(coef_np, q))
        coef = jnp.asarray(coef_np)

        def prods(u, l):
            return shoup_mul(
                buf[:, u],
                _bcast(coef[:, u, l], npay),
                _bcast(coef_sh[:, u, l], npay),
                q,
            )

    else:
        rows, cols = shoot_coeff_indices(plan)
        coef = A[jnp.asarray(rows), jnp.asarray(cols)].astype(jnp.uint32)
        coef = jnp.where(jnp.asarray(mask)[None, :, :], coef, jnp.uint32(0))

        def prods(u, l):
            return mmul(buf[:, u], _bcast(coef[:, u, l], npay), q)

    m, n = plan.m, plan.n
    cols_out = []
    for l in range(n):
        acc = prods(0, l)
        for u in range(1, m):
            acc = madd(acc, prods(u, l), q)
        cols_out.append(acc)
    return jnp.stack(cols_out, axis=1)


def shoot_rounds(w: jnp.ndarray, plan: PrepareShootPlan, q: int) -> jnp.ndarray:
    """Tree-reduce toward w[:, 0] (Algorithm 1 lines 2-10)."""
    K, p = plan.K, plan.p
    radix = p + 1
    n = plan.n
    for t, shifts in enumerate(plan.shoot_shifts, start=1):
        stride = radix ** (t - 1)
        acc = w
        for rho, s in enumerate(shifts, start=1):
            shifted = jnp.roll(w, s % K, axis=0)  # from k - s
            # live targets l (digit_t = 0, lower digits 0) absorb slot
            # l + rho*stride from the sender
            src_l = np.arange(n) + rho * stride
            valid = (
                (src_l < n)
                & ((np.arange(n) // stride) % radix == 0)
                & (np.arange(n) % stride == 0)
            )
            src_l = np.where(valid, src_l, 0)
            contrib = jnp.take(shifted, jnp.asarray(src_l), axis=1)
            mask = jnp.asarray(valid)
            contrib = jnp.where(
                _bcast(mask[None, :], w.ndim - 2), contrib, jnp.uint32(0)
            )
            acc = madd(acc, contrib, q)
        w = acc
    return w


def encode_universal(
    x: jnp.ndarray,
    A: jnp.ndarray | np.ndarray,
    *,
    p: int = 1,
    q: int = M31,
    plan: PrepareShootPlan | None = None,
) -> jnp.ndarray:
    """All-to-all encode of ANY K×K matrix A: out[k] = (x @ A)[k] over GF(q).

    x: (K, *payload) uint32 canonical; A: (K, K) uint32. The function is
    jit-compatible (all schedule decisions are static).
    """
    K = x.shape[0]
    if plan is None:
        plan = plan_prepare_shoot(K, p)
    buf = prepare_phase(x, plan)
    w = shoot_init(buf, plan, A, q)
    w = shoot_rounds(w, plan, q)
    return w[:, 0]


def encode_oracle(x: np.ndarray, A: np.ndarray, q: int = M31) -> np.ndarray:
    """Host oracle: (x @ A) mod q, exact, supports payload dims (K, *payload)."""
    f = Field(q)
    x = f.asarray(x)
    A = f.asarray(A)
    if x.ndim == 1:
        return f.matmul(x[None, :], A)[0]
    flat = x.reshape(x.shape[0], -1)
    out = f.matmul(flat.T, A).T  # (payload, K) @ (K, K) → transpose back
    return out.reshape(x.shape)
