"""Lagrange Coded Computing (LCC) — the paper's §VI use case
[Yu et al., AISTATS'19], extended to true (N, K) erasure codes.

Task: K workers hold data blocks X_1..X_K; compute f(X_i) = X_i @ W for all
i, tolerating stragglers. LCC encodes the blocks as evaluations of the
degree-(K−1) polynomial u(z) with u(ω_i) = X_i at N ≥ K points α_j — which
is EXACTLY the all-to-all-encode of a Lagrange matrix (Theorem 4: inverse
Vandermonde then forward Vandermonde, both by draw-and-loose). Worker j
computes f(u(α_j)) = u(α_j) @ W — evaluations of the degree-(K−1) polynomial
f∘u — and any K results interpolate back to f(X_i) = (f∘u)(ω_i).

Two regimes:

* ``R == 0`` (N = K, the original §VI example): the square Lagrange
  generator runs through the Theorem 4 draw-and-loose composite
  (inverse-Vandermonde ∘ forward-Vandermonde).
* ``R > 0`` (N = K + R coded replicas, the serving tier's straggler /
  fault-tolerance regime): the K data rows are zero-padded to N
  processors and encoded with the **padded Lagrange/Vandermonde
  generator** A (A[:K, :] = lagrange_matrix(α_0..α_{N−1}, ω_0..ω_{K−1}),
  rows K..N−1 zero) in ONE universal prepare-and-shoot all-to-all encode
  — on a mesh, the same generator executes through ``ir_encode_jit``
  (see :func:`lcc_encode_collective`). Any K of the N coded shards
  reconstruct every X_i bit-exactly (:func:`lcc_decode`).

Everything is exact over GF(q) (data quantized to field elements), so the
decode is bit-exact. The decode runs in two forms: :func:`lcc_decode`, host
numpy, the exact oracle; and :func:`lcc_decode_device`, one compiled device
program whose K×K Lagrange matrix (and its Shoup duals) are runtime
arguments, so every K-subset of responders shares one executable — the
universal point of the paper: the same schedule, only the coefficients
change. Coded serving rebuilds its state with the device form.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.draw_loose import encode_lagrange
from repro.core.field import M31, NTT, Field, madd, shoup_mul, shoup_precompute
from repro.core.matrices import distinct_points, lagrange_matrix
from repro.core.prepare_shoot import encode_universal
from repro.core.schedule import plan_draw_loose, plan_prepare_shoot


@dataclass(frozen=True)
class LCCPlan:
    K: int
    p: int
    q: int
    plan_omega: object
    plan_alpha: object
    #: parity shards beyond K — N = K + R total coded replicas
    R: int = 0
    #: N evaluation points α_0..α_{N−1} when R > 0 (else plan_alpha.points)
    alphas: np.ndarray | None = None

    @property
    def N(self) -> int:
        return self.K + self.R

    @property
    def omega_points(self):
        return self.plan_omega.points

    @property
    def alpha_points(self):
        return self.alphas if self.alphas is not None else self.plan_alpha.points


def build_lcc(K: int, p: int = 1, q: int = NTT, R: int = 0) -> LCCPlan:
    """LCC plan for K data shards and N = K + R coded shards.

    R = 0 reproduces the original square (N = K) §VI example; R > 0 adds
    parity evaluation points so any K-of-N shards decode."""
    if R < 0:
        raise ValueError(f"R must be ≥ 0, got {R}")
    plan_omega = plan_draw_loose(K, p, q, seed=101)
    if R == 0:
        return LCCPlan(
            K=K, p=p, q=q,
            plan_omega=plan_omega,
            plan_alpha=plan_draw_loose(K, p, q, seed=202),
        )
    f = Field(q)
    return LCCPlan(
        K=K, p=p, q=q,
        plan_omega=plan_omega,
        plan_alpha=None,
        R=R,
        alphas=distinct_points(f, K + R, seed=202),
    )


def lcc_generator(plan: LCCPlan) -> np.ndarray:
    """The (N, N) all-to-all-encode generator of the LCC code: row k < K is
    the Lagrange row Φ_k evaluated at every α_j (a column-scaled Vandermonde
    in the ω basis), rows K..N−1 are zero (they multiply the padding).
    ``x_padded @ A`` = the N coded shards."""
    f = Field(plan.q)
    N = plan.N
    A = np.zeros((N, N), dtype=np.uint64)
    A[: plan.K, :] = lagrange_matrix(
        f, np.asarray(plan.alpha_points), np.asarray(plan.omega_points)
    )
    return A


def lcc_pad(plan: LCCPlan, X) -> jnp.ndarray:
    """Zero-pad (K, *payload) data to the (N, *payload) processor count the
    padded generator expects (a no-op at R = 0)."""
    X = jnp.asarray(X)
    if X.shape[0] != plan.K:
        raise ValueError(f"X must have K={plan.K} rows, got {X.shape[0]}")
    if plan.R == 0:
        return X
    return jnp.concatenate(
        [X, jnp.zeros((plan.R,) + X.shape[1:], X.dtype)], axis=0
    )


def lcc_encode(plan: LCCPlan, X: jnp.ndarray) -> jnp.ndarray:
    """X: (K, *block) field elements with X[i] held by worker i as u(ω_i).
    Returns the N = K + R coded blocks u(α_j), one per worker.

    N = K: one all-to-all encode of the Lagrange matrix via the Theorem 4
    draw-and-loose composite. N > K: one universal prepare-and-shoot encode
    of the padded Lagrange generator over N processors (jit-compatible)."""
    if plan.R == 0:
        return encode_lagrange(X, plan.plan_omega, plan.plan_alpha)
    xp = lcc_pad(plan, X)
    return encode_universal(xp, lcc_generator(plan), p=plan.p, q=plan.q)


def lcc_encode_collective(mesh, axis: str, plan: LCCPlan):
    """Mesh path: jitted (N, *payload) → (N, *payload) encode of the padded
    Lagrange generator, communication = ppermute rounds on ``axis`` (size N)
    — the prepare-and-shoot ScheduleIR executed through
    ``dist.collectives.ir_encode_jit``. Input rows K..N−1 must be the zero
    padding (:func:`lcc_pad`). The input may live anywhere (e.g. on the one
    device that serves the model): row j is first placed on the axis's
    device j, as the encode's sharding requires."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.dist.collectives import ps_encode_jit

    K_axis = int(mesh.shape[axis])
    if K_axis != plan.N:
        raise ValueError(
            f"mesh axis {axis!r} has {K_axis} devices, need N={plan.N}"
        )
    fn, _ = ps_encode_jit(mesh, axis, lcc_generator(plan), p=plan.p, q=plan.q)
    rows = NamedSharding(mesh, PartitionSpec(axis))
    return lambda xp: fn(jax.device_put(xp, rows))


def _validate_responders(plan: LCCPlan, responders) -> list[int]:
    responders = [int(r) for r in responders]
    if len(set(responders)) != len(responders):
        raise ValueError(f"duplicate responders: {sorted(responders)}")
    bad = [r for r in responders if not 0 <= r < plan.N]
    if bad:
        raise ValueError(f"responders {bad} outside [0, {plan.N})")
    if len(responders) < plan.K:
        raise ValueError(
            f"need ≥{plan.K} responders to interpolate a degree-"
            f"{plan.K - 1} polynomial, have {len(responders)}"
        )
    return sorted(responders)[: plan.K]


def lcc_decode(plan: LCCPlan, values: np.ndarray, responders) -> np.ndarray:
    """Reconstruct all K data blocks from any K-of-N coded shards.

    ``values[i]`` is the coded shard held by worker ``responders[i]``
    (u(α_{responders[i]})); raises ValueError on fewer than K responders,
    duplicates, or out-of-range indices — never returns garbage."""
    f = Field(plan.q)
    K = plan.K
    order = {int(r): i for i, r in enumerate(responders)}
    chosen = _validate_responders(plan, responders)
    Y = np.stack(
        [np.asarray(values[order[r]], dtype=np.uint64) % f.q for r in chosen]
    )
    # interpolate the degree-(K−1) polynomial from its values at the K
    # surviving α's, evaluate at every ω: one Lagrange matrix application
    L = lagrange_matrix(
        f, np.asarray(plan.omega_points), np.asarray(plan.alpha_points)[chosen]
    )
    flat = Y.reshape(K, -1)
    out = f.matmul(flat.T, L).T
    return out.reshape((K,) + Y.shape[1:])


def lcc_interpolate(rows, L, L_pre, *, q: int):
    """out[k] = Σ_i L[i, k] · rows[i] mod q for K (1, S) uint32 rows, uint32
    only (Shoup products, modular adds); returns the (K, S) stack."""
    K = len(rows)
    out = []
    for k in range(K):
        acc = shoup_mul(rows[0], L[0, k], L_pre[0, k], q)
        for i in range(1, K):
            acc = madd(acc, shoup_mul(rows[i], L[i, k], L_pre[i, k], q), q)
        out.append(acc)
    return jnp.concatenate(out, axis=0)


#: the coefficients are arguments, so one executable per (K, S) and q serves
#: every responder set
_interpolate = jax.jit(lcc_interpolate, static_argnames="q")


def compile_lcc_decode_device(plan: LCCPlan, S: int):
    """Compile :func:`lcc_decode_device` for shards of S words ahead of the
    first decode; returns the compiled program (``memory_analysis()``)."""
    K, u32 = plan.K, jnp.uint32
    return _interpolate.lower(
        [jax.ShapeDtypeStruct((1, S), u32)] * K,
        jax.ShapeDtypeStruct((K, K), u32),
        jax.ShapeDtypeStruct((K, K), u32),
        q=plan.q,
    ).compile()


def lcc_decode_device(plan: LCCPlan, shards, responders) -> jax.Array:
    """:func:`lcc_decode` on the device: ``shards[i]``, the host uint32
    array of S words held by ``responders[i]``, is uploaded as it is (one
    row each: stacking them on the host first costs a copy of the whole
    payload) and one compiled program interpolates. Returns the K data
    blocks as a (K, S) uint32 device array, bit-identical to
    :func:`lcc_decode`. Needs exactly K distinct responders; the (K, K)
    Lagrange matrix for them is built on the host."""
    responders = [int(r) for r in responders]
    _validate_responders(plan, responders)
    if len(responders) != plan.K or len(shards) != plan.K:
        raise ValueError(
            f"the device decode takes exactly K={plan.K} shards and "
            f"responders, got {len(shards)} and {len(responders)}"
        )
    # data[k] = Σ_i L[i, k] · shards[i]: L takes the values at the
    # responders' α (in the order given) to the K data points ω
    L = lagrange_matrix(
        Field(plan.q), np.asarray(plan.omega_points),
        np.asarray(plan.alpha_points)[responders],
    )
    rows = [jax.device_put(np.asarray(v, np.uint32).reshape(1, -1)) for v in shards]
    return _interpolate(rows, L.astype(np.uint32), shoup_precompute(L, plan.q), q=plan.q)


def lcc_compute_and_decode(
    plan: LCCPlan, encoded: np.ndarray, W: np.ndarray, responders: list[int]
) -> np.ndarray:
    """Each responder j supplies Y_j = u(α_j) @ W (mod q); interpolate back
    to f(X_i) for all i from any K responses (linearity of f: the responses
    are evaluations of the degree-(K−1) polynomial f∘u)."""
    f = Field(plan.q)
    responders = [int(r) for r in responders]
    Y = np.stack(
        [f.matmul(np.asarray(encoded[j], dtype=np.uint64), W) for j in responders]
    )
    return lcc_decode(plan, Y, responders)


__all__ = [
    "LCCPlan",
    "build_lcc",
    "lcc_generator",
    "lcc_pad",
    "lcc_encode",
    "lcc_encode_collective",
    "lcc_decode",
    "lcc_decode_device",
    "compile_lcc_decode_device",
    "lcc_compute_and_decode",
]
