"""Public jit'd wrappers for the GF(q) matmul Pallas kernel.

Handles zero-padding to block multiples (zeros are absorbing for mod-q
accumulation), small-shape fallbacks, and a vmapped batched form used by the
shoot-phase initialization (w[k] = buf[k] @ coef[k]).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .kernel import gf_matmul_pallas
from .ref import gf_matmul_ref


def _pad_to(x: jnp.ndarray, mult0: int, mult1: int) -> jnp.ndarray:
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 == 0 and p1 == 0:
        return x
    return jnp.pad(x, ((0, p0), (0, p1)))


@functools.partial(
    jax.jit, static_argnames=("q", "block_m", "block_n", "block_k", "interpret")
)
def gf_matmul(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    q: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """C = (A @ B) mod q for arbitrary (M, K) x (K, N) uint32 inputs.

    Shapes are padded up to block multiples; for tiny operands (< one block)
    the block sizes shrink to the padded shape (still 8/128-aligned when
    possible).
    """
    M, K = a.shape
    _, N = b.shape
    if M == 0 or N == 0 or K == 0:
        # empty operand (e.g. a slot emptied by fuse_trivial_rounds): the
        # mod-q sum over zero terms is zero — don't pad up into the kernel
        return jnp.zeros((M, N), dtype=jnp.uint32)
    bm = min(block_m, _round_up(M, 8))
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, _round_up(K, 8))
    ap = _pad_to(a.astype(jnp.uint32), bm, bk)
    bp = _pad_to(b.astype(jnp.uint32), bk, bn)
    out = gf_matmul_pallas(
        ap, bp, q=q, block_m=bm, block_n=bn, block_k=bk, interpret=interpret
    )
    return out[:M, :N]


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def gf_matmul_batched(
    a: jnp.ndarray, b: jnp.ndarray, *, q: int, interpret: bool = False
) -> jnp.ndarray:
    """Batched C[i] = (A[i] @ B[i]) mod q via vmap over the Pallas kernel.

    a: (B, M, K), b: (B, K, N). Used for the shoot-phase init where every
    processor contracts its prepare buffer against its own coefficient tile.
    """
    B, M, K = a.shape
    _, _, N = b.shape
    if M == 0 or N == 0 or K == 0:
        return jnp.zeros((B, M, N), dtype=jnp.uint32)
    bm = min(128, _round_up(M, 8))
    bn = min(128, _round_up(N, 128))
    bk = min(512, _round_up(K, 8))
    ap = jax.vmap(lambda x: _pad_to(x, bm, bk))(a.astype(jnp.uint32))
    bp = jax.vmap(lambda x: _pad_to(x, bk, bn))(b.astype(jnp.uint32))
    fn = functools.partial(
        gf_matmul_pallas, q=q, block_m=bm, block_n=bn, block_k=bk, interpret=interpret
    )
    out = jax.vmap(fn)(ap, bp)
    return out[:, :M, :N]


def gf_matmul_reference(a, b, *, q):
    """Alias of the pure-jnp oracle (testing convenience)."""
    return gf_matmul_ref(a, b, q)


def encode_direct(x: jnp.ndarray, G: jnp.ndarray | np.ndarray, *, q: int, interpret: bool = False):
    """Direct (non-collective) encode baseline: X @ G mod q via the kernel.

    x: (S, K) payload-major state limbs; G: (K, N) generator. This is the
    per-node compute of the coded-checkpoint path.
    """
    return gf_matmul(x, jnp.asarray(np.asarray(G, dtype=np.uint32)), q=q, interpret=interpret)
