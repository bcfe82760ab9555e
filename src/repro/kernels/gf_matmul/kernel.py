"""Pallas TPU kernel: GF(q) modular matmul via 7-bit-limb MXU decomposition.

TPU adaptation (DESIGN §3/§7): the MXU has no 64-bit integer path, so a
direct ``(a*b) % q`` contraction cannot use it. Instead each uint32 operand
is split into five 7-bit limbs (35 bits ≥ 32); the product becomes

    A·B = Σ_{c=0}^{8} D_c · 2^{7c},   D_c = Σ_{i+j=c} A_i · B_j

where each ``A_i · B_j`` is an int8×int8→int32 matmul — the MXU's native
integer mode. The MXU multiplies int8 as SIGNED, so a limb must stay below
2^7: 8-bit limbs (four per word) came back wrong on a v5e for any byte
≥ 128. A class sum adds at most five products, 5·127²·block_k < 2^31 for
block_k ≤ 16384, so the int32 accumulation is exact. The nine class sums
D_c are then folded modulo q on the VPU once per output tile:
Barrett-reduce D_c and Shoup-multiply by the constant 2^{7c} mod q.

Grid: (M/bm, N/bn, K/bk); the K dimension accumulates into the uint32
output block (canonical mod-q residues) across grid steps.

VMEM per step (defaults bm=bn=128, bk=512):
    A block 128·512·4 B = 256 KiB, B block 512·128·4 B = 256 KiB,
    out 64 KiB, limb temporaries ≈ 10·(block bytes)/4 — comfortably < 16 MiB.
MXU alignment: bm, bn multiples of 128; bk multiple of 8 (≥ 128 preferred).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.field import shoup_precompute

_LIMB_BITS = 7
_LIMB_MASK = (1 << _LIMB_BITS) - 1
_NLIMB = -(-32 // _LIMB_BITS)  # 5
_NCLASS = 2 * _NLIMB - 1  # 9


def _fold_constants(q: int):
    """(2^{7c} mod q, shoup(2^{7c} mod q)) for c = 0..8."""
    rs = [(1 << (_LIMB_BITS * c)) % q for c in range(_NCLASS)]
    pres = [int(shoup_precompute(r, q)) for r in rs]
    return rs, pres


def _barrett(x_i32, q: int):
    """x mod q for 0 <= x < 2^31 given as int32 (kernel-local Barrett).

    t = floor(x * floor(2^32/q) / 2^32) via 16-bit-limb high-mul, then one
    conditional subtract (see field.barrett32; re-implemented here on uint32
    values so the kernel body has no cross-module jnp closures).
    """
    m = (1 << 32) // q
    x = x_i32.astype(jnp.uint32)
    # umulhi32_full(x, m) with m < 2^32
    a1, a0 = x >> 16, x & 0xFFFF
    b1, b0 = jnp.uint32(m >> 16), jnp.uint32(m & 0xFFFF)
    m0 = a0 * b0
    c1 = a0 * b1
    c2 = a1 * b0
    hi2 = a1 * b1
    w = c1 + (m0 >> 16)
    carry = jnp.where(w > jnp.uint32(0xFFFFFFFF) - c2, jnp.uint32(1), jnp.uint32(0))
    w = w + c2
    t = hi2 + (w >> 16) + (carry << 16)
    r = x - t * jnp.uint32(q)
    return jnp.where(r >= q, r - jnp.uint32(q), r)


def _shoup(a_u32, c: int, c_pre: int, q: int):
    """(a * c) mod q for constant c with precomputed Shoup dual."""
    a = a_u32
    a1, a0 = a >> 16, a & 0xFFFF
    b1, b0 = jnp.uint32(c_pre >> 16), jnp.uint32(c_pre & 0xFFFF)
    m0 = a0 * b0
    cc1 = a0 * b1
    cc2 = a1 * b0
    hi2 = a1 * b1
    w = cc1 + (m0 >> 16)
    carry = jnp.where(w > jnp.uint32(0xFFFFFFFF) - cc2, jnp.uint32(1), jnp.uint32(0))
    w = w + cc2
    t = hi2 + (w >> 16) + (carry << 16)
    r = a * jnp.uint32(c) - t * jnp.uint32(q)
    return jnp.where(r >= q, r - jnp.uint32(q), r)


def _limb(x_u32, i: int):
    """Limb i of a uint32 block: bits [7i, 7i+7), as a nonnegative int8."""
    return ((x_u32 >> (_LIMB_BITS * i)) & _LIMB_MASK).astype(jnp.int8)


def _gf_matmul_kernel(a_ref, b_ref, out_ref, *, q: int, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ref[...]  # (bm, bk) uint32
    b = b_ref[...]  # (bk, bn) uint32
    a_limbs = [_limb(a, i) for i in range(_NLIMB)]
    b_limbs = [_limb(b, j) for j in range(_NLIMB)]

    rs, pres = _fold_constants(q)
    folded = None
    for c in range(_NCLASS):
        d = None
        for i in range(max(0, c - _NLIMB + 1), min(_NLIMB, c + 1)):
            j = c - i
            # int8 x int8 -> int32: the MXU-native integer mode
            prod = jax.lax.dot_general(
                a_limbs[i],
                b_limbs[j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            d = prod if d is None else d + prod
        dq = _barrett(d, q)  # < q
        term = dq if c == 0 else _shoup(dq, rs[c], pres[c], q)
        if folded is None:
            folded = term
        else:
            s = folded + term
            folded = jnp.where(s >= q, s - jnp.uint32(q), s)

    acc = out_ref[...] + folded  # both < q: sum < 2^32
    out_ref[...] = jnp.where(acc >= q, acc - jnp.uint32(q), acc)


@functools.partial(
    jax.jit, static_argnames=("q", "block_m", "block_n", "block_k", "interpret")
)
def gf_matmul_pallas(
    a: jnp.ndarray,
    b: jnp.ndarray,
    *,
    q: int,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    """C = (A @ B) mod q. a: (M, K) uint32, b: (K, N) uint32, shapes must be
    multiples of the block sizes (ops.py pads)."""
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0, (
        a.shape,
        b.shape,
        (block_m, block_n, block_k),
    )
    assert block_k <= 16384, "int32 limb accumulation bound"
    k_steps = K // block_k
    grid = (M // block_m, N // block_n, k_steps)
    return pl.pallas_call(
        functools.partial(_gf_matmul_kernel, q=q, k_steps=k_steps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.uint32),
        interpret=interpret,
    )(a, b)
