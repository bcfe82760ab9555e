"""Pallas TPU kernel: fused butterfly-round multiply-accumulate.

One radix-(p+1) butterfly round computes, per processor row b and payload
column n:   out[b, n] = Σ_ρ tw[b, ρ] · parts[ρ, b, n]   (mod q).

Fusing the radix Shoup-multiplies and modular adds into one kernel avoids
``radix - 1`` HBM round-trips of the (B, P) intermediate that the naive
composition materializes. All arithmetic is uint32-only (Shoup with
precomputed duals; no 64-bit values), so the body lowers for TPU VPU lanes.

Tiling: grid (B/bb, P/bp); twiddle blocks are (bb, radix) and broadcast over
the payload grid dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _butterfly_kernel(parts_ref, tw_ref, tw_sh_ref, out_ref, *, q: int, radix: int):
    acc = None
    for r in range(radix):
        a = parts_ref[r]  # (bb, bp) uint32
        c = tw_ref[:, r : r + 1]  # (bb, 1)
        c_pre = tw_sh_ref[:, r : r + 1]
        # Shoup multiply (see core.field.shoup_mul; inlined for the kernel)
        a1, a0 = a >> 16, a & 0xFFFF
        b1, b0 = c_pre >> 16, c_pre & 0xFFFF
        m0 = a0 * b0
        c1 = a0 * b1
        c2 = a1 * b0
        hi2 = a1 * b1
        w = c1 + (m0 >> 16)
        carry = jnp.where(w > jnp.uint32(0xFFFFFFFF) - c2, jnp.uint32(1), jnp.uint32(0))
        w = w + c2
        t = hi2 + (w >> 16) + (carry << 16)
        r_ = a * c - t * jnp.uint32(q)
        term = jnp.where(r_ >= q, r_ - jnp.uint32(q), r_)
        if acc is None:
            acc = term
        else:
            s = acc + term
            acc = jnp.where(s >= q, s - jnp.uint32(q), s)
    out_ref[...] = acc


@functools.partial(
    jax.jit, static_argnames=("q", "block_b", "block_p", "interpret")
)
def butterfly_mac_pallas(
    parts: jnp.ndarray,  # (radix, B, P) uint32
    tw: jnp.ndarray,  # (B, radix) uint32
    tw_sh: jnp.ndarray,  # (B, radix) uint32
    *,
    q: int,
    block_b: int = 256,
    block_p: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    radix, B, P = parts.shape
    assert B % block_b == 0 and P % block_p == 0, (parts.shape, block_b, block_p)
    grid = (B // block_b, P // block_p)
    return pl.pallas_call(
        functools.partial(_butterfly_kernel, q=q, radix=radix),
        grid=grid,
        in_specs=[
            pl.BlockSpec((radix, block_b, block_p), lambda i, j: (0, i, j)),
            pl.BlockSpec((block_b, radix), lambda i, j: (i, 0)),
            pl.BlockSpec((block_b, radix), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, block_p), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, P), jnp.uint32),
        interpret=interpret,
    )(parts, tw, tw_sh)
