"""Public jit'd wrapper for the fused butterfly-round MAC kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import butterfly_mac_pallas
from .ref import butterfly_mac_ref


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("q", "interpret"))
def butterfly_mac(
    parts: jnp.ndarray,  # (radix, B, *payload) uint32
    tw: jnp.ndarray,  # (B, radix) uint32
    tw_sh: jnp.ndarray,  # (B, radix) uint32
    *,
    q: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """out[b, ...] = Σ_ρ tw[b, ρ] · parts[ρ, b, ...] (mod q); pads/reshapes
    payload to the kernel's 2D tiling."""
    radix, B = parts.shape[0], parts.shape[1]
    payload = parts.shape[2:]
    flat = parts.reshape(radix, B, -1)
    P = flat.shape[-1]
    bb = min(256, _round_up(B, 8))
    bp = min(512, _round_up(P, 128))
    pb = (-B) % bb
    pp = (-P) % bp
    flat = jnp.pad(flat, ((0, 0), (0, pb), (0, pp)))
    twp = jnp.pad(tw.astype(jnp.uint32), ((0, pb), (0, 0)))
    twsp = jnp.pad(tw_sh.astype(jnp.uint32), ((0, pb), (0, 0)))
    out = butterfly_mac_pallas(
        flat.astype(jnp.uint32), twp, twsp, q=q, block_b=bb, block_p=bp,
        interpret=interpret,
    )
    return out[:B, :P].reshape(B, *payload)


def butterfly_mac_reference(parts, tw, tw_sh, *, q):
    flat = parts.reshape(parts.shape[0], parts.shape[1], -1)
    out = butterfly_mac_ref(flat, tw, tw_sh, q)
    return out.reshape(parts.shape[1:])
