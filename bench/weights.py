"""Seeded random weights, made on the device in one jitted call.

The tree has the program's parameter layout (its shapes and shardings, read
abstractly from the model), but the values are the benchmark's own: every
matrix a truncated normal (std 0.02, cut at 2 std), every norm scale 1, in
the type the model serves in. The reference reads these same arrays, so
nothing it compares with was made by the program.
"""

from __future__ import annotations

import numpy as np

STD = 0.02


def key_for(seed: int):
    """A threefry key from 64 bits of any non-negative seed."""
    import jax
    import jax.numpy as jnp

    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


def _is_scale(path) -> bool:
    last = path[-1]
    return getattr(last, "key", None) == "scale"


def make(shapes, shardings, seed: int):
    """Weights for a tree of ``ShapeDtypeStruct`` ``shapes`` placed by
    ``shardings``; one jitted call from ``seed``."""
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            if _is_scale(path):
                out.append(jnp.ones(s.shape, s.dtype))
            else:
                k = jax.random.fold_in(key, i)
                w = STD * jax.random.truncated_normal(k, -2.0, 2.0, s.shape, jnp.float32)
                out.append(w.astype(s.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(key_for(seed))
