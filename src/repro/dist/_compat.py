"""The one ``shard_map`` call every executor uses.

Varying-manual-axes checking is off: the checker rejects valid
ppermute/psum mixtures inside the unrolled collective loops.
"""

from __future__ import annotations

import jax


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
