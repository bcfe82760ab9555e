"""Production mesh construction + the network topology it implies.

A FUNCTION (not a module constant) so importing never touches jax device
state. Single pod: (data=16, model=16) = 256 chips (TPU v5e-256). Multi-pod:
(pod=2, data=16, model=16) = 512 chips; the ``pod`` axis carries only
data-parallel gradient reduction (DCN-friendly), ``model`` stays inside the
pod's ICI domain.

:func:`production_topology` models the coded-checkpoint encode domain (the
DP replicas) as a recursive :class:`~repro.topo.model.Hierarchy` so
``launch.profiles.resolve_profile`` can pick the encode algorithm from the
network rather than hard-coding the flat schedule — the pure host-side
mirror of :func:`make_production_mesh` (no devices needed to price it).
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def production_topology(*, multi_pod: bool = False):
    """Topology of the DP-replica encode domain of the production mesh.

    Each DP replica is a model-parallel group of 16 chips; the 16 replicas
    of a pod sit 4-per-slice across 4 slices, so replica↔replica traffic is
    chip-level ICI inside a slice, slice-trunk ICI across slices, and DCI
    across pods. Multi-pod (K = 32 replicas): three-level chip < slice < pod
    ``Hierarchy(levels=(4, 4, 2))``. Single pod (K = 16): two-level
    ``Hierarchy(levels=(4, 4))``. Per-level α/β come from
    ``topo.model.default_level_costs`` (ICI → geometric midpoint → DCI).
    """
    from repro.topo import Hierarchy

    return Hierarchy(levels=(4, 4, 2) if multi_pod else (4, 4))


def mesh_encode_levels(mesh, axes) -> tuple[int, ...]:
    """Innermost-first level sizes of an encode domain spanning ``axes``
    (given outermost → innermost, the order multilevel_encode_jit takes)."""
    return tuple(int(mesh.shape[a]) for a in reversed(tuple(axes)))


def topology_for_mesh(mesh, axes):
    """Derive the :class:`Hierarchy` a mesh's encode axes imply (outermost
    axis = slowest level), for autotuning against a live mesh."""
    from repro.topo import Hierarchy

    return Hierarchy(levels=mesh_encode_levels(mesh, axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """jax.make_mesh with every axis Auto (sharding propagated by XLA). A
    mesh smaller than the host takes the first ``prod(shape)`` devices."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))
