"""shard_map executors for the paper's all-to-all encode schedules — ONE
generic :func:`ir_encode_jit` that runs any :class:`~repro.core.ir.ScheduleIR`.

One processor per mesh slot: an array of global shape ``(K, *payload)`` is
sharded ``P(axes)`` so the device at flattened mesh index ``k`` holds packet
``x_k`` as a ``(1, *payload)`` block. Each :class:`~repro.core.ir.CommRound`
decomposes into its port groups (transfers sharing (port, slots, mode) — a
uniform permutation), and every port group becomes exactly one
``jax.lax.ppermute`` over the composite encode axes; each
:class:`~repro.core.ir.LocalOp` becomes a Shoup-multiplied modular
contraction against baked per-device coefficient constants. The per-family
``*_encode_jit`` entry points are now dispatches: they build the plan,
compile it with ``plan.to_ir()``, and hand the IR to the generic executor —
the round structure, coefficient tables, and masks all come from the SAME
compile-time plans as the host simulators, so the mesh path and the
single-host oracle agree bit-for-bit by construction.

Communication discipline (tested via compiled HLO): every IR round lowers to
``collective-permute`` only — never to a K-sized ``all-gather``. The
committed ppermute budgets (``expected_permute_count`` and friends) are
unchanged by the IR refactor and asserted at dispatch time
(``ir_permute_count(ir) ≤ budget``; equality in the non-degenerate regimes
the jaxpr tests pin).

:func:`allgather_encode_jit` is the deliberate baseline that DOES
all-gather, kept as the cost-model foil.

All device arithmetic is the uint32-only tier of core/field.py (Shoup
multiplies by compile-time coefficient duals), so the same bodies lower for
CPU hosts and TPU.

Paper-notation glossary: ``K`` processors (= product of the mesh encode
axes), ``p`` ports per round (each ``ppermute`` is one port), ``C1`` rounds,
``C2`` per-port elements; ``I``/``G`` the two-level k_intra × k_inter split
of :func:`hierarchical_encode_jit`; *digit-reduction slots* — the §IV shoot
buffer layout (one slot per (p+1)-ary numeral of the remaining target
offset; round t zeroes digit t by shipping the slots with digit_t = ρ on
port ρ). :func:`multilevel_encode_jit` generalizes to any K = Π K_level
hierarchy: one gather over the innermost mesh axis, then one digit-reduction
shoot per outer axis, innermost first.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist._compat import shard_map as _smap
from repro.core.field import M31, NTT, madd, shoup_mul, shoup_precompute
from repro.core.ir import (
    INPUT_SLOT,
    CommRound,
    LocalOp,
    ScheduleIR,
    ir_permute_count,
    round_port_groups,
)
from repro.core.schedule import (
    PrepareShootPlan,
    digit_reduction_slots,
    plan_butterfly,
    plan_prepare_shoot,
)

__all__ = [
    "ir_encode_jit",
    "ps_encode_jit",
    "allgather_encode_jit",
    "butterfly_jit",
    "hierarchical_encode_jit",
    "multilevel_encode_jit",
    "shoot_round_slots",
    "expected_permute_count",
    "expected_hier_permute_count",
    "expected_multilevel_permute_count",
]


def _bcast(coef, npay: int):
    """Append payload broadcast dims to a coefficient array."""
    return coef.reshape(coef.shape + (1,) * npay)


def _mesh_platform(mesh) -> str:
    """Platform of the devices the program is compiled for — the mesh's,
    not the process default backend (a TPU mesh built from a CPU-default
    process, or a described topology, still gets the TPU lowering)."""
    return mesh.devices.flat[0].platform


def _lowering(platform: str) -> str:
    """LocalOp lowering for a mesh platform: ``"pallas"`` (the
    ``gf_matmul``/``butterfly_mac`` kernels) on TPU, ``"fused"`` (one
    batched Shoup contraction) elsewhere. The tests replace this function to
    run the Pallas kernels in interpret mode on a CPU mesh."""
    return "pallas" if platform == "tpu" else "fused"


def _lower_local(step: LocalOp, bake) -> dict:
    """Strength-reduce one LocalOp for the executor. Rows whose coefficients
    are uniform across devices split into three classes: all-zero rows write
    zeros, {0,1}-rows become pure madd chains (the pipeline pass's shadow
    copies and combines), and the remaining *general* rows are stacked into
    ONE batched contraction — a single Shoup-multiplied jnp expression in
    the ``fused`` lowering, or one ``gf_matmul``/``butterfly_mac`` kernel
    call in the ``pallas`` one."""
    c = np.asarray(step.coeffs)
    ones = np.all(c == 1, axis=0)
    zeros = np.all(c == 0, axis=0)
    uniform01 = ones | zeros
    zero_rows, add_rows, gen_rows = [], [], []
    for i in range(c.shape[1]):
        if zeros[i].all():
            zero_rows.append(i)
        elif uniform01[i].all():
            add_rows.append((i, tuple(int(j) for j in np.nonzero(ones[i])[0])))
        else:
            gen_rows.append(i)
    return {
        "update": step.update,
        "zero": tuple(zero_rows),
        "adds": tuple(add_rows),
        "gen": tuple(gen_rows),
        "coef_idx": bake(c[:, gen_rows, :]) if gen_rows else None,
    }


# ---------------------------------------------------------------------------
# THE generic executor: any ScheduleIR whose rounds are mesh permutations
# ---------------------------------------------------------------------------


def ir_encode_jit(
    mesh,
    axes,
    ir: ScheduleIR,
    *,
    q: int = M31,
    name: str = "ir_encode",
):
    """Jitted mesh executor of any :class:`ScheduleIR`: device ``k`` (the
    flattened index over ``axes``, outermost first — exactly how ``P(axes)``
    shards the packet dimension) runs processor ``k``'s program.

    Every port group of every round is one ``ppermute`` over the composite
    ``axes`` (tuple axis names flatten row-major, matching the sharding);
    receive coefficients and LocalOp contractions are baked per-device Shoup
    constants sharded on their leading K dimension. ``mode="store"`` groups
    must cover every device (a partial permutation would zero-fill the rest);
    ``mode="add"`` groups may be partial — non-receivers add ppermute's
    zeros, a no-op.

    Inputs/outputs are in DEVICE order; for an IR with a non-identity
    ``placement`` (e.g. after ``topo.passes.remap_digits``) the caller
    permutes host-side: device ``placement[k]`` holds logical packet k.

    General LocalOp rows run through the ``gf_matmul``/``butterfly_mac``
    Pallas kernels on a TPU mesh and through one batched Shoup contraction
    (``"fused"``) on any other platform; both are bit-exact against
    ``encode_oracle`` (tests/test_fused_encode.py).

    The fused program is named ``jit_<name>`` (``jit_ir_encode`` by
    default) in HLO and in a device profile, and each CommRound ``r`` and
    LocalOp ``i`` runs under ``jax.named_scope`` ``round<r>`` / ``local<i>``,
    so a profile's op metadata says which step an op belongs to.
    """
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    platform = _mesh_platform(mesh)
    lowering = _lowering(platform)
    pallas_interp = platform != "tpu"
    K = 1
    for ax in axes:
        K *= int(mesh.shape[ax])
    if K != ir.K:
        raise ValueError(f"mesh axes {axes!r} give {K} devices, IR has {ir.K}")

    consts: list[np.ndarray] = []  # all (K, ...) — sharded on dim 0

    def bake(arr):
        arr = np.asarray(arr, dtype=np.uint32)
        consts.append(arr)
        consts.append(shoup_precompute(arr, q))
        return len(consts) - 2

    # ("comm", [(pairs, src_slots, dst_slots, mode, coef_idx)], round_no)
    # | ("local", out_slots, in_slots, spec)
    ops = []
    round_no = -1
    for step in ir.steps:
        if isinstance(step, CommRound):
            round_no += 1
            groups = []
            for g in round_port_groups(step):
                if g.mode == "store" and len(g.pairs) != K:
                    raise ValueError(
                        "store-mode port group must cover every device "
                        f"(got {len(g.pairs)} of {K})"
                    )
                coef_idx = None
                if g.coeffs_by_dst is not None:
                    coef = np.ones((K, len(g.slots)), dtype=np.uint32)
                    for dst, cs in g.coeffs_by_dst.items():
                        if cs is not None:
                            coef[dst] = cs
                    coef_idx = bake(coef)
                groups.append(
                    (
                        g.pairs,
                        tuple(ss for ss, _ in g.slots),
                        tuple(ds for _, ds in g.slots),
                        g.mode,
                        coef_idx,
                    )
                )
            if groups:
                ops.append(("comm", groups, round_no))
        elif isinstance(step, LocalOp):
            if step.coeffs is None:
                raise ValueError(
                    "structure-only IR (LocalOp.coeffs=None) cannot execute — "
                    "recompile with the generator matrix"
                )
            ops.append(
                ("local", step.out_slots, step.in_slots, _lower_local(step, bake))
            )
        else:  # pragma: no cover
            raise TypeError(f"unknown IR step {type(step).__name__}")

    def apply_op(op, buf, cs):
        """One IR step on a slot→array buffer dict (inside shard_map)."""
        first = next(iter(buf.values()))
        npay = first.ndim - 1
        zero = jnp.zeros_like(first)
        if op[0] == "comm":
            updates = []
            for pairs, src_slots, dst_slots, mode, coef_idx in op[1]:
                payload = jnp.stack(
                    [buf.get(s, zero) for s in src_slots], axis=1
                )  # (1, n_slots, *pay)
                recv = jax.lax.ppermute(payload, axes, pairs)
                if coef_idx is not None:
                    recv = shoup_mul(
                        recv,
                        _bcast(cs[coef_idx], npay),
                        _bcast(cs[coef_idx + 1], npay),
                        q,
                    )
                for i, ds in enumerate(dst_slots):
                    updates.append((ds, recv[:, i], mode))
            for ds, v, mode in updates:  # sends all read pre-round state
                buf[ds] = v if mode == "store" else (
                    madd(buf[ds], v, q) if ds in buf else v
                )
            return buf
        _, out_slots, in_slots, spec = op
        xs = [buf.get(s, zero) for s in in_slots]  # all reads pre-op
        new = dict(buf) if spec["update"] else {}
        for i in spec["zero"]:
            new[out_slots[i]] = zero
        for i, js in spec["adds"]:
            acc = zero
            for j in js:
                acc = xs[j] if acc is zero else madd(acc, xs[j], q)
            new[out_slots[i]] = acc
        if spec["gen"]:
            c, csh = cs[spec["coef_idx"]], cs[spec["coef_idx"] + 1]
            stacked = jnp.stack(xs, axis=1)  # (1, n_in, *pay)
            if lowering == "pallas":
                from repro.kernels.butterfly.ops import butterfly_mac
                from repro.kernels.gf_matmul.ops import gf_matmul

                flat = stacked[0].reshape(len(in_slots), -1)  # (n_in, P)
                if len(spec["gen"]) == 1:
                    out = butterfly_mac(
                        flat[:, None, :], c[0], csh[0], q=q,
                        interpret=pallas_interp,
                    )  # (1, P)
                else:
                    out = gf_matmul(c[0], flat, q=q, interpret=pallas_interp)
                for r, i in enumerate(spec["gen"]):
                    new[out_slots[i]] = out[r].reshape(first.shape)
            else:  # "fused": madd-fold of row-batched Shoup multiplies —
                # each term is (1, n_gen, *pay) and folds immediately, so
                # XLA fuses the chain in one pass instead of materializing
                # the full (n_gen, n_in, *pay) product
                acc = None
                for j in range(len(in_slots)):
                    term = shoup_mul(
                        xs[j][:, None],
                        _bcast(c[:, :, j], npay),
                        _bcast(csh[:, :, j], npay),
                        q,
                    )
                    acc = term if acc is None else madd(acc, term, q)
                for r, i in enumerate(spec["gen"]):
                    new[out_slots[i]] = acc[:, r]
        return new

    cs_dev = [jnp.asarray(a) for a in consts]
    scopes, n_local = [], 0
    for op in ops:
        if op[0] == "comm":
            scopes.append(f"round{op[2]}")
        else:
            scopes.append(f"local{n_local}")
            n_local += 1

    def body(x, cs):
        buf = {INPUT_SLOT: x}
        for scope, op in zip(scopes, ops):
            with jax.named_scope(scope):
                buf = apply_op(op, buf, cs)
        return buf[ir.out_slot]

    mapped = _smap(body, mesh, in_specs=(P(axes), P(axes)), out_specs=P(axes))

    def encode(x):
        return mapped(x, cs_dev)

    encode.__name__ = encode.__qualname__ = name
    return jax.jit(encode)


# ---------------------------------------------------------------------------
# universal prepare-and-shoot (§IV)
# ---------------------------------------------------------------------------


def shoot_round_slots(plan: PrepareShootPlan, t: int, rho: int):
    """(dst_slots, src_slots) for shoot round ``t`` (1-based), port ``rho``:
    receiver slot ``l`` (digit_t = 0, lower digits 0) absorbs sender slot
    ``l + rho·(p+1)^{t-1}``. Mirrors prepare_shoot.shoot_rounds exactly; the
    collective ships ONLY these slots (the paper's digit-t message slices).
    """
    return digit_reduction_slots(plan.n, plan.p, t, rho)


def expected_permute_count(plan: PrepareShootPlan) -> int:
    """Number of ppermute ops ps_encode_jit emits: p per prepare round plus
    one per non-empty (round, port) shoot slice — the plan/collective
    agreement contract checked in tests/test_dist_unit.py. (The IR path
    emits exactly this in the regular m ≤ K regime and never more.)"""
    count = plan.Tp * plan.p
    for t in range(1, plan.Ts + 1):
        for rho in range(1, plan.p + 1):
            dst, _ = shoot_round_slots(plan, t, rho)
            if dst.size:
                count += 1
    return count


def _apply_pipeline(ir: ScheduleIR, pipeline: str):
    """Apply a named ``topo.passes`` pipeline at dispatch time (e.g.
    ``pipeline="pipeline"`` for the software-pipelined rounds picked by the
    autotuner / a launch profile). Comm rounds are never touched, so the
    entry point's ppermute budget check still binds the rewritten IR."""
    if not pipeline:
        return ir
    from repro.topo.passes import apply_pipeline

    return apply_pipeline(ir, pipeline)


def _check_budget(ir: ScheduleIR, budget: int):
    n = ir_permute_count(ir)
    if n > budget:
        raise AssertionError(
            f"{ir.algorithm} IR needs {n} ppermutes, committed budget is {budget}"
        )


def ps_encode_jit(
    mesh,
    axis: str,
    A: np.ndarray,
    *,
    p: int = 1,
    q: int = M31,
    pipeline: str = "",
):
    """Jitted mesh executor of the universal encode: ``out = x @ A`` over
    GF(q) for ANY K×K matrix A, K = mesh.shape[axis].

    Returns ``(fn, plan)``; ``fn`` maps a ``(K, *payload)`` uint32 array
    (sharded or shardable over ``axis``) to the encoded array of the same
    shape. A is a host array: the IR's coefficients and their Shoup duals
    are baked in as per-device compile-time constants. The program is named
    ``jit_ps_encode``.
    """
    K = int(mesh.shape[axis])
    A = np.asarray(A)
    if A.shape != (K, K):
        raise ValueError(f"A must be ({K}, {K}) to match mesh axis {axis!r}, got {A.shape}")
    plan = plan_prepare_shoot(K, p)
    ir = _apply_pipeline(plan.to_ir(A, q=q), pipeline)
    _check_budget(ir, expected_permute_count(plan))
    return ir_encode_jit(mesh, axis, ir, q=q, name="ps_encode"), plan


def allgather_encode_jit(mesh, axis: str, A: np.ndarray, *, q: int = M31):
    """Baseline mesh encode: all-gather every packet, then each device
    contracts locally with its own column of A — C1 = O(log K) but
    C2 = Θ(K/p). Kept as the cost-model foil for ps_encode_jit
    (deliberately NOT routed through ir_encode_jit: its point is the
    all-gather the IR path never emits)."""
    K = int(mesh.shape[axis])
    A = np.asarray(A)
    if A.shape != (K, K):
        raise ValueError(f"A must be ({K}, {K}), got {A.shape}")
    # device k needs column A[:, k]: ship as a (K, K) array sharded on dim 0
    cols = np.ascontiguousarray(A.T).astype(np.uint32)  # cols[k, j] = A[j, k]
    cols_shoup = shoup_precompute(cols, q)

    def body(x, c, cs):
        # x: (1, *payload); c/cs: (1, K)
        npay = x.ndim - 1
        xs = jax.lax.all_gather(x, axis, axis=0, tiled=True)  # (K, *payload)
        acc = None
        for j in range(K):
            term = shoup_mul(xs[j], _bcast(c[0, j], npay), _bcast(cs[0, j], npay), q)
            acc = term if acc is None else madd(acc, term, q)
        return acc[None]

    mapped = _smap(body, mesh, in_specs=(P(axis), P(axis), P(axis)), out_specs=P(axis))
    c_dev = jnp.asarray(cols)
    cs_dev = jnp.asarray(cols_shoup)

    def allgather_encode(x):
        return mapped(x, c_dev, cs_dev)

    return jax.jit(allgather_encode)


# ---------------------------------------------------------------------------
# two-level hierarchical encode on a 2D mesh
# ---------------------------------------------------------------------------


def expected_hier_permute_count(plan) -> int:
    """ppermute budget of hierarchical_encode_jit: one per non-empty intra
    gather port plus one per inter (round, port) with live slots — the
    plan/collective agreement contract (mirrors expected_permute_count)."""
    from repro.topo.hierarchical import hier_shoot_message_size

    count = sum(len(ports) for ports in plan.intra_rounds)
    for t in range(1, len(plan.inter_shifts) + 1):
        for rho in range(1, plan.p + 1):
            if hier_shoot_message_size(plan, t, rho):
                count += 1
    return count


def hierarchical_encode_jit(
    mesh,
    inter_axis: str,
    intra_axis: str,
    A: np.ndarray,
    *,
    p: int = 1,
    q: int = M31,
    pipeline: str = "",
):
    """Jitted two-level mesh executor of the universal encode: ``out = x @ A``
    over GF(q) for ANY K×K matrix A, K = mesh.shape[inter_axis] ×
    mesh.shape[intra_axis]; device (g, i) holds packet k = g·I + i.

    Three phases (repro.topo.hierarchical — the topology-aligned schedule):
    (p+1)-ary doubling all-gather over the fast ``intra_axis``, a local Shoup
    contraction against baked per-device coefficients, then the §IV
    digit-reduction shoot over the slow ``inter_axis``. Every port group is
    one ppermute, so intra traffic never crosses the slow domain. Bit-exact
    vs. the single-level ``ps_encode_jit`` / ``encode_oracle`` (modular sums
    reassociate exactly).

    The two-level schedule is exactly the depth-2 case of the recursive one
    (``plan_multilevel(K, p, (I, G))`` lowers to the same rounds — asserted
    in tests), so ``HierarchicalPlan.to_ir`` compiles through the multilevel
    IR builder and this dispatch shares :func:`ir_encode_jit` with
    everything else.

    Returns ``(fn, plan)`` with plan a :class:`HierarchicalPlan`.
    """
    from repro.topo.hierarchical import plan_hierarchical

    G = int(mesh.shape[inter_axis])
    I = int(mesh.shape[intra_axis])
    K = G * I
    A = np.asarray(A)
    if A.shape != (K, K):
        raise ValueError(
            f"A must be ({K}, {K}) to match mesh axes "
            f"({inter_axis!r}×{intra_axis!r}), got {A.shape}"
        )
    plan = plan_hierarchical(K, p, k_intra=I)
    ir = _apply_pipeline(plan.to_ir(A, q=q), pipeline)
    _check_budget(ir, expected_hier_permute_count(plan))
    return ir_encode_jit(mesh, (inter_axis, intra_axis), ir, q=q), plan


# ---------------------------------------------------------------------------
# recursive multi-level encode on an N-D mesh
# ---------------------------------------------------------------------------


def expected_multilevel_permute_count(plan) -> int:
    """ppermute budget of multilevel_encode_jit: one per non-empty intra
    gather port plus one per (level, round, port) with live slots — the
    plan/collective agreement contract (mirrors expected_hier_permute_count)."""
    from repro.topo.hierarchical import multilevel_message_size

    count = sum(len(ports) for ports in plan.intra_rounds)
    for j in range(1, len(plan.levels)):
        for t in range(1, len(plan.level_shifts[j - 1]) + 1):
            for rho in range(1, plan.p + 1):
                if multilevel_message_size(plan, j, t, rho):
                    count += 1
    return count


def multilevel_encode_jit(
    mesh,
    axes,
    A: np.ndarray,
    *,
    p: int = 1,
    q: int = M31,
    pipeline: str = "",
):
    """Jitted N-level mesh executor of the universal encode: ``out = x @ A``
    over GF(q) for ANY K×K matrix A, K = Π mesh.shape[ax] over ``axes``.

    ``axes`` is ordered outermost (slowest links, e.g. ``"pod"``) →
    innermost (fastest, e.g. ``"chip"``), matching how ``P(tuple(axes))``
    shards the packet axis: the LAST mesh axis varies fastest, so device
    (c_{L−1}, …, c_1, c_0) holds packet k = c_0 + K_0·(c_1 + K_1·(…)).

    Phases (repro.topo.hierarchical — the recursive topology-aligned
    schedule): (p+1)-ary doubling all-gather over the innermost axis, a
    local Shoup contraction against baked per-device coefficients, then one
    §IV digit-reduction shoot per outer axis, innermost first — every round
    permutes exactly ONE level's coordinate, so traffic never rides a slower
    level than its phase. Bit-exact vs. ``ps_encode_jit`` / ``encode_oracle``
    (modular sums reassociate exactly). With two axes this is exactly
    ``hierarchical_encode_jit``'s schedule; both are
    ``ir_encode_jit(mesh, axes, plan.to_ir(A))`` dispatches.

    Returns ``(fn, plan)`` with plan a :class:`MultiLevelPlan`.
    """
    from repro.topo.hierarchical import plan_multilevel

    axes = tuple(axes)
    sizes = [int(mesh.shape[ax]) for ax in axes]
    K = 1
    for s in sizes:
        K *= s
    levels = tuple(reversed(sizes))  # innermost (last mesh axis) first
    A = np.asarray(A)
    if A.shape != (K, K):
        raise ValueError(
            f"A must be ({K}, {K}) to match mesh axes {axes!r}, got {A.shape}"
        )
    plan = plan_multilevel(K, p, levels)
    ir = _apply_pipeline(plan.to_ir(A, q=q), pipeline)
    _check_budget(ir, expected_multilevel_permute_count(plan))
    return ir_encode_jit(mesh, axes, ir, q=q), plan


# ---------------------------------------------------------------------------
# radix-(p+1) DFT butterfly (§V-A)
# ---------------------------------------------------------------------------


def butterfly_jit(
    mesh,
    axis: str,
    *,
    p: int = 1,
    q: int = NTT,
    inverse: bool = False,
    pipeline: str = "",
):
    """Jitted mesh butterfly: forward computes ``x @ butterfly_target_matrix``
    (the digit-reversed K-point DFT), inverse undoes it exactly (Lemma 5).

    Returns ``(fn, plan)``. Round t exchanges within digit-t groups via p
    radix-1 ppermutes (one per port group of the butterfly IR) and combines
    with the plan's (inverse) twiddles — C1 = C2 = H rounds/elements,
    mirroring core/draw_loose.butterfly_apply.
    """
    K = int(mesh.shape[axis])
    plan = plan_butterfly(K, p, q)
    ir = _apply_pipeline(plan.to_ir(inverse=inverse), pipeline)
    _check_budget(ir, plan.H * p)
    return ir_encode_jit(mesh, axis, ir, q=q), plan
