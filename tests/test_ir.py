"""ScheduleIR equivalence suite: every plan family compiles to the SAME IR
pipeline, and the pipeline agrees with the matrix oracle, the closed-form
C1/C2, and the committed ppermute budgets.

* property test (hyputil): for every family and K ∈ {8, 12, 16},
  ``interpret(plan.to_ir())`` is bit-exact vs. the matrix oracle and
  ``ir_messages`` equals the interpreter's recorded ``round_messages``;
* ``fuse_trivial_rounds`` is exact and actually removes trivial structure;
* ``remap_digits`` partners are torus neighbors (hop count 1) in EVERY round
  on 2×4 / 4×2 / 4×4 tori, stays bit-exact, and the autotuner flips to the
  remapped schedule on the torus;
* ``fit_level_costs`` recovers planted per-level α/β from synthetic sweeps;
* subprocess: the remapped butterfly executes on an 8-device torus mesh via
  the generic ``ir_encode_jit`` (the CI torus-mesh step).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from hyputil import given, settings, st
from repro.core.field import M31, NTT, Field
from repro.core.ir import (
    fuse_trivial_rounds,
    ir_allgather,
    ir_messages,
    ir_permute_count,
)
from repro.core.matrices import (
    butterfly_target_matrix,
    random_matrix,
    random_vector,
)
from repro.core.prepare_shoot import encode_oracle
from repro.core.schedule import (
    draw_loose_target_matrix,
    plan_butterfly,
    plan_draw_loose,
    plan_prepare_shoot,
)
from repro.core.simulator import interpret
from repro.topo import (
    PIPELINES,
    Hierarchy,
    LinkCost,
    Ring,
    Torus2D,
    Torus3D,
    TwoLevel,
    autotune,
    fit_level_costs,
    fuse_rounds,
    ir_time,
    lower,
    max_round_hops,
    plan_hierarchical,
    plan_multilevel,
    plan_multilevel_dft,
    plan_ring,
    plan_two_level_dft,
    remap_digits,
    round_features,
    split_contended,
    multilevel_dft_matrix,
    two_level_dft_matrix,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F = Field(M31)


# ---------------------------------------------------------------------------
# IR ≡ oracle ≡ closed forms, for every family (property over K ∈ {8,12,16})
# ---------------------------------------------------------------------------


def _cases():
    """(label, build() → (ir, target_matrix, q, c1, c2)) for every family."""
    cases = []
    for K in (8, 12, 16):
        for p in (1, 2):
            def mk_ps(K=K, p=p):
                plan = plan_prepare_shoot(K, p)
                A = random_matrix(F, K, seed=K * 7 + p)
                return plan.to_ir(A), A, M31, plan.c1, None  # C2 ≤ closed form
            cases.append((f"prepare-shoot-{K}-{p}", mk_ps))

            def mk_ring(K=K, p=p):
                plan = plan_ring(K, p)
                A = random_matrix(F, K, seed=K + p)
                return plan.to_ir(A), A, M31, plan.c1, plan.c2
            cases.append((f"ring-{K}-{p}", mk_ring))

            def mk_ag(K=K, p=p):
                A = random_matrix(F, K, seed=K - p)
                return ir_allgather(K, p, A), A, M31, None, None
            cases.append((f"allgather-{K}-{p}", mk_ag))

        for I in (2, 4):
            if K % I:
                continue

            def mk_h(K=K, I=I):
                plan = plan_hierarchical(K, 1, I)
                A = random_matrix(F, K, seed=K * 3 + I)
                return plan.to_ir(A), A, M31, plan.c1, plan.c2
            cases.append((f"hierarchical-{K}-{I}", mk_h))

        def mk_dl(K=K):
            plan = plan_draw_loose(K, 1, NTT, seed=1)
            return plan.to_ir(), draw_loose_target_matrix(plan), NTT, plan.c1, plan.c2
        cases.append((f"draw-loose-{K}", mk_dl))

    for K, levels in [(8, (2, 2, 2)), (12, (3, 2, 2)), (16, (2, 2, 4)), (16, (4, 2, 2))]:

        def mk_ml(K=K, levels=levels):
            plan = plan_multilevel(K, 1, levels)
            A = random_matrix(F, K, seed=K * 31 + levels[0])
            return plan.to_ir(A), A, M31, plan.c1, plan.c2
        cases.append((f"multilevel-{K}-{levels}", mk_ml))

    for K in (8, 16):

        def mk_bf(K=K):
            plan = plan_butterfly(K, 1, NTT)
            f = Field(NTT)
            return plan.to_ir(), butterfly_target_matrix(f, K, 2), NTT, plan.c1, plan.c2
        cases.append((f"butterfly-{K}", mk_bf))

        def mk_dft2(K=K):
            plan = plan_two_level_dft(K, 1, NTT, 2 if K == 8 else 4)
            return plan.to_ir(), two_level_dft_matrix(plan), NTT, plan.c1, plan.c2
        cases.append((f"two-level-dft-{K}", mk_dft2))

    for K, levels in [(8, (2, 2, 2)), (16, (4, 4)), (16, (2, 2, 2, 2)), (16, (4, 2, 2))]:

        def mk_mldft(K=K, levels=levels):
            plan = plan_multilevel_dft(K, 1, NTT, levels)
            return (
                fuse_trivial_rounds(plan.to_ir()),
                multilevel_dft_matrix(plan),
                NTT,
                plan.c1,
                plan.c2,
            )
        cases.append((f"multilevel-dft-{K}-{levels}", mk_mldft))
    return cases


_CASES = _cases()


def _check_case(idx, seed_salt=0):
    from repro.topo.lower import lower_ir

    label, build = _CASES[idx]
    ir, target, q, c1, c2 = build()
    f = Field(q)
    x = random_vector(f, ir.K, seed=len(label) + seed_salt)
    out, st_ = interpret(ir, x, f)
    np.testing.assert_array_equal(out, encode_oracle(x, target, q), err_msg=label)
    assert list(lower_ir(ir).rounds) == ir_messages(ir) == st_.round_messages, label
    assert ir.c1 == st_.C1 and ir.c2 == st_.C2, label
    if c1 is not None:
        assert st_.C1 == c1, label
    if c2 is not None:
        assert st_.C2 == c2, label


@settings(max_examples=40, deadline=None)
@given(st.sampled_from(range(len(_CASES))), st.integers(min_value=0, max_value=7))
def test_every_family_ir_matches_oracle_and_messages(idx, seed_salt):
    """Property (hyputil): interpret(plan.to_ir()) == x @ target bit-exactly
    over random inputs, the measured C1/C2 match the plan's closed forms,
    and lower()'s rounds == ir_messages == the interpreter's recorded
    per-round message maps."""
    _check_case(idx, seed_salt)


@pytest.mark.parametrize("idx", range(len(_CASES)), ids=[l for l, _ in _CASES])
def test_every_family_ir_pipeline(idx):
    """Exhaustive non-property sweep of the same contract (runs even when
    hypothesis is unavailable)."""
    _check_case(idx)


# ---------------------------------------------------------------------------
# fuse_trivial_rounds
# ---------------------------------------------------------------------------


def test_fuse_trivial_rounds_exact_and_effective():
    """Trivial multilevel levels and all-ones DFT twiddles vanish; outputs
    are bit-identical before and after."""
    f = Field(NTT)
    plan = plan_multilevel_dft(8, 1, NTT, (2, 2, 2))
    ir = plan.to_ir()
    fused = fuse_trivial_rounds(ir)
    n_local = lambda s: sum(1 for t in s.steps if not hasattr(t, "transfers"))
    assert n_local(fused) < n_local(ir)  # the stage-0 all-ones twiddle died
    assert ir_messages(fused) == ir_messages(ir)
    x = random_vector(f, 8, seed=2)
    np.testing.assert_array_equal(interpret(ir, x, f)[0], interpret(fused, x, f)[0])

    # a trivial hierarchy level contributes zero rounds either way
    A = random_matrix(F, 12, seed=9)
    tri = plan_multilevel(12, 1, (3, 4, 1)).to_ir(A)
    ref = plan_multilevel(12, 1, (3, 4)).to_ir(A)
    assert ir_messages(fuse_trivial_rounds(tri)) == ir_messages(ref)
    x = random_vector(F, 12, seed=3)
    np.testing.assert_array_equal(
        interpret(fuse_trivial_rounds(tri), x, F)[0], interpret(ref, x, F)[0]
    )


def test_fuse_keeps_truncating_identity_and_empty_rounds_are_loud():
    """A LocalOp replaces the buffer, so an 'identity' op whose out_slots
    don't cover every live slot is a truncation, not a no-op — fuse must
    keep it. And an empty CommRound is a loud error (the §I model never
    schedules one), not a silent skip, in both ir_messages and interpret."""
    from repro.core.ir import CommRound, LocalOp, ScheduleIR, Transfer

    K = 2
    gather = CommRound(
        tuple(
            Transfer(k, (k + 1) % K, port=1, slots=((0, 1),), mode="store")
            for k in range(K)
        )
    )
    eye = np.broadcast_to(np.eye(1, dtype=np.uint64), (K, 1, 1)).copy()
    truncate = LocalOp((0,), (0,), eye)  # identity on slot 0 — but slot 1 is live
    ship1 = CommRound(
        tuple(
            Transfer(k, (k + 1) % K, port=1, slots=((1, 0),), mode="store")
            for k in range(K)
        )
    )
    ir = ScheduleIR("synthetic", K, 1, (gather, truncate, ship1))
    fused = fuse_trivial_rounds(ir)
    assert len(fused.steps) == 3  # the truncating identity survived
    x = random_vector(F, K, seed=1)
    np.testing.assert_array_equal(interpret(ir, x, F)[0], interpret(fused, x, F)[0])

    empty = ScheduleIR("synthetic", K, 1, (gather, CommRound(()), ship1))
    with pytest.raises(ValueError, match="empty communication round"):
        ir_messages(empty)
    with pytest.raises(ValueError, match="empty communication round"):
        interpret(empty, x, F)
    assert len(fuse_trivial_rounds(empty).steps) == 2  # fuse removes it


# ---------------------------------------------------------------------------
# remap_digits: torus-native butterfly
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("rows,cols", [(2, 4), (4, 2), (4, 4)])
def test_remap_digits_hop_count_1_and_exact(rows, cols):
    """Acceptance: every round's partners are torus neighbors after the
    pass (the plain butterfly is multi-hop), and the relabeled schedule
    stays bit-exact with unchanged C1/C2."""
    K = rows * cols
    topo = Torus2D(rows, cols)
    plan = plan_butterfly(K, 1, NTT)
    ir = plan.to_ir()
    assert max_round_hops(ir, topo) > 1
    rir = remap_digits(ir, topo)
    assert max_round_hops(rir, topo) == 1
    f = Field(NTT)
    x = random_vector(f, K, seed=K)
    out, st_ = interpret(rir, x, f)
    np.testing.assert_array_equal(
        out, encode_oracle(x, butterfly_target_matrix(f, K, 2), NTT)
    )
    assert st_.C1 == plan.H and st_.C2 == plan.H
    assert ir_permute_count(rir) == ir_permute_count(ir)


def test_autotune_flips_to_remapped_butterfly_on_torus():
    """Acceptance: on the 2D torus the remap-digits pipeline's rewrite
    prices cheaper (contention 1, single-hop) and the tuner picks the
    (butterfly, remap-digits) candidate; on flat topologies no remap
    candidate is even offered (the pipeline's predicate rejects)."""
    r = autotune(16, 1, 65536, Torus2D(4, 4), q=NTT, generator="dft")
    assert r.algorithm == "butterfly+remap-digits"
    chosen = r.chosen
    assert chosen.base_algorithm == "butterfly"
    assert chosen.pipeline == "remap-digits"
    assert chosen.estimate.max_contention == 1
    plain = next(c for c in r.candidates if c.algorithm == "butterfly")
    assert chosen.predicted_time < plain.predicted_time
    from repro.topo import FullyConnected

    flat = autotune(16, 1, 65536, FullyConnected(16), q=NTT, generator="dft")
    assert all(c.pipeline != "remap-digits" for c in flat.candidates)


def test_autotuner_offers_multilevel_dft_on_hierarchy():
    """The first post-IR algorithm participates with no bespoke simulator /
    lowering / executor: it appears, prices, and can win on a deep
    hierarchy with a DFT generator."""
    topo = Hierarchy(levels=(4, 2, 2))
    r = autotune(16, 1, 65536, topo, q=NTT, generator="dft")
    names = [c.algorithm for c in r.candidates]
    assert "multilevel-dft" in names
    cand = next(c for c in r.candidates if c.algorithm == "multilevel-dft")
    assert cand.c1 == cand.c2 == 4  # log2 16, per-level stages
    # structured beats the universal multilevel on the same topology
    uni = next(c for c in r.candidates if c.algorithm == "multilevel")
    assert cand.predicted_time < uni.predicted_time


# ---------------------------------------------------------------------------
# pass pipelines: exactness + ppermute budget, over every family × fabric
# ---------------------------------------------------------------------------

#: a contended ring whose LinkCost γ > 0 — the only regime in which
#: split_contended can strictly win (γ = 0 makes the per-link max subadditive)
_GAMMA_RING = lambda K: Ring(K, cost=LinkCost(1e-6, 4.0 / 50e9, gamma=0.5))


def _pipeline_topos(K):
    """Per-K fabrics to exercise every pass predicate: contended ring
    (split/fuse), tori (remap), two-level + hierarchy (align)."""
    topos = [_GAMMA_RING(K)]
    if K == 8:
        topos += [Torus2D(2, 4), Torus3D(depth=2, rows=2, cols=2),
                  TwoLevel(k_intra=4, k_inter=2), Hierarchy(levels=(2, 2, 2))]
    elif K == 12:
        topos += [TwoLevel(k_intra=4, k_inter=3), Hierarchy(levels=(4, 3))]
    elif K == 16:
        topos += [Torus2D(4, 4), Torus3D(depth=2, rows=2, cols=4),
                  TwoLevel(k_intra=4, k_inter=4), Hierarchy(levels=(4, 2, 2))]
    return topos


@pytest.mark.parametrize("idx", range(len(_CASES)), ids=[l for l, _ in _CASES])
def test_every_pipeline_stays_exact_and_within_ppermute_budget(idx):
    """Property (ISSUE acceptance): every registered PassPipeline, applied to
    every family's compiled IR at K ∈ {8, 12, 16} on every fabric where its
    predicate passes, stays bit-exact vs. the matrix oracle and never exceeds
    the original IR's ppermute budget."""
    label, build = _CASES[idx]
    ir, target, q, _, _ = build()
    f = Field(q)
    x = random_vector(f, ir.K, seed=idx)
    want = encode_oracle(x, target, q)
    budget = ir_permute_count(ir)
    applied = 0
    for topo in _pipeline_topos(ir.K):
        for pl in PIPELINES.values():
            if not pl.applicable(ir, topo):
                continue
            rewritten = pl.apply(ir, topo)
            applied += 1
            ctx = f"{label} × {pl.name} × {topo.name}"
            np.testing.assert_array_equal(
                interpret(rewritten, x, f)[0], want, err_msg=ctx
            )
            assert ir_permute_count(rewritten) <= budget, ctx
            if rewritten is not ir and pl.name != "remap-digits":
                # price-guarded passes never regress the α-β price
                # (remap minimizes HOPS; the autotuner prices it separately)
                assert ir_time(rewritten, topo) <= ir_time(ir, topo) * (
                    1 + 1e-9
                ), ctx
    assert applied > 0, f"no pipeline applicable anywhere for {label}"


def test_split_contended_strictly_improves_on_contended_ring():
    """ISSUE acceptance: on a ring whose links degrade under contention
    (γ > 0) the staggered schedule strictly beats the original α-β price,
    preserving the ppermute count and bit-exactness."""
    K, p = 16, 2
    topo = _GAMMA_RING(K)
    plan = plan_prepare_shoot(K, p)
    A = random_matrix(F, K, seed=3)
    ir = plan.to_ir(A)
    pay = (1 << 20) // 4
    split = split_contended(ir, topo, pay)
    assert split is not ir
    assert ir_time(split, topo, pay) < ir_time(ir, topo, pay)
    assert split.c1 > ir.c1  # staggering costs rounds, wins time
    assert ir_permute_count(split) == ir_permute_count(ir)
    x = random_vector(F, K, seed=4)
    np.testing.assert_array_equal(
        interpret(split, x, F)[0], encode_oracle(x, A, M31)
    )
    # γ = 0 additive model: the identical call is a provable no-op
    assert split_contended(ir, Ring(K), pay) is ir


def test_fuse_rounds_merges_legal_neighbors_and_repacks_split():
    """fuse_rounds merges adjacent hazard-free rounds within the p-port
    budget (synthetic IR: 2 rounds → 1, bit-identical), and re-packs
    split_contended's staggering back to the original round count when the
    pricing topology doesn't charge for contention."""
    from repro.core.ir import CommRound, ScheduleIR, Transfer

    K, p = 4, 2
    a = CommRound(tuple(
        Transfer(k, (k + 1) % K, port=1, slots=((0, 1),), mode="store")
        for k in range(K)
    ))
    b = CommRound(tuple(
        Transfer(k, (k + 2) % K, port=1, slots=((0, 2),), mode="store")
        for k in range(K)
    ))
    ir = ScheduleIR("synthetic", K, p, (a, b))
    fused = fuse_rounds(ir, Ring(K))
    assert fused.c1 == 1 and ir.c1 == 2
    assert ir_permute_count(fused) == ir_permute_count(ir)  # 2 port groups
    x = random_vector(F, K, seed=7)
    np.testing.assert_array_equal(interpret(fused, x, F)[0], interpret(ir, x, F)[0])
    # p=1 would blow the port budget: the merge must be refused
    assert fuse_rounds(ScheduleIR("synthetic", K, 1, (a, b)), Ring(K)).c1 == 2

    topo = _GAMMA_RING(16)
    base = plan_prepare_shoot(16, 2).to_ir(random_matrix(F, 16, seed=5))
    split = split_contended(base, topo, 1 << 18)
    assert split.c1 > base.c1
    repacked = fuse_rounds(split, Ring(16), 1 << 18)  # γ = 0: merging is free
    assert repacked.c1 == base.c1


def test_remap_digits_torus3d_hop_count_1_and_exact():
    """Torus3D: the 3D Gray embedding makes every butterfly partner a torus
    neighbor for all-2/4 dims, bit-exactly, with unchanged budgets."""
    f = Field(NTT)
    for depth, rows, cols in [(2, 2, 2), (2, 2, 4)]:
        K = depth * rows * cols
        topo = Torus3D(depth=depth, rows=rows, cols=cols)
        plan = plan_butterfly(K, 1, NTT)
        ir = plan.to_ir()
        if (depth, rows, cols) != (2, 2, 2):
            # (all-size-2 dims are already neighbor-complete; 2×2×4 is not)
            assert max_round_hops(ir, topo) > 1
        rir = remap_digits(ir, topo)
        assert max_round_hops(rir, topo) == 1, (depth, rows, cols)
        x = random_vector(f, K, seed=K)
        np.testing.assert_array_equal(
            interpret(rir, x, f)[0],
            encode_oracle(x, butterfly_target_matrix(f, K, 2), NTT),
        )
        assert ir_permute_count(rir) == ir_permute_count(ir)


def test_remap_digits_radix_reexpression_on_binary_torus():
    """A radix-4 butterfly (p = 3) has no radix-4 digits on a 2×8 torus; the
    pass re-expresses its digits in binary (radix 4 is a 2-power) and still
    finds a low-dilation embedding — exact, budget preserved."""
    from repro.topo.passes import _remap_radix

    f = Field(NTT)
    K, p = 16, 3
    topo = Torus2D(2, 8)
    plan = plan_butterfly(K, p, NTT)
    ir = plan.to_ir()
    assert _remap_radix(ir, topo) == (2, 4)
    rir = remap_digits(ir, topo)
    assert rir is not ir
    assert max_round_hops(rir, topo) < max_round_hops(ir, topo)
    x = random_vector(f, K, seed=11)
    np.testing.assert_array_equal(
        interpret(rir, x, f)[0],
        encode_oracle(x, butterfly_target_matrix(f, K, p + 1), NTT),
    )
    assert ir_permute_count(rir) == ir_permute_count(ir)


def test_remap_digits_greedy_fallback_warns_and_stays_exact():
    """Satellite: forcing the assignment search over its exhaustive limit
    takes the greedy-swap fallback, which WARNS (never silently truncates —
    the historical H > 12 behavior) and still returns an exact relabeling."""
    f = Field(NTT)
    K = 16
    topo = Torus2D(4, 4)
    plan = plan_butterfly(K, 1, NTT)
    ir = plan.to_ir()
    with pytest.warns(RuntimeWarning, match="greedy swap"):
        rir = remap_digits(ir, topo, exhaustive_limit=1)
    assert max_round_hops(rir, topo) == 1  # greedy finds the Gray embedding
    x = random_vector(f, K, seed=13)
    np.testing.assert_array_equal(
        interpret(rir, x, f)[0],
        encode_oracle(x, butterfly_target_matrix(f, K, 2), NTT),
    )


# ---------------------------------------------------------------------------
# calibration fit
# ---------------------------------------------------------------------------


def test_fit_level_costs_recovers_planted_alpha_beta():
    """Synthetic sweep: price schedules with a KNOWN per-level α/β, feed the
    exact walls to the fitter, recover the constants."""
    from repro.topo import LinkCost

    topo = Hierarchy(levels=(2, 2, 2))
    true = (
        LinkCost(1e-6, 1e-10),
        LinkCost(3e-6, 8e-10),
        LinkCost(1e-5, 8e-9),
    )
    schedules = {
        "prepare-shoot": lower(plan_prepare_shoot(8, 1)).rounds,
        "hierarchical": lower(plan_hierarchical(8, 1, 2)).rounds,
        "multilevel": lower(plan_multilevel(8, 1, (2, 2, 2))).rounds,
        "ring": lower(plan_ring(8, 1)).rounds,
    }
    samples = []
    for rounds in schedules.values():
        feats = round_features(rounds, topo)
        for pay in (1 << 10, 1 << 14, 1 << 18):
            wall = sum(
                r["msgs"] * true[r["level"]].alpha
                + r["elems"] * pay * true[r["level"]].beta
                for r in feats
            )
            samples.append({"payload_elems": pay, "wall_s": wall, "rounds": feats})
    fitted = fit_level_costs(samples, n_levels=3)
    for got, want in zip(fitted, true):
        assert got.alpha == pytest.approx(want.alpha, rel=1e-6)
        assert got.beta == pytest.approx(want.beta, rel=1e-6)
    with pytest.raises(ValueError):
        fit_level_costs(samples[:2], n_levels=3)


# ---------------------------------------------------------------------------
# generic executor on a torus mesh (subprocess; the CI torus-mesh step)
# ---------------------------------------------------------------------------


def test_remapped_butterfly_on_torus_mesh():
    """8 forced host devices as a 2×4 (y × x) torus mesh: the Gray-remapped
    butterfly IR runs through the generic ir_encode_jit, is bit-exact vs.
    the butterfly target matrix under the placement permutation, and lowers
    to collective-permutes only with the committed H·p budget."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import NTT, Field
        from repro.core.matrices import butterfly_target_matrix, random_vector
        from repro.core.prepare_shoot import encode_oracle
        from repro.core.schedule import plan_butterfly
        from repro.topo import Torus2D, max_round_hops, remap_digits
        from repro.dist.collectives import ir_encode_jit

        f = Field(NTT)
        K = 8
        topo = Torus2D(2, 4)
        plan = plan_butterfly(K, 1, NTT)
        rir = remap_digits(plan.to_ir(), topo)
        assert max_round_hops(rir, topo) == 1
        mesh = make_mesh((2, 4), ("y", "x"))
        fn = ir_encode_jit(mesh, ("y", "x"), rir, q=NTT)
        x = random_vector(f, (K, 16), seed=5)
        place = np.asarray(rir.placement)
        inv = np.empty(K, np.int64); inv[place] = np.arange(K)
        out_dev = np.asarray(
            fn(jnp.asarray(x[inv].astype(np.uint32))), dtype=np.uint64)
        out = out_dev[place]
        G = butterfly_target_matrix(f, K, 2)
        np.testing.assert_array_equal(out, encode_oracle(x, G, NTT))
        jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((K, 4), jnp.uint32))
        assert str(jaxpr).count("ppermute") == plan.H * 1
        txt = fn.lower(jax.ShapeDtypeStruct((K, 16), jnp.uint32)).compile().as_text()
        assert txt.count("collective-permute") > 0 and "all-gather" not in txt
        print("torus remap exec ok")
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert r.returncode == 0, f"child failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "torus remap exec ok" in r.stdout


def test_remapped_butterfly_on_torus3d_mesh():
    """8 forced host devices as a 2×2×2 (z × y × x) 3D torus mesh: the
    3D-embedded butterfly IR runs through the generic ir_encode_jit,
    bit-exact under the placement permutation, collective-permutes only
    (the CI 3D-torus-mesh step)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import NTT, Field
        from repro.core.matrices import butterfly_target_matrix, random_vector
        from repro.core.prepare_shoot import encode_oracle
        from repro.core.schedule import plan_butterfly
        from repro.topo import Torus3D, max_round_hops, remap_digits
        from repro.dist.collectives import ir_encode_jit

        f = Field(NTT)
        K = 8
        topo = Torus3D(depth=2, rows=2, cols=2)
        plan = plan_butterfly(K, 1, NTT)
        rir = remap_digits(plan.to_ir(), topo)
        assert max_round_hops(rir, topo) == 1
        mesh = make_mesh((2, 2, 2), ("z", "y", "x"))
        fn = ir_encode_jit(mesh, ("z", "y", "x"), rir, q=NTT)
        x = random_vector(f, (K, 16), seed=6)
        place = np.asarray(rir.placement if rir.placement is not None
                           else np.arange(K))
        inv = np.empty(K, np.int64); inv[place] = np.arange(K)
        out_dev = np.asarray(
            fn(jnp.asarray(x[inv].astype(np.uint32))), dtype=np.uint64)
        out = out_dev[place]
        G = butterfly_target_matrix(f, K, 2)
        np.testing.assert_array_equal(out, encode_oracle(x, G, NTT))
        jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((K, 4), jnp.uint32))
        assert str(jaxpr).count("ppermute") == plan.H * 1
        txt = fn.lower(jax.ShapeDtypeStruct((K, 16), jnp.uint32)).compile().as_text()
        assert txt.count("collective-permute") > 0 and "all-gather" not in txt
        print("torus3d remap exec ok")
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert r.returncode == 0, f"child failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "torus3d remap exec ok" in r.stdout


def test_ir_permute_counts_match_committed_budgets():
    """Host-side: the IR's port-group count equals the legacy committed
    budgets for the canonical configs (equality, not just ≤)."""
    from repro.dist.collectives import (
        expected_hier_permute_count,
        expected_multilevel_permute_count,
        expected_permute_count,
    )

    for K, p in [(8, 1), (8, 2), (16, 1), (27, 2), (64, 3)]:
        plan = plan_prepare_shoot(K, p)
        assert ir_permute_count(plan.to_ir()) == expected_permute_count(plan)
    for K, I, p in [(8, 2, 1), (8, 4, 2), (12, 3, 1), (16, 4, 2)]:
        plan = plan_hierarchical(K, p, I)
        assert ir_permute_count(plan.to_ir()) == expected_hier_permute_count(plan)
    for K, levels, p in [(8, (2, 2, 2), 1), (12, (3, 2, 2), 1), (24, (2, 3, 4), 2)]:
        plan = plan_multilevel(K, p, levels)
        assert ir_permute_count(plan.to_ir()) == expected_multilevel_permute_count(plan)
    for K, p in [(8, 1), (9, 2), (16, 1)]:
        q = NTT if p == 1 else M31
        plan = plan_butterfly(K, p, q)
        assert ir_permute_count(plan.to_ir()) == plan.H * p
