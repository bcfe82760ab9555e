"""Differential harness for the fused-kernel executor and the
``pipeline-rounds`` comm/compute-overlap rewrite (ISSUE 8).

Three layers of evidence, cheapest first:

* **host-side fuzz** — for every algorithm family at K ∈ {8, 12, 16}, both
  fields, random/Vandermonde/Lagrange generators and odd payload shapes,
  ``interpret(pipeline_rounds(ir))`` is bit-exact vs. the matrix oracle,
  the ppermute budget is byte-identical to the un-rewritten IR, and C1 is
  unchanged (the rewrite must never add or touch a comm round);
* **rewrite structure** — the pass actually fires (returns a different IR
  with ``overlap=True`` shadow contractions) on the prologue-heavy families
  at 64k-element payloads, and prices strictly cheaper there;
* **subprocess mesh differential** — on a forced-host 8-device mesh the
  two executor lowerings (``fused``, and ``pallas`` in interpret mode,
  pinned through the ``dist.collectives._lowering`` seam) × {no pipeline,
  "pipeline"} all produce the exact oracle bytes, the pipelined executors
  keep the committed jaxpr ppermute budgets, the compiled HLO stays
  collective-permute-only, and every step of the fused program carries its
  ``round<r>`` / ``local<i>`` scope.

Property tests are hypothesis-driven when hypothesis is installed
(tests/hyputil.py); the exhaustive parametrized sweeps below double as the
seeded-random fallback and always run.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from hyputil import given, settings, st
from repro.core.field import M31, NTT, Field
from repro.core.ir import fuse_trivial_rounds, ir_allgather, ir_permute_count
from repro.core.matrices import (
    butterfly_target_matrix,
    distinct_points,
    lagrange_matrix,
    random_matrix,
    random_vector,
    vandermonde,
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
    plan_hierarchical,
    plan_multilevel,
    plan_multilevel_dft,
    plan_ring,
    plan_two_level_dft,
    multilevel_dft_matrix,
    two_level_dft_matrix,
)
from repro.topo.model import FullyConnected
from repro.topo.passes import PIPELINES, ir_compute_time, ir_time, pipeline_rounds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F = Field(M31)

#: payload size at which the α-β + MAC pricing makes the overlap rewrite
#: profitable (the ISSUE's ≥64k-element acceptance regime)
BIG = 1 << 16


def _gen(field: Field, kind: str, K: int, seed: int) -> np.ndarray:
    """General-generator taxonomy the executors must be universal over."""
    if kind == "random":
        return random_matrix(field, K, seed=seed)
    if kind == "vandermonde":
        return vandermonde(field, distinct_points(field, K, seed=seed))
    if kind == "lagrange":
        omegas = distinct_points(field, K, seed=seed)
        alphas = distinct_points(field, K, seed=seed + 1)
        return lagrange_matrix(field, alphas, omegas)
    raise ValueError(kind)


def _cases():
    """(label, build() → (ir, target, q)) — every family × K ∈ {8, 12, 16},
    general families additionally × field × generator kind."""
    cases = []
    for K in (8, 12, 16):
        for q in (M31, NTT):
            for gk in ("random", "vandermonde", "lagrange"):
                f = Field(q)

                def mk_ps(K=K, q=q, gk=gk, f=f):
                    A = _gen(f, gk, K, seed=K + len(gk))
                    return plan_prepare_shoot(K, 1).to_ir(A, q=q), A, q

                cases.append((f"ps-{K}-{q & 0xffff:x}-{gk}", mk_ps))

        def mk_ps2(K=K):
            A = _gen(F, "random", K, seed=K * 5)
            return plan_prepare_shoot(K, 2).to_ir(A), A, M31

        cases.append((f"ps-{K}-p2", mk_ps2))

        def mk_ring(K=K):
            A = _gen(F, "vandermonde", K, seed=K)
            return plan_ring(K, 1).to_ir(A), A, M31

        cases.append((f"ring-{K}", mk_ring))

        def mk_ag(K=K):
            A = _gen(F, "lagrange", K, seed=K)
            return ir_allgather(K, 1, A), A, M31

        cases.append((f"allgather-{K}", mk_ag))

        for I in (2, 4):
            if K % I:
                continue

            def mk_h(K=K, I=I):
                A = _gen(F, "random", K, seed=K * 3 + I)
                return plan_hierarchical(K, 1, I).to_ir(A), A, M31

            cases.append((f"hierarchical-{K}-{I}", mk_h))

        def mk_dl(K=K):
            plan = plan_draw_loose(K, 1, NTT, seed=1)
            return plan.to_ir(), draw_loose_target_matrix(plan), NTT

        cases.append((f"draw-loose-{K}", mk_dl))

    for K, levels in [(8, (2, 2, 2)), (12, (3, 2, 2)), (16, (2, 2, 4))]:

        def mk_ml(K=K, levels=levels):
            A = _gen(F, "vandermonde", K, seed=K * 31 + levels[0])
            return plan_multilevel(K, 1, levels).to_ir(A), A, M31

        cases.append((f"multilevel-{K}-{levels}", mk_ml))

    for K in (8, 16):

        def mk_bf(K=K):
            f = Field(NTT)
            plan = plan_butterfly(K, 1, NTT)
            return plan.to_ir(), butterfly_target_matrix(f, K, 2), NTT

        cases.append((f"butterfly-{K}", mk_bf))

        def mk_dft2(K=K):
            plan = plan_two_level_dft(K, 1, NTT, 2 if K == 8 else 4)
            return plan.to_ir(), two_level_dft_matrix(plan), NTT

        cases.append((f"two-level-dft-{K}", mk_dft2))

        def mk_mldft(K=K):
            levels = (2, 2, 2) if K == 8 else (2, 2, 2, 2)
            plan = plan_multilevel_dft(K, 1, NTT, levels)
            return fuse_trivial_rounds(plan.to_ir()), multilevel_dft_matrix(plan), NTT

        cases.append((f"multilevel-dft-{K}", mk_mldft))
    return cases


_CASES = _cases()


def _check_case(idx: int, seed_salt: int = 0):
    label, build = _CASES[idx]
    ir, target, q = build()
    f = Field(q)
    topo = FullyConnected(ir.K)
    piped = pipeline_rounds(ir, topo, payload_elems=BIG)
    # comm structure untouched: byte-identical ppermute budget and C1
    assert ir_permute_count(piped) == ir_permute_count(ir), label
    assert piped.c1 == ir.c1, label
    x = random_vector(f, ir.K, seed=len(label) + seed_salt)
    out, _ = interpret(piped, x, f)
    np.testing.assert_array_equal(out, encode_oracle(x, target, q), err_msg=label)


@pytest.mark.parametrize("idx", range(len(_CASES)), ids=[l for l, _ in _CASES])
def test_pipelined_every_family_bit_exact(idx):
    """Exhaustive seeded sweep (the no-hypothesis fallback): the pipelined
    IR is bit-exact vs. the matrix oracle with the ppermute budget and C1
    unchanged, for every family/field/generator combination. (Odd payload
    shapes — padding — are exercised on the real mesh in
    test_kernel_modes_differential_on_mesh; the host interpreter is
    scalar-payload by contract.)"""
    _check_case(idx)


@settings(max_examples=40, deadline=None)
@given(
    st.sampled_from(range(len(_CASES))),
    st.integers(min_value=0, max_value=99),
)
def test_pipelined_every_family_property(idx, seed_salt):
    """Property form of the same contract over random inputs
    (hypothesis-driven when available)."""
    _check_case(idx, seed_salt)


def test_pipeline_rounds_fires_on_prologue_families():
    """On the prologue-heavy families at 64k-element payloads the rewrite
    must actually trigger: a different IR, at least one overlap=True update
    LocalOp per pipelined round, comm rounds byte-identical, and a strictly
    cheaper α-β+MAC price."""
    from repro.core.ir import CommRound, LocalOp

    builds = {
        "prepare-shoot": lambda: plan_prepare_shoot(8, 1).to_ir(
            random_matrix(F, 8, seed=0)
        ),
        "hierarchical": lambda: plan_hierarchical(12, 1, 4).to_ir(
            random_matrix(F, 12, seed=1)
        ),
        "multilevel": lambda: plan_multilevel(8, 1, (2, 2, 2)).to_ir(
            random_matrix(F, 8, seed=2)
        ),
    }
    topo8 = FullyConnected(8)
    for name, build in builds.items():
        ir = build()
        topo = FullyConnected(ir.K)
        piped = pipeline_rounds(ir, topo, payload_elems=BIG)
        assert piped is not ir, f"{name}: rewrite did not fire"
        overlaps = [
            s for s in piped.steps if isinstance(s, LocalOp) and s.overlap
        ]
        assert overlaps and all(s.update for s in overlaps), name
        assert [s for s in piped.steps if isinstance(s, CommRound)] == [
            s for s in ir.steps if isinstance(s, CommRound)
        ], f"{name}: comm rounds must be byte-identical"
        t0 = ir_time(ir, topo, payload_elems=BIG)
        t1 = ir_time(piped, topo, payload_elems=BIG)
        assert t1 < t0, (name, t0, t1)
    # structure-only IRs (autotune candidates carry coeffs=None) also rewrite
    bare = plan_multilevel(8, 1, (2, 2, 2)).to_ir()
    assert pipeline_rounds(bare, topo8, payload_elems=BIG) is not bare


def test_pipeline_registered_and_declines_non_prologue_irs():
    """"pipeline" is in the pass registry (the autotuner's ``+pipeline``
    suffix comes from here); families with no deferrable prologue —
    allgather, ring, butterfly — come back unchanged (identity, not a
    broken rewrite)."""
    assert "pipeline" in PIPELINES
    topo = FullyConnected(8)
    for ir in (
        ir_allgather(8, 1, random_matrix(F, 8, seed=3)),
        plan_ring(8, 1).to_ir(random_matrix(F, 8, seed=4)),
        plan_butterfly(8, 1, NTT).to_ir(),
    ):
        assert PIPELINES["pipeline"].apply(ir, topo, BIG) is ir
    ir = plan_prepare_shoot(8, 1).to_ir(random_matrix(F, 8, seed=3))
    piped = PIPELINES["pipeline"].apply(ir, topo, BIG)
    assert piped is not ir
    # overlap credit: the pipelined IR's charged compute is strictly below
    # what the same steps would cost with the overlap flags stripped (some
    # work actually hides under the wire)
    charged = ir_compute_time(piped, topo, BIG)
    from dataclasses import replace as _rp
    from repro.core.ir import LocalOp

    flat = _rp(
        piped,
        steps=tuple(
            _rp(s, overlap=False) if isinstance(s, LocalOp) and s.overlap else s
            for s in piped.steps
        ),
    )
    assert charged < ir_compute_time(flat, topo, BIG)


# ---------------------------------------------------------------------------
# subprocess: the two LocalOp lowerings on a real forced-host mesh
# ---------------------------------------------------------------------------


def run_child(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert r.returncode == 0, f"child failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_kernel_modes_differential_on_mesh():
    """Both LocalOp lowerings × {"", "pipeline"} on the 8-device mesh: ps
    (both fields, Lagrange + random generators, odd payload), multilevel,
    hierarchical and butterfly — every lowering produces the exact oracle
    bytes. The test pins the lowering by replacing ``_lowering``; pallas
    then runs in interpret mode on CPU (same kernels the TPU path jits)."""
    out = run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, NTT, Field
        from repro.core.matrices import (
            distinct_points, lagrange_matrix, random_matrix, random_vector)
        from repro.core.prepare_shoot import encode_oracle
        from repro.dist import collectives
        from repro.dist.collectives import (
            butterfly_jit, hierarchical_encode_jit, multilevel_encode_jit,
            ps_encode_jit)

        LOWERINGS = ("fused", "pallas")

        def pin(lowering):  # the test seam: what a mesh platform maps to
            collectives._lowering = lambda platform: lowering

        K = 8
        mesh1 = make_mesh((8,), ("enc",))
        mesh2 = make_mesh((4, 2), ("inter", "intra"))
        mesh3 = make_mesh((2, 2, 2), ("pod", "slice", "chip"))
        for q in (M31, NTT):
            f = Field(q)
            omg = distinct_points(f, K, seed=0)
            alp = distinct_points(f, K, seed=1)
            gens = {
                "lagrange": lagrange_matrix(f, alp, omg),
                "random": random_matrix(f, K, seed=2),
            }
            x = random_vector(f, (K, 16, 3), seed=3)  # odd payload: padding
            xs = jnp.asarray(x.astype(np.uint32))
            for name, A in gens.items():
                want = encode_oracle(x, A, q)
                for kern in LOWERINGS:
                    pin(kern)
                    for pipe in ("", "pipeline"):
                        fn, _ = ps_encode_jit(mesh1, "enc", np.asarray(A),
                                              p=1, q=q, pipeline=pipe)
                        got = np.asarray(fn(xs), dtype=np.uint64)
                        assert np.array_equal(got, want), (q, name, kern, pipe)
                        kernel_ran = "pallas_call" in str(jax.make_jaxpr(fn)(xs))
                        assert kernel_ran == (kern == "pallas"), (kern, pipe)
        # multilevel + hierarchical: fused/pallas with the pipeline applied
        f = Field(M31)
        A = random_matrix(f, K, seed=4)
        x = random_vector(f, (K, 7), seed=5)
        xs = jnp.asarray(x.astype(np.uint32))
        want = encode_oracle(x, A, M31)
        for kern, pipe in [("fused", "pipeline"), ("pallas", "pipeline"),
                           ("fused", "")]:
            pin(kern)
            fn, _ = multilevel_encode_jit(
                mesh3, ("pod", "slice", "chip"), np.asarray(A), p=1,
                pipeline=pipe)
            assert np.array_equal(np.asarray(fn(xs), dtype=np.uint64), want), (
                "ml", kern, pipe)
            fn, _ = hierarchical_encode_jit(
                mesh2, "inter", "intra", np.asarray(A), p=1, pipeline=pipe)
            assert np.array_equal(np.asarray(fn(xs), dtype=np.uint64), want), (
                "hier", kern, pipe)
        # butterfly (NTT twiddles hit the butterfly_mac lowering)
        from repro.core.matrices import butterfly_target_matrix
        fq = Field(NTT)
        xb = random_vector(fq, (K, 5), seed=6)
        xbs = jnp.asarray(xb.astype(np.uint32))
        wantb = encode_oracle(xb, butterfly_target_matrix(fq, K, 2), NTT)
        for kern in LOWERINGS:
            pin(kern)
            fnb, _ = butterfly_jit(mesh1, "enc", q=NTT)
            assert np.array_equal(np.asarray(fnb(xbs), dtype=np.uint64), wantb), kern
            assert ("pallas_call" in str(jax.make_jaxpr(fnb)(xbs))) == (kern == "pallas")
        print("kernel modes ok")
        """
    )
    assert "kernel modes ok" in out


def test_pipelined_budget_regression_and_hlo():
    """Satellite (c): with pipeline="pipeline" every executor still emits
    EXACTLY the committed jaxpr ppermute budget, and the compiled HLO is
    collective-permute-only (no all-gather) — the overlap rewrite must not
    leak extra communication."""
    out = run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, Field
        from repro.core.matrices import random_matrix
        from repro.dist.collectives import (
            expected_hier_permute_count, expected_multilevel_permute_count,
            expected_permute_count, hierarchical_encode_jit,
            multilevel_encode_jit, ps_encode_jit)

        f = Field(M31)
        A = np.asarray(random_matrix(f, 8, seed=0))
        shape = jax.ShapeDtypeStruct((8, 4), jnp.uint32)
        mesh1 = make_mesh((8,), ("enc",))
        for p in (1, 2):
            fn, plan = ps_encode_jit(mesh1, "enc", A, p=p, pipeline="pipeline")
            n = str(jax.make_jaxpr(fn)(shape)).count("ppermute")
            assert n == expected_permute_count(plan), ("ps", p, n)
        mesh2 = make_mesh((4, 2), ("inter", "intra"))
        fn, plan = hierarchical_encode_jit(
            mesh2, "inter", "intra", A, p=1, pipeline="pipeline")
        n = str(jax.make_jaxpr(fn)(shape)).count("ppermute")
        assert n == expected_hier_permute_count(plan), ("hier", n)
        mesh3 = make_mesh((2, 2, 2), ("pod", "slice", "chip"))
        fn, plan = multilevel_encode_jit(
            mesh3, ("pod", "slice", "chip"), A, p=1, pipeline="pipeline")
        n = str(jax.make_jaxpr(fn)(shape)).count("ppermute")
        assert n == expected_multilevel_permute_count(plan), ("ml", n)
        txt = fn.lower(jax.ShapeDtypeStruct((8, 16), jnp.uint32)).compile().as_text()
        assert txt.count("collective-permute") > 0
        assert "all-gather" not in txt, "pipelined encode must not all-gather"
        print("pipelined budgets ok")
        """
    )
    assert "pipelined budgets ok" in out


#: the builds whose fused program the scope test reads: (id, builder source)
_SCOPE_BUILDS = {
    "ps-p1": 'ps_encode_jit(mesh1, "enc", A, p=1)',
    "ps-p2": 'ps_encode_jit(mesh1, "enc", A, p=2)',
    "ps-p1-pipeline": 'ps_encode_jit(mesh1, "enc", A, p=1, pipeline="pipeline")',
    "hierarchical-4x2": 'hierarchical_encode_jit(mesh2, "inter", "intra", A, p=1)',
    "multilevel-2x2x2": 'multilevel_encode_jit(mesh3, AXES3, A, p=1)',
    "multilevel-2x2x2-pipeline": (
        'multilevel_encode_jit(mesh3, AXES3, A, p=1, pipeline="pipeline")'
    ),
    "butterfly-ntt": 'butterfly_jit(mesh1, "enc", q=NTT)',
}


@pytest.fixture(scope="module")
def scoped_programs(tmp_path_factory):
    """Lower and compile every build of ``_SCOPE_BUILDS`` once on the forced
    8-device mesh; per build, the IR's CommRound and LocalOp counts, the
    lowered text with its debug locations and the compiled HLO text."""
    out_dir = tmp_path_factory.mktemp("scopes")
    builds = ",\n".join(f"    {k!r}: lambda: {v}" for k, v in _SCOPE_BUILDS.items())
    run_child(
        f"""
import json, os
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.core.field import M31, NTT, Field
from repro.core.ir import CommRound, LocalOp
from repro.core.matrices import random_matrix
from repro.dist.collectives import (
    _apply_pipeline, butterfly_jit, hierarchical_encode_jit,
    multilevel_encode_jit, ps_encode_jit)

A = np.asarray(random_matrix(Field(M31), 8, seed=0))
AXES3 = ("pod", "slice", "chip")
mesh1 = make_mesh((8,), ("enc",))
mesh2 = make_mesh((4, 2), ("inter", "intra"))
mesh3 = make_mesh((2, 2, 2), AXES3)
BUILDS = {{
{builds}
}}
PIPES = {{k: "pipeline" if k.endswith("-pipeline") else "" for k in BUILDS}}
for key, build in BUILDS.items():
    fn, plan = build()
    ir = plan.to_ir() if key.startswith("butterfly") else plan.to_ir(A, q=M31)
    ir = _apply_pipeline(ir, PIPES[key])
    lowered = fn.lower(jax.ShapeDtypeStruct((8, 16), jnp.uint32))
    rec = {{
        "rounds": sum(isinstance(s, CommRound) for s in ir.steps),
        "locals": sum(isinstance(s, LocalOp) for s in ir.steps),
        "lowered": lowered.as_text(debug_info=True),
        "compiled": lowered.compile().as_text(),
    }}
    with open(os.path.join({str(out_dir)!r}, key + ".json"), "w") as fh:
        json.dump(rec, fh)
print("scoped programs written")
"""
    )
    return {
        key: json.loads((out_dir / f"{key}.json").read_text())
        for key in _SCOPE_BUILDS
    }


@pytest.mark.parametrize("build", list(_SCOPE_BUILDS))
def test_fused_program_steps_carry_their_scopes(scoped_programs, build):
    """Every step of the one fused program is attributable in a device
    profile: the lowered text names every ``round<r>`` and every
    ``local<i>`` of the IR (the compiler may fold a LocalOp's ops away, so
    the locals are read before compilation), and in the compiled HLO every
    collective-permute sits under a ``round<r>`` scope, each round owning at
    least one."""
    rec = scoped_programs[build]
    assert rec["rounds"] >= 1 and rec["locals"] >= 1, rec["rounds"]
    want = {f"round{r}" for r in range(rec["rounds"])} | {
        f"local{i}" for i in range(rec["locals"])
    }
    named = set(re.findall(r'["/]((?:round|local)\d+)/', rec["lowered"]))
    assert named == want, (build, sorted(named ^ want))

    permutes = [
        line for line in rec["compiled"].splitlines()
        if re.search(r"\scollective-permute(-start)?\(", line)
    ]
    assert permutes, build
    owners = []
    for line in permutes:
        m = re.search(r'op_name="[^"]*/round(\d+)/', line)
        assert m, (build, line)
        owners.append(int(m.group(1)))
    assert set(owners) == set(range(rec["rounds"])), (build, sorted(set(owners)))
