"""hierarchical_encode_jit on a 2D (inter × intra) mesh and
multilevel_encode_jit on a 3D (pod × slice × chip) mesh of 8 host devices.

Subprocess-isolated like tests/test_distributed.py (the XLA device-count
override must not leak). Acceptance: on 4×2 and 2×2×2 meshes the level-
aligned collectives are bit-exact vs. the single-program prepare_shoot
oracle for Vandermonde and DFT generators, and they lower to
collective-permutes only with exactly the plans' committed ppermute budgets.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
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


def test_hierarchical_encode_bitexact_vandermonde_and_dft():
    """4×2 and 2×4 meshes, p ∈ {1, 2}, Vandermonde (M31) + DFT (NTT) + a
    random matrix — all bit-exact vs. the matrix oracle and vs. the flat
    single-axis ps_encode_jit on the same inputs."""
    run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, NTT, Field
        from repro.core.matrices import (
            dft_matrix, distinct_points, random_matrix, random_vector, vandermonde)
        from repro.core.prepare_shoot import encode_oracle
        from repro.dist.collectives import hierarchical_encode_jit, ps_encode_jit

        K = 8
        for (G, I) in [(4, 2), (2, 4)]:
            mesh = make_mesh((G, I), ("inter", "intra"))
            for q in (M31, NTT):
                f = Field(q)
                gens = {
                    "random": random_matrix(f, K, seed=0),
                    "vandermonde": vandermonde(f, distinct_points(f, K, seed=1)),
                }
                if (q - 1) % K == 0:
                    gens["dft"] = dft_matrix(f, K)
                x = random_vector(f, (K, 16), seed=2)
                for p in (1, 2):
                    for name, A in gens.items():
                        fn, plan = hierarchical_encode_jit(
                            mesh, "inter", "intra", np.asarray(A), p=p, q=q)
                        out = fn(jnp.asarray(x.astype(np.uint32)))
                        np.testing.assert_array_equal(
                            np.asarray(out, dtype=np.uint64), encode_oracle(x, A, q))
        # same packets through the flat single-axis oracle executor
        mesh1 = make_mesh((8,), ("enc",))
        mesh2 = make_mesh((4, 2), ("inter", "intra"))
        f = Field(M31)
        A = np.asarray(vandermonde(f, distinct_points(f, K, seed=3)))
        x = random_vector(f, (K, 8), seed=4)
        f1, _ = ps_encode_jit(mesh1, "enc", A, p=1)
        f2, _ = hierarchical_encode_jit(mesh2, "inter", "intra", A, p=1)
        xs = jnp.asarray(x.astype(np.uint32))
        np.testing.assert_array_equal(np.asarray(f1(xs)), np.asarray(f2(xs)))
        print("OK")
        """
    )


def test_hierarchical_lowers_to_permutes_only():
    """jaxpr: exactly the committed ppermute budget; compiled HLO: at least
    one collective-permute and no all-gather (mirrors ps_encode_jit's
    communication-discipline assertion)."""
    out = run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, Field
        from repro.core.matrices import random_matrix
        from repro.dist.collectives import (
            expected_hier_permute_count, hierarchical_encode_jit)

        f = Field(M31)
        A = np.asarray(random_matrix(f, 8, seed=0))
        mesh = make_mesh((4, 2), ("inter", "intra"))
        for p in (1, 2):
            fn, plan = hierarchical_encode_jit(mesh, "inter", "intra", A, p=p)
            jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8, 4), jnp.uint32))
            n = str(jaxpr).count("ppermute")
            assert n == expected_hier_permute_count(plan), (p, n)
        fn, plan = hierarchical_encode_jit(mesh, "inter", "intra", A, p=1)
        txt = fn.lower(jax.ShapeDtypeStruct((8, 16), jnp.uint32)).compile().as_text()
        assert txt.count("collective-permute") > 0
        assert "all-gather" not in txt, "hierarchical encode must not all-gather"
        print("collective-permutes ok")
        """
    )
    assert "collective-permutes ok" in out


def test_multilevel_encode_bitexact_on_2x2x2():
    """2×2×2 pod×slice×chip mesh, p ∈ {1, 2}, Vandermonde + DFT + random —
    the recursive three-level collective is bit-exact vs. the matrix oracle
    and vs. the flat single-axis ps_encode_jit on the same inputs."""
    run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, NTT, Field
        from repro.core.matrices import (
            dft_matrix, distinct_points, random_matrix, random_vector, vandermonde)
        from repro.core.prepare_shoot import encode_oracle
        from repro.dist.collectives import multilevel_encode_jit, ps_encode_jit

        K = 8
        mesh = make_mesh((2, 2, 2), ("pod", "slice", "chip"))
        axes = ("pod", "slice", "chip")
        for q in (M31, NTT):
            f = Field(q)
            gens = {
                "random": random_matrix(f, K, seed=0),
                "vandermonde": vandermonde(f, distinct_points(f, K, seed=1)),
            }
            if (q - 1) % K == 0:
                gens["dft"] = dft_matrix(f, K)
            x = random_vector(f, (K, 16), seed=2)
            for p in (1, 2):
                for name, A in gens.items():
                    fn, plan = multilevel_encode_jit(mesh, axes, np.asarray(A), p=p, q=q)
                    out = fn(jnp.asarray(x.astype(np.uint32)))
                    np.testing.assert_array_equal(
                        np.asarray(out, dtype=np.uint64), encode_oracle(x, A, q))
        # same packets through the flat single-axis oracle executor
        mesh1 = make_mesh((8,), ("enc",))
        f = Field(M31)
        A = np.asarray(vandermonde(f, distinct_points(f, K, seed=3)))
        x = random_vector(f, (K, 8), seed=4)
        f1, _ = ps_encode_jit(mesh1, "enc", A, p=1)
        f3, _ = multilevel_encode_jit(mesh, axes, A, p=1)
        xs = jnp.asarray(x.astype(np.uint32))
        np.testing.assert_array_equal(np.asarray(f1(xs)), np.asarray(f3(xs)))
        print("OK")
        """
    )


def test_multilevel_lowers_to_permutes_only_2x2x2():
    """Acceptance: on the 2×2×2 mesh the jaxpr has exactly the committed
    ppermute budget and the compiled HLO is collective-permute-only (no
    all-gather) — including through the coded-checkpoint dispatch."""
    out = run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, Field
        from repro.core.matrices import random_matrix
        from repro.coded.rs_checkpoint import build_parity_plan, encode_parity_collective
        from repro.dist.collectives import (
            expected_multilevel_permute_count, multilevel_encode_jit)

        f = Field(M31)
        A = np.asarray(random_matrix(f, 8, seed=0))
        mesh = make_mesh((2, 2, 2), ("pod", "slice", "chip"))
        axes = ("pod", "slice", "chip")
        for p in (1, 2):
            fn, plan = multilevel_encode_jit(mesh, axes, A, p=p)
            jaxpr = jax.make_jaxpr(fn)(jax.ShapeDtypeStruct((8, 4), jnp.uint32))
            n = str(jaxpr).count("ppermute")
            assert n == expected_multilevel_permute_count(plan), (p, n)
        fn, plan = multilevel_encode_jit(mesh, axes, A, p=1)
        txt = fn.lower(jax.ShapeDtypeStruct((8, 16), jnp.uint32)).compile().as_text()
        assert txt.count("collective-permute") > 0
        assert "all-gather" not in txt, "multilevel encode must not all-gather"
        # coded-checkpoint dispatch: a tuple of DP axes routes to the
        # multilevel executor with the same ppermute-only discipline
        pplan = build_parity_plan(8, p=1)
        fn_c = encode_parity_collective(mesh, axes, pplan)
        txt = fn_c.lower(jax.ShapeDtypeStruct((8, 16), jnp.uint32)).compile().as_text()
        assert txt.count("collective-permute") > 0 and "all-gather" not in txt
        print("collective-permutes ok")
        """
    )
    assert "collective-permutes ok" in out


def test_multilevel_permute_budget_host_side():
    """The committed multilevel budget matches the lowered schedule's
    per-round sender out-degree — no devices needed."""
    from repro.dist.collectives import expected_multilevel_permute_count
    from repro.topo import lower, plan_multilevel

    for K, levels, p in [
        (8, (2, 2, 2), 1),
        (8, (2, 2, 2), 2),
        (12, (3, 2, 2), 1),
        (16, (2, 2, 2, 2), 1),
        (24, (2, 3, 4), 2),
    ]:
        plan = plan_multilevel(K, p, levels)
        low = lower(plan)
        ports = 0
        for msgs in low.rounds:
            out_deg: dict[int, int] = {}
            for (src, _dst) in msgs:
                out_deg[src] = out_deg.get(src, 0) + 1
            ports += max(out_deg.values())
        assert expected_multilevel_permute_count(plan) == ports, (K, levels, p)


def test_hier_permute_budget_host_side():
    """The committed budget matches the lowered schedule's non-empty
    (round, port) structure — no devices needed."""
    from repro.dist.collectives import expected_hier_permute_count
    from repro.topo import lower, plan_hierarchical

    for K, I, p in [(8, 2, 1), (8, 4, 2), (12, 3, 1), (16, 4, 2)]:
        plan = plan_hierarchical(K, p, I)
        low = lower(plan)
        # one ppermute per port per round = each sender's out-degree
        ports = 0
        for msgs in low.rounds:
            out_deg: dict[int, int] = {}
            for (src, _dst) in msgs:
                out_deg[src] = out_deg.get(src, 0) + 1
            ports += max(out_deg.values())
        assert expected_hier_permute_count(plan) == ports, (K, I, p)
