"""Distributed collectives + pipeline on 8 placeholder host devices.

Run in a subprocess so the XLA_FLAGS device-count override never leaks into
the main test process (smoke tests must see 1 device)."""

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


def test_ps_encode_and_baseline_collectives():
    run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("enc",))
        from repro.core.field import M31, Field
        from repro.core.matrices import random_matrix, random_vector
        from repro.core.prepare_shoot import encode_oracle
        from repro.dist.collectives import ps_encode_jit, allgather_encode_jit

        f = Field(M31)
        A = random_matrix(f, 8, seed=0)
        x = random_vector(f, (8, 16), seed=1)
        for p in (1, 2):
            fn, plan = ps_encode_jit(mesh, "enc", np.asarray(A), p=p)
            out = fn(jnp.asarray(x.astype(np.uint32)))
            np.testing.assert_array_equal(np.asarray(out, dtype=np.uint64), encode_oracle(x, A))
        ag = allgather_encode_jit(mesh, "enc", np.asarray(A))
        np.testing.assert_array_equal(
            np.asarray(ag(jnp.asarray(x.astype(np.uint32))), dtype=np.uint64),
            encode_oracle(x, A),
        )
        print("OK")
        """
    )


def test_butterfly_collective_and_inverse():
    run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("enc",))
        from repro.core.field import NTT, Field
        from repro.core.matrices import butterfly_target_matrix, random_vector
        from repro.core.prepare_shoot import encode_oracle
        from repro.dist.collectives import butterfly_jit

        f = Field(NTT)
        x = random_vector(f, (8, 4), seed=2)
        fn, plan = butterfly_jit(mesh, "enc", p=1)
        out = fn(jnp.asarray(x.astype(np.uint32)))
        G = butterfly_target_matrix(f, 8, 2)
        np.testing.assert_array_equal(np.asarray(out, dtype=np.uint64), encode_oracle(x, G, NTT))
        ifn, _ = butterfly_jit(mesh, "enc", p=1, inverse=True)
        np.testing.assert_array_equal(np.asarray(ifn(out)), x.astype(np.uint32))
        print("OK")
        """
    )


def test_collective_hlo_has_permutes_not_allgather():
    """The prepare-and-shoot collective must lower to collective-permute ops
    (paper schedule), NOT to a K-sized all-gather."""
    out = run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((8,), ("enc",))
        from repro.core.field import M31, Field
        from repro.core.matrices import random_matrix
        from repro.dist.collectives import ps_encode_jit

        f = Field(M31)
        fn, plan = ps_encode_jit(mesh, "enc", np.asarray(random_matrix(f, 8, seed=0)), p=1)
        lowered = fn.lower(jax.ShapeDtypeStruct((8, 16), jnp.uint32))
        txt = lowered.compile().as_text()
        n_cp = txt.count("collective-permute")
        assert n_cp > 0, "expected collective-permute ops"
        assert "all-gather" not in txt, "universal encode must not all-gather"
        print("collective-permutes:", n_cp)
        """
    )
    assert "collective-permutes:" in out


def test_pipeline_gpipe():
    run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("pipe",))
        from repro.dist.pipeline import pipeline_apply, stack_stage_params

        def stage(params, x):
            W, b = params
            return jnp.tanh(x @ W + b)

        rng = np.random.default_rng(0)
        S, d = 4, 8
        plist = [
            (jnp.asarray(rng.normal(size=(d, d)).astype(np.float32) * 0.3),
             jnp.asarray(rng.normal(size=(d,)).astype(np.float32) * 0.1))
            for _ in range(S)
        ]
        x = jnp.asarray(rng.normal(size=(6, 3, d)).astype(np.float32))
        out = jax.jit(lambda p, xx: pipeline_apply(stage, p, xx, mesh=mesh, axis="pipe"))(
            stack_stage_params(plist), x
        )
        ref = x
        for pms in plist:
            ref = jax.vmap(lambda mb: stage(pms, mb))(ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
        print("OK")
        """
    )


def test_coded_checkpoint_collective_roundtrip():
    """The coded-checkpoint mesh path (rs_checkpoint.encode_parity_collective
    → dist.collectives.ps_encode_jit) produces the same parity packets as the
    single-program path, and the recovery solve is bit-exact from them."""
    run_child(
        """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.coded.rs_checkpoint import (
            build_parity_plan, encode_parity, encode_parity_collective, recover_lost)

        K = 8
        mesh = make_mesh((8,), ("dp",))
        plan = build_parity_plan(K, p=1)
        rng = np.random.default_rng(3)
        shards = rng.integers(0, 1 << 16, size=(K, 32), dtype=np.uint32)
        fn = encode_parity_collective(mesh, "dp", plan)
        parity = np.asarray(fn(jnp.asarray(shards)), dtype=np.uint64)
        ref = np.asarray(encode_parity(jnp.asarray(shards), plan), dtype=np.uint64)
        np.testing.assert_array_equal(parity, ref)
        lost = [1, 6]
        rec = recover_lost(
            plan, lost,
            {k: shards[k].astype(np.uint64) for k in range(K) if k not in lost},
            {k: parity[k] for k in range(K) if k not in lost},
        )
        for k in lost:
            np.testing.assert_array_equal(rec[k], shards[k].astype(np.uint64))
        print("OK")
        """
    )


def test_sharding_rules_divisibility():
    """Divisibility-aware logical→physical mapping (no subprocess needed)."""
    import jax

    from repro.dist.sharding import ShardingRules, spec_for
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("model",))
    rules = ShardingRules()
    # divisible dim → sharded; non-divisible → replicated
    s1 = spec_for(mesh, rules, ("batch", "d_ff"), (4, 16))
    assert s1 == jax.sharding.PartitionSpec(None, "model") or s1 == jax.sharding.PartitionSpec(
        None, ("model",)
    ) or str(s1).count("model")
    s2 = spec_for(mesh, rules, ("heads",), (7,))  # 7 % 1 == 0 → still maps
    assert "model" in str(s2)
