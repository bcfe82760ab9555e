"""Topology benchmark: flat vs. two-level vs. three-level encode on 8
forced-host devices, plus the calibration sweep the α/β fitter consumes.

Times ``ps_encode_jit`` (1D mesh), ``hierarchical_encode_jit`` (4×2
inter×intra mesh), ``multilevel_encode_jit`` (2×2×2 pod×slice×chip mesh —
the recursive three-level schedule) and the ``allgather_encode_jit`` foil on
the same Vandermonde encode ACROSS A PAYLOAD SWEEP, in a subprocess with
``--xla_force_host_platform_device_count=8`` (the override must not leak
into sibling benchmarks). All timing goes through ``benchmarks.common.
time_fn`` (samples routed into ``bench.topology.*_us`` metrics
histograms), and the child ALSO runs the three-level encode through
``ir_encode_jit(tracer=...)`` — the traced per-round dispatch path — so
every CommRound leaves a span with measured wall µs next to the α-β
model's prediction. Emits ``results/BENCH_topology.json`` with:

* the measured wall times next to the autotuner's α-β predictions on the
  matching two-level topology (``measured_s`` feeds straight back into
  ``autotune(..., measured=...)`` / ``resolve_profile(measured=...)``);
* a ``three_level`` block with the same sweep priced on the
  ``Hierarchy(levels=(2, 2, 2))`` model;
* a ``calibration`` block — offline aggregate ``samples`` (one per
  (algorithm, payload): whole-encode seconds + analytic per-round
  ``{level, msgs, elems}`` rows) AND a ``live`` sub-block fitted from the
  traced per-round spans (the ROADMAP "feed the fit from LIVE sweep
  telemetry" item — ``repro.obs.feed``). The persisted
  ``fitted_level_costs`` come from the live fit when it succeeds, and are
  verified to round-trip through ``topo.calibrate.load_fitted_costs`` —
  the exact loader ``launch.profiles.resolve_profile`` uses;
* a ``fused_kernels`` block — the same flat schedule timed with the three
  ``kernels=`` LocalOp lowerings (legacy ``jnp`` loop vs the batched
  ``fused`` contraction, plus ``fused`` with the ``pipeline`` overlap
  rewrite) at the ≥64k-element payloads, with measured fused-vs-jnp
  speedups (the ISSUE 8 wall-clock acceptance);
* the child's metrics-registry snapshot under ``metrics``.

The traced spans are also persisted under ``results/traces/
bench_topology.{jsonl,trace.json}`` (Perfetto-loadable);
``launch/perf_report.py`` renders the predicted-vs-measured tables and
``render_drift`` renders the per-round drift from the trace.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

from .common import emit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PAYLOADS = (1 << 12, 1 << 14, 1 << 16)

_CHILD = """
    import json
    import numpy as np, jax, jax.numpy as jnp
    from benchmarks.common import time_fn
    from repro.launch.mesh import make_mesh
    from repro.core.field import M31, Field
    from repro.core.matrices import distinct_points, vandermonde, random_vector
    from repro.dist.collectives import (
        allgather_encode_jit, hierarchical_encode_jit, ir_encode_jit,
        multilevel_encode_jit, ps_encode_jit)
    from repro.obs import Tracer, get_registry
    from repro.topo import Hierarchy, plan_multilevel

    K = 8
    PAYLOADS = %(payloads)r
    f = Field(M31)
    A = np.asarray(vandermonde(f, distinct_points(f, K, seed=0)))

    mesh1 = make_mesh((8,), ("enc",))
    mesh2 = make_mesh((4, 2), ("inter", "intra"))
    mesh3 = make_mesh((2, 2, 2), ("pod", "slice", "chip"))
    fn_ps, _ = ps_encode_jit(mesh1, "enc", A, p=1)
    fn_h, _ = hierarchical_encode_jit(mesh2, "inter", "intra", A, p=1)
    fn_m, _ = multilevel_encode_jit(mesh3, ("pod", "slice", "chip"), A, p=1)
    fn_ag = allgather_encode_jit(mesh1, "enc", A)
    fns = {"prepare-shoot": fn_ps, "hierarchical": fn_h,
           "multilevel": fn_m, "allgather": fn_ag}
    # the traced per-round dispatch of the SAME three-level schedule:
    # every CommRound becomes one span with measured wall vs predicted us
    topo3 = Hierarchy(levels=(2, 2, 2))
    ir3 = plan_multilevel(K, 1, (2, 2, 2)).to_ir(A)
    tracer = Tracer()
    fn_traced = ir_encode_jit(
        mesh3, ("pod", "slice", "chip"), ir3, tracer=tracer, topo=topo3)
    sweep = {alg: {} for alg in fns}
    live_windows = []
    for pay in PAYLOADS:
        x = jnp.asarray(random_vector(f, (K, pay), seed=1).astype(np.uint32))
        outs = {alg: np.asarray(fn(x)) for alg, fn in fns.items()}
        ref = outs["prepare-shoot"]
        for alg, o in outs.items():
            assert np.array_equal(ref, o), f"flat and {alg} disagree"
        for alg, fn in fns.items():
            sweep[alg][str(pay)] = time_fn(
                fn, x, warmup=1, iters=5,
                metric=f"bench.topology.{alg}_us")
        # traced run: first call compiles the per-round dispatches; only
        # the second call's spans are calibration-grade measurements
        assert np.array_equal(ref, np.asarray(fn_traced(x)))
        n0 = len(tracer.spans)
        assert np.array_equal(ref, np.asarray(fn_traced(x)))
        live_windows.append((n0, len(tracer.spans)))
    measured_spans = []
    for n0, n1 in live_windows:
        measured_spans += [s.to_dict() for s in tracer.spans[n0:n1]]
    print(json.dumps({
        "sweep": sweep,
        "spans": measured_spans,
        "metrics": get_registry().snapshot(),
    }))
"""

# Fused-kernel / pipelined-rounds comparison (ISSUE 8): its own 16-device
# child — K=16, p=2 prepare-shoot is the contraction-heaviest flat schedule
# the forced host can carry (a 3×9 shoot-init contraction per device), so the
# LocalOp lowering (kernels=) and the comm/compute-overlap rewrite
# (pipeline="pipeline") are visible over the emulated wire time at the
# ISSUE's ≥64k-element payloads. All variants are bit-exact by construction
# (asserted below and in tests/test_fused_encode.py).
_CHILD_FUSED = """
    import json
    import numpy as np, jax, jax.numpy as jnp
    from benchmarks.common import time_fn
    from repro.launch.mesh import make_mesh
    from repro.core.field import M31, Field
    from repro.core.matrices import distinct_points, vandermonde, random_vector
    from repro.dist.collectives import ps_encode_jit

    K = 16
    PAYLOADS = %(payloads)r
    f = Field(M31)
    A = np.asarray(vandermonde(f, distinct_points(f, K, seed=0)))
    mesh = make_mesh((16,), ("enc",))
    variants = {
        "jnp": ps_encode_jit(mesh, "enc", A, p=2, kernels="jnp")[0],
        "fused": ps_encode_jit(mesh, "enc", A, p=2, kernels="fused")[0],
        "fused+pipeline": ps_encode_jit(
            mesh, "enc", A, p=2, kernels="fused", pipeline="pipeline")[0],
    }
    rows = {}
    for pay in PAYLOADS:
        x = jnp.asarray(random_vector(f, (K, pay), seed=2).astype(np.uint32))
        ref, row = None, {}
        for name, fn in variants.items():
            o = np.asarray(fn(x))
            ref = o if ref is None else ref
            assert np.array_equal(ref, o), f"kernels={name} disagrees"
            row[name] = time_fn(
                fn, x, warmup=2, iters=9,
                metric=f"bench.topology.kernels_{name.replace('+', '_')}_us")
        rows[str(pay)] = row
    print(json.dumps(rows))
"""

FUSED_PAYLOADS = (1 << 16, 1 << 17)


def _run_fused_child():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    env["JAX_PLATFORMS"] = "cpu"  # emulated host devices, never the chip
    env["PYTHONPATH"] = os.pathsep.join([REPO, os.path.join(REPO, "src")])
    r = subprocess.run(
        [
            sys.executable,
            "-c",
            textwrap.dedent(_CHILD_FUSED % {"payloads": FUSED_PAYLOADS}),
        ],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(f"bench_topology fused child failed:\n{r.stdout}\n{r.stderr}")
    return json.loads(r.stdout.strip().splitlines()[-1])


def run():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"  # emulated host devices, never the chip
    # repo root (for benchmarks.common) + src (for repro)
    env["PYTHONPATH"] = os.pathsep.join([REPO, os.path.join(REPO, "src")])
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_CHILD % {"payloads": PAYLOADS})],
        capture_output=True,
        text=True,
        env=env,
        timeout=1200,
    )
    if r.returncode != 0:
        raise RuntimeError(f"bench_topology child failed:\n{r.stdout}\n{r.stderr}")
    child = json.loads(r.stdout.strip().splitlines()[-1])
    sweep = child["sweep"]
    spans = child["spans"]

    # α-β predictions for the same scenario on the matching topologies
    from repro.core.schedule import plan_prepare_shoot
    from repro.obs import drift_rows, round_measurements, write_chrome_trace, write_spans_jsonl
    from repro.topo import (
        Hierarchy,
        TwoLevel,
        autotune,
        fit_level_costs,
        lower,
        lower_allgather,
        plan_hierarchical,
        plan_multilevel,
        round_features,
    )

    K, PAY = 8, 1 << 14
    measured_us = {alg: times[str(PAY)] for alg, times in sweep.items()}
    topo = TwoLevel(k_intra=2, k_inter=4)
    result = autotune(K, 1, PAY * 4, topo, generator="vandermonde")

    def predicted_rows(res):
        return {
            c.algorithm: {
                "us": c.predicted_time * 1e6,
                "c1": c.c1,
                "c2": c.c2,
                "pipeline": c.pipeline,
            }
            for c in res.candidates
        }

    predicted = predicted_rows(result)
    two_level_us = {a: u for a, u in measured_us.items() if a != "multilevel"}
    record = {
        "K": K,
        "p": 1,
        "payload_elems": PAY,
        "mesh": "4x2 (inter x intra), forced-host",
        "topology": "two-level k_intra=2 k_inter=4",
        "autotuner_choice": result.algorithm,
        "autotuner_choice_pipeline": result.chosen.pipeline,
        "measured_us": two_level_us,
        # seconds, the unit autotune(..., measured=...) compares against
        "measured_s": {alg: us * 1e-6 for alg, us in two_level_us.items()},
        "predicted": predicted,
        "metrics": child["metrics"],
    }
    # three-level sweep: the same encode priced on the recursive hierarchy
    topo3 = Hierarchy(levels=(2, 2, 2))
    result3 = autotune(K, 1, PAY * 4, topo3, generator="vandermonde")
    # only multilevel actually ran on the 2×2×2 mesh — the flat/two-level
    # numbers above were measured on their own meshes and stay in the
    # top-level block (a measured_s map must match its stated mesh)
    three_level_us = {a: u for a, u in measured_us.items() if a == "multilevel"}
    record["three_level"] = {
        "mesh": "2x2x2 (pod x slice x chip), forced-host",
        "topology": "hierarchy levels=(2, 2, 2)",
        "autotuner_choice": result3.algorithm,
        "measured_us": three_level_us,
        "measured_s": {alg: us * 1e-6 for alg, us in three_level_us.items()},
        "predicted": predicted_rows(result3),
    }
    # calibration block: offline aggregate samples (whole-encode seconds ×
    # analytic round features) + the live per-round span fit
    rounds_by_alg = {
        "prepare-shoot": lower(plan_prepare_shoot(K, 1)).rounds,
        "hierarchical": lower(plan_hierarchical(K, 1, 2)).rounds,
        "multilevel": lower(plan_multilevel(K, 1, (2, 2, 2))).rounds,
        "allgather": lower_allgather(K, 1).rounds,
    }
    samples = []
    for alg, rounds in rounds_by_alg.items():
        feats = round_features(rounds, topo3)
        for pay_str, us in sweep[alg].items():
            samples.append(
                {
                    "algorithm": alg,
                    "payload_elems": int(pay_str),
                    "wall_s": us * 1e-6,
                    "rounds": feats,
                }
            )
    offline_fit = fit_level_costs(samples, n_levels=3)
    live_samples = round_measurements(spans)
    try:
        live_fit = fit_level_costs(live_samples, n_levels=3)
    except ValueError:
        live_fit = None
    # the persisted (load_fitted_costs-visible) costs are the LIVE fit when
    # the traced sweep produced one — telemetry-fed calibration; the offline
    # aggregate fit stays alongside for comparison
    fitted = live_fit if live_fit is not None else offline_fit
    record["calibration"] = {
        "model": "hierarchy levels=(2, 2, 2)",
        "samples": samples,
        "fitted_level_costs": [
            {"level": j, "alpha_s": c.alpha, "beta_s_per_elem": c.beta}
            for j, c in enumerate(fitted)
        ],
        "source": "live-trace" if live_fit is not None else "offline-aggregate",
        "offline_fitted_level_costs": [
            {"level": j, "alpha_s": c.alpha, "beta_s_per_elem": c.beta}
            for j, c in enumerate(offline_fit)
        ],
        "live": {
            "samples": live_samples,
            "fitted_level_costs": None
            if live_fit is None
            else [
                {"level": j, "alpha_s": c.alpha, "beta_s_per_elem": c.beta}
                for j, c in enumerate(live_fit)
            ],
            "note": "per-round spans from ir_encode_jit(tracer=...) on the "
            "2x2x2 forced-host mesh (repro.obs.feed)",
        },
        "note": "forced-host CPU emulation — the fit demonstrates the "
        "measured→α/β path; run on real ICI/DCI hardware for usable costs",
    }
    # fused/pipelined vs unfused lowering at >=64k payloads (ISSUE 8
    # acceptance: a measured wall-clock improvement over the unfused path,
    # not just a predicted-us delta)
    fused_rows = _run_fused_child()
    record["fused_kernels"] = {
        "mesh": "16 (enc), forced-host",
        "algorithm": "prepare-shoot",
        "K": 16,
        "p": 2,
        "measured_us": fused_rows,
        "speedup_fused_vs_jnp": {
            pay: row["jnp"] / row["fused"] for pay, row in fused_rows.items()
        },
        "speedup_fused_pipeline_vs_jnp": {
            pay: row["jnp"] / row["fused+pipeline"]
            for pay, row in fused_rows.items()
        },
        "note": "same ps_encode_jit schedule; jnp = legacy per-(i,j) loop "
        "kept behind the flag, fused = madd-folded row-batched Shoup "
        "contraction, fused+pipeline adds the pipeline-rounds overlap "
        "rewrite. On forced-host CPU the contraction folds are XLA-fused "
        "either way, so the fused delta is modest; the pipelined row is "
        "the measured win (and the Pallas lowering targets real TPUs).",
    }
    # per-round predicted-vs-measured drift from the traced sweep
    record["drift"] = drift_rows(spans)
    os.makedirs(os.path.join(REPO, "results"), exist_ok=True)
    out_path = os.path.join(REPO, "results", "BENCH_topology.json")
    with open(out_path, "w") as fh:
        json.dump(record, fh, indent=2)
    # persist the trace itself (Perfetto-loadable + machine-readable)
    traces = os.path.join(REPO, "results", "traces")
    write_spans_jsonl(spans, os.path.join(traces, "bench_topology.jsonl"))
    write_chrome_trace(
        spans,
        os.path.join(traces, "bench_topology.trace.json"),
        process_name="bench_topology",
    )
    # the persisted block must round-trip through the loader resolve_profile
    # uses — the calibration loop is only closed if this re-reads exactly
    from repro.topo import load_fitted_costs

    reloaded = load_fitted_costs(out_path)
    assert reloaded == fitted, f"calibration round-trip failed: {reloaded}"
    for alg, us in measured_us.items():
        pred = (
            record["three_level"]["predicted"]
            if alg == "multilevel"
            else predicted
        ).get(alg, {})
        emit(
            f"topology_encode_{alg}_K8",
            us,
            f"pred_us={pred.get('us', float('nan')):.1f},C1={pred.get('c1', '-')}",
        )
    for pay, row in fused_rows.items():
        for name, us in row.items():
            emit(
                f"topology_kernels_{name}_K16_{pay}",
                us,
                f"speedup_vs_jnp={row['jnp'] / us:.2f}x",
            )


if __name__ == "__main__":
    run()
