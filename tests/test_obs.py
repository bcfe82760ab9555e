"""Observability layer: span tracing, Chrome-trace export, metrics registry,
the traced per-round executor, and the live calibration feed.

The expensive traced-executor test forks a subprocess with 8 forced host
devices (same harness as tests/test_ir.py) and asserts the ISSUE acceptance
criteria: exactly one span per CommRound with the α-β prediction attached,
bit-exact output vs. the fused path, and an UNCHANGED ppermute budget on the
untraced executor's jaxpr."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.obs import (
    MetricsRegistry,
    Span,
    Tracer,
    drift_rows,
    feed_calibration,
    read_spans,
    refit_from_spans,
    round_measurements,
    spans_to_chrome,
    write_chrome_trace,
    write_spans_jsonl,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# tracer + chrome export
# ---------------------------------------------------------------------------


def test_span_nesting_and_ordering():
    tr = Tracer()
    with tr.span("outer", kind="root") as outer:
        with tr.span("inner-a", i=0):
            pass
        with tr.span("inner-b", i=1) as b:
            with tr.span("leaf"):
                pass
    assert [s.name for s in tr.spans] == ["outer", "inner-a", "inner-b", "leaf"]
    assert [s.depth for s in tr.spans] == [0, 1, 1, 2]
    assert [s.parent for s in tr.spans] == [None, 0, 0, 2]
    # start-ordered, children contained in parents, durations filled
    assert all(s.dur_us >= 0 for s in tr.spans)
    for s in tr.spans[1:]:
        p = tr.spans[s.parent]
        assert p.ts_us <= s.ts_us
        assert s.ts_us + s.dur_us <= p.ts_us + p.dur_us + 1e-6
    assert outer.attrs == {"kind": "root"}
    assert b.attrs == {"i": 1}


def test_chrome_trace_roundtrip(tmp_path):
    """Spans → Chrome trace JSON: valid X events, monotonic timestamps,
    args carrying attrs — and read_spans loads them back."""
    tr = Tracer()
    with tr.span("encode", algorithm="multilevel"):
        with tr.span("round[0]", comm_round=0, predicted_us=12.5):
            pass
        with tr.span("round[1]", comm_round=1, predicted_us=30.0):
            pass
    rec = spans_to_chrome(tr.spans, process_name="test")
    evs = rec["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "test"
    xs = [e for e in evs if e["ph"] == "X"]
    assert [e["name"] for e in xs] == ["encode", "round[0]", "round[1]"]
    ts = [e["ts"] for e in xs]
    assert ts == sorted(ts)
    assert all(e["dur"] >= 0 for e in xs)
    assert xs[1]["args"] == {"comm_round": 0, "predicted_us": 12.5}

    chrome = tmp_path / "t.trace.json"
    jsonl = tmp_path / "t.jsonl"
    write_chrome_trace(tr.spans, str(chrome))
    write_spans_jsonl(tr.spans, str(jsonl))
    for path in (chrome, jsonl):
        back = read_spans(str(path))
        assert [s["name"] for s in back] == ["encode", "round[0]", "round[1]"]
        assert back[1]["attrs"]["comm_round"] == 0
    # the jsonl sink additionally preserves the span tree
    back = read_spans(str(jsonl))
    assert [s["parent"] for s in back] == [None, 0, 0]
    # and both files satisfy the CI schema gate
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_trace

        assert check_trace.check_trace(json.load(open(chrome))) == []
        assert check_trace.main([str(chrome)]) == 0
        assert check_trace.main([str(jsonl)]) == 0
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_snapshot_deterministic(tmp_path):
    def run():
        reg = MetricsRegistry()
        reg.counter("encode.rounds").inc(3)
        reg.gauge("serve.tokens_per_s").set(123.5)
        h = reg.histogram("encode.round_us", level=1)
        for v in (5.0, 1.0, 9.0, 3.0):
            h.observe(v)
        reg.histogram("encode.round_us", level=0).observe(2.0)
        return reg

    a, b = run().snapshot(), run().snapshot()
    assert a == b
    assert list(a) == sorted(a)  # deterministic key order
    assert a["encode.rounds"] == {"type": "counter", "value": 3.0}
    hist = a["encode.round_us{level=1}"]
    assert hist["count"] == 4 and hist["min"] == 1.0 and hist["max"] == 9.0
    assert hist["p50"] == 3.0 or hist["p50"] == 5.0
    p1, p2 = tmp_path / "a.json", tmp_path / "b.json"
    run().write_json(str(p1))
    run().write_json(str(p2))
    assert p1.read_text() == p2.read_text()


def test_metrics_registry_contracts():
    reg = MetricsRegistry()
    c = reg.counter("x")
    assert reg.counter("x") is c  # same series → same instrument
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(TypeError):
        reg.gauge("x")  # kind mismatch on an existing series
    assert reg.counter("x", shard=0) is not c  # labels make a new series
    reg.reset()
    assert reg.snapshot() == {}


# ---------------------------------------------------------------------------
# calibration feed + drift (pure host-side, synthetic spans)
# ---------------------------------------------------------------------------


def _synth_round_span(i, level, dur_us, elems, predicted_us=10.0):
    return Span(
        name=f"round[{i}]",
        ts_us=float(i * 100),
        dur_us=dur_us,
        attrs={
            "algorithm": "multilevel",
            "comm_round": i,
            "level": level,
            "msgs": 1,
            "elems": elems,
            "payload_elems": 1,  # β multiplies elems × payload in the fit
            "predicted_us": predicted_us,
        },
    )


def test_round_measurements_and_refit():
    # α=1ms, β=1µs/elem at level 0; α=2ms, β=2µs/elem at level 1 —
    # recoverable exactly because the synthetic walls ARE the model
    spans = []
    i = 0
    for level, (a_s, b_s) in enumerate([(1e-3, 1e-6), (2e-3, 2e-6)]):
        for elems in (10, 100, 1000):
            spans.append(
                _synth_round_span(i, level, (a_s + b_s * elems) * 1e6, elems)
            )
            i += 1
    ms = round_measurements(spans)
    assert len(ms) == 6
    assert ms[0]["rounds"] == [{"level": 0, "msgs": 1, "elems": 10}]
    fitted = refit_from_spans(spans)  # n_levels inferred = 2
    assert len(fitted) == 2
    assert fitted[0].alpha == pytest.approx(1e-3, rel=1e-6)
    assert fitted[0].beta == pytest.approx(1e-6, rel=1e-6)
    assert fitted[1].alpha == pytest.approx(2e-3, rel=1e-6)
    assert fitted[1].beta == pytest.approx(2e-6, rel=1e-6)
    with pytest.raises(ValueError):
        refit_from_spans([])  # no traced rounds


def test_feed_calibration_persists_where_loader_reads(tmp_path):
    """Acceptance: the live feed lands exactly where load_fitted_costs —
    and therefore resolve_profile(calibration=...) — reads fitted costs."""
    from repro.launch.profiles import resolve_profile
    from repro.topo import load_fitted_costs

    spans = []
    i = 0
    for level, (a_s, b_s) in enumerate([(0.5, 1e-6), (2.0, 1e-5)]):
        for elems in (10, 100, 1000):
            spans.append(
                _synth_round_span(i, level, (a_s + b_s * elems) * 1e6, elems)
            )
            i += 1
    path = tmp_path / "BENCH_topology.json"
    # pre-existing record keys must survive the merge
    path.write_text(json.dumps({"K": 8, "calibration": {"note": "old"}}))
    fitted = feed_calibration(spans, str(path))
    rec = json.loads(path.read_text())
    assert rec["K"] == 8 and rec["calibration"]["note"] == "old"
    assert rec["calibration"]["source"] == "live-trace"
    assert tuple(load_fitted_costs(str(path))) == tuple(fitted)
    # absurdly slow fitted α (0.5 s / 2 s) must dominate candidate pricing
    prof = resolve_profile(multi_pod=False, calibration=str(path))
    assert prof.fitted_costs == tuple(fitted)
    assert prof.tune.chosen.predicted_time > 1.0

    # the trace-path variant: resolve_profile refits from the file itself
    jsonl = tmp_path / "enc.jsonl"
    write_spans_jsonl(spans, str(jsonl))
    prof2 = resolve_profile(multi_pod=False, calibration=str(jsonl))
    assert prof2.fitted_costs is not None
    assert prof2.fitted_costs[0].alpha == pytest.approx(0.5, rel=1e-5)
    assert prof2.tune.chosen.predicted_time > 1.0


def test_drift_rows_and_render():
    from repro.launch.perf_report import render_drift

    spans = [
        _synth_round_span(0, 0, dur_us=12.0, elems=10, predicted_us=10.0),
        _synth_round_span(1, 1, dur_us=99.0, elems=10, predicted_us=10.0),
    ]
    rows = drift_rows(spans, threshold=0.5)
    assert [r["round"] for r in rows] == [1, 0]  # worst first
    assert rows[0]["flagged"] and not rows[1]["flagged"]
    assert rows[1]["rel_err"] == pytest.approx(0.2)
    table = render_drift(spans)
    assert "| 1 | multilevel | 1 | 10.0 | 99.0 |" in table
    assert "1/2 rounds flagged" in table


# ---------------------------------------------------------------------------
# traced executor on a forced-host 8-device mesh (subprocess)
# ---------------------------------------------------------------------------


def test_traced_ir_encode_one_span_per_round():
    """ISSUE acceptance: ir_encode_jit(tracer=...) on a 2×2×2 forced-host
    mesh emits exactly one span per CommRound (with predicted_us + level
    calibration attrs), stays bit-exact vs. the fused path, and the
    UNTRACED executor's jaxpr ppermute budget is unchanged."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    code = """
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.core.field import M31, Field
        from repro.core.ir import ir_permute_count
        from repro.core.matrices import distinct_points, vandermonde, random_vector
        from repro.dist.collectives import ir_encode_jit
        from repro.obs import Tracer, feed_calibration, get_registry
        from repro.topo import Hierarchy, plan_multilevel

        K = 8
        f = Field(M31)
        A = np.asarray(vandermonde(f, distinct_points(f, K, seed=0)))
        ir = plan_multilevel(K, 1, (2, 2, 2)).to_ir(A)
        mesh = make_mesh((2, 2, 2), ("pod", "slice", "chip"))
        topo = Hierarchy(levels=(2, 2, 2))
        x = jnp.asarray(random_vector(f, (K, 32), seed=3).astype(np.uint32))

        fused = ir_encode_jit(mesh, ("pod", "slice", "chip"), ir)
        ref = np.asarray(fused(x))
        # untraced budget UNCHANGED: one ppermute per port group
        jaxpr = jax.make_jaxpr(fused)(jax.ShapeDtypeStruct((K, 4), jnp.uint32))
        budget = ir_permute_count(ir)
        assert str(jaxpr).count("ppermute") == budget, (
            str(jaxpr).count("ppermute"), budget)

        tracer = Tracer()
        fn = ir_encode_jit(mesh, ("pod", "slice", "chip"), ir,
                           tracer=tracer, topo=topo)
        out = np.asarray(fn(x))
        assert np.array_equal(out, ref), "traced output != fused output"
        roots = [s for s in tracer.spans if s.name == "ir_encode"]
        comm = [s for s in tracer.spans if "comm_round" in s.attrs]
        assert len(roots) == 1
        assert len(comm) == ir.c1 == 3, (len(comm), ir.c1)
        assert [s.attrs["comm_round"] for s in comm] == [0, 1, 2]
        for s in comm:
            assert s.parent == 0 and s.dur_us > 0
            for key in ("predicted_us", "level", "msgs", "elems",
                        "transfers", "ppermutes", "payload_elems"):
                assert key in s.attrs, (s.name, key)
        assert sum(s.attrs["ppermutes"] for s in comm) == budget
        # levels innermost-out: chip=0, slice=1, pod=2
        assert [s.attrs["level"] for s in comm] == [0, 1, 2]
        snap = get_registry().snapshot()
        assert snap["encode.rounds"]["value"] == 3
        assert snap["encode.ppermutes"]["value"] == budget
        assert snap["encode.bytes_on_wire"]["value"] > 0
        assert snap["encode.round_us{level=0}"]["count"] == 1
        # the live feed closes on these very spans
        import tempfile, os as _os
        tmp = tempfile.mkdtemp()
        path = _os.path.join(tmp, "cal.json")
        fn(x)  # second traced call: 6 round spans total -> fit solvable
        fitted = feed_calibration(tracer.spans, path, n_levels=3)
        from repro.topo import load_fitted_costs
        assert tuple(load_fitted_costs(path)) == tuple(fitted)
        print("traced encode ok")
    """
    r = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        env=env,
        timeout=600,
    )
    assert r.returncode == 0, f"child failed:\nSTDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "traced encode ok" in r.stdout


def test_traced_interpret_oracle():
    """The interpret oracle takes the same tracer= and emits one span per
    CommRound without changing its output."""
    import numpy as np

    from repro.core.field import M31, Field
    from repro.core.matrices import distinct_points, random_vector, vandermonde
    from repro.core.simulator import interpret
    from repro.topo import Hierarchy, plan_multilevel

    K = 8
    f = Field(M31)
    A = np.asarray(vandermonde(f, distinct_points(f, K, seed=0)))
    ir = plan_multilevel(K, 1, (2, 2, 2)).to_ir(A)
    x = random_vector(f, (K,), seed=2)
    ref, _ = interpret(ir, x, f)
    tr = Tracer()
    out, _ = interpret(ir, x, f, tracer=tr, topo=Hierarchy(levels=(2, 2, 2)))
    np.testing.assert_array_equal(ref, out)
    comm = [s for s in tr.spans if "comm_round" in s.attrs]
    assert len(comm) == ir.c1
    assert tr.spans[0].name == "interpret"
    assert all("predicted_us" in s.attrs for s in comm)


# ---------------------------------------------------------------------------
# serve engine: batched EOS sync + metrics
# ---------------------------------------------------------------------------


def test_engine_batched_eos_and_metrics():
    """generate() only host-syncs the EOS check every eos_check_every steps
    (saved syncs counted), and records serve throughput metrics; a tracer
    yields one span per decode step."""
    import numpy as np

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.serve.engine import Engine

    cfg = smoke_config("qwen3-1.7b").replace(n_layers=1)
    model = build_model(cfg)
    import jax

    params = model.init(jax.random.key(0))
    reg = MetricsRegistry()
    tr = Tracer()
    eng = Engine(model, params, max_len=64, tracer=tr, metrics=reg)
    res = eng.generate(
        [[1, 2, 3], [4, 5]], max_new_tokens=12, eos_id=None, eos_check_every=4
    )
    assert res.tokens.shape[0] == 2 and res.steps > 0
    snap = reg.snapshot()
    assert snap["serve.steps"]["value"] == res.steps
    assert snap["serve.step_us"]["count"] == res.steps
    assert snap["serve.tokens_per_s"]["value"] > 0
    steps = [s for s in tr.spans if s.name == "serve.step"]
    assert len(steps) == res.steps
    # eos_id set but never produced: every off-cycle step saves one sync
    reg2 = MetricsRegistry()
    eng2 = Engine(model, params, max_len=64, metrics=reg2)
    res2 = eng2.generate(
        [[1, 2, 3]], max_new_tokens=12, eos_id=-1, eos_check_every=4
    )
    saved = reg2.snapshot()["serve.eos_syncs_saved"]["value"]
    # steps not on the 4-cycle and not the final step skip the host sync
    due = sum(
        1 for s in range(1, res2.steps + 1)
        if s % 4 == 0 or s == res2.steps
    )
    assert saved == res2.steps - due
