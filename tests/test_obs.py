"""Observability layer: span tracing, Chrome-trace export, the metrics
registry, and the fixed-batch engine's spans and metrics."""

import pytest

from repro.obs import (
    MetricsRegistry,
    Tracer,
    read_spans,
    spans_to_chrome,
    write_chrome_trace,
    write_spans_jsonl,
)


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


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_metrics_snapshot_deterministic(tmp_path):
    def run():
        reg = MetricsRegistry()
        reg.counter("serve.snapshots").inc(3)
        reg.gauge("serve.tokens_per_s").set(123.5)
        h = reg.histogram("serve.step_us", slot=1)
        for v in (5.0, 1.0, 9.0, 3.0):
            h.observe(v)
        reg.histogram("serve.step_us", slot=0).observe(2.0)
        return reg

    a, b = run().snapshot(), run().snapshot()
    assert a == b
    assert list(a) == sorted(a)  # deterministic key order
    assert a["serve.snapshots"] == {"type": "counter", "value": 3.0}
    hist = a["serve.step_us{slot=1}"]
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
