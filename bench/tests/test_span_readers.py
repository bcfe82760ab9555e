"""The readers of the program's coded-layer and serve-loop spans, on
synthetic evidence, and a traced CPU rehearsal of the coded cell that
reports each span reader.

Run with ``pytest bench/tests``."""

import pytest

from bench.harness import BENCH, Evidence, load_module, run_cell
from bench.tests.tiny import overrides
from bench.trace_reduce import TraceSummary

CODED = "qwen3-1.7b.coded-kill"
#: metric -> (span it reads, the reading of DURATIONS in its unit)
SPAN_READERS = {
    "coded.snapshot_device_ms.coded": ("serve.snapshot.device", 200.0),
    "coded.snapshot_to_host_ms.coded": ("serve.snapshot.to_host", 200.0),
    "coded.snapshot_store_ms.coded": ("serve.snapshot.store", 200.0),
    "coded.recovery_decode_s.coded": ("serve.recovery.decode", 0.3),
    "coded.recovery_to_device_s.coded": ("serve.recovery.to_device", 0.3),
}
#: seconds; p50 0.2, first 0.3
DURATIONS = (0.3, 0.1, 0.2)
UNSPANNED = "serve.unspanned_s.coded"


def _reader(name):
    return load_module(BENCH / "metrics" / f"{name}.py", f"test_reader_{name}")


def _ev(spans=(), trace=None):
    return Evidence(cell={}, config={}, traffic={}, facts={}, spans=list(spans),
                    trace=trace, peaks={})


@pytest.mark.parametrize("metric", sorted(SPAN_READERS))
def test_span_reader(metric):
    span, want = SPAN_READERS[metric]
    read = _reader(metric).read
    others = [("bench.snapshot", 9.0, {}), ("serve.snapshot", 9.0, {"tick": 0})]
    spans = others + [(span, d, {}) for d in DURATIONS]
    assert read(_ev(spans)) == pytest.approx(want)
    assert read(_ev(others)) is None


def _trace(host, window=(1_000, 11_000)):
    return TraceSummary(window=window, devices=[], host=host)


@pytest.mark.parametrize("host,want_ns", [
    # nested, overlapping and repeated spans count once; a span that
    # crosses the window's edge counts inside it; bench.* spans count not
    ([("bench.window", 1_000, 11_000), ("serve.admit", 500, 2_000),
      ("serve.prefill", 1_500, 1_800), ("serve.snapshot", 3_000, 6_000),
      ("serve.snapshot", 3_000, 6_000), ("serve.snapshot.device", 3_100, 4_000),
      ("serve.poll", 5_500, 7_000), ("bench.snapshot", 7_000, 8_000),
      ("serve.harvest", 10_000, 12_000)], 1_000 + 3_000),
    ([("serve.recovery", 0, 20_000)], 0),
    ([("serve.poll", 2_000, 3_000)], 9_000),
], ids=["overlapping", "covering", "one"])
def test_unspanned_reader(host, want_ns):
    read = _reader(UNSPANNED).read
    assert read(_ev(trace=_trace(host))) == pytest.approx(want_ns / 1e9)


def test_unspanned_reader_without_its_spans():
    read = _reader(UNSPANNED).read
    assert read(_ev()) is None
    assert read(_ev(trace=_trace([("bench.window", 1_000, 11_000),
                                  ("bench.snapshot", 2_000, 3_000),
                                  ("serve.before", 0, 900)]))) is None


def test_traced_rehearsal_reports_the_span_readers():
    """A CPU trace has no TPU planes: the span readers report, the readers
    of the device trace and of its host intervals do not."""
    line = run_cell(CODED, 2**31 + 5, 3.0, True, require_tpu=False,
                    overrides=overrides("coded"), cache=False)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"coded.snapshot_ms.coded",
                                    "coded.recovery_s.coded", *SPAN_READERS}
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(v > 0 for v in m.values())
    # the recovery's children lie inside the guard's own recovery_us
    assert (m["coded.recovery_decode_s.coded"] + m["coded.recovery_to_device_s.coded"]
            <= m["coded.recovery_s.coded"])
