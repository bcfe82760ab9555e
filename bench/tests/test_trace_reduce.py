"""The trace reduction on a small trace recorded on one v5e chip
(``record_trace.py``): three jitted matrix products, each followed by a 20 ms
host sleep under a ``fixture.sleep`` span, inside ``bench.window``.

Busy time is checked against a brute-force count over 1 us bins, the per-op
seconds against a plain sum, and the idle gaps against what the recorder
did: about 60 ms of them under ``fixture.sleep``."""

from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce
from bench.harness import HarnessError, peaks_for

FIXTURE = Path(__file__).parent / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.load(FIXTURE, host_prefixes=("bench.", "fixture."))


def _bins(summary, dev):
    lo, hi = summary.window
    n = int((hi - lo) // 1000) + 1
    busy = np.zeros(n, bool)
    for _, s, e in dev.ops:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            busy[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    return busy


def test_window_and_devices(summary):
    assert len(summary.devices) == 1
    assert summary.devices[0].ops and summary.devices[0].modules
    assert 0.06 < summary.window_s < 5.0


def test_busy_and_idle_share(summary):
    busy_bins = _bins(summary, summary.devices[0]).sum() * 1e-6
    assert summary.busy_s() == pytest.approx(busy_bins, abs=len(summary.devices[0].ops) * 2e-6)
    assert 0.0 < summary.busy_s() < summary.window_s
    assert summary.idle_share() == pytest.approx(1 - summary.busy_s() / summary.window_s)
    assert summary.idle_share() > 0.06 / summary.window_s * 0.9


def test_per_op_seconds(summary):
    ops = summary.op_seconds()
    lo, hi = summary.window[0] - trace_reduce.SLACK_NS, summary.window[1] + trace_reduce.SLACK_NS
    plain = sum((e - s) for _, s, e in summary.devices[0].ops if s >= lo and e <= hi) / 1e9
    assert sum(ops.values()) == pytest.approx(plain)
    assert max(ops.values()) > 0
    runs = summary.module_runs(lambda n: "lambda" in n)
    assert len(runs[0]) == 3


def test_gaps_are_put_down_to_the_host_span(summary):
    idle = summary.idle_by_span()
    assert sum(idle.values()) == pytest.approx(summary.window_s - summary.busy_s(), rel=1e-6)
    assert idle.get("fixture.sleep", 0.0) > 0.055
    b = summary.breakdown()
    assert b["idle_gaps"][0][0] == "fixture.sleep"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_unknown_device_kind_is_an_error():
    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(HarnessError):
        peaks_for("TPU v99")
