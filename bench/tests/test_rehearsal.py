"""Each driver end to end at a tiny size on the CPU, the result line's schema,
and the command's refusal to run anywhere but on the chips a cell asks for.

Run with ``pytest bench/tests`` (the repository's own suite collects only
``tests/``)."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.harness import BENCH, ROOT, load_spec, run_cell
from bench.tests.tiny import overrides

CODED = "qwen3-1.7b.coded-kill"


def _cpu_env(devices: int = 1) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    return env


def _schema(line: dict, workload: str, trace: bool) -> None:
    spec = load_spec()
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    section = "per_layer" if trace else "end_to_end"
    names = {m["name"]: m["unit"] for m in spec[section]
             if "workloads" not in m or workload in m["workloads"]}
    assert set(line["metrics"]) <= set(names)
    for name, m in line["metrics"].items():
        assert m["unit"] == names[name] and isinstance(m["value"], float)
    if not trace and line["correct"]:
        assert set(line["metrics"]) == set(names)
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    json.dumps(line)


def test_coded_cell_end_to_end(capsys):
    line = run_cell(CODED, 2**31 + 77, 3.0, False, require_tpu=False,
                    overrides=overrides("coded"), cache=False)
    _schema(line, CODED, trace=False)
    assert line["correct"], line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert "compiles inside the window: 0" in capsys.readouterr().out
    assert set(line["checks"]) == {"max_logit_shortfall", "no_request_finished",
                                   "kill_missing", "kill_unrecovered",
                                   "recovered_words_wrong"}


def test_traced_run_reports_only_what_it_read():
    """A CPU trace has no TPU planes: only the span readers report."""
    line = run_cell(CODED, 5, 3.0, True, require_tpu=False,
                    overrides=overrides("coded"), cache=False)
    _schema(line, CODED, trace=True)
    assert set(line["metrics"]) == {"coded.snapshot_ms.coded", "coded.recovery_s.coded"}
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_encode_driver_on_four_host_devices():
    out = subprocess.run([sys.executable, str(BENCH / "tests" / "encode_cpu.py")],
                         env=_cpu_env(4), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    _schema(line, "lcc-k3r1-ntt.kv-share-4chip", trace=False)
    assert line["correct"] and line["device"]["count"] == 4
    assert line["checks"]["encode_words_wrong"]["value"] == 0


@pytest.mark.parametrize("workload", sorted(c["name"] for c in load_spec()["workloads"]))
def test_command_refuses_the_cpu(workload):
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_cpu_env(4), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""


def test_command_needs_the_program(tmp_path):
    """A directory with only BENCHMARK.json and bench/ runs nothing."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CODED, "--seed", "1",
         "--seconds", "1"], cwd=tmp_path, env=_cpu_env(), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
