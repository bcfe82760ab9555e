"""The harness: one cell of ``BENCHMARK.json``, run from its data files.

A cell names a configuration and a traffic mix. Everything else is found by
name under ``bench/``:

* ``configs/<config>.json``    the configuration as it is run;
* ``traffic/<mix>.json``       the mix's parameters and its ``driver``;
* ``drivers/<driver>.py``      ``run(ctx) -> Outcome``;
* ``metrics/<metric>.py``      ``read(ev) -> float | None``, one per per-layer metric;
* ``limits/<workload>.json``   the limit of each number that decides ``correct``;
* ``references/<name>.py``     the plain reference a configuration names.

A driver builds what it needs, warms every shape its traffic uses, then opens
``ctx.window()``: set-up ends there, the profiler (``--trace 1``) runs inside
it, and compiles inside it are counted. After the window it calls
``ctx.record_memory()``, frees the program's state and checks what the timed
path produced against the reference.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SRC = ROOT / "src"
#: the persistent compile cache: a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"
#: where a traced run writes its profile before reducing it
TRACE_DIR = ROOT / ".bench_trace"


class HarnessError(RuntimeError):
    """A cell that cannot run here: no chip, a missing file, a config that
    differs from the program's. The command exits non-zero on it."""


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_spec() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_cell(spec: dict, workload: str) -> dict:
    for cell in spec["workloads"]:
        if cell["name"] == workload:
            return cell
    raise HarnessError(f"no workload {workload!r} in BENCHMARK.json")


def cell_metrics(spec: dict, workload: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``workload`` reports."""
    return [m for m in spec[section]
            if "workloads" not in m or workload in m["workloads"]]


def load_module(path: Path, name: str):
    """A module from a file whose name need not be an identifier."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise HarnessError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reference_module(config: dict):
    return importlib.import_module(f"bench.references.{config['reference']}")


@dataclass
class Check:
    """One number that decides ``correct``: it passes while ``value <= limit``."""

    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a driver hands back after its window and its check."""

    attempted: int
    failed: int
    #: end-to-end metric name -> value (set-up is the harness's)
    e2e: dict[str, float]
    checks: dict[str, Check]
    #: counts the per-layer readers need (FLOPs, bytes, calls, ...)
    facts: dict = field(default_factory=dict)
    #: host spans of the window: (name, dur_s, attrs)
    spans: list = field(default_factory=list)


class Ctx:
    """What a driver gets: its cell, config, mix and seed, and the window."""

    def __init__(self, cell, config, traffic, seed, seconds, trace, limits,
                 compile_log=None, t_start=None, keep_trace=None):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.limits = limits
        self.compile_log = compile_log
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.keep_trace = keep_trace
        self.setup_s: float | None = None
        self.setup_compiles = ""
        self.window_s: float | None = None
        self.compiles_in_window: int | None = None
        self.memory_peak_bytes: int | None = None
        self.trace_file: Path | None = None

    def limit(self, name: str) -> float:
        return float(self.limits[name]["limit"])

    @contextlib.contextmanager
    def window(self):
        """Set-up ends on entry. With ``--trace 1`` the profiler records the
        window, which a ``bench.window`` annotation marks on the host."""
        import jax

        self.setup_s = time.perf_counter() - self.t_start
        # the check after the window compiles too: keep set-up's count apart
        self.setup_compiles = self.compile_log.summary() if self.compile_log else ""
        before = self.compile_log.compiles if self.compile_log else 0
        tdir = None
        if self.trace:
            tdir = TRACE_DIR / f"{self.cell['name']}-{self.seed}"
            shutil.rmtree(tdir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(tdir), profiler_options=opts)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.window_s = time.perf_counter() - t0
            if tdir is not None:
                jax.profiler.stop_trace()
                found = sorted(tdir.rglob("*.xplane.pb"))
                self.trace_file = found[-1] if found else None
            if self.compile_log is not None:
                self.compiles_in_window = self.compile_log.compiles - before

    def record_memory(self, devices) -> None:
        """The peak on the fullest chip, read before the reference runs."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = int(max(peaks)) if peaks else None


@dataclass
class Evidence:
    """What a per-layer reader reads."""

    cell: dict
    config: dict
    traffic: dict
    facts: dict
    spans: list
    trace: object | None  # trace_reduce.TraceSummary
    peaks: dict

    def span_durations(self, name: str) -> list[float]:
        return [d for n, d, _ in self.spans if n == name]


def peaks_for(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    for row in table["devices"]:
        if row["device_kind"] == device_kind:
            return row
    raise HarnessError(f"no peaks for device kind {device_kind!r} in bench/peaks.json")


def read_per_layer(spec, workload, ev: Evidence) -> dict:
    out = {}
    for m in cell_metrics(spec, workload, "per_layer"):
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{len(out)}")
        value = reader.read(ev)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def prepare_jax(require_tpu_chips: int | None, cache: bool = True):
    """Import JAX with the checkout's compile cache; with ``require_tpu_chips``
    refuse anything but that many TPU chips."""
    if cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    if cache:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if require_tpu_chips is not None:
        if devs[0].platform != "tpu":
            raise HarnessError(
                f"needs a TPU, found platform {devs[0].platform!r} ({devs[0].device_kind})")
        if len(devs) < require_tpu_chips:
            raise HarnessError(
                f"the cell asks for {require_tpu_chips} chips, found {len(devs)}")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    return jax


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float | None = None,
             overrides: dict | None = None, keep_trace: str | None = None,
             cache: bool = True) -> dict:
    """Run one cell; returns the result line as a dict (``checks`` last).

    ``overrides`` (the CPU tests) replace entries of the cell's
    config or traffic: ``{"config": {...}, "traffic": {...}}``."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = load_spec()
    cell = find_cell(spec, workload)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload}.json")
    for key, val in (overrides or {}).get("config", {}).items():
        config[key] = val
    for key, val in (overrides or {}).get("traffic", {}).items():
        traffic[key] = val
    jax = prepare_jax(cell["chips"] if require_tpu else None, cache=cache)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        raise HarnessError(f"the program's src/ must sit beside bench/ ({e})") from e
    log = enable_compile_cache()
    if not cache:  # the CPU rehearsals: count compiles, keep nothing
        jax.config.update("jax_enable_compilation_cache", False)
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    ctx = Ctx(cell, config, traffic, seed, seconds, trace, limits,
              compile_log=log, t_start=t_start, keep_trace=keep_trace)
    out: Outcome = driver.run(ctx)

    say(f"set-up {ctx.setup_s:.3f} s; {ctx.setup_compiles}")
    say(f"compiles inside the window: {ctx.compiles_in_window}")
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    metrics = {}
    breakdown = None
    if trace:
        from bench import trace_reduce

        summary = None
        if ctx.trace_file is not None:
            summary = trace_reduce.load(ctx.trace_file, n_devices=cell["chips"])
            if summary.devices:  # no device planes (a CPU rehearsal): no device numbers
                device["busy_s"] = summary.busy_s()
                device["window_s"] = summary.window_s
                breakdown = summary.breakdown()
            else:
                summary = None
            if keep_trace:
                dest = Path(keep_trace)
                dest.mkdir(parents=True, exist_ok=True)
                shutil.copy(ctx.trace_file, dest / f"{workload}-{seed}.xplane.pb")
            shutil.rmtree(TRACE_DIR / f"{workload}-{seed}", ignore_errors=True)
        ev = Evidence(cell, config, traffic, out.facts, out.spans, summary,
                      peaks_for(d0.device_kind) if d0.platform == "tpu" else {})
        metrics = read_per_layer(spec, workload, ev)
    else:
        values = dict(out.e2e, setup_s=ctx.setup_s)
        for m in cell_metrics(spec, workload, "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}
    correct = out.failed == 0 and all(c.ok for c in out.checks.values())
    line = {"correct": correct, "attempted": int(out.attempted),
            "failed": int(out.failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": c.value, "limit": c.limit}
                      for k, c in out.checks.items()}
    return line
