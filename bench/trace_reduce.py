"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reads.

``load(path)`` reads the trace with ``jax.profiler.ProfileData`` and keeps:

* the window: the host annotation ``bench.window`` that the harness opens
  around the measured window;
* per device plane (``/device:TPU:<i>``), the events of its op line
  (``XLA Ops``: one event per operation run, named ``<program>/<instruction>``)
  and of its program line (``XLA Modules``: one event per program run);
* the host annotations (``TraceAnnotation``) of every host thread whose
  names start with one of ``HOST_PREFIXES``.

From these: busy time (the union of op intervals inside the window), the
idle share, device time per op name, device time per program, and the idle
gaps of device 0, each put down to the innermost host span that covers its
middle.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

WINDOW = "bench.window"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
#: a gap with no other host span open is put down to this
NO_SPAN = "no host span"
#: device timestamps sit about 1 ms before the host spans that dispatched
#: them (a v5e trace; see bench/tests/data/fixture.xplane.pb): a run counts
#: as inside the window if it lies within this much of it
SLACK_NS = 2_000_000
#: the host spans kept: the harness's own and the program's serving spans
#: (JAX's internal host events are left out)
HOST_PREFIXES = ("bench.", "serve.")


@dataclass
class Device:
    name: str
    ops: list = field(default_factory=list)      # (name, start_ns, end_ns)
    modules: list = field(default_factory=list)  # (name, start_ns, end_ns)


@dataclass
class TraceSummary:
    window: tuple[float, float]
    devices: list[Device]
    host: list  # (name, start_ns, end_ns)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    # -- busy and idle ------------------------------------------------------
    def _busy_intervals(self, dev: Device) -> list[tuple[float, float]]:
        lo, hi = self.window
        spans = sorted((max(s, lo), min(e, hi)) for _, s, e in dev.ops if e > lo and s < hi)
        merged: list[list[float]] = []
        for s, e in spans:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.devices:
            return 0.0
        per = [sum(e - s for s, e in self._busy_intervals(d)) for d in self.devices]
        return float(np.mean(per)) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    # -- device time by name ------------------------------------------------
    def _inside(self, events):
        lo, hi = self.window[0] - SLACK_NS, self.window[1] + SLACK_NS
        return [(n, s, e) for n, s, e in events if s >= lo and e <= hi]

    def op_seconds(self) -> dict[str, float]:
        """Device seconds per op name inside the window, mean over devices."""
        tot: dict[str, float] = defaultdict(float)
        for d in self.devices:
            for n, s, e in self._inside(d.ops):
                tot[n] += (e - s) / 1e9
        k = max(len(self.devices), 1)
        return {n: v / k for n, v in tot.items()}

    def module_counts(self, device: int = 0) -> dict[str, int]:
        """Runs per program name inside the window, on one device."""
        out: dict[str, int] = defaultdict(int)
        for n, _, _ in self._inside(self.devices[device].modules):
            out[n] += 1
        return dict(out)

    def module_runs(self, match) -> list[list[float]]:
        """Per device, the durations (s) of the program runs whose name
        ``match(name)`` accepts, inside the window."""
        return [[(e - s) / 1e9 for n, s, e in self._inside(d.modules) if match(n)]
                for d in self.devices]

    def op_runs(self, match) -> list[list[float]]:
        """Per device, the durations (s) of the ops ``match`` accepts."""
        return [[(e - s) / 1e9 for n, s, e in self._inside(d.ops) if match(n)]
                for d in self.devices]

    # -- idle gaps ----------------------------------------------------------
    def gaps(self, device: int = 0) -> list[tuple[float, float]]:
        if not self.devices:
            return [self.window]
        busy = self._busy_intervals(self.devices[device])
        out, t = [], self.window[0]
        for s, e in busy:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.window[1]:
            out.append((t, self.window[1]))
        return out

    def gap_owner(self, gap: tuple[float, float]) -> str:
        """The innermost host span (not the window) covering the gap's middle."""
        mid = (gap[0] + gap[1]) / 2
        best, best_len = NO_SPAN, float("inf")
        for n, s, e in self.host:
            if n != WINDOW and s <= mid <= e and e - s < best_len:
                best, best_len = n, e - s
        return best

    def idle_by_span(self, device: int = 0) -> dict[str, float]:
        tot: dict[str, float] = defaultdict(float)
        for g in self.gaps(device):
            tot[self.gap_owner(g)] += (g[1] - g[0]) / 1e9
        return dict(tot)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds().items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_by_span().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, v] for n, v in ops],
                "idle_gaps": [[n, v] for n, v in idle]}


def op_name(name: str) -> str:
    """An op event is named by its whole HLO line (``%fusion.3 = bf16[...]
    fusion(...)``); keep the instruction's name (``fusion.3``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def program_name(name: str) -> str:
    """``jit_tick(5788495926759934351)`` -> ``jit_tick``."""
    return name.split("(", 1)[0]


def _events(line, rename=lambda n: n) -> list:
    return [(rename(e.name), e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def _in_programs(ops: list, modules: list) -> list:
    """Name each op ``<program>/<op>`` by the program run it falls in (op
    names such as ``while.1`` recur across programs)."""
    runs = sorted(modules, key=lambda m: m[1])
    out, i = [], 0
    for n, s, e in sorted(ops, key=lambda o: o[1]):
        while i < len(runs) and runs[i][2] < s:
            i += 1
        inside = i < len(runs) and runs[i][1] <= s
        out.append((f"{program_name(runs[i][0])}/{n}" if inside else n, s, e))
    return out


def from_profile(pd, n_devices: int | None = None,
                 host_prefixes: tuple = HOST_PREFIXES) -> TraceSummary:
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == OP_LINE:
                    dev.ops = _events(line, op_name)
                elif line.name == MODULE_LINE:
                    dev.modules = _events(line)
            dev.ops = _in_programs(dev.ops, dev.modules)
            devices.append(dev)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name.startswith(host_prefixes))
    devices.sort(key=lambda d: int(d.name[len(DEVICE_PREFIX):].split()[0]))
    if n_devices is not None:
        devices = devices[:n_devices]
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    return TraceSummary(window=max(wins, key=lambda w: w[1] - w[0]),
                        devices=devices, host=host)


def load(path, n_devices: int | None = None,
         host_prefixes: tuple = HOST_PREFIXES) -> TraceSummary:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(path)), n_devices, host_prefixes)
