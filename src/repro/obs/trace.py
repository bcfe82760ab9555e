"""Nestable span tracing for the serving loop and the coded layer.

A :class:`Tracer` records :class:`Span` records — named, attributed,
wall-clocked intervals on one monotonic timeline (``time.perf_counter``
anchored at tracer creation). Spans nest: ``with tracer.span("outer"):``
inside another span records the parent index and depth, so an export
(``repro.obs.export``) can reconstruct the call tree and Perfetto renders
the nesting from the ``"X"`` complete-event containment.

Every span also opens a ``jax.profiler.TraceAnnotation`` of its name, so
under a running profile it lies on the host lines of the device trace and
an idle gap on the device can be put down to the span that covered it.
With no profile running an annotation costs next to nothing. JAX is
imported on the first span, not with this module.

The tracer is deliberately dumb — no sampling, no threads, no flushing
policy. Instrumented layers (``serve.engine.Engine``,
``serve.engine.ContinuousEngine``, ``serve.coded.CodedServeGuard``) open
spans around their steps and phases and attach their counts as span
attributes. Code that takes an optional tracer opens its spans through
:func:`optional_span`.
"""

from __future__ import annotations

import contextlib
import time
from contextlib import contextmanager
from dataclasses import dataclass, field


@dataclass
class Span:
    """One traced interval. ``ts_us``/``dur_us`` are microseconds on the
    owning tracer's monotonic timeline; ``parent`` is the index (into
    ``Tracer.spans``) of the enclosing span, or ``None`` at top level.
    ``attrs`` may be extended while the span is open (e.g. a measured
    byte count discovered mid-span)."""

    name: str
    ts_us: float
    dur_us: float = 0.0
    depth: int = 0
    parent: int | None = None
    attrs: dict = field(default_factory=dict)

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "ts_us": self.ts_us,
            "dur_us": self.dur_us,
            "depth": self.depth,
            "parent": self.parent,
            "attrs": dict(self.attrs),
        }


class Tracer:
    """Collects spans; see module doc. Spans are appended at OPEN time so
    ``spans`` is in start order and a parent always precedes its children;
    ``dur_us`` is filled when the span closes."""

    def __init__(self):
        self._t0 = time.perf_counter()
        self._stack: list[int] = []
        self.spans: list[Span] = []

    def now_us(self) -> float:
        """Microseconds since tracer creation (monotonic)."""
        return (time.perf_counter() - self._t0) * 1e6

    @contextmanager
    def span(self, name: str, **attrs):
        """Open a nested span; yields the :class:`Span` so callers can add
        attrs (``sp.attrs["bytes"] = n``) before it closes. The span runs
        inside a ``TraceAnnotation`` of the same name."""
        from jax.profiler import TraceAnnotation

        sp = Span(
            name=name,
            ts_us=self.now_us(),
            depth=len(self._stack),
            parent=self._stack[-1] if self._stack else None,
            attrs=dict(attrs),
        )
        idx = len(self.spans)
        self.spans.append(sp)
        self._stack.append(idx)
        try:
            with TraceAnnotation(name):
                yield sp
        finally:
            sp.dur_us = self.now_us() - sp.ts_us
            self._stack.pop()

    def to_dicts(self) -> list[dict]:
        return [s.to_dict() for s in self.spans]


def optional_span(tracer: Tracer | None, name: str, **attrs):
    """``tracer.span(name, **attrs)``, or a no-op context (yielding None)
    when ``tracer`` is None: one code path whether tracing is on or off."""
    if tracer is None:
        return contextlib.nullcontext()
    return tracer.span(name, **attrs)
