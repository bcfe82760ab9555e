"""Host spans on the profiler's clock.

``AnnotatingTracer`` is the program's ``obs.trace.Tracer`` whose every span
also opens a ``jax.profiler.TraceAnnotation`` of the same name, so the
engine's ``serve.*`` spans appear on the host lines of a device trace and an
idle gap on the device can be put down to what the host was doing.
"""

from __future__ import annotations

from contextlib import contextmanager

from repro.obs.trace import Tracer


class AnnotatingTracer(Tracer):
    @contextmanager
    def span(self, name: str, **attrs):
        import jax

        with jax.profiler.TraceAnnotation(name):
            with super().span(name, **attrs) as sp:
                yield sp
