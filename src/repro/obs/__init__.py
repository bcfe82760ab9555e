# Runtime telemetry: spans and metrics for the serving loop and the coded
# layer.
#
# - trace.py    Tracer/Span: nestable, attributed wall-clock spans, each
#               also a jax.profiler.TraceAnnotation on the profiler's
#               clock; the serving engines and the coded guard open them
#               around their phases and stamp their counts on them
# - export.py   Chrome-trace-event JSON (Perfetto-loadable) + JSONL span
#               sinks under results/traces/, and the reader for both
# - metrics.py  process-local counters/gauges/histograms registry with
#               deterministic JSON snapshots (serve.step_us,
#               serve.ttft_ms, serve.snapshots, ...)
#
# The encode's per-round device times come from a device profile of its one
# fused program: ir_encode_jit scopes each CommRound's ops round<r> and each
# LocalOp's local<i>.

from .export import (  # noqa: F401
    DEFAULT_TRACE_DIR,
    default_trace_path,
    read_spans,
    spans_to_chrome,
    write_chrome_trace,
    write_spans_jsonl,
)
from .metrics import (  # noqa: F401
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from .trace import Span, Tracer, optional_span  # noqa: F401
