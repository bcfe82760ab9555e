# Runtime telemetry for the encode pipeline (ROADMAP: live-fed calibration).
#
# - trace.py    Tracer/Span: nestable, attributed wall-clock spans, each
#               also a jax.profiler.TraceAnnotation on the profiler's
#               clock; the instrumented layers (ir_encode_jit(tracer=...),
#               the interpret oracle, the serving engines and the coded
#               guard, benchmarks/run.py --trace) stamp their counts and
#               per-CommRound metadata onto them
# - export.py   Chrome-trace-event JSON (Perfetto-loadable) + JSONL span
#               sinks under results/traces/, and the reader for both
# - metrics.py  process-local counters/gauges/histograms registry with
#               deterministic JSON snapshots (encode.rounds,
#               encode.round_us{level=}, serve.step_us, ...)
# - feed.py     the live calibration loop: traced round spans → per-level
#               α/β refit → persisted where topo.calibrate.load_fitted_costs
#               (and hence launch.profiles.resolve_profile) reads them,
#               plus the predicted-vs-measured drift rows perf_report renders

from .export import (  # noqa: F401
    DEFAULT_TRACE_DIR,
    default_trace_path,
    read_spans,
    spans_to_chrome,
    write_chrome_trace,
    write_spans_jsonl,
)
from .feed import (  # noqa: F401
    comm_round_spans,
    drift_rows,
    feed_calibration,
    fitted_costs_from_trace,
    persist_fitted_costs,
    refit_from_spans,
    round_measurements,
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
