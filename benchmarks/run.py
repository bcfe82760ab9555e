# One function per paper table/figure. Print ``name,us_per_call,derived`` CSV.
#
# The paper is theory-only; its "tables" are Theorems 1-4 + Figures 1-4, each
# of which gets a benchmark module; the coded-system applications (Remark 1,
# §VI) and the dry-run roofline get their own.
#
# ``--trace`` wraps every module's run() in a ``repro.obs`` span and writes
# the whole-suite Chrome trace (results/traces/bench_suite.trace.json —
# Perfetto-loadable) plus the metrics-registry snapshot
# (results/bench_metrics.json: the per-sample latency histograms
# ``benchmarks.common.time_fn`` fed) after the run.
import sys
import traceback


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    trace = "--trace" in argv
    from . import (
        bench_universal,      # Theorem 1 / Lemmas 1-3 / Fig. 1-3
        bench_dft,            # Theorem 2 / Fig. 4
        bench_vandermonde,    # Theorem 3 / Remark 5
        bench_lagrange,       # Theorem 4 + LCC (§VI)
        bench_kernels,        # DESIGN §7 kernels
        bench_coded_ckpt,     # Remark 1 application (coded checkpointing)
        bench_gradient_coding,# straggler mitigation application
        bench_dryrun_roofline,# deliverable (g) table
        bench_topology,       # repro.topo: flat vs hierarchical on 8 devices
        bench_serve,          # continuous-batching vs fixed-batch serving
        bench_coded_serve,    # LCC fault-tolerant serving overhead + recovery
    )

    tracer = None
    if trace:
        from repro.obs import Tracer

        tracer = Tracer()

    print("name,us_per_call,derived")
    failures = []
    for mod in (
        bench_universal,
        bench_dft,
        bench_vandermonde,
        bench_lagrange,
        bench_kernels,
        bench_coded_ckpt,
        bench_gradient_coding,
        bench_dryrun_roofline,
        bench_topology,
        bench_serve,
        bench_coded_serve,
    ):
        name = mod.__name__.rsplit(".", 1)[-1]
        try:
            if tracer is not None:
                with tracer.span(f"bench.{name}"):
                    mod.run()
            else:
                mod.run()
        except Exception:
            failures.append(mod.__name__)
            traceback.print_exc()
    if tracer is not None:
        import os

        from repro.obs import get_registry, write_chrome_trace

        out = write_chrome_trace(
            tracer.spans,
            "results/traces/bench_suite.trace.json",
            process_name="bench_suite",
        )
        get_registry().write_json(os.path.join("results", "bench_metrics.json"))
        print(f"trace: {out}", file=sys.stderr)
    if failures:
        print(f"FAILED: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
