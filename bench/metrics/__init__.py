"""Per-layer readers, one file per metric of ``BENCHMARK.json``'s ``per_layer``."""
