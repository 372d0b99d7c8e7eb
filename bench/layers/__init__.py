"""Per-layer metric readers, one file per metric of BENCHMARK.json;
each defines ``read(ctx)`` and returns a number or None."""
