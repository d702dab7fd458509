"""Per-layer metric readers, one module per metric, named as the metric in
``BENCHMARK.json``. Each has ``read(ctx) -> float | None``; ``ctx`` is the
harness's ``Context``. A reader that finds nothing to read returns None,
and the metric is left out of the result line."""
