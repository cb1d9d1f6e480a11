"""Per-layer metric readers, one file per metric of ``BENCHMARK.json``.

Each module states its ``LAYER``, ``UNIT``, ``SOURCE`` and ``MOVES`` and
has ``read(ctx) -> float | None``; ``None`` when it finds nothing to
read, and the harness then leaves the metric out of the line."""
