"""Per-layer metric readers: ``read(ctx, args) -> float | None``. A reader
that finds nothing to read returns None and the metric is left out."""
