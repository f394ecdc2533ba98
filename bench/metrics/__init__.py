"""Per-layer metric readers, one file per metric: ``read(ctx)``."""
