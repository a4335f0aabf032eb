"""End-to-end metric readers, one file per metric, ``read(ctx)``."""
