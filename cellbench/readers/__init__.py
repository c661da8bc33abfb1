"""One module per way of reading a per-layer metric, found by a metric's ``reader``."""
