"""Per-layer metric readers: one module per metric, named after it,
each with ``read(ctx) -> float | None``.  ``ctx`` is the traced run's
context: host-timer samples, the reduced trace, the window, the cell's
mix and configuration.  A reader that finds nothing to read returns
None and the metric is left out of the line."""
