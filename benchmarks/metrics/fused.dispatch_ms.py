"""Median host milliseconds of one ``fused.run()``: the submit of one
superstep (asynchronous — not a step time)."""


def read(ctx):
    return ctx["median_ms"]("fused.run")
