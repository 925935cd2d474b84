"""Median milliseconds of the ``fused.train_submit`` span: the jitted
call of one superstep alone, timed inside the program (asynchronous
submit, not a step time)."""

from benchmarks.lib import inside


def read(ctx):
    return inside.median_ms("fused.train_submit")
