"""Median milliseconds of the ``fused.fetch_metrics`` span: the host's
wait at a class end for every queued superstep — the barrier of the
training loop, seen from inside ``decision.run``."""

from benchmarks.lib import inside


def read(ctx):
    return inside.median_ms("fused.fetch_metrics")
