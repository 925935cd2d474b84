"""Median milliseconds of ``loop.turnaround``: class-end fetch returned
-> next superstep submitted, the host time the device idles on at
each class end (crosses decision.run, the workflow loop, loader.run
and the head of fused.run)."""

from benchmarks.lib import inside


def read(ctx):
    return inside.median_ms("loop.turnaround")
