"""Milliseconds of the first train submit (the gauge
``fused.first_train_submit_seconds``): trace + compile or cache load
of the step program + the parameters' upload."""

from benchmarks.lib import inside


def read(ctx):
    return inside.gauge_ms("fused.first_train_submit_seconds")
