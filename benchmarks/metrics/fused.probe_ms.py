"""Milliseconds under the program's ``fused.probe`` span: the
forward-only program of the units that have a ``probe`` (a mixture of
experts' load), built, compiled or loaded, run on the first minibatch
and fetched — once, right after the first train firing."""

from benchmarks.lib import inside


def read(ctx):
    return inside.sum_ms("fused.probe")
