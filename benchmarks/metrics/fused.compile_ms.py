"""Milliseconds of backend compile (or persistent-cache load) that fell
inside a ``fused.*`` span (counter ``fused.compile_seconds``).  0 is a
reading where the program counts compiles at all (``xla.compiles``)."""

from benchmarks.lib import inside


def read(ctx):
    return inside.counter_ms("fused.compile_seconds", "xla.compiles")
