"""Milliseconds of tracing (a jitted function to its jaxpr) that jax
reported inside a ``fused.*`` span (counter ``fused.trace_seconds``:
the union of the reported intervals, an inner trace counted once).  The
compile cache does not save it.  0 is a reading where the program
counts compiles at all (``xla.compiles``); None on a program without
the counter."""

from benchmarks.lib import inside
from veles_tpu import events


def read(ctx):
    if "fused.trace_seconds" not in events.COUNTERS:
        return None
    return inside.counter_ms("fused.trace_seconds", "xla.compiles")
