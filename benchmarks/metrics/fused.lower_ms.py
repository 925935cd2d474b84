"""Milliseconds of lowering (a jaxpr to its module, the Pallas kernels'
Mosaic lowering among it) that jax reported inside a ``fused.*`` span
(counter ``fused.lower_seconds``).  The compile cache does not save
it.  None on a program without the counter."""

from benchmarks.lib import inside
from veles_tpu import events


def read(ctx):
    if "fused.lower_seconds" not in events.COUNTERS:
        return None
    return inside.counter_ms("fused.lower_seconds", "xla.compiles")
