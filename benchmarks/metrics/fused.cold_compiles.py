"""Backend compiles inside a ``fused.*`` span that the persistent
compile cache did NOT serve (counter ``fused.cold_compiles``): 0 in a
warm run, so a "warm" set-up that compiled says so itself.  A count, so
0 is a reading where the program counts compiles at all
(``xla.compiles``); None on a program without the counter."""

from veles_tpu import events, telemetry


def read(ctx):
    if "fused.cold_compiles" not in events.COUNTERS \
            or not telemetry.counter("xla.compiles").value:
        return None
    return float(telemetry.counter("fused.cold_compiles").value)
