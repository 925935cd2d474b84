"""What the program recorded about itself: reads of the telemetry
registry (``veles_tpu.telemetry``) of the process the cell ran in.
The harness runs the program in-process, so after a run the registry
holds every span and counter of it; a reader returns None where the
program has no such span or counter (a parent commit from before the
name existed).

Medians come from the registry's log buckets (32 a decade: up to
7.5 % off, typically 2 %) over the whole process — set-up's firings
and the few round the harness's barriers included."""

from __future__ import annotations

from typing import Optional

from veles_tpu import telemetry


def median_ms(name: str) -> Optional[float]:
    """Median of the span / histogram ``name``, in milliseconds."""
    q = telemetry.histogram(name).quantile(0.5)
    return None if q is None else 1e3 * q


def sum_ms(name: str) -> Optional[float]:
    """Total time under the span ``name``, in milliseconds."""
    h = telemetry.histogram(name)
    return 1e3 * h.sum if h.count else None


def gauge_ms(name: str) -> Optional[float]:
    v = telemetry.gauge(name).value
    return None if v is None else 1e3 * v


def counter_ms(name: str, witness: str) -> Optional[float]:
    """A seconds counter in milliseconds; 0 is a reading only where
    the ``witness`` counter shows the program counts at all."""
    if not telemetry.counter(witness).value:
        return None
    return 1e3 * float(telemetry.counter(name).value)
