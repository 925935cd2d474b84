"""Milliseconds of set-up that no span names: on the program's set-up
timeline, from its first span's start to its seal (the first train
class's fetch returned), the time under no span, or under
``workflow.initialize`` / ``workflow.run`` alone (``lib/timeline.py``).
None on a program that keeps no timeline."""

from benchmarks.lib import timeline


def read(ctx):
    tl = timeline.get()
    return None if tl is None else 1e3 * timeline.breakdown(tl)["unspanned_s"]
