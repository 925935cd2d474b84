"""Milliseconds under the program's ``workflow.initialize`` span: the
set-up the program owns (units' ``initialize``, the host parameter fill
among them — ``init.<unit>`` splits it)."""

from benchmarks.lib import inside


def read(ctx):
    return inside.sum_ms("workflow.initialize")
