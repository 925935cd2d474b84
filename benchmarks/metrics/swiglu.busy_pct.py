"""Share of the device's busy time spent under the ``mlp/swiglu`` scope
(a dense SwiGLU's gate and up products and its activation, forward,
recomputed forward and backward; the down projection is the ``dense``
that follows, outside it): whether the dense MLP sets the pace of the
step.  Read from the trace itself, whatever scopes the cell's kind
sums; None where no op carries the scope (a program from before it)."""

import re

from benchmarks.lib import xplane

SWIGLU = {"swiglu": re.compile(r"mlp/(swiglu)")}


def read(ctx):
    if not ctx.get("trace"):
        return None
    sc = xplane.scope_times(ctx["trace"], SWIGLU) or {}
    if not sc.get("busy_s") or not sc.get("swiglu_s"):
        return None
    return 100.0 * sc["swiglu_s"] / sc["busy_s"]
