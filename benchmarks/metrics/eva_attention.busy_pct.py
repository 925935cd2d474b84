"""Share of the device's busy time spent under the ``eva/`` scopes
(summaries, local, remote; forward, recomputed forward and backward):
whether the attention mechanism is the pace-setter of the step."""


def read(ctx):
    sc = ctx.get("scopes") or {}
    if not sc.get("busy_s") or not sc.get("eva_s"):
        return None
    return 100.0 * sc["eva_s"] / sc["busy_s"]
