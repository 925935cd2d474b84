"""Share of the device's busy time spent under the ``moe/`` scopes
(router, dispatch, experts, shared; forward, recomputed forward and
backward): whether the mixture of experts sets the pace of the step."""


def read(ctx):
    sc = ctx.get("scopes") or {}
    if not sc.get("busy_s") or not sc.get("moe_s"):
        return None
    return 100.0 * sc["moe_s"] / sc["busy_s"]
