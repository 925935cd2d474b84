"""Share of the device's busy time spent under the ``gdn/`` scopes
(conv, rule, gate_norm; forward, recomputed forward and backward):
whether Gated DeltaNet sets the pace of the step."""


def read(ctx):
    sc = ctx.get("scopes") or {}
    if not sc.get("busy_s") or not sc.get("gdn_s"):
        return None
    return 100.0 * sc["gdn_s"] / sc["busy_s"]
