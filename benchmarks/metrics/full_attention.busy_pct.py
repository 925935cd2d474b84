"""Share of the device's busy time spent under the ``attn/core`` scope
(forward, recomputed forward and backward): whether the attention core
sets the pace of the step."""


def read(ctx):
    sc = ctx.get("scopes") or {}
    if not sc.get("busy_s") or not sc.get("attention_s"):
        return None
    return 100.0 * sc["attention_s"] / sc["busy_s"]
