"""Share of the device's busy time spent under the ``attn/window`` scope
(forward, recomputed forward and backward of the layers that have a
window): whether the windowed cores set the pace of the step — they do
where a kernel visits the blocks left of the window."""


def read(ctx):
    sc = ctx.get("scopes") or {}
    if not sc.get("busy_s") or not sc.get("window_attention_s"):
        return None
    return 100.0 * sc["window_attention_s"] / sc["busy_s"]
