"""Share of the traced window in which no operation ran on the device
(mean over the chips used)."""


def read(ctx):
    red = ctx.get("reduced") or {}
    if not red.get("window_s") or red.get("busy_s") is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
