"""The whole step's share of the chip's peak: the benchmark's FLOPs per
trained image x images of the traced window, over the traced window's
wall time x chips x peak bf16 FLOP/s."""

from benchmarks.lib import flops


def read(ctx):
    tr, red = ctx["traced"], ctx.get("reduced") or {}
    if not tr.get("images") or not red.get("window_s"):
        return None
    cfg = ctx["cfg"]
    per_image = flops.train_flops_per_image(cfg["layers"],
                                            cfg["input_shape"])
    return 100.0 * per_image * tr["images"] / (
        red["window_s"] * ctx["chips"] * ctx["peaks"]["flops_bf16"])
