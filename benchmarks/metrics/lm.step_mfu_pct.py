"""The whole step's share of the chip's peak, for a language-model
configuration with Gated DeltaNet, attention and mixture-of-experts
layers: ``flops_lm.py`` model FLOPs per trained row (matmuls alone; the
rule by the recurrence's count, the routed experts by the expected
share held here; recomputed forwards NOT counted) x rows of the traced
window, over the traced window's wall time x chips x peak bf16
FLOP/s."""

from benchmarks.lib import flops_lm


def read(ctx):
    tr, red = ctx["traced"], ctx.get("reduced") or {}
    if not tr.get("images") or not red.get("window_s") \
            or not ctx.get("seq_len"):
        return None
    per_row = flops_lm.train_flops_per_row(ctx["cfg"]["layers"],
                                           ctx["seq_len"])
    return 100.0 * per_row * tr["images"] / (
        red["window_s"] * ctx["chips"] * ctx["peaks"]["flops_bf16"])
