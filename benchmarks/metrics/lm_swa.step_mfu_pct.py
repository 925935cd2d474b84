"""The whole step's share of the chip's peak, for a language-model
configuration whose attention layers are windowed and full causal
attention beside a mixture of experts: ``flops_lm_swa.py`` model FLOPs
per trained row (matmuls alone; a core by the keys a query reads, the
routed experts by the expected share held here; recomputed forwards
NOT counted) x rows of the traced window, over the traced window's wall
time x chips x peak bf16 FLOP/s."""

from benchmarks.lib import flops_lm_swa


def read(ctx):
    tr, red = ctx["traced"], ctx.get("reduced") or {}
    if not tr.get("images") or not red.get("window_s") \
            or not ctx.get("seq_len"):
        return None
    per_row = flops_lm_swa.train_flops_per_row(ctx["cfg"]["layers"],
                                               ctx["seq_len"])
    return 100.0 * per_row * tr["images"] / (
        red["window_s"] * ctx["chips"] * ctx["peaks"]["flops_bf16"])
