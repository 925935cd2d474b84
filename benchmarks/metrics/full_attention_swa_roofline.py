"""The full causal attention cores' share of their roofline in a
configuration whose layers are plain grouped-query ``attention``
(scores, softmax, weighted sums of the layers that have NO window; the
projections are outside): the analytic floor of the mechanism in one
train step (``flops_lm_swa.attention_floor_seconds``: forward +
backward, per layer the larger of FLOPs / peak — a query counts the
``n + 1`` keys up to itself — and minimum bytes / peak: MXU-bound,
8.37 ms a row and layer at 8 192) x steps of the traced window, over
the device time under the ``attn/core`` scope there (which the windowed
layers, under ``attn/window``, do not share).  Recomputed forwards are
in the time and not in the floor."""

from benchmarks.lib import flops_lm_swa


def read(ctx):
    tr, sc = ctx["traced"], ctx.get("scopes") or {}
    if not tr.get("images") or not sc.get("attention_s"):
        return None
    mix, pk = ctx["mix"], ctx["peaks"]
    rows = int(mix["minibatch"]) // ctx["chips"]
    floor = flops_lm_swa.attention_floor_seconds(
        ctx["cfg"]["layers"], ctx["seq_len"], rows,
        pk["flops_bf16"], pk["hbm_bytes_per_s"], windowed=False)
    steps = tr["images"] / float(mix["minibatch"])
    return 100.0 * floor * steps / sc["attention_s"]
