"""The causal attention core's share of its roofline (scores, softmax,
weighted sums; the projections are outside): the analytic floor of the
mechanism in one train step (``flops_lm.attention_floor_seconds``:
forward + backward, per layer the larger of FLOPs / peak and minimum
bytes / peak: MXU-bound, 134 ms the layer) x steps of the traced
window, over the device time under the ``attn/core`` scope there.
Recomputed forwards are in the time and not in the floor."""

from benchmarks.lib import flops_lm


def read(ctx):
    tr, sc = ctx["traced"], ctx.get("scopes") or {}
    if not tr.get("images") or not sc.get("attention_s"):
        return None
    mix, pk = ctx["mix"], ctx["peaks"]
    rows = int(mix["minibatch"]) // ctx["chips"]
    floor = flops_lm.attention_floor_seconds(
        ctx["cfg"]["layers"], ctx["seq_len"], rows,
        pk["flops_bf16"], pk["hbm_bytes_per_s"])
    steps = tr["images"] / float(mix["minibatch"])
    return 100.0 * floor * steps / sc["attention_s"]
