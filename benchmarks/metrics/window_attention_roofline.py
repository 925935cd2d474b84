"""The windowed attention cores' share of their roofline (scores,
softmax, weighted sums of the layers that have a window; the
projections are outside): the analytic floor of the mechanism in one
train step (``flops_lm_swa.attention_floor_seconds``: forward +
backward, per layer the larger of FLOPs / peak — a query counts the
``min(n + 1, window)`` keys it reads — and minimum bytes / peak:
MXU-bound, 1.96 ms a row and layer) x steps of the traced window, over
the device time under the ``attn/window`` scope there.  Recomputed
forwards, and whatever a kernel's blocks score beyond the window, are
in the time and not in the floor."""

from benchmarks.lib import flops_lm_swa


def read(ctx):
    tr, sc = ctx["traced"], ctx.get("scopes") or {}
    if not tr.get("images") or not sc.get("window_attention_s"):
        return None
    mix, pk = ctx["mix"], ctx["peaks"]
    rows = int(mix["minibatch"]) // ctx["chips"]
    floor = flops_lm_swa.attention_floor_seconds(
        ctx["cfg"]["layers"], ctx["seq_len"], rows,
        pk["flops_bf16"], pk["hbm_bytes_per_s"], windowed=True)
    steps = tr["images"] / float(mix["minibatch"])
    return 100.0 * floor * steps / sc["window_attention_s"]
