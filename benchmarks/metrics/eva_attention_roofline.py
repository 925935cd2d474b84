"""EVA attention's share of its roofline: the analytic floor of the
attention of one train step (``flops_seq.eva_floor_seconds``:
summaries + local + remote, forward + backward, per layer the larger
of FLOPs / peak and minimum bytes / peak) x steps of the traced
window, over the device time under the ``eva/`` scopes there.
Recomputed forwards are in the time and not in the floor."""

from benchmarks.lib import flops_seq


def read(ctx):
    tr, sc = ctx["traced"], ctx.get("scopes") or {}
    if not tr.get("images") or not sc.get("eva_s"):
        return None
    mix, pk = ctx["mix"], ctx["peaks"]
    rows = int(mix["minibatch"]) // ctx["chips"]
    floor = flops_seq.eva_floor_seconds(
        ctx["cfg"]["layers"], ctx["seq_len"], rows,
        pk["flops_bf16"], pk["hbm_bytes_per_s"])
    steps = tr["images"] / float(mix["minibatch"])
    return 100.0 * floor * steps / sc["eva_s"]
