"""The kernels' share of their roofline, as a whole: the analytic
floor of one train step (sum over layers of the larger of MXU time
and minimum-HBM time, from shapes alone) x steps of the traced window,
over the device's BUSY time there.  Reads the same work whatever
implements it."""

from benchmarks.lib import flops


def read(ctx):
    tr, red = ctx["traced"], ctx.get("reduced") or {}
    if not tr.get("images") or not red.get("busy_s"):
        return None
    cfg, mix, pk = ctx["cfg"], ctx["mix"], ctx["peaks"]
    # a data-parallel step gives each chip its share of the minibatch
    per_chip = int(mix["minibatch"]) // ctx["chips"]
    floor, _ = flops.step_floor_seconds(
        cfg["layers"], cfg["input_shape"], per_chip,
        pk["flops_bf16"], pk["hbm_bytes_per_s"])
    steps = tr["images"] / float(mix["minibatch"])
    return 100.0 * floor * steps / red["busy_s"]
