"""The control and the planted faults of a ``train_resident`` cell that
spans several chips, read on the chip at the cell's own global
minibatch and **judged by the cell's own limits** (``check.judge``),
as a run judges the program.  By hand, like ``control_chip.py``; the
harness does not run this.  Sides, each put in the program's place and
compared with the reference:

- ``fp8``, ``bf16``: the reference in float8_e4m3 (the step below the
  bf16 the configuration states) and with bf16 operands (a witness on
  the program's side);
- ``half_batch``: half of each global minibatch left out;
- ``no_allreduce``: **the exchange between chips left out**.  Every
  chip follows its own shard of each minibatch (rows ``[c, c + 1) *
  minibatch / chips``) with its local gradient; the reading is what a
  run would fetch: chip 0's copy of the momentum and of the
  parameters' change, and the loss summed over the chips (the metric
  carry is still summed).  ``least`` beside it is the least gap of any
  chip's copy.
- ``program`` (not a default side; needs the cell's chips): the sound
  program through the harness with a window of 0 s — one more seed of
  the lower reading, without a measured window.

    python3 benchmarks/tests/control_dp_chip.py <workload> \
        [--sides=fp8,bf16,half_batch,no_allreduce,program] <seed> [...]

The reference sides need one chip and no program.  Prints one JSON
line per seed.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PRECISIONS = ("fp8", "bf16")
SIDES = PRECISIONS + ("half_batch", "no_allreduce")


def no_allreduce(cfg, seed, idx, chips, blk):
    """[one reading a chip]: chip ``c``'s own trajectory, the loss of
    every reading the sum over the chips."""
    from benchmarks.lib import check
    per = idx.shape[1] // chips
    local = [check.follow_reference(cfg, seed, idx[:, c * per:(c + 1) * per],
                                    block_rows=blk)
             for c in range(chips)]
    loss = sum(r["loss_sum"] for r in local)
    return [dict(r, loss_sum=loss) for r in local]


def judged(side, ref, limits):
    from benchmarks.lib import check
    g = check.gaps(side, ref)
    ok, _ = check.judge(g, limits)
    return dict({n: g[n] for n in check.NAMES}, correct=ok, at=g["at"])


def readings(mix, cfg, seed, sides=SIDES, device_info=None, device=None):
    """{side: its three gaps, ``correct`` by the mix's limits}."""
    import numpy as np

    from benchmarks import run
    from benchmarks.lib import check
    k, mb = int(mix["superstep"]), int(mix["minibatch"])
    chips = int(mix.get("chips", 1))
    blk = int(mix.get("reference_block_rows", 0))
    limits = mix["limits"]
    idx = np.random.default_rng(seed).permutation(
        int(mix["n_train"]))[:k * mb].reshape(k, mb)
    ref = check.follow_reference(cfg, seed, idx, block_rows=blk)
    out = {"workload": mix["name"], "seed": seed, "limits": limits,
           "ref_loss_sum": ref["loss_sum"]}
    for side in sides:
        if side == "program":
            r = run.run_cell(mix, cfg, seed, 0.0, 0, device=device,
                             t_start=time.time(), device_info=device_info)
            out[side] = dict({n: r["compared"][n]["value"]
                              for n in check.NAMES}, correct=r["correct"])
        elif side == "no_allreduce":
            each = [judged(r, ref, limits)
                    for r in no_allreduce(cfg, seed, idx, chips, blk)]
            out[side] = dict(each[0], least={
                n: min(e[n] for e in each) for n in check.NAMES},
                correct=any(e["correct"] for e in each))
        else:
            kw = {"precision": side} if side in PRECISIONS \
                else {"fault": side}
            out[side] = judged(check.follow_reference(
                cfg, seed, idx, block_rows=blk, **kw), ref, limits)
    return out


def main(workload, seeds, sides=SIDES):
    from benchmarks import run
    jax = run.setup_jax()
    mix, cfg = run.load_cell(workload)
    info = None
    if "program" in sides:
        chips = int(mix.get("chips", 1))
        info = run.device_info(run.require_chips(jax, chips), chips)
    for seed in seeds:
        print(json.dumps(readings(mix, cfg, seed, sides, info)),
              flush=True)


if __name__ == "__main__":
    rest = sys.argv[2:]
    kw = {}
    if rest and rest[0].startswith("--sides="):
        kw["sides"] = tuple(rest.pop(0)[len("--sides="):].split(","))
    main(sys.argv[1], [int(s) for s in rest], **kw)
