"""The control and the planted faults of a ``train_resident_lm_swa``
cell, read on the chip at the cell's own size (by hand; the harness
does not run this; like ``control_lm_chip.py``): the reference in
float8_e4m3 where the configuration states bfloat16, the reference with
bf16 operands (a witness on the program's side), and the reference with
a fault planted — the window left out of the window layers, YaRN's
frequencies replaced by the default law on the full layer, YaRN's
1.277 scale left out, the top-8 weights not renormalised, one held
expert left out, half of every minibatch left out — each put in the
program's place, compared with the
reference exactly as a run compares the program, and judged by the
cell's own limits (``check.judge``: ``correct`` false is what each has
to read).

    python3 benchmarks/tests/control_mellum2_chip.py <workload> \
        [--sides=fp8,bf16,no_window,...] <seed> [...]

Needs no measured window and no program: only the first call's rows
are made.  Prints one JSON line per seed and side as it goes.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PRECISIONS = ("fp8", "bf16")


def main(workload, seeds, sides=None):
    import numpy as np

    from benchmarks import run
    from benchmarks.lib import check
    from benchmarks.lib import reference_mellum2 as reference
    from benchmarks.traffic import train_resident_lm_swa
    run.setup_jax()
    sides = sides or PRECISIONS[:1] + reference.FAULTS
    mix, cfg = run.load_cell(workload)
    k, mb = int(mix["superstep"]), int(mix["minibatch"])
    for seed in seeds:
        cell = train_resident_lm_swa.Cell(mix, cfg, seed, 0.0, False)
        cell.first = {"indices": np.random.default_rng(seed).permutation(
            int(mix["n_train"]))[:k * mb].reshape(k, mb)}
        t0 = time.time()
        ref = cell.follow_reference()
        print(json.dumps({"workload": workload, "seed": seed,
                          "limits": mix["limits"],
                          "ref_loss_sum": ref["loss_sum"],
                          "ref_seconds": round(time.time() - t0, 1)}),
              flush=True)
        for side in sides:
            kw = {"precision": side} if side in PRECISIONS \
                else {"fault": side}
            t0 = time.time()
            g = check.gaps(cell.follow_reference(**kw), ref)
            out = {n: g[n] for n in check.NAMES}
            out.update(seed=seed, side=side, at=g["at"],
                       correct=check.judge(g, mix["limits"])[0],
                       seconds=round(time.time() - t0, 1))
            print(json.dumps(out), flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    chosen = None
    for a in list(args):
        if a.startswith("--sides="):
            chosen = tuple(a.split("=", 1)[1].split(","))
            args.remove(a)
    main(args[0], [int(s) for s in args[1:]], chosen)
