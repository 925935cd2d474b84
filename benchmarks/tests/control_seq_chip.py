"""The control and the planted faults of a ``train_resident_seq`` cell,
read on the chip at the cell's own size (by hand; the harness does not
run this; like ``control_chip.py``): the reference in float8_e4m3 where
the configuration states bfloat16, the reference with bf16 operands (a
witness on the program's side), and the reference with a fault planted
— the remote summaries left out, one prediction head left out — each
put in the program's place, compared with the reference exactly as
a run compares the program, and judged by the cell's own limits
(``check.judge``: ``correct`` false is what each has to read).

    python3 benchmarks/tests/control_seq_chip.py <workload> \
        [--sides=fp8,bf16,no_remote,seven_heads] <seed> [...]

Needs no measured window and no program: only the first call's rows
are made.  Prints one JSON line per seed.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PRECISIONS = ("fp8", "bf16")
SIDES = PRECISIONS + ("no_remote", "seven_heads")


def main(workload, seeds, sides=SIDES):
    import numpy as np

    from benchmarks import run
    from benchmarks.lib import check
    from benchmarks.traffic import train_resident_seq
    run.setup_jax()
    mix, cfg = run.load_cell(workload)
    k, mb = int(mix["superstep"]), int(mix["minibatch"])
    for seed in seeds:
        cell = train_resident_seq.Cell(mix, cfg, seed, 0.0, False)
        cell.first = {"indices": np.random.default_rng(seed).permutation(
            int(mix["n_train"]))[:k * mb].reshape(k, mb)}
        t0 = time.time()
        ref = cell.follow_reference()
        out = {"workload": workload, "seed": seed,
               "limits": mix["limits"],
               "ref_loss_sum": ref["loss_sum"],
               "ref_seconds": round(time.time() - t0, 1)}
        for side in sides:
            kw = {"precision": side} if side in PRECISIONS \
                else {"fault": side}
            g = check.gaps(cell.follow_reference(**kw), ref)
            out[side] = {n: g[n] for n in check.NAMES}
            out[side]["correct"] = check.judge(g, mix["limits"])[0]
            out[side]["at"] = g["at"]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    rest = sys.argv[2:]
    kw = {}
    if rest and rest[0].startswith("--sides="):
        kw["sides"] = tuple(rest.pop(0)[len("--sides="):].split(","))
    main(sys.argv[1], [int(s) for s in rest], **kw)
