"""The control and the planted faults, read on the chip at a cell's own
size: the reference in float8 (the step below the bf16 the
configurations state), and the reference with half of each minibatch
left out, each put in the program's place and compared with the
reference exactly as a run compares the program.  Two witnesses beside
them: the reference with bf16 operands (the program's own precision)
and an fp8 recipe with per-tensor scales.  ``program`` (not a default
side) is the program itself through the harness with its
``compute_dtype`` one step down (``faults.lower_precision``): under
bf16 the program has no such path — float8 raises in its weight-decay
update (chip run, PR 24) — and a control that crashes sets no reading,
which the line then says.

    python3 benchmarks/tests/control_chip.py <workload> \
        [--sides=program,fp8,...] <seed> [...]

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


PRECISIONS = ("fp8", "fp8_scaled", "bf16")


def main(workload, seeds, sides=PRECISIONS + ("half_batch",)):
    import numpy as np

    from benchmarks import run
    from benchmarks.lib import check
    from benchmarks.tests import faults
    jax = run.setup_jax()
    mix, cfg = run.load_cell(workload)
    if "program" in sides:
        chips = int(mix.get("chips", 1))
        info = run.device_info(run.require_chips(jax, chips), chips)
    k, mb = int(mix["superstep"]), int(mix["minibatch"])
    blk = int(mix.get("reference_block_rows", 0))
    for seed in seeds:
        idx = np.random.default_rng(seed).permutation(
            int(mix["n_train"]))[:k * mb].reshape(k, mb)
        ref = check.follow_reference(cfg, seed, idx, block_rows=blk)
        out = {"workload": workload, "seed": seed,
               "ref_loss_sum": ref["loss_sum"]}
        for side in sides:
            if side == "program":
                try:
                    r = run.run_cell(mix, cfg, seed, 0.0, 0,
                                     t_start=time.time(),
                                     device_info=info,
                                     sabotage=faults.lower_precision)
                except Exception as e:      # no reading: it has failed
                    out[side] = {"crashed": f"{type(e).__name__}: "
                                            f"{str(e)[:200]}"}
                    continue
                out[side] = {n: r["compared"][n]["value"]
                             for n in check.NAMES}
                out[side]["correct"] = r["correct"]
                continue
            kw = {"precision": side} if side in PRECISIONS \
                else {"fault": side}
            other = check.follow_reference(cfg, seed, idx,
                                           block_rows=blk, **kw)
            g = check.gaps(other, ref)
            out[side] = {n: g[n] for n in check.NAMES}
            out[side]["at"] = g["at"]
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    rest = sys.argv[2:]
    kw = {}
    if rest and rest[0].startswith("--sides="):
        kw["sides"] = tuple(rest.pop(0)[len("--sides="):].split(","))
    main(sys.argv[1], [int(s) for s in rest], **kw)
