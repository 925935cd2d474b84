"""The program's side of the comparison that decides ``correct``, read
on the chip at a cell's own size on many seeds in ONE process (set-up
is most of a run, and a training cell's readings need no measured
window): each seed builds the cell, drives it through its warm epoch
and one firing more, frees it and follows the reference.

    python3 benchmarks/tests/readings_chip.py <workload> <seed> [...]

Prints one JSON line per seed: the numbers compared, where the worst
leaf was, and ``correct`` under the limits the cell's file holds now.
The limits are set from these lines and ``control_chip.py``'s.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload, seeds):
    from benchmarks import run
    jax = run.setup_jax()
    mix, cfg = run.load_cell(workload)
    chips = int(mix.get("chips", 1))
    info = run.device_info(run.require_chips(jax, chips), chips)
    for seed in seeds:
        t0 = time.time()
        r = run.run_cell(mix, cfg, seed, 0.0, 0, t_start=t0,
                         device_info=info)
        out = {"workload": workload, "seed": seed,
               "correct": r["correct"]}
        out.update({n: c["value"] for n, c in r["compared"].items()})
        out["at"] = r["run"]["detail"]["at"]
        out["seconds"] = round(time.time() - t0, 1)
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], [int(s) for s in sys.argv[2:]])
