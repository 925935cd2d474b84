"""The ``chips`` > 1 branch (``DataParallel(...).install()``) rehearsed
on four virtual CPU devices at the tiny size: a count and a
correctness check, never a speed.  A process of its own, because the
device count is fixed before JAX starts:

    JAX_PLATFORMS=cpu \\
    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    python3 benchmarks/tests/dp4_rehearsal.py
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    from veles_tpu.backends import make_device

    from benchmarks import run
    from benchmarks.tests import tiny
    mix = dict(tiny.MIX, chips=4, name="tiny.train_dp4")
    r = run.run_cell(mix, tiny.CFG, 7, 0.3, 0, device=make_device("cpu"),
                     t_start=time.time(),
                     device_info={"platform": "cpu", "kind": "cpu",
                                  "count": 4})
    print(json.dumps({"correct": r["correct"],
                      "compared": r["compared"],
                      "attempted": r["attempted"]}))


if __name__ == "__main__":
    main()
