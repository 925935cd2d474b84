"""The GA parent leaves the chip to its evaluator child.

Sorted first on purpose (``test_0_``): a chip belongs to one process
at a time, and every other module here makes the pytest process that
owner through the ``tpu_device`` fixture.  This one must run while the
pytest process holds no backend, because it starts the real GA parent
(``python -m veles_tpu --optimize``), whose evaluator child needs the
chip.  Run out of order it fails loudly instead of skipping on the
contention the way the old in-module version did.
"""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WF = """
    from veles_tpu.models import mnist

    def create_workflow(launcher):
        return mnist.create_workflow(launcher)

    def run(launcher):
        launcher.create_workflow(create_workflow)
        launcher.initialize()
        launcher.run()
"""

CFG = """
    from veles_tpu.config import root
    from veles_tpu.genetics import Tune

    root.mnist.loader = {"minibatch_size": 25, "n_train": 100,
                         "n_valid": 40}
    root.mnist.decision = {"max_epochs": 1}
    root.mnist.layers = [
        {"type": "all2all_tanh",
         "->": {"output_sample_shape": 16},
         "<-": {"learning_rate": Tune(0.1, 0.01, 1.0)}},
        {"type": "softmax",
         "->": {"output_sample_shape": 10},
         "<-": {"learning_rate": 0.1}},
    ]
"""


def test_ga_parent_stays_off_the_chip_and_its_evaluator_owns_it(
        tmp_path):
    """`python -m veles_tpu -b tpu-evaluator --optimize` with N>1
    workers: ONE evaluator process says hello from the TPU, the float
    tunes train as a population-batched cohort on it, and the parent —
    which builds no Launcher and never loads jax — cannot have held
    the chip, or that hello would not exist."""
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), \
        "this pytest process already owns a jax backend; run " \
        "tests_tpu/ as a whole (this module sorts first) or this " \
        "module alone"

    wf = tmp_path / "wf.py"
    wf.write_text(textwrap.dedent(WF))
    cfg = tmp_path / "cfg.py"
    cfg.write_text(textwrap.dedent(CFG))
    res = subprocess.run(
        [sys.executable, "-m", "veles_tpu", "-b", "tpu-evaluator",
         "--optimize", "4:1", "--ga-workers", "2", str(wf), str(cfg)],
        capture_output=True, text=True, cwd=REPO, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    assert "tpu-evaluator mode" in res.stderr, res.stderr[-3000:]
    assert " owns tpu;" in res.stderr, res.stderr[-3000:]
    assert "cohorts:" in res.stderr      # the batched path ran
    assert "falling back" not in res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert np.isfinite(out["fitness"])
    # the child is gone with its parent: the chip is free again for
    # the modules that follow
    assert not xla_bridge.backends_are_initialized()
