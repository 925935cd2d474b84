"""``control_dp_chip.py`` at the tiny size on the CPU: the control and
every planted fault of a several-chip cell come out not correct by the
mix's own limits (counts and correctness checks, never speeds)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import check  # noqa: E402
from benchmarks.tests import control_dp_chip, tiny  # noqa: E402

MIX = dict(tiny.MIX, chips=4, name="tiny.train_dp4",
           traffic="train_resident_dp")


@pytest.fixture(scope="module")
def read():
    return control_dp_chip.readings(MIX, tiny.CFG, 2800000123)


@pytest.mark.parametrize("side", control_dp_chip.SIDES)
def test_side_is_judged_by_the_mixs_limits(read, side):
    got = read[side]
    assert set(check.NAMES) <= set(got)
    # bf16 is a witness on the program's side: the tiny mix's program
    # is f32, so there it fails too
    assert got["correct"] is False


def test_exchange_left_out_fails_on_every_chips_copy(read):
    least = read["no_allreduce"]["least"]
    lim = MIX["limits"]
    assert least["momentum_gap"] > lim["momentum_gap"]
    assert least["update_gap"] > lim["update_gap"]


def test_kind_runs_the_cell_on_four_virtual_devices():
    code = (
        "import json, time\n"
        "from veles_tpu.backends import make_device\n"
        "from benchmarks import run\n"
        "from benchmarks.tests import test_control_dp as t, tiny\n"
        "r = run.run_cell(t.MIX, tiny.CFG, 2800000321, 0.3, 0,\n"
        "                 device=make_device('cpu'), t_start=time.time(),\n"
        "                 device_info={'platform': 'cpu', 'kind': 'cpu',\n"
        "                              'count': 4})\n"
        "print(json.dumps({'correct': r['correct'],\n"
        "                  'attempted': r['attempted']}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert out["attempted"] > 0


@pytest.mark.parametrize("change", [{"chips": 1}, {"minibatch": 6}])
def test_kind_refuses_a_mix_it_cannot_share_out(change):
    from benchmarks.traffic import train_resident_dp
    with pytest.raises(ValueError, match="train_resident_dp"):
        train_resident_dp.Cell(dict(MIX, **change), tiny.CFG, 1, 0.1,
                               False)
