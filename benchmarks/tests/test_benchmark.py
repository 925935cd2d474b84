"""Tests of the benchmark itself, run by hand (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They hold on the CPU at a tiny size: counts, the trace reduction on a
recorded sample, and the comparison that decides ``correct`` — shown to
fail for the control and for each fault a training cell can have.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run                      # noqa: E402
from benchmarks.lib import check, flops, xplane  # noqa: E402
from benchmarks.tests import faults, tiny       # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}


def _cfg(name):
    return run.load_json("configs", f"{name}.json")


@pytest.mark.parametrize("name,macs,params", [
    ("alexnet", 1_135_256_096, 62_378_344),
    ("vgg16", 15_470_264_320, 138_357_544)])
def test_flops_of_the_config_files(name, macs, params):
    cfg = _cfg(name)
    assert flops.forward_macs(cfg["layers"], cfg["input_shape"]) == macs
    assert flops.param_count(cfg["layers"], cfg["input_shape"]) == params
    assert cfg["forward_macs_per_image"] == macs
    assert cfg["train_flops_per_image"] == 6.0 * macs


def test_alexnet_file_is_the_models_own_layers():
    from veles_tpu.models.alexnet import alexnet_layers
    assert _cfg("alexnet")["layers"] == json.loads(
        json.dumps(alexnet_layers(1000)))


def test_manifest_and_files_agree():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"] for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        mix, cfg = run.load_cell(cell["name"])
        assert (mix["config"], mix["traffic"], mix["chips"]) == \
            (cell["config"], cell["traffic"], cell["chips"])
        assert set(mix["per_layer"]) <= per_layer
        assert set(mix["end_to_end"]) <= e2e
        for name in mix["per_layer"]:
            assert callable(run.metric_reader(name))
    for c in bench["configs"]:
        assert _cfg(c["name"])["reduced"] == c["reduced"]


def test_step_floor_is_below_the_pure_mxu_time_times_two():
    cfg = _cfg("alexnet")
    floor, rows = flops.step_floor_seconds(
        cfg["layers"], cfg["input_shape"], 128, 197e12, 819e9)
    mxu = flops.train_flops_per_image(
        cfg["layers"], cfg["input_shape"]) * 128 / 197e12
    assert mxu * 0.9 < floor < 2.5 * mxu
    assert {r["bound"] for r in rows} == {"mxu", "hbm"}


# -- the trace reduction, on a small recorded sample ---------------------

def test_xplane_reduction_on_the_recorded_sample():
    with open(os.path.join(HERE, "trace_sample.json")) as f:
        sample = json.load(f)
    trace = {"devices": {k: [tuple(e) for e in v]
                         for k, v in sample["devices"].items()},
             "spans": [tuple(e) for e in sample["spans"]]}
    red = xplane.reduce(trace)
    exp = sample["expected"]
    assert red["window_s"] == pytest.approx(exp["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(exp["busy_s"], rel=1e-9)
    assert red["idle_gaps"][0][0] == exp["longest_gap_label"]
    assert red["device_ops"][0][0] == exp["top_op"]


def test_union_and_self_times():
    ev = [("while", 0, 100), ("a", 10, 20), ("b", 40, 50), ("c", 120, 10)]
    assert xplane.union(ev) == [(0, 100), (120, 130)]
    assert xplane.union(ev, 50, 125) == [(50, 100), (120, 125)]
    assert xplane.self_times(ev) == {"while": 30, "a": 20, "b": 50,
                                     "c": 10}


# -- the command without a chip -------------------------------------------

def test_command_fails_without_a_tpu_before_any_data():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    t0 = time.time()
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "alexnet.train_resident", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    assert p.stdout.strip() == ""
    assert time.time() - t0 < 60


def test_four_chip_branch_on_virtual_cpu_devices():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "dp4_rehearsal.py")],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0


# -- correct: sound runs pass, the control and every fault fail ----------

def _run(seed, sabotage=None, trace=0):
    from veles_tpu.backends import make_device
    return run.run_cell(tiny.MIX, tiny.CFG, seed, 0.3, trace,
                        device=make_device("cpu"), t_start=time.time(),
                        device_info=CPU_INFO, sabotage=sabotage)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_sound_run_is_correct(seed):
    r = _run(seed)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"setup_s", "train_images_per_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "compared"


def test_traced_run_reports_per_layer_metrics_only():
    r = _run(5, trace=1)
    assert r["correct"], r["compared"]
    # no TPU plane on the CPU: the trace readers return nothing
    assert set(r["metrics"]) == {
        "loader.run_ms", "fused.dispatch_ms",
        "fused.compiles_in_window", "decision.epoch_end_ms"}
    assert r["metrics"]["fused.compiles_in_window"]["value"] == 0.0


@pytest.mark.parametrize("fault,number", [
    ("lower_precision", "momentum_gap"), ("state_unchanged", "update_gap"),
    ("half_batch", "loss_gap")])
def test_broken_timed_path_is_not_correct(fault, number):
    """The rest of a run with the timed path broken underneath: the
    program's own lower-precision path switched on (the control), and
    each fault a one-chip training cell can have."""
    r = _run(11, sabotage=getattr(faults, fault))
    assert not r["correct"], r["compared"]
    c = r["compared"][number]
    assert c["value"] > c["limit"], r["compared"]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("side", ["bf16", "half_batch",
                                  "state_unchanged"])
def test_control_and_faults_fail_the_limits(seed, side):
    """The reference in the next precision down (bf16 under the tiny
    cell's f32) or with a fault, put in the program's place."""
    k, mb = tiny.MIX["superstep"], tiny.MIX["minibatch"]
    idx = np.random.default_rng(seed).permutation(
        tiny.MIX["n_train"])[:k * mb].reshape(k, mb)
    ref = check.follow_reference(tiny.CFG, seed, idx)
    kw = {"precision": side} if side == "bf16" else {"fault": side}
    other = check.follow_reference(tiny.CFG, seed, idx, **kw)
    ok, compared = check.judge(check.gaps(other, ref),
                               tiny.MIX["limits"])
    assert not ok, compared


def test_reference_in_row_blocks_agrees_with_itself():
    idx = np.arange(32).reshape(4, 8)
    a = check.follow_reference(tiny.CFG, 9, idx)
    b = check.follow_reference(tiny.CFG, 9, idx, block_rows=4)
    g = check.gaps(b, a)
    assert max(g[n] for n in check.NAMES) < 1e-5, g
