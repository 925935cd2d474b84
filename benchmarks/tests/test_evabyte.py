"""Tests of the benchmark's sequence half, run by hand (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_evabyte.py -q

On the CPU at a tiny size: counts, the configuration file against the
model file, a tiny ``train_resident_seq`` mix through ``run_cell``, and
the comparison that decides ``correct`` shown to fail for the control
and the planted faults.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run                              # noqa: E402
from benchmarks.lib import check, flops_seq             # noqa: E402
from benchmarks.lib import reference_evabyte as ref     # noqa: E402
from benchmarks.lib import seeded_seq                   # noqa: E402
from benchmarks.tests import tiny_seq                   # noqa: E402
from benchmarks.traffic import train_resident_seq       # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_config_file_is_the_model_files_own_layers_at_4_blocks():
    from veles_tpu.models.evabyte import PUBLISHED, evabyte_layers
    cfg = run.load_json("configs", "evabyte.json")
    assert cfg["layers"] == json.loads(json.dumps(evabyte_layers(4)))
    assert cfg["num_hidden_layers"] == 4
    assert cfg["published_num_hidden_layers"] == PUBLISHED["n_layers"]
    for ours, theirs in (("hidden_size", "hidden_size"),
                         ("intermediate_size", "intermediate_size"),
                         ("vocab_size", "vocab_size"),
                         ("window_size", "window_size"),
                         ("chunk_size", "chunk_size"),
                         ("n_pred_heads", "num_pred_heads"),
                         ("n_heads", "num_attention_heads"),
                         ("seq_len", "max_seq_length"),
                         ("init_std", "init_std")):
        assert PUBLISHED[ours] == cfg[theirs], ours
    assert len(cfg["assumed"]) == 6


def test_counts_of_the_config_file():
    cfg = run.load_json("configs", "evabyte.json")
    mix = run.load_json("workloads", "evabyte.train_packed32k.json")
    t = mix["seq_len"]
    assert ref.param_count(cfg["layers"]) == cfg["parameters"] \
        == 821_366_784
    per_token = flops_seq.forward_flops_per_row(cfg["layers"], t) / t
    assert per_token == cfg["forward_flops_per_token"] == 1_770_029_056
    assert flops_seq.train_flops_per_row(cfg["layers"], t) \
        == cfg["train_flops_per_row"]
    fw = ref.flatten(cfg["layers"])[2]["->"]
    assert flops_seq.eva_keys_per_query(fw, t) == 1024.5 + 960
    floor = flops_seq.eva_floor_seconds(cfg["layers"], t, 1, 197e12,
                                        819e9)
    assert 0.06 < floor < 0.07          # MXU-bound: 4 x 16.2 ms


def test_rows_are_seeded_and_hold_separators():
    a = np.asarray(seeded_seq.dataset(2 ** 31 + 9, 4, 128, median_len=48))
    b = np.asarray(seeded_seq.dataset(2 ** 31 + 9, 4, 128, median_len=48))
    c = np.asarray(seeded_seq.dataset(2 ** 31 + 10, 4, 128, median_len=48))
    assert a.dtype == np.int32 and a.shape == (4, 128)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    assert a.min() >= 0 and a.max() == 256 and (a == 256).sum() >= 2


def test_scope_paths_are_told_apart():
    again = train_resident_seq.is_recomputed_forward
    assert not again("jit(train_step)/while/body/fwd/fwd2_eva_attention/"
                     "jvp(eva/local)/dot_general")
    assert again("jit(train_step)/while/body/bwd/fwd2_eva_attention/"
                 "recompute/jvp(eva/remote)/exp")
    assert again("x/bwd/fwd2_eva_attention/transpose(jvp(bwd/fwd2))/"
                 "recompute/jvp()/checkpoint/rematted_computation/"
                 "eva/local/exp")
    assert not again("x/bwd/fwd2_eva_attention/transpose(jvp(bwd/fwd2))/"
                     "recompute/jvp()/checkpoint/eva/local/dot_general")
    assert train_resident_seq.EVA.search(
        "a/transpose(jvp(eva/summaries))/mul").group(1) == "summaries"


def _run(seed, sabotage=None, trace=0):
    from veles_tpu.backends import make_device
    return run.run_cell(tiny_seq.MIX, tiny_seq.CFG, seed, 0.3, trace,
                        device=make_device("cpu"), t_start=time.time(),
                        device_info=CPU_INFO, sabotage=sabotage)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_sound_run_is_correct(seed):
    r = _run(seed)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"setup_s", "train_images_per_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["run"]["train_bytes_per_s"] == pytest.approx(
        r["run"]["train_images_per_s"] * tiny_seq.MIX["seq_len"])


def test_traced_run_reports_what_the_cpu_can():
    r = _run(5, trace=1)
    assert r["correct"], r["compared"]
    # no TPU plane on the CPU: the trace readers return nothing
    assert set(r["metrics"]) == {
        "loader.run_ms", "fused.dispatch_ms",
        "fused.compiles_in_window", "decision.epoch_end_ms"}
    # whole firings lay between the trace's barriers
    assert r["run"]["traced_firings"] >= tiny_seq.MIX["trace_firings"]


def _unchanged(cell):
    """A state left as it was: the step's outputs thrown away."""
    fused = cell.w.fused
    step = fused._train_step

    def same(params, opt, acc, conf, *rest):
        _, _, acc, conf = step(*jax_copy((params, opt)), acc, conf, *rest)
        return params, opt, acc, conf
    fused._train_step = same


def jax_copy(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(jnp.array, tree)


def test_a_state_left_unchanged_is_not_correct():
    r = _run(11, sabotage=_unchanged)
    assert not r["correct"], r["compared"]
    assert r["compared"]["update_gap"]["value"] > \
        r["compared"]["update_gap"]["limit"]


@pytest.mark.parametrize("side", ["bf16", "no_remote", "seven_heads",
                                  "state_unchanged"])
def test_control_and_faults_fail_the_limits(side):
    """The reference in the next precision down (bf16 under the tiny
    cell's f32) or with a fault planted, in the program's place."""
    cfg, mix = tiny_seq.CFG, tiny_seq.MIX
    rows = np.asarray(seeded_seq.dataset(
        7, mix["n_train"], mix["seq_len"], **cfg["dataset"]["->"]))
    fed = rows[np.array([[2], [0]])]
    make = lambda: ref.init_params(7, cfg["layers"],  # noqa: E731
                                   cfg["init_std"])
    want = ref.follow(cfg["layers"], make(), fed, seq_block=32)
    kw = {"precision": side} if side == "bf16" else {"fault": side}
    other = ref.follow(cfg["layers"], make(), fed, seq_block=32, **kw)
    ok, compared = check.judge(check.gaps(other, want), mix["limits"])
    assert not ok, compared


def test_reference_in_blocks_of_positions_agrees_with_itself():
    cfg = tiny_seq.CFG
    fed = np.asarray(seeded_seq.dataset(9, 2, 128, median_len=48))[
        np.array([[0], [1]])]
    make = lambda: ref.init_params(9, cfg["layers"],  # noqa: E731
                                   cfg["init_std"])
    a = ref.follow(cfg["layers"], make(), fed)
    b = ref.follow(cfg["layers"], make(), fed, seq_block=32)
    g = check.gaps(b, a)
    assert max(g[n] for n in check.NAMES) < 1e-5, g
