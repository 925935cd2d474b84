"""Tests of the benchmark's ``laguna`` half, run by hand (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_laguna.py -q

On the CPU at a tiny size: the configuration file against the model
file and the published keys, the counts, the scopes and readers, a
tiny ``train_resident_lm_gate`` mix through ``run_cell``, the cell
failing at once on a program without the gate, and the comparison that
decides ``correct`` shown to fail for the control and every planted
fault.
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

from benchmarks import run                               # noqa: E402
from benchmarks.lib import check, flops_lm_gate          # noqa: E402
from benchmarks.lib import reference_laguna as ref       # noqa: E402
from benchmarks.lib import seeded_tokens                 # noqa: E402
from benchmarks.tests import tiny_laguna                 # noqa: E402
from benchmarks.traffic import train_resident_lm_gate    # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "laguna.train_packed8k"
CHANGED = {"num_hidden_layers": (48, 5), "num_experts": (256, 8),
           "vocab_size": (100352, 12544)}


def _catalog():
    path = os.environ.get("ARCHITECTURES_CATALOG", "")
    if not os.path.exists(path):
        pytest.skip("no catalog of architectures: set ARCHITECTURES_CATALOG "
                    "to its architectures.jsonl")
    with open(path) as f:
        return next(json.loads(line) for line in f
                    if '"Laguna-S-2.1"' in line)


def test_config_file_is_the_model_files_own_layers_at_the_cut():
    from veles_tpu.models.laguna import laguna_layers
    cfg = run.load_json("configs", "laguna.json")
    assert cfg["layers"] == json.loads(json.dumps(laguna_layers()))
    assert set(cfg["reduced"]) - {"dataset_length"} == set(CHANGED)
    assert set(cfg["reduced_from"]) == set(cfg["reduced"])
    row = _catalog()
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in CHANGED:
            assert (cfg["published_" + key], cfg[key]) == CHANGED[key]
            assert value == CHANGED[key][0]
        else:
            assert cfg[key] == value, key
    flat = ref.flatten(cfg["layers"])
    moe = [c["->"] for c in flat if c["type"] == "moe"]
    assert len(moe) == 4 and all(
        (m["experts_total"], m["top_k"], m["expert_size"],
         m["shared_size"], m["experts_held"], m["first_held"],
         m["routed_scale"]) == (256, 10, 1024, 1024, 8, 0, 2.5)
        for m in moe)
    att = [c["->"] for c in flat if c["type"] == "attention"]
    assert [(a["n_heads"], a["n_kv_heads"], a["head_size"], a["window"],
             a["gate"]) for a in att] == [(48, 8, 128, None, True)] \
        + [(72, 8, 128, 512, True)] * 3 + [(48, 8, 128, None, True)]
    rope = cfg["rope_parameters"]
    assert [a["rope"] for a in att] == [rope["full_attention"]] \
        + [rope["sliding_attention"]] * 3 + [rope["full_attention"]]
    assert [c["->"]["intermediate_size"] for c in flat
            if c["type"] == "swiglu"] == [12288]
    assert cfg["layer_types_held"] == cfg["layer_types"][:5]
    assert len(cfg["assumed"]) == 11
    assert "32 chips share each layer" in cfg["deployment"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "laguna")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna", "train_resident_lm_gate", 1)
    listed = set(run.per_layer_of(bench, {"name": CELL}))
    assert len(listed) == 23 and listed >= {
        "lm_gate.step_mfu_pct", "swiglu.busy_pct", "moe_roofline",
        "window_attention_roofline", "full_attention_swa_roofline"}
    assert not listed & {"lm_swa.step_mfu_pct", "lm.step_mfu_pct",
                         "full_attention_roofline"}


def test_counts_of_the_config_file():
    cfg = run.load_json("configs", "laguna.json")
    mix = run.load_json("workloads", CELL + ".json")
    t, rows = mix["seq_len"], mix["minibatch"]
    assert (t, rows, mix["superstep"], mix["n_train"]) == (8192, 4, 2, 16)
    assert ref.param_count(cfg["layers"]) == cfg["parameters"] \
        == 811_029_504
    parts = cfg["parameters_by_part"]
    assert parts["leading_dense_layer"] \
        + 3 * (parts["window_moe_layer_outside_routed"]
               + 8 * parts["one_expert"]) \
        + parts["full_attention_moe_layer_outside_routed"] \
        + 8 * parts["one_expert"] + 2 * parts["embedding_or_head"] \
        + parts["final_norm"] == cfg["parameters"]
    assert flops_lm_gate.train_flops_per_row(cfg["layers"], t) \
        == cfg["train_flops_per_row"] == 3 * t \
        * cfg["forward_flops_per_token"]
    # a step of four rows: 120.0 TFLOP
    assert rows * cfg["train_flops_per_row"] == pytest.approx(120.0e12,
                                                              rel=1e-3)
    with pytest.raises(ValueError, match="unknown layer type"):
        from benchmarks.lib import flops_lm_swa
        flops_lm_swa.forward_flops_per_row(cfg["layers"], t)


def test_scopes_and_readers():
    find = lambda p: [m for m, rx in  # noqa: E731
                      train_resident_lm_gate.MECHANISMS.items()
                      if rx.search(p)]
    assert find("x/fwd/fwd2_attention/jvp(attn/core)/splash") \
        == ["attention"]
    assert find("x/fwd/fwd7_attention/jvp(attn/window)/splash") \
        == ["window_attention"]
    assert find("x/bwd/fwd7_attention/transpose(jvp(attn/gate))/mul") \
        == ["gate"]
    assert find("x/fwd/fwd5_swiglu/jvp(mlp/swiglu)/dot_general") \
        == ["swiglu"]
    assert find("x/fwd/fwd6_dense/dot_general") == []
    cfg = run.load_json("configs", "laguna.json")
    ctx = {"traced": {"images": 8.0}, "mix": {"minibatch": 4},
           "chips": 1, "cfg": cfg, "seq_len": 8192,
           "reduced": {"window_s": 10.0},
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "scopes": {"busy_s": 2.0}}
    assert run.metric_reader("lm_gate.step_mfu_pct")(ctx) \
        == pytest.approx(100 * 2 * 120.0e12 / (10.0 * 197e12), rel=1e-3)
    # swiglu.busy_pct reads the trace itself; nothing without one
    assert run.metric_reader("swiglu.busy_pct")(ctx) is None
    trace = {"devices": {"/device:TPU:0": [("a", 0, 1000),
                                           ("b", 1000, 3000)]},
             "stats": {"/device:TPU:0": [
                 {"tf_op": "x/fwd/fwd5_swiglu/jvp(mlp/swiglu)/dot"},
                 {"tf_op": "x/fwd/fwd6_dense/dot"}]}}
    assert run.metric_reader("swiglu.busy_pct")(
        dict(ctx, trace=trace)) == pytest.approx(25.0)


def _run(seed, sabotage=None, trace=0, mix=None):
    from veles_tpu.backends import make_device
    return run.run_cell(mix or tiny_laguna.MIX, tiny_laguna.CFG, seed,
                        0.3, trace, device=make_device("cpu"),
                        t_start=time.time(), device_info=CPU_INFO,
                        sabotage=sabotage)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_sound_run_is_correct(seed):
    from veles_tpu import telemetry
    telemetry.reset()
    r = _run(seed)
    assert r["correct"], r["compared"]
    assert r["attempted"] > 0 and r["failed"] == 0
    journal = r["run"]["journal"]
    assert [(e["window"], e["rope"], e["rotary"], e["gate"])
            for e in journal["attn.path"]] == [
        (None, "yarn", 8, "per_head")] + [
        (32, "default", 16, "per_head")] * 3 + [
        (None, "yarn", 8, "per_head")]
    assert [(e["shared"], e["routed_scale"])
            for e in journal["moe.share"]] == [(True, 2.5)] * 4
    assert [e["dropped"] for e in journal["moe.load"]] == [0] * 4
    differs = r["run"]["detail"]["routing_differs_share"]
    assert len(differs) == 4 and max(differs.values()) < 0.01


def test_traced_run_reports_what_the_cpu_can():
    r = _run(5, trace=1)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {
        "workflow.initialize_ms", "fused.first_submit_ms",
        "fused.compile_ms", "fused.submit_ms", "fused.fetch_wait_ms",
        "loop.turnaround_ms", "fused.compiles_in_window"}


@pytest.mark.parametrize("missing", ["gate", "rotary", "routed_scale"])
def test_the_cell_fails_at_once_on_a_program_without_the_layers(
        monkeypatch, missing):
    import inspect

    from veles_tpu.ops import attention, moe
    if missing == "rotary":
        monkeypatch.setattr(attention, "rope_frequencies",
                            lambda spec, d: (np.zeros(d // 2), 1.0))
    else:
        cls = attention.Attention if missing == "gate" else moe.MoE
        sig = inspect.signature(cls)
        monkeypatch.setattr(cls, "__signature__", sig.replace(
            parameters=[p for p in sig.parameters.values()
                        if p.name != missing]), raising=False)
    t0 = time.time()
    with pytest.raises(RuntimeError, match="no per-head gate"):
        _run(3)
    assert time.time() - t0 < 5.0


def _fed(seed=7):
    cfg, mix = tiny_laguna.CFG, tiny_laguna.MIX
    rows = np.asarray(seeded_tokens.dataset(
        seed, mix["n_train"], mix["seq_len"], **cfg["dataset"]["->"]))
    return rows[np.array([[2, 5], [0, 7]])]


@pytest.mark.parametrize("side", ("bf16",) + ref.FAULTS
                         + ("state_unchanged",))
def test_control_and_faults_fail_the_limits(side):
    """The reference in the next precision down (bf16 under the tiny
    cell's f32) or with a fault planted, in the program's place."""
    cfg, mix = tiny_laguna.CFG, tiny_laguna.MIX
    make = lambda: ref.init_params(7, cfg["layers"],  # noqa: E731
                                   cfg["init_std"])
    want = ref.follow(cfg["layers"], make(), _fed(), seq_block=32)
    kw = {"precision": side} if side == "bf16" else {"fault": side}
    other = ref.follow(cfg["layers"], make(), _fed(), seq_block=32, **kw)
    ok, compared = check.judge(check.gaps(other, want), mix["limits"])
    assert not ok, compared
