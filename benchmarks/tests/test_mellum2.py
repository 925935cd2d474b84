"""Tests of the benchmark's ``mellum2`` half, run by hand (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_mellum2.py -q

On the CPU at a tiny size: the configuration file against the model
file and the published keys, the counts, the scopes of the two kinds of
attention core, a tiny ``train_resident_lm_swa`` mix through
``run_cell``, and the comparison that decides ``correct`` shown to fail
for the control and every planted fault.
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
from benchmarks.lib import check, flops_lm, flops_lm_swa  # noqa: E402
from benchmarks.lib import reference_mellum2 as ref      # noqa: E402
from benchmarks.lib import seeded_tokens                 # noqa: E402
from benchmarks.tests import tiny_mellum2                # noqa: E402
from benchmarks.traffic import train_resident_lm_swa     # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "mellum2.train_packed8k"
CHANGED = {"num_hidden_layers": (28, 4), "num_experts": (64, 16),
           "vocab_size": (98304, 24576)}


def _catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("the catalog of architectures is not on this machine")
    with open(path) as f:
        return next(json.loads(line) for line in f
                    if '"Mellum2-12B-A2.5B-Instruct"' in line)


def test_config_file_is_the_model_files_own_layers_at_the_cut():
    from veles_tpu.models.mellum2 import mellum2_layers
    cfg = run.load_json("configs", "mellum2.json")
    assert cfg["layers"] == json.loads(json.dumps(mellum2_layers()))
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
    # every width as published, the router as wide as published
    flat = ref.flatten(cfg["layers"])
    moe = [c["->"] for c in flat if c["type"] == "moe"]
    assert len(moe) == 4 and all(
        (m["experts_total"], m["top_k"], m["expert_size"],
         m["shared_size"], m["experts_held"], m["first_held"])
        == (64, 8, 896, 0, 16, 0) for m in moe)
    att = [c["->"] for c in flat if c["type"] == "attention"]
    assert [(a["n_heads"], a["n_kv_heads"], a["head_size"], a["window"])
            for a in att] == [(32, 4, 128, 1024)] * 3 + [(32, 4, 128,
                                                          None)]
    rope = cfg["rope_parameters"]
    assert [a["rope"] for a in att] == [rope["sliding_attention"]] * 3 \
        + [rope["full_attention"]]
    assert cfg["layer_types_held"] == cfg["layer_types"][:4] == [
        "sliding_attention"] * 3 + ["full_attention"]
    assert flat[0]["->"]["hidden_size"] == 2304
    assert len(cfg["assumed"]) == 8 and len(cfg["departures"]) == 2
    assert "4 chips share each layer" in cfg["deployment"]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "mellum2")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "mellum2", "train_resident_lm_swa", 1)
    mix = run.load_json("workloads", CELL + ".json")
    listed = {m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", [])}
    assert listed == set(mix["per_layer"])
    assert "lm.step_mfu_pct" not in listed
    assert "full_attention_roofline" not in listed


def test_counts_of_the_config_file():
    cfg = run.load_json("configs", "mellum2.json")
    mix = run.load_json("workloads", CELL + ".json")
    t, rows = mix["seq_len"], mix["minibatch"]
    assert (t, rows, mix["superstep"], mix["n_train"]) == (8192, 4, 2, 16)
    assert ref.param_count(cfg["layers"]) == cfg["parameters"] \
        == 595_153_152
    assert cfg["state_bytes"] == 10 * cfg["parameters"]
    parts = cfg["parameters_by_part"]
    assert parts["attention_with_out_projection"] == 21_233_664
    assert parts["one_expert"] == 6_193_152
    assert 4 * (parts["attention_with_out_projection"]
                + parts["two_norms_a_layer"] + parts["router"]
                + 16 * parts["one_expert"]) \
        + 2 * parts["embedding_or_head"] + parts["final_norm"] \
        == cfg["parameters"]
    per_token = flops_lm_swa.forward_flops_per_row(cfg["layers"], t) / t
    assert per_token == cfg["forward_flops_per_token"] == 497_691_648
    assert flops_lm_swa.train_flops_per_row(cfg["layers"], t) \
        == cfg["train_flops_per_row"]
    # a step of four rows: 48.9 TFLOP
    assert rows * cfg["train_flops_per_row"] == pytest.approx(48.9e12,
                                                              rel=1e-3)
    flat = ref.flatten(cfg["layers"])
    window, full = flat[2]["->"], flat[17]["->"]
    # a query counts min(n + 1, 1024) keys under a window
    assert flops_lm_swa.pairs(t, 1024) == sum(
        min(n + 1, 1024) for n in range(t))
    assert flops_lm_swa.pairs(t, None) == t * (t + 1) // 2
    assert flops_lm_swa.pairs(512, 1024) == 512 * 513 // 2
    assert flops_lm_swa.attention_core_flops(window, t) \
        == pytest.approx(0.129e12, rel=5e-3)
    assert flops_lm_swa.attention_core_flops(full, t) \
        == pytest.approx(0.550e12, rel=5e-3)
    floor = lambda windowed: flops_lm_swa.attention_floor_seconds(  # noqa
        cfg["layers"], t, rows, 197e12, 819e9, windowed)
    assert floor(True) == pytest.approx(0.0235, rel=5e-3)    # MXU-bound
    assert floor(False) == pytest.approx(0.0335, rel=5e-3)
    # the accepted reader's floor of the experts counts this layer
    # right with no shared expert: router + 2 held experts a token; its
    # bytes count one gate vector (2 304 parameters) that is not there
    moe = flat[5]["->"]
    assert flops_lm.moe_flops(moe, 2304, t) \
        == t * 2304 * (2 * 64 + 6 * 896 * 2)
    assert flops_lm.moe_weights(moe, 2304) \
        == 2304 * 64 + 16 * 6_193_152 + 2304
    assert flops_lm.moe_floor_seconds(
        cfg["layers"], t, rows, 197e12, 819e9) == pytest.approx(
            4 * rows * 3 * flops_lm.moe_flops(moe, 2304, t) / 197e12)
    # flops_lm itself reads nothing of this configuration's attention
    with pytest.raises(ValueError, match="unknown layer type"):
        flops_lm.forward_flops_per_row(cfg["layers"], t)
    assert flops_lm.attention_floor_seconds(
        cfg["layers"], t, rows, 197e12, 819e9) == 0.0


def test_scopes_of_the_two_cores_are_told_apart():
    find = lambda p: [m for m, rx in  # noqa: E731
                      train_resident_lm_swa.MECHANISMS.items()
                      if rx.search(p)]
    assert find("jit(train_step)/while/body/fwd/fwd2_attention/"
                "jvp(attn/window)/splash_mqa_fwd") == ["window_attention"]
    assert find("x/bwd/fwd17_attention/recompute/jvp(attn/core)/"
                "splash_mqa_fwd") == ["attention"]
    assert find("x/bwd/fwd7_attention/transpose(jvp(attn/window))/"
                "splash_mqa_dkv") == ["window_attention"]
    assert find("x/bwd/fwd5_moe/transpose(jvp(moe/experts))/gmm") == [
        "moe"]
    assert find("x/loss/block/dot_general") == ["loss"]
    assert find("x/fwd/fwd3_dense/dot_general") == []
    # the readers: each reads its own scope's seconds
    ctx = {"scopes": {"busy_s": 2.0, "window_attention_s": 0.1,
                      "attention_s": 0.2, "moe_s": 1.0}}
    assert run.metric_reader("window_attention.busy_pct")(ctx) == 5.0
    assert run.metric_reader("full_attention.busy_pct")(ctx) == 10.0
    assert run.metric_reader("window_attention.busy_pct")(
        {"scopes": {"busy_s": 2.0, "attention_s": 0.2}}) is None
    cfg = run.load_json("configs", "mellum2.json")
    ctx.update(traced={"images": 8.0}, mix={"minibatch": 4}, chips=1,
               cfg=cfg, seq_len=8192, reduced={"window_s": 4.0},
               peaks={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    assert run.metric_reader("window_attention_roofline")(ctx) \
        == pytest.approx(100 * 2 * 0.0235 / 0.1, rel=5e-3)
    # the ONE full layer's floor: 33.5 ms a step of four rows
    assert run.metric_reader("full_attention_swa_roofline")(ctx) \
        == pytest.approx(100 * 2 * 0.0335 / 0.2, rel=5e-3)
    assert run.metric_reader("lm_swa.step_mfu_pct")(ctx) \
        == pytest.approx(100 * 2 * 48.9e12 / (4.0 * 197e12), rel=1e-3)
    assert run.metric_reader("moe_roofline")(ctx) > 0
    for name in ("window_attention_roofline", "lm_swa.step_mfu_pct",
                 "full_attention_swa_roofline"):
        assert run.metric_reader(name)(
            {"traced": {}, "scopes": None, "reduced": {}}) is None


def _run(seed, sabotage=None, trace=0):
    from veles_tpu.backends import make_device
    return run.run_cell(tiny_mellum2.MIX, tiny_mellum2.CFG, seed, 0.3,
                        trace, device=make_device("cpu"),
                        t_start=time.time(), device_info=CPU_INFO,
                        sabotage=sabotage)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_sound_run_is_correct(seed):
    from veles_tpu import telemetry
    telemetry.reset()
    r = _run(seed)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"setup_s", "train_images_per_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    journal = r["run"]["journal"]
    assert [(e["form"], e["window"], e["rope"])
            for e in journal["attn.path"]] == [
        ("xla", 32, "default")] * 3 + [("xla", None, "yarn")]
    assert journal["attn.window_layers"] == 3
    # probed a minibatch at a time: one share a layer, never a second
    assert [(e["rows"], e["shared"]) for e in journal["moe.share"]] \
        == [(2 * 128 * 2, False)] * 4
    assert [e["dropped"] for e in journal["moe.load"]] == [0] * 4
    assert not journal["gdn.path"]
    # f32 against f32: the routers agree on every token's experts
    differs = r["run"]["detail"]["routing_differs_share"]
    assert len(differs) == 4 and max(differs.values()) < 0.01


def test_traced_run_reports_what_the_cpu_can():
    r = _run(5, trace=1)
    assert r["correct"], r["compared"]
    # no TPU plane on the CPU: the trace readers return nothing
    assert set(r["metrics"]) == {
        "loader.run_ms", "fused.dispatch_ms",
        "fused.compiles_in_window", "decision.epoch_end_ms"}
    assert r["run"]["traced_firings"] >= tiny_mellum2.MIX["trace_firings"]


def test_the_cell_fails_at_once_on_a_program_without_the_layer_type(
        monkeypatch):
    from veles_tpu.ops import registry
    monkeypatch.delitem(registry.forward_registry, "attention")
    t0 = time.time()
    with pytest.raises(RuntimeError,
                       match=r"no layer types \['attention'\]"):
        _run(3)
    assert time.time() - t0 < 5.0


def _fed(seed=7):
    cfg, mix = tiny_mellum2.CFG, tiny_mellum2.MIX
    rows = np.asarray(seeded_tokens.dataset(
        seed, mix["n_train"], mix["seq_len"], **cfg["dataset"]["->"]))
    return rows[np.array([[2, 5], [0, 7]])]


@pytest.mark.parametrize("side", ("bf16",) + ref.FAULTS
                         + ("state_unchanged",))
def test_control_and_faults_fail_the_limits(side):
    """The reference in the next precision down (bf16 under the tiny
    cell's f32) or with a fault planted, in the program's place."""
    cfg, mix = tiny_mellum2.CFG, tiny_mellum2.MIX
    make = lambda: ref.init_params(7, cfg["layers"],  # noqa: E731
                                   cfg["init_std"])
    want = ref.follow(cfg["layers"], make(), _fed(), seq_block=32)
    kw = {"precision": side} if side == "bf16" else {"fault": side}
    other = ref.follow(cfg["layers"], make(), _fed(), seq_block=32, **kw)
    ok, compared = check.judge(check.gaps(other, want), mix["limits"])
    assert not ok, compared


def test_reference_in_blocks_of_positions_agrees_with_itself():
    cfg = tiny_mellum2.CFG
    make = lambda: ref.init_params(9, cfg["layers"],  # noqa: E731
                                   cfg["init_std"])
    a = ref.follow(cfg["layers"], make(), _fed(9))
    b = ref.follow(cfg["layers"], make(), _fed(9), seq_block=32)
    g = check.gaps(b, a)
    assert max(g[n] for n in check.NAMES) < 1e-5, g
    for i in a["choices0"]:
        np.testing.assert_array_equal(a["choices0"][i], b["choices0"][i])
