"""Tests of the benchmark's language-model half, run by hand (not
tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_qwen3next.py -q

On the CPU at a tiny size: the configuration file against the model
file and the published keys, the counts, the seeded rows, a tiny
``train_resident_lm`` mix through ``run_cell``, and the comparison that
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
from benchmarks.lib import check, flops_lm               # noqa: E402
from benchmarks.lib import reference_qwen3next as ref    # noqa: E402
from benchmarks.lib import seeded_tokens                 # noqa: E402
from benchmarks.tests import tiny_lm                     # noqa: E402
from benchmarks.traffic import train_resident_lm         # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}
CELL = "qwen3next.train_packed32k"
#: the catalog's config (architectures.jsonl), every number of it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4,
    "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 512, "num_attention_heads": 16,
    "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
    "vocab_size": 151936}


def test_config_file_is_the_model_files_own_layers_at_the_cut():
    from veles_tpu.models.qwen3next import qwen3next_layers
    cfg = run.load_json("configs", "qwen3next.json")
    assert cfg["layers"] == json.loads(json.dumps(qwen3next_layers()))
    changed = set(cfg["reduced"]) - {"dataset_length"}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in changed:
            assert cfg[key] != value
            assert cfg["published_" + key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 32, 18992)
    # every width as published, the router as wide as published
    flat = ref.flatten(cfg["layers"])
    moe = next(c["->"] for c in flat if c["type"] == "moe")
    assert (moe["experts_total"], moe["top_k"], moe["expert_size"],
            moe["shared_size"], moe["experts_held"], moe["first_held"]) \
        == (512, 10, 512, 512, 32, 0)
    att = next(c["->"] for c in flat if c["type"] == "gated_attention")
    assert (att["n_heads"], att["n_kv_heads"], att["head_size"],
            att["rotary_size"], att["rope_theta"]) == (16, 2, 256, 64, 1e7)
    gdn = next(c["->"] for c in flat if c["type"] == "gated_delta_net")
    assert (gdn["n_key_heads"], gdn["n_value_heads"],
            gdn["key_head_size"], gdn["value_head_size"],
            gdn["conv_kernel"]) == (16, 32, 128, 128, 4)
    assert len(cfg["assumed"]) == 6 and len(cfg["departures"]) == 2
    for key in ("reduced_from", "deployment"):
        assert cfg[key]
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "qwen3next")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_counts_of_the_config_file():
    cfg = run.load_json("configs", "qwen3next.json")
    mix = run.load_json("workloads", CELL + ".json")
    t = mix["seq_len"]
    assert ref.param_count(cfg["layers"]) == cfg["parameters"] \
        == 625_667_136
    parts = cfg["parameters_by_part"]
    assert 3 * (parts["gated_delta_net_mixer_with_out_projection"]
                + 32 * parts["one_expert"] + parts["router"]
                + parts["shared_expert_and_its_gate"]
                + parts["two_norms_a_layer"]) \
        + parts["gated_attention_mixer_with_out_projection"] \
        + 32 * parts["one_expert"] + parts["router"] \
        + parts["shared_expert_and_its_gate"] \
        + parts["two_norms_a_layer"] + 2 * parts["embedding_or_head"] \
        + parts["final_norm"] == cfg["parameters"]
    per_token = flops_lm.forward_flops_per_row(cfg["layers"], t) / t
    assert per_token == cfg["forward_flops_per_token"] == 661_594_112
    assert flops_lm.train_flops_per_row(cfg["layers"], t) \
        == cfg["train_flops_per_row"]
    assert cfg["train_flops_per_row"] == pytest.approx(65.0e12, rel=1e-3)
    floors = [fn(cfg["layers"], t, 1, 197e12, 819e9) for fn in (
        flops_lm.gdn_floor_seconds, flops_lm.attention_floor_seconds,
        flops_lm.moe_floor_seconds)]
    assert 0.007 < floors[0] < 0.009     # HBM-bound: 3 x 2.6 ms
    assert 0.13 < floors[1] < 0.14       # MXU-bound: one layer, 134 ms
    assert 0.024 < floors[2] < 0.026     # MXU-bound: 4 x 6.1 ms
    # the whole step's floor the issue reckons: 0.33 s at 197 TFLOP/s
    assert cfg["train_flops_per_row"] / 197e12 == pytest.approx(0.33,
                                                                rel=0.01)


def test_rows_are_seeded_and_hold_separators():
    kw = dict(n_values=63, separator=63, median_len=48)
    a = np.asarray(seeded_tokens.dataset(2 ** 31 + 9, 4, 128, **kw))
    b = np.asarray(seeded_tokens.dataset(2 ** 31 + 9, 4, 128, **kw))
    c = np.asarray(seeded_tokens.dataset(2 ** 31 + 10, 4, 128, **kw))
    assert a.dtype == np.int32 and a.shape == (4, 128)
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    assert a.min() >= 0 and a.max() == 63 and (a == 63).sum() >= 2
    assert len(np.unique(a)) > 32


def test_scopes_of_the_mechanisms_are_told_apart():
    find = lambda p: [m for m, rx in  # noqa: E731
                      train_resident_lm.MECHANISMS.items() if rx.search(p)]
    assert find("jit(train_step)/while/body/fwd/fwd2_gated_delta_net/"
                "jvp(gdn/rule)/dot_general") == ["gdn"]
    assert find("x/bwd/fwd17_gated_attention/recompute/jvp(attn/core)/"
                "splash_mqa_fwd") == ["attention"]
    assert find("x/bwd/fwd5_moe/transpose(jvp(moe/experts))/gmm") == [
        "moe"]
    assert find("x/loss/block/dot_general") == ["loss"]
    assert find("x/fwd/fwd3_dense/dot_general") == []


def _run(seed, sabotage=None, trace=0):
    from veles_tpu.backends import make_device
    return run.run_cell(tiny_lm.MIX, tiny_lm.CFG, seed, 0.3, trace,
                        device=make_device("cpu"), t_start=time.time(),
                        device_info=CPU_INFO, sabotage=sabotage)


@pytest.mark.parametrize("seed", [3, 2 ** 31 + 77])
def test_sound_run_is_correct(seed):
    r = _run(seed)
    assert r["correct"], r["compared"]
    assert set(r["metrics"]) == {"setup_s", "train_images_per_s"}
    assert r["attempted"] > 0 and r["failed"] == 0
    # (the journal is the process's: a second run here adds to it)
    journal = r["run"]["journal"]
    assert [e["dropped"] for e in journal["moe.load"][-4:]] == [0] * 4
    assert len(journal["gdn.path"]) % 3 == 0 and journal["attn.path"]
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
    assert r["run"]["traced_firings"] >= tiny_lm.MIX["trace_firings"]


def test_the_cell_fails_at_once_on_a_program_without_the_layer_types(
        monkeypatch):
    from veles_tpu.ops import registry
    for kind in ("gated_delta_net", "gated_attention", "moe"):
        monkeypatch.delitem(registry.forward_registry, kind)
    t0 = time.time()
    with pytest.raises(RuntimeError, match="no layer types"):
        _run(3)
    assert time.time() - t0 < 5.0


@pytest.mark.parametrize("side", ("bf16",) + ref.FAULTS
                         + ("state_unchanged",))
def test_control_and_faults_fail_the_limits(side):
    """The reference in the next precision down (bf16 under the tiny
    cell's f32) or with a fault planted, in the program's place."""
    cfg, mix = tiny_lm.CFG, tiny_lm.MIX
    rows = np.asarray(seeded_tokens.dataset(
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
    cfg = tiny_lm.CFG
    fed = np.asarray(seeded_tokens.dataset(
        9, 2, 128, **cfg["dataset"]["->"]))[np.array([[0], [1]])]
    make = lambda: ref.init_params(9, cfg["layers"],  # noqa: E731
                                   cfg["init_std"])
    a = ref.follow(cfg["layers"], make(), fed)
    b = ref.follow(cfg["layers"], make(), fed, seq_block=32)
    g = check.gaps(b, a)
    assert max(g[n] for n in check.NAMES) < 1e-5, g
    for i in a["choices0"]:
        np.testing.assert_array_equal(a["choices0"][i], b["choices0"][i])
