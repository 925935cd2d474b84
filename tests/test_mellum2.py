"""ISSUE 34: plain grouped-query attention under a window of keys and
without one, each with a RoPE law of its own (YaRN on the full
layers), over the ONE core ``gated_attention`` runs too, and a mixture
of experts with no shared expert — Mellum2 at the tiny preset, on
XLA:CPU in f32, against the plain reference the benchmark also uses
(``benchmarks/lib/reference_mellum2.py``: no ``veles_tpu`` import,
matmul precision "highest", the mask written out from its definition,
the YaRN frequencies from their formulas, a masked loop over the held
experts)."""

import hashlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.lib import reference_mellum2 as ref  # noqa: E402
from veles_tpu import events, prng, telemetry  # noqa: E402
from veles_tpu.backends import make_device  # noqa: E402
from veles_tpu.engine import core as engine_core  # noqa: E402
from veles_tpu.loader import ArrayLoader  # noqa: E402
from veles_tpu.loader.synthetic import PackedTokensLoader  # noqa: E402
from veles_tpu.models import evabyte, qwen3next  # noqa: E402
from veles_tpu.models.mellum2 import (  # noqa: E402
    CUT, PUBLISHED, TINY, layer_types, mellum2_layers)
from veles_tpu.ops import attention, moe  # noqa: E402
from veles_tpu.ops import sequence as seq  # noqa: E402
from veles_tpu.ops.fused import FusedStepRunner  # noqa: E402
from veles_tpu.ops.registry import forward_registry  # noqa: E402
from veles_tpu.ops.standard_workflow import StandardWorkflow  # noqa: E402

T, ROWS = TINY["seq_len"], 2
HIDDEN = TINY["hidden_size"]
LAYERS = mellum2_layers(**TINY)
FLAT = ref.flatten(LAYERS)
WINDOW, FULL = 2, 17          # flat indices: a window layer, the full one
YARN = PUBLISHED["rope_parameters"]["full_attention"]


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=2e-5):
    scale = max(1.0, float(np.abs(np.asarray(want)).max()))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


def _unit(i, rows=ROWS, **over):
    """(unit, its config, seeded parameters, an input) of flat layer i
    of the tiny model (``over``: other sizes)."""
    cfg = dict(FLAT[i], **{"->": dict(FLAT[i]["->"], **over)})
    kind = cfg["type"]
    fw = {k: v for k, v in cfg["->"].items() if k != "weights_stddev"}
    unit = forward_registry[kind][0](None, name=f"u{i}_{kind}", **fw)
    shapes = ref.param_shapes(
        [{"type": "embedding", "->": {"vocab_size": 8,
                                      "hidden_size": HIDDEN}}, cfg])[1]
    params = {n: ref.init_leaf(17, i, n, s, 0.2)
              for n, s in shapes.items()}
    x = jax.random.normal(jax.random.key(i), (rows, T, HIDDEN),
                          jnp.float32)
    return unit, cfg, params, x


MOE = next(i for i, c in enumerate(FLAT) if c["type"] == "moe")


# -- the layer types against the reference ----------------------------------

@pytest.mark.parametrize("i", [WINDOW, FULL, MOE],
                         ids=["window", "full", "moe_no_shared"])
def test_layer_forward_and_backward_match_the_reference(i):
    unit, cfg, params, x = _unit(i)
    kind = cfg["type"]
    assert unit.param_shapes(x.shape) == {
        k: tuple(v.shape) for k, v in params.items()}
    assert set(unit.param_names) == set(params)
    want = ref.layer_forward(cfg, params, x)
    got, back = unit.apply_fwd(params, x, train=True)
    assert got.shape == unit.output_shape_for(x.shape)
    _close(got, want)
    err = jax.random.normal(jax.random.key(99), want.shape, jnp.float32)
    loss = lambda p, xx: jnp.sum(  # noqa: E731
        ref.layer_forward(cfg, p, xx) * err)
    gd = forward_registry[kind][1](None, forward=unit, name="gd")
    err_in, grads = gd.backward_from_saved(params, back, err)
    want_p, want_x = jax.grad(loss, argnums=(0, 1))(params, x)
    _close(err_in, want_x)
    assert set(grads) == set(params)
    for name in params:
        _close(grads[name], want_p[name])


def test_the_layers_list_is_three_window_layers_and_a_full_one():
    assert layer_types(8, 4) == (["sliding_attention"] * 3
                                 + ["full_attention"]) * 2
    kinds = [[c["type"] for c in e.get("layers", [e])] for e in LAYERS]
    assert [k for k in kinds if len(k) == 3] == [
        ["rmsnorm", "attention", "dense"]] * 4
    assert [k for k in kinds if len(k) == 2] == [["rmsnorm", "moe"]] * 4
    assert kinds[0] == ["embedding"] and kinds[-1] == ["lm_head"]
    full = ref.flatten(mellum2_layers())
    att = [c["->"] for c in full if c["type"] == "attention"]
    assert [a["window"] for a in att] == [1024, 1024, 1024, None]
    assert [a["rope"]["rope_type"] for a in att] == ["default"] * 3 \
        + ["yarn"]
    assert all((a["n_heads"], a["n_kv_heads"], a["head_size"])
               == (32, 4, 128) for a in att)
    assert all(c["->"]["shared_size"] == 0 for c in full
               if c["type"] == "moe")
    assert ref.param_count(mellum2_layers()) == 595_153_152
    # whole: 28 layers, every expert, every vocabulary row
    assert ref.param_count(mellum2_layers(28, 64, 0, 98304)) \
        == 28 * 417_747_456 + 2 * 98304 * 2304 + 2304


def test_program_flops_of_the_units_match_the_issue():
    """``profiling.py``'s count at the published sizes: a window
    layer's core 0.129 TFLOP a row of 8 192, the full one's 0.550; the
    experts by the expected 2 held a token."""
    layers = ref.flatten(mellum2_layers())
    t = CUT["seq_len"]

    def unit_of(i):
        fw = {k: v for k, v in layers[i]["->"].items()
              if k != "weights_stddev"}
        unit = forward_registry[layers[i]["type"]][0](
            None, name="u", **fw)
        unit.input.mem = np.zeros((1, t, PUBLISHED["hidden_size"]),
                                  np.float32)
        return unit

    proj = 2.0 * t * 2304 * (4096 + 2 * 512)
    window, full = unit_of(WINDOW), unit_of(FULL)
    assert window.core_flops(t) == pytest.approx(0.129e12, rel=5e-3)
    assert full.core_flops(t) == pytest.approx(0.550e12, rel=5e-3)
    assert window.mxu_flops_per_sample() == proj + window.core_flops(t)
    assert full.mxu_flops_per_sample() == proj + full.core_flops(t)
    assert unit_of(MOE).mxu_flops_per_sample() / t == \
        2 * 2304 * 64 + 6 * 2304 * 896 * 2


# -- the window ----------------------------------------------------------------

def _qkv(t, nkv=1, group=2, d=8, rows=1, seed=1):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (rows, t, nkv, group, d)),
            jax.random.normal(ks[1], (rows, t, nkv, d)),
            jax.random.normal(ks[2], (rows, t, nkv, d)))


def test_a_key_1024_back_is_not_read_and_one_1023_back_is():
    t, w, n = 1280, 1024, 1200
    q, k, v = _qkv(t)
    base = attention.core_xla(q, k, v, window=w)
    bump = jnp.ones_like(v[:, 0])
    out = attention.core_xla(q, k, v.at[:, n - w].add(bump), window=w)
    np.testing.assert_array_equal(out[:, n], base[:, n])
    # ... but the query before it still reads that key
    assert float(jnp.abs(out[:, n - 1] - base[:, n - 1]).max()) > 1e-6
    inside = attention.core_xla(q, k, v.at[:, n - w + 1].add(bump),
                                window=w)
    assert float(jnp.abs(inside[:, n] - base[:, n]).max()) > 1e-6
    # the first 1024 queries have their whole past inside the window
    full = attention.core_xla(q, k, v)
    _close(base[:, :w], full[:, :w], tol=1e-6)
    assert float(jnp.abs(base[:, w:] - full[:, w:]).max()) > 1e-4
    # the reference's mask, from its definition
    ok = np.asarray(ref.allowed(jnp.arange(t), jnp.arange(t), w))
    assert ok[n, n] and ok[n, n - w + 1] and not ok[n, n - w] \
        and not ok[n, n + 1]
    assert (ok.sum(1) == np.minimum(np.arange(t) + 1, w)).all()


@pytest.mark.parametrize("window", [None, 32, 48, 100, 1000])
def test_xla_core_in_blocks_equals_one_block_under_a_window(window):
    q, k, v = _qkv(T, nkv=2, d=16, rows=ROWS)
    whole = attention.core_xla(q, k, v, block=T, window=window)
    _close(attention.core_xla(q, k, v, block=32, window=window), whole)
    _close(whole, ref.masked_attention(q * 4.0, k, v, window))
    # and its gradient: a block reads keys from its window's left edge
    err = jax.random.normal(jax.random.key(8), whole.shape)
    g_blocks = jax.grad(lambda *a: jnp.sum(attention.core_xla(
        *a, block=32, window=window) * err), argnums=(0, 1, 2))(q, k, v)
    g_whole = jax.grad(lambda *a: jnp.sum(ref.masked_attention(
        a[0] * 4.0, a[1], a[2], window) * err), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_blocks, g_whole):
        _close(a, b)


def test_the_kernels_block_list_leaves_out_blocks_left_of_the_window():
    """The cell's window of 1024 keys takes the band kernels; a window
    their blocks do not tile (1000 keys) keeps the shipped kernel, whose
    mask tables, at the cell's row, are checked here (made on the host;
    no chip): a query block visits 3 key blocks of 512 under that
    window, 16 without one, forward and in both backward kernels."""
    t, block, w = 8192, 512, 1000
    band = attention.attention_path("tpu", 128, t, window=1024, group=8)
    assert (band["form"], band["tiles"], band["visited"]) == (
        "band", {"block_q": 256, "slab": 1280}, 1.25)
    path = attention.attention_path("tpu", 128, t, window=w, group=8)
    assert (path["form"], path["window"], path["kv_blocks"]) == (
        "splash", w, 3)
    assert path["tiles"] == {"block_q": block, "block_kv": block}
    full = attention.attention_path("tpu", 128, t)
    assert (full["window"], full["kv_blocks"]) == (None, t // block)
    assert attention.attention_path("cpu", 128, t, window=w) == {
        "form": "xla", "reason": "platform", "window": w}
    kernel = attention._splash_kernel(t, 8, block, w)
    assert kernel is attention._splash_kernel(t, 8, block, w)
    assert kernel is not attention._splash_kernel(t, 8, block)
    fwd = np.asarray(kernel.fwd_mask_info.block_mask)
    # the grid is as wide as the window's blocks, not as the row's
    assert fwd.shape[-2:] == (t // block, path["kv_blocks"])
    nxt = np.asarray(kernel.fwd_mask_info.data_next)
    for i in range(t // block):
        visited = sorted(set(nxt[0, i][fwd[0, i] != 0].tolist()))
        want = list(range(attention.first_key(i * block, w) // block,
                          i + 1))
        assert visited == want, (i, visited)
    for info in (kernel.dq_mask_info, kernel.dkv_mask_info):
        live = np.asarray(info.block_mask) != 0
        assert live.sum() == 1 + 2 + 3 * (t // block - 2)
    causal = attention._splash_kernel(t, 8, block)
    assert (np.asarray(causal.fwd_mask_info.block_mask) != 0).sum() \
        == (t // block) * (t // block + 1) // 2


@pytest.mark.parametrize("i", [WINDOW, FULL], ids=["window", "full"])
def test_attention_is_causal_and_maps_queries_to_their_key_head(i):
    unit, _, params, x = _unit(i)
    n = 70
    y0 = unit.forward(params, x)
    y1 = unit.forward(params, x.at[:, n].add(1.0))
    np.testing.assert_array_equal(y0[:, :n], y1[:, :n])
    assert float(jnp.abs(y0[:, n:] - y1[:, n:]).max()) > 1e-4
    # 4 query heads over 2 key heads: key head 1 serves queries 2, 3
    d = unit.head_size
    wk = params["wk"].at[:, d:].add(0.1)
    y2 = unit.forward(dict(params, wk=wk), x).reshape(ROWS, T, 4, d)
    y0 = y0.reshape(ROWS, T, 4, d)
    np.testing.assert_array_equal(y0[:, :, :2], y2[:, :, :2])
    assert float(jnp.abs(y0[:, :, 2:] - y2[:, :, 2:]).max()) > 1e-4


# -- RoPE -------------------------------------------------------------------------

def test_yarn_frequencies_equal_the_written_formulas():
    d = 128
    inv, scale = seq.rope_frequencies(YARN, d)
    assert scale == 1.2772588722239782 == 0.1 * math.log(16) + 1.0
    e = [5e5 ** (-2.0 * j / d) for j in range(d // 2)]
    dim = lambda r: d * math.log(8192 / (2 * math.pi * r)) / (  # noqa
        2 * math.log(5e5))
    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), 127)
    assert (low, high) == (18, 35)
    for j in range(d // 2):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        want = e[j] / 16 * ramp + e[j] * (1 - ramp)
        assert inv[j] == pytest.approx(want, rel=1e-6), j
    # fast dimensions keep their frequency, slow ones are stretched 16x
    np.testing.assert_allclose(inv[:19], e[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], np.asarray(e[35:]) / 16,
                               rtol=1e-6)
    theirs, a = ref.rope_law(YARN, d)
    np.testing.assert_allclose(inv, theirs, rtol=1e-6)
    assert a == scale
    # the specification without a scale of its own: 0.1 ln(factor) + 1
    bare = {k: v for k, v in YARN.items() if k != "attention_factor"}
    assert seq.rope_frequencies(bare, d)[1] == pytest.approx(scale)
    with pytest.raises(ValueError, match="rope_type"):
        seq.rope_frequencies({"rope_type": "ntk", "rope_theta": 1.0}, d)


def test_yarn_at_factor_one_and_the_default_law_are_the_old_rope():
    d = 128
    plain, one = seq.rope_frequencies(
        {"rope_type": "default", "rope_theta": 5e5}, d)
    same, scale = seq.rope_frequencies(
        dict(YARN, factor=1, attention_factor=None), d)
    np.testing.assert_allclose(same, plain, rtol=1e-7)
    assert (one, scale) == (1.0, 1.0)
    x = jax.random.normal(jax.random.key(2), (ROWS, T, 3, d))
    _close(seq.rope(x, inv_freq=plain), seq.rope(x, 5e5), tol=1e-6)
    # the scale is on cos and sin alike: on the rotated vector
    inv, a = seq.rope_frequencies(YARN, d)
    _close(seq.rope(x, inv_freq=inv, scale=a),
           a * seq.rope(x, inv_freq=inv), tol=1e-6)
    _close(seq.rope(x, inv_freq=inv, scale=a), ref.rotate(x, inv, a))
    assert float(jnp.abs(seq.rope(x, inv_freq=inv)[:, 5:]
                         - seq.rope(x, 5e5)[:, 5:]).max()) > 1e-3


def test_each_layer_type_rotates_by_its_own_law():
    window, _, _, _ = _unit(WINDOW)
    full, _, _, _ = _unit(FULL)
    assert (window.rope_kind, window.rope_scale) == ("default", 1.0)
    assert (full.rope_kind, full.rope_scale) == (
        "yarn", 1.2772588722239782)
    assert window.window == TINY["sliding_window"] and full.window is None
    assert not np.allclose(window.inv_freq, full.inv_freq)


# -- the journal --------------------------------------------------------------------

def test_attention_path_and_share_are_journaled_with_the_new_fields():
    from types import SimpleNamespace
    telemetry.reset()
    unit, _, params, x = _unit(WINDOW, head_size=128, window=64)
    unit.forward(params, x)
    assert unit.path == {"form": "xla", "reason": "platform",
                         "window": 64}
    unit.device = SimpleNamespace(platform="tpu")
    assert unit._path(1024) == {
        "form": "splash", "window": 64, "kv_blocks": 2,
        "tiles": {"block_q": 512, "block_kv": 512}}
    assert unit._path(1024, batched=True)["reason"] == "batched"
    seen = telemetry.recent_events(events.EV_ATTN_PATH)[-3:]
    assert [(e["form"], e["window"], e["rope"]) for e in seen] == [
        ("xla", 64, "default"), ("splash", 64, "default"),
        ("xla", 64, "default")]
    assert seen[1]["kv_blocks"] == 2 and "kv_blocks" not in seen[0]
    # a window the band kernels' blocks tile takes them
    band, _, _, _ = _unit(WINDOW, head_size=128, window=256)
    band.device = SimpleNamespace(platform="tpu")
    assert band._path(1024) == {
        "form": "band", "window": 256, "visited": 2.0,
        "tiles": {"block_q": 256, "slab": 512}}
    last = telemetry.recent_events(events.EV_ATTN_PATH)[-1]
    assert (last["form"], last["visited"], last["rope"]) == (
        "band", 2.0, "default") and "kv_blocks" not in last
    assert telemetry.gauge(events.GAUGE_ATTN_BAND_LAYERS).value == 1
    assert band._path(1024, batched=True)["form"] == "xla"
    assert telemetry.gauge(events.GAUGE_ATTN_BAND_LAYERS).value == 0
    gated = attention.GatedAttention(None, name="g")
    gated.device = SimpleNamespace(platform="tpu")
    gated.head_size = 128
    assert gated._path(1024)["kv_blocks"] == 2
    last = telemetry.recent_events(events.EV_ATTN_PATH)[-1]
    assert (last["window"], last["rope"]) == (None, "default")
    m, _, params, x = _unit(MOE)
    m.forward(params, x)
    share = telemetry.recent_events(events.EV_MOE_SHARE)[-1]
    assert share["shared"] is False and share["experts_held"] == 2
    old = moe.MoE(None, name="m")
    assert "s_mix" in old.param_names and "s_mix" not in m.param_names
    assert old._share(64, 64)[0]["shared"] is True


def test_moe_without_a_shared_expert_traces_no_shared_op():
    unit, _, params, x = _unit(MOE)
    text = jax.make_jaxpr(unit.forward)(params, x).pretty_print(
        name_stack=True)
    assert "moe/experts" in text and "moe/shared" not in text
    assert not hasattr(unit, "s_gate")
    shared = moe.MoE(None, name="m", experts_total=8, experts_held=2,
                     shared_size=32)
    assert set(shared.param_shapes((1, T, HIDDEN))) \
        - set(unit.param_shapes((1, T, HIDDEN))) == set(moe.MoE.SHARED)


# -- the mixture of experts ------------------------------------------------------------

def test_the_four_shares_add_up():
    """The routed parts of the four shares (experts 0-1, 2-3, 4-5, 6-7
    of 8: the tiny model's 0-15 ... 48-63 of 64) sum to the uncut
    reference's layer; nothing is counted twice — there is no shared
    expert."""
    _, cfg, params, x = _unit(MOE, experts_held=8)
    fw = cfg["->"]
    whole = ref.moe(x, params, fw, held=(0, 8))
    parts = []
    for first in (0, 2, 4, 6):
        unit, _, _, _ = _unit(MOE, experts_held=2, first_held=first)
        mine = dict(params, **{n: params[n][first:first + 2]
                               for n in ("w_gate", "w_up", "w_down")})
        parts.append(unit.forward(mine, x))
        _close(parts[-1], ref.moe(x, mine, dict(
            fw, experts_held=2, first_held=first)))
        assert float(jnp.abs(parts[-1]).max()) > 1e-3
    _close(sum(parts), whole)


def test_a_router_that_sends_every_token_here_drops_nothing_at_4_rows():
    unit, cfg, params, x = _unit(MOE, rows=4)
    held = slice(unit.first_held, unit.first_held + unit.experts_held)
    x = jnp.abs(x)
    params = dict(params, router=params["router"].at[:, held].add(0.5))
    load = unit.report_probe(jax.device_get(unit.probe(params, x)))
    assert load["local_assignments"] == 4 * T * unit.top_k
    assert load["dropped"] == 0
    assert unit.share["rows"] == 4 * T * unit.top_k
    assert telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).value == 0
    want, back = jax.vjp(lambda p, xx: ref.layer_forward(cfg, p, xx),
                         params, x)
    y, mine = jax.vjp(unit.forward, params, x)
    _close(y, want)
    for a, b in zip(jax.tree.leaves(mine(jnp.ones_like(want))),
                    jax.tree.leaves(back(jnp.ones_like(want)))):
        _close(a, b)


def test_the_cells_dispatch_block_and_load_are_sized_from_shapes():
    """Four rows of 8 192: blocks of 4 096 tokens, 32 768 buffer rows
    each, 8 blocks; a held expert expects 4 096 rows a step."""
    tokens = CUT["minibatch"] * CUT["seq_len"]
    block = moe.block_tokens(tokens, 8, 2304)
    assert (block, block * 8, tokens // block) == (4096, 32768, 8)
    assert tokens * 8 // 64 == 4096
    # every call of the grouped products takes tiles that pad nothing,
    # here: 1 152 on the 2 304 side, 896 on the 896 side ...
    path = moe.grouped_path("tpu", block * 8, 2304, 896)
    assert path == {"form": "gmm", "tiles": moe.GroupedTiles(2)}
    got = moe.call_tiles(path["tiles"], 12288, 2304, 896, moe.FOLD_TOKENS)
    assert got["tiles"]["in"]["forward"] == (512, 1152, 896)
    assert got["tiles"]["out"]["forward"] == (512, 896, 1152)
    assert got["tiles"]["fold"] == (512, 512, 1152)
    assert {p for c in ("in", "out") for p in got["padding"][c].values()} \
        == {1.0} and got["padding"]["fold"] == 1.0
    # ... and for the other configuration, whose forward keeps its tiles
    tiles = moe.grouped_path("tpu", 40960, 2048, 512)["tiles"]
    assert tiles(4096, 2048, 512) == (512, 1024, 512)
    assert tiles(4096, 512, 2048) == (512, 512, 1024)


# -- the whole model through StandardWorkflow -----------------------------------------------

def _workflow(rows, superstep=2, mb=ROWS, layers=LAYERS, epochs=1):
    prng.seed_all(11)
    return StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=mb, name="loader"),
        layers=layers, loss_function="next_byte",
        decision_config={"max_epochs": epochs}, superstep=superstep,
        name="mellum2_tiny")


def _rows(n=4, t=T, vocab=TINY["vocab_held"]):
    return np.asarray(jax.random.randint(
        jax.random.key(5), (n, t), 0, vocab), np.int32)


def _run_two_steps(monkeypatch, limit=None, recompute=None):
    """2 SGD steps (one firing of superstep 2) of 2 rows each from
    seeded weights; returns (workflow, w0, the rows of each step)."""
    if limit is not None:
        monkeypatch.setattr(FusedStepRunner, "_device_bytes_limit",
                            lambda self: limit)
    if recompute is not None:
        monkeypatch.setattr(FusedStepRunner, "_decide_recompute",
                            lambda self, cd: recompute)
    rows = _rows()
    w = _workflow(rows)
    w.initialize(device=make_device("cpu"))
    w0 = ref.init_params(3, LAYERS, 0.05)
    for f, p in zip(w.forwards, w0):
        assert set(f.param_vectors()) == set(p), f.name
        for name, vec in f.param_vectors().items():
            vec.mem = np.asarray(p[name])
    w.loader.run()
    idx = np.array(w.loader.superstep_indices, copy=True)
    w.fused.run()
    return w, w0, rows[idx]


def _norms(tree):
    return {f"{i}.{k}": float(jnp.sqrt(jnp.sum(jnp.square(v))))
            for i, d in enumerate(tree) for k, v in d.items()}


def test_two_steps_through_standardworkflow_match_the_reference(
        monkeypatch):
    telemetry.reset()
    w, w0, fed = _run_two_steps(monkeypatch)
    assert fed.shape == (2, ROWS, T)
    chain = engine_core.chain_of(w.forwards)
    assert sum(isinstance(e, tuple) for e in chain) \
        == 2 * TINY["n_layers"]
    want = ref.follow(LAYERS, ref.init_params(3, LAYERS, 0.05), fed)
    _, loss_sum, count, _ = w.fused.take_class_metrics()
    assert count == 2 * ref.valid_count(ROWS, T)
    assert loss_sum == pytest.approx(want["loss_sum"], rel=1e-5)
    params = [w.fused._params[f.name] for f in w.forwards]
    opt = [w.fused._opt[g.name] for g in w.gds]
    update = _norms([{k: p[k] - z[k] for k in p}
                     for p, z in zip(params, w0)])
    momentum = _norms(opt)
    assert set(update) == set(want["update"])
    floor = 1e-3 * np.median(list(want["grad0"].values()))
    for key in want["update"]:
        if want["grad0"][key] < floor:   # moves by round-off alone
            continue
        assert update[key] == pytest.approx(want["update"][key],
                                            rel=5e-4, abs=1e-9), key
        assert momentum[key] == pytest.approx(want["momentum"][key],
                                              rel=5e-4, abs=1e-9), key
    # what the units journaled about themselves at ``initialize``...
    ev = telemetry.recent_events(events.EV_ATTN_PATH)
    assert [(e["form"], e["window"], e["rope"]) for e in ev] == [
        ("xla", 32, "default")] * 3 + [("xla", None, "yarn")]
    assert telemetry.gauge(events.GAUGE_ATTN_WINDOW_LAYERS).value == 3
    shares = telemetry.recent_events(events.EV_MOE_SHARE)
    assert len(shares) == 4 and all(
        (e["experts_total"], e["experts_held"], e["top_k"], e["rows"],
         e["shared"]) == (8, 2, 2, ROWS * T * 2, False) for e in shares)
    # ... and after the first firing: every layer's load, nothing dropped
    loads = telemetry.recent_events(events.EV_MOE_LOAD)
    assert [e["unit"] for e in loads] == [
        f.name for f in w.forwards if isinstance(f, moe.MoE)]
    assert all(e["dropped"] == 0 for e in loads)
    assert telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).value == 0
    # the step's scopes tell the window layers' core from the full one's
    fused = w.fused
    acc, conf = fused._fresh_acc()
    idx, mask = fused._superstep_arrays()
    text = jax.make_jaxpr(fused._train_step)(
        fused._params, fused._opt, acc, conf,
        w.loader.original_data.unmap(), fused._target_store(), idx,
        mask, fused._lr_rates_array(idx.shape[0]), fused._rng_counter
    ).pretty_print(name_stack=True)
    assert re.search(r"fwd2_attention/\S*attn/window", text)
    assert re.search(r"fwd17_attention/\S*attn/core", text)
    assert not re.search(r"fwd17_attention/\S*attn/window", text)
    assert not re.search(r"fwd2_attention/\S*attn/core", text)
    assert "moe/shared" not in text and "moe/dispatch" in text
    # the reference reports what every token chose in every moe layer
    assert sorted(want["choices0"]) == [
        i for i, c in enumerate(FLAT) if c["type"] == "moe"]
    got = w.fused.probe_units(jnp.asarray(fed[0]))
    for i, theirs in want["choices0"].items():
        mine = np.asarray(got[f"fwd{i}_moe"]["choice"])
        assert mine.shape == theirs.shape == (ROWS, T, 2)


def test_recomputation_on_and_off_give_the_same_step(monkeypatch):
    telemetry.reset()
    keep, _, _ = _run_two_steps(monkeypatch)
    ev = telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1]
    assert (ev["policy"], ev["reason"]) == ("keep", "no_limit")
    p_keep = jax.device_get(keep.fused._params)
    rec, _, _ = _run_two_steps(monkeypatch, limit=1 << 22)
    ev = telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1]
    assert (ev["policy"], ev["reason"]) == ("recompute",
                                            "kept_exceeds_free")
    assert ev["blocks"] == 2 * TINY["n_layers"]
    p_rec = jax.device_get(rec.fused._params)
    for layer in p_keep:
        for name in p_keep[layer]:
            np.testing.assert_allclose(p_rec[layer][name],
                                       p_keep[layer][name],
                                       rtol=1e-5, atol=1e-6)


def test_blocked_loss_at_two_rows_equals_the_unblocked(monkeypatch):
    telemetry.reset()
    whole, _, _ = _run_two_steps(monkeypatch, recompute=False)
    ev = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (ev["blocks"], ev["reason"]) == (0, "no_limit")
    acc_whole = np.asarray(whole.fused._acc)
    p_whole = jax.device_get(whole.fused._params)
    state = whole.fused._state_bytes(jnp.float32)
    logits = 4 * 4 * ROWS * T * TINY["vocab_held"]
    cut, _, _ = _run_two_steps(monkeypatch, limit=state + 2 * logits,
                               recompute=False)
    ev = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (ev["blocks"], ev["reason"], ev["bytes_whole"]) == (
        8, "whole_exceeds_free", logits)
    np.testing.assert_allclose(np.asarray(cut.fused._acc), acc_whole,
                               rtol=1e-6)
    p_cut = jax.device_get(cut.fused._params)
    for layer in p_whole:
        for name in p_whole[layer]:
            np.testing.assert_allclose(p_cut[layer][name],
                                       p_whole[layer][name],
                                       rtol=1e-5, atol=1e-6)


def test_the_real_sizes_block_the_loss_of_four_rows():
    """The decision's arithmetic at the cell's sizes on a v5e
    (16 909 336 064 B): 4 arrays of [4, 8192, 1, 24576] f32 are 12.9 GB
    beside 5.95 GB of state — 32 blocks of 256 positions, 0.4 GB each."""
    limit, state = 16909336064, 595153152 * 10
    whole = 16 * CUT["minibatch"] * CUT["seq_len"] * CUT["vocab_held"]
    free, t, n = limit - state, CUT["seq_len"], 2
    assert 4 * whole > free
    while 16 * whole > n * free and t % (2 * n) == 0:
        n *= 2
    assert (n, t // n, whole // n) == (32, 256, 402653184)


def test_model_file_trains_and_the_loss_falls():
    prng.seed_all(7)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedTokensLoader(
            wf, name="loader", n_train=8, seq_len=T, minibatch_size=4,
            vocab_size=TINY["vocab_held"], median_len=64),
        layers=LAYERS, loss_function="next_byte",
        decision_config={"max_epochs": 4}, superstep=2, name="tiny")
    w.initialize(device=make_device("cpu"))
    ids = np.asarray(w.loader.original_data.mem)
    assert ids.min() >= 0 and ids.max() == TINY["vocab_held"] - 1
    w.run()
    losses = [h["loss"] for h in w.decision.history]
    assert len(losses) == 4 and losses[-1] < losses[0]
    assert all(np.isfinite(losses))
    assert losses[0] == pytest.approx(np.log(TINY["vocab_held"]), rel=0.1)


# -- the configurations that share the changed code ------------------------------------------

#: sha256 of the tiny train step's jaxpr as the commit before the
#: ``mlp/swiglu`` scope traces it (addresses blanked, the padding before each name
#: stack collapsed, the ``mlp/swiglu`` scope the pinned step predates
#: taken out of the name stacks: :func:`_step_text`)
PARENT_STEP = {
    "qwen3next": "7779767606e3a0feea4642503dcb3ba8"
                 "be0cb141a2d6762bcf0ec449647edfcb",
    "evabyte": "29a2dece6b8b44029e6e8de750eb9cf5"
               "0e595748f69a5e9124659783dff949ea",
}


def _step_text(text: str) -> str:
    """A step's jaxpr as :data:`PARENT_STEP` hashes it: the same ops
    under the same name stacks, ``mlp/swiglu`` left out of them."""
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r"(transpose\()?jvp\(mlp/swiglu\)(\)?)(?:/([^\]]*))?\]",
                  lambda m: f"{m[1] or ''}jvp({m[3] or ''}){m[2]}]", text)
    return re.sub(r" +#", " #", text)


@pytest.mark.parametrize("name", sorted(PARENT_STEP))
def test_the_other_sequence_models_step_is_the_parents(name):
    """``rope``, the attention core, ``moe`` and ``swiglu`` changed
    under ``qwen3next`` and ``evabyte``: the step each traces is, op
    for op, the one it traced before."""
    layers, t, vocab = {
        "qwen3next": (qwen3next.qwen3next_layers(**qwen3next.TINY),
                      qwen3next.TINY["seq_len"],
                      qwen3next.TINY["vocab_held"]),
        "evabyte": (evabyte.evabyte_layers(**evabyte.TINY),
                    evabyte.TINY["seq_len"], 320)}[name]
    w = _workflow(_rows(4, t, vocab), layers=layers)
    w.initialize(device=make_device("cpu"))
    fused = w.fused
    w.loader.run()
    fused._ensure_params()
    acc, conf = fused._fresh_acc()
    idx, mask = fused._superstep_arrays()
    text = jax.make_jaxpr(fused._train_step)(
        fused._params, fused._opt, acc, conf,
        w.loader.original_data.unmap(), fused._target_store(), idx,
        mask, fused._lr_rates_array(idx.shape[0]),
        fused._rng_counter).pretty_print(name_stack=True)
    assert hashlib.sha256(_step_text(text).encode()).hexdigest() \
        == PARENT_STEP[name]
