"""ISSUE 32: Gated DeltaNet, gated grouped-query attention, a mixture of
experts of which the device holds a share, and the loss in blocks of
positions — Qwen3-Next at the tiny preset, on XLA:CPU in f32, against
the plain reference the benchmark also uses (``benchmarks/lib/
reference_qwen3next.py``: no ``veles_tpu`` import, matmul precision
"highest", the rule as the token-by-token recurrence, a plain softmax,
a masked loop over the experts)."""

import os
import sys
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.lib import reference_qwen3next as ref  # noqa: E402
from veles_tpu import events, prng, telemetry  # noqa: E402
from veles_tpu.backends import make_device  # noqa: E402
from veles_tpu.engine import core as engine_core  # noqa: E402
from veles_tpu.loader import ArrayLoader  # noqa: E402
from veles_tpu.loader.synthetic import PackedTokensLoader  # noqa: E402
from veles_tpu.models import evabyte  # noqa: E402
from veles_tpu.models.qwen3next import (  # noqa: E402
    CUT, PUBLISHED, TINY, qwen3next_layers)
from veles_tpu.ops import attention, deltanet, moe  # noqa: E402
from veles_tpu.ops import deltanet_pallas  # noqa: E402
from veles_tpu.ops import sequence as seq  # noqa: E402
from veles_tpu.ops.fused import FusedStepRunner  # noqa: E402
from veles_tpu.ops.registry import forward_registry  # noqa: E402
from veles_tpu.ops.standard_workflow import StandardWorkflow  # noqa: E402

T, ROWS = TINY["seq_len"], 2
HIDDEN = TINY["hidden_size"]
LAYERS = qwen3next_layers(**TINY)
FLAT = ref.flatten(LAYERS)
TYPES = ("gated_delta_net", "gated_attention", "moe")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=2e-5):
    scale = max(1.0, float(np.abs(np.asarray(want)).max()))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


def _unit(kind, **over):
    """(unit, its config, seeded parameters, an input) of the first
    layer of that type in the tiny model (``over``: other sizes)."""
    i = next(i for i, c in enumerate(FLAT) if c["type"] == kind)
    cfg = dict(FLAT[i], **{"->": dict(FLAT[i]["->"], **over)})
    fw = {k: v for k, v in cfg["->"].items() if k != "weights_stddev"}
    unit = forward_registry[kind][0](None, name=f"u_{kind}", **fw)
    shapes = ref.param_shapes(
        [{"type": "embedding", "->": {"vocab_size": 8,
                                      "hidden_size": HIDDEN}}, cfg])[1]
    params = {n: ref.init_leaf(17, i, n, s, 0.2)
              for n, s in shapes.items()}
    if kind == "gated_delta_net":
        # a slower decay than the published initialisation's, so that
        # the state carries over many tokens and chunks
        params["a_log"] = jnp.log(jnp.linspace(0.02, 0.5,
                                               shapes["a_log"][0]))
    x = jax.random.normal(jax.random.key(i), (ROWS, T, HIDDEN),
                          jnp.float32)
    return unit, cfg, params, x


# -- the three layer types against the reference --------------------------

@pytest.mark.parametrize("kind", TYPES)
def test_layer_forward_and_backward_match_the_reference(kind):
    unit, cfg, params, x = _unit(kind)
    assert unit.param_shapes(x.shape) == {
        k: tuple(v.shape) for k, v in params.items()}
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
    assert unit.mxu_flops_per_sample.__func__ is not \
        seq.SequenceUnit.mxu_flops_per_sample


def test_program_flops_of_the_three_units_match_the_issue():
    """``profiling.py``'s count at the published sizes, a token, the
    entry's ``dense`` out-projection added: DeltaNet 70.5 M, attention
    projections 54.5 M + 268.4 M of causal scores at 32 k, MoE 12.3 M
    at this chip's share."""
    layers = ref.flatten(qwen3next_layers())
    t = CUT["seq_len"]

    def flops(kind):
        cfg = next(c for c in layers if c["type"] == kind)
        fw = {k: v for k, v in cfg["->"].items()
              if k != "weights_stddev"}
        unit = forward_registry[kind][0](None, name="u", **fw)
        unit.input.mem = np.zeros((1, t, PUBLISHED["hidden_size"]),
                                  np.float32)
        return unit.mxu_flops_per_sample() / t

    dense = 2.0 * 4096 * 2048          # the entry's out-projection
    assert flops("gated_delta_net") + dense == pytest.approx(
        70.5e6, rel=2e-3)
    assert flops("gated_attention") + dense == pytest.approx(
        54.5e6 + 268.4e6, rel=2e-3)
    assert flops("moe") == pytest.approx(12.3e6, rel=5e-3)


# -- the gated delta rule ---------------------------------------------------

def _rule_inputs(t=T, hk=2, hv=4, dk=16, dv=16, seed=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(  # noqa: E731
        a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (ROWS, t, hk, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (ROWS, t, hk, dk)))
    v = jax.random.normal(ks[2], (ROWS, t, hv, dv))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (ROWS, t, hv))) * 0.3
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (ROWS, t, hv)))
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk", [4, 16, 64, 128])
def test_chunked_rule_equals_the_token_recurrence(chunk):
    args = _rule_inputs()
    want = ref.delta_rule(*args)
    _close(deltanet.rule_recurrent(*args), want)
    got = deltanet.rule_chunked(*args, chunk, jnp.float32)
    _close(got, want)
    # and its backward is the recurrence's: that of a carried state
    err = jax.random.normal(jax.random.key(8), want.shape)
    g_want = jax.grad(lambda *a: jnp.sum(ref.delta_rule(*a) * err),
                      argnums=range(5))(*args)
    g_got = jax.grad(
        lambda *a: jnp.sum(deltanet.rule_chunked(
            *a, chunk, jnp.float32) * err), argnums=range(5))(*args)
    for a, b in zip(g_got, g_want):
        _close(a, b, tol=5e-5)


def test_chunk_products_in_blocks_equal_the_whole(monkeypatch):
    """The ``[C, C]`` products a few chunks at a time (what a 32 k row
    runs) against all at once, forward and backward."""
    args = _rule_inputs()
    run = lambda *a: deltanet.rule_chunked(  # noqa: E731
        *a, 4, jnp.float32)
    want, back = jax.vjp(run, *args)
    monkeypatch.setattr(deltanet, "CHUNKS_AT_ONCE", 8)
    got, mine = jax.vjp(run, *args)
    _close(got, want)
    err = jax.random.normal(jax.random.key(8), want.shape)
    for a, b in zip(mine(err), back(err)):
        _close(a, b)


def test_chunked_rule_survives_the_published_decay():
    """``A_log`` = log of up to 16: a chunk's cumulative decay
    underflows f32 (exp(-21 * 64)); no overflow, no NaN, and still the
    recurrence."""
    q, k, v, g, beta = _rule_inputs()
    g = g * 60.0
    want = ref.delta_rule(q, k, v, g, beta)
    got, back = jax.vjp(
        lambda *a: deltanet.rule_chunked(*a, 64, jnp.float32),
        q, k, v, g, beta)
    _close(got, want)
    assert all(bool(jnp.all(jnp.isfinite(a)))
               for a in back(jnp.ones_like(got)))


# -- the fused chunk products (ISSUE 33), in Pallas interpret mode ----------

def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max()
                 / max(float(np.abs(want).max()), 1e-30))


def _chunk_inputs(chunk, r, cd, n=2, hk=2, d=128, seed=5, g_scale=1.0):
    """(the five arrays of ``_chunk_products``, their tiles): ``n``
    chunks of one row, ``hk`` key heads each serving ``r`` value heads
    of ``d``, at the sizes the compiled kernels tile."""
    q, k, v, g, beta = _rule_inputs(n * chunk, hk, hk * r, d, d, seed)
    parts = deltanet.chunk_parts(q[:1], k[:1], v[:1], g[:1] * g_scale,
                                 beta[:1], chunk, cd)
    tiles = deltanet_pallas.tiles_for(chunk, d, d, r)
    assert tiles == (deltanet_pallas.PAIRS, 2 if (chunk, r) == (64, 2)
                     else 1)
    return parts, tiles


@pytest.mark.parametrize("cd,tol", [(jnp.float32, 5e-5),
                                    (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("chunk", [64, 128])
def test_fused_chunk_products_equal_the_xla_form(chunk, r, cd, tol):
    """The two kernels against ``_chunk_products`` and ``jax.vjp`` of
    it: u, w, the decayed q k^T, log Gamma, and the cotangents to q,
    k, v, g, beta — two value heads of a chunk of 64 side by side in
    one 128-row system, else a system a value head; every f32 product
    in three bf16 passes (``Precision.HIGH``), so f32 agrees to 5e-5
    and bf16 to its own rounding."""
    parts, tiles = _chunk_inputs(chunk, r, cd)
    want, back = jax.vjp(partial(deltanet._chunk_products, cd), *parts)
    got, mine = jax.vjp(
        lambda *a: deltanet_pallas.chunk_products(*a, tiles, True),
        *parts)
    for name, a, b in zip(("u", "w", "a_qk", "gsum"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _gap(a, b) <= tol, (name, _gap(a, b))
    cots = tuple(
        jax.random.normal(jax.random.key(40 + i), w.shape).astype(w.dtype)
        for i, w in enumerate(want))
    for name, a, b in zip(("q", "k", "v", "g", "beta"), mine(cots),
                          back(cots)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _gap(a, b) <= tol, (name, _gap(a, b))


def test_fused_chunk_products_survive_the_published_decay():
    """``g`` x 60, as ``A_log`` = log 16 makes it: the decay is masked
    before the exp in the kernels too — finite, and the XLA form's."""
    parts, tiles = _chunk_inputs(64, 2, jnp.float32, g_scale=60.0)
    want, back = jax.vjp(partial(deltanet._chunk_products, jnp.float32),
                         *parts)
    got, mine = jax.vjp(
        lambda *a: deltanet_pallas.chunk_products(*a, tiles, True),
        *parts)
    ones = tuple(jnp.ones_like(w) for w in want)
    for a, b in zip(got + mine(ones), want + back(ones)):
        assert bool(jnp.all(jnp.isfinite(a)))
        assert _gap(a, b) <= 5e-5, _gap(a, b)


@pytest.mark.parametrize("chunk,hv", [(64, 2), (64, 1), (128, 2)])
def test_chunked_rule_over_the_fused_products_is_the_recurrence(chunk, hv):
    """``rule_chunked`` with the kernels' products equals the token
    recurrence of the reference, and its gradient the recurrence's:
    the scan reads the kernels' four arrays as it reads the XLA
    form's."""
    q, k, v, g, beta = (a[:1] for a in _rule_inputs(256, 1, hv, 128, 128))
    args = (q, k, v, g, beta)
    tiles = deltanet_pallas.tiles_for(chunk, 128, 128, hv)
    run = lambda *a: deltanet.rule_chunked(  # noqa: E731
        *a, chunk, jnp.float32, tiles, True)
    want = ref.delta_rule(*args)
    _close(run(*args), want, tol=5e-5)
    err = jax.random.normal(jax.random.key(8), want.shape)
    g_want = jax.grad(lambda *a: jnp.sum(ref.delta_rule(*a) * err),
                      argnums=range(5))(*args)
    g_got = jax.grad(lambda *a: jnp.sum(run(*a) * err),
                     argnums=range(5))(*args)
    for a, b in zip(g_got, g_want):
        _close(a, b, tol=1e-4)


def test_fused_chunk_products_pad_a_ragged_count_of_pairs():
    """Three chunks of one key head: three pairs in a grid step of
    eight, the rest padding that reaches no result."""
    parts, tiles = _chunk_inputs(64, 2, jnp.float32, n=3, hk=1)
    want = deltanet._chunk_products(jnp.float32, *parts)
    got = deltanet_pallas.chunk_products(*parts, tiles, True)
    for a, b in zip(got, want):
        assert a.shape == b.shape and _gap(a, b) <= 5e-5
    with pytest.raises(ValueError, match="do not tile"):
        deltanet_pallas.chunk_products(
            *_chunk_inputs(64, 1, jnp.float32)[0],
            deltanet_pallas.Tiles(8, 2), True)


@pytest.mark.parametrize(
    "platform,chunk,dk,dv,r,batched,products,reason", [
        ("cpu", 64, 128, 128, 2, False, "xla", "platform"),
        ("tpu", 64, 128, 128, 2, False, "fused", None),
        ("tpu", 64, 128, 128, 2, True, "xla", "batched"),
        ("tpu", 64, 128, 128, 1, False, "fused", None),
        ("tpu", 128, 128, 256, 2, False, "fused", None),
        ("tpu", 64, 16, 16, 2, False, "xla", "head_size"),
        ("tpu", 64, 128, 64, 2, False, "xla", "head_size"),
        ("tpu", 16, 128, 128, 2, False, "xla", "chunk"),
        ("tpu", 256, 128, 128, 2, False, "xla", "chunk"),
        ("tpu", 64, 128, 128, 32, False, "xla", "chunk")])
def test_products_path_is_chosen_from_platform_and_shapes(
        platform, chunk, dk, dv, r, batched, products, reason):
    path = deltanet.products_path(platform, chunk, dk, dv, r, batched)
    assert (path["products"], path.get("reason")) == (products, reason)
    if products == "fused":
        assert path["tiles"] == deltanet_pallas.tiles_for(chunk, dk, dv, r)
        assert path["tiles"].pack * chunk in (64, 128)
        assert r % path["tiles"].pack == 0


def test_gdn_path_is_journaled_and_a_vmap_leaves_the_kernels():
    """On the CPU a unit journals ``chunked`` with ``products`` ``xla``
    / ``platform``; a unit that believes it is on a TPU at the
    published head size takes the kernels, and under ``vmap`` (a
    cohort, an ensemble) falls back to the XLA form and says why; a
    ragged row has no products to choose."""
    from types import SimpleNamespace
    telemetry.reset()
    unit, _, _, _ = _unit("gated_delta_net", key_head_size=128,
                          value_head_size=128, chunk_size=64)
    shapes = unit.param_shapes((1, T, HIDDEN))
    params = {n: 0.2 * jax.random.normal(jax.random.key(j), (2,) + sh)
              for j, (n, sh) in enumerate(sorted(shapes.items()))}
    x = jax.random.normal(jax.random.key(9), (1, T, HIDDEN), jnp.float32)
    one = lambda i: {n: p[i] for n, p in params.items()}  # noqa: E731
    want = jnp.stack([unit.forward(one(i), x) for i in range(2)])
    assert unit.path == {"form": "chunked", "chunk": 64,
                         "products": "xla", "reason": "platform"}
    unit.device = SimpleNamespace(platform="tpu")
    assert unit._path(T)["products"] == "fused"
    got = jax.vmap(unit.forward, in_axes=(0, None))(params, x)
    _close(got, want)
    assert unit._path(100) == {"form": "recurrent", "reason": "ragged",
                               "chunk": 1}
    seen = telemetry.recent_events(events.EV_GDN_PATH)[-4:]
    assert [(e["form"], e["products"], e["reason"]) for e in seen] == [
        ("chunked", "xla", "platform"), ("chunked", "fused", None),
        ("chunked", "xla", "batched"), ("recurrent", None, "ragged")]
    assert seen[1]["tiles"] == {"pairs": 8, "pack": 2}
    assert all(e["unit"] == unit.name for e in seen)


@pytest.mark.parametrize("kind", ["gated_delta_net", "gated_attention"])
def test_mixers_are_causal(kind):
    """A change at position n moves no output before n (through the
    convolution, the rule's state and the attention alike)."""
    unit, _, params, x = _unit(kind)
    n = 70
    y0 = unit.forward(params, x)
    y1 = unit.forward(params, x.at[:, n].add(1.0))
    np.testing.assert_array_equal(y0[:, :n], y1[:, :n])
    assert float(jnp.abs(y0[:, n:] - y1[:, n:]).max()) > 1e-4


def test_rule_path_is_chosen_from_the_row():
    assert deltanet.rule_path(128, 64) == {"form": "chunked", "chunk": 64}
    assert deltanet.rule_path(100, 64)["form"] == "recurrent"
    unit, cfg, params, x = _unit("gated_delta_net", chunk_size=48)
    _close(unit.forward(params, x), ref.layer_forward(cfg, params, x))


# -- gated attention ----------------------------------------------------------

def test_attention_maps_eight_queries_to_a_key_head():
    """16 query heads over 2 key heads: a change of key head 1's
    projection moves query heads 8-15 and leaves 0-7 as they were."""
    unit, _, params, x = _unit("gated_attention", n_heads=16,
                               n_kv_heads=2, head_size=8, rotary_size=2)
    d = 8
    y0 = unit.forward(params, x).reshape(ROWS, T, 16, d)
    wk = params["wk"].at[:, d:].add(0.1 * jax.random.normal(
        jax.random.key(6), (HIDDEN, d)))
    y1 = unit.forward(dict(params, wk=wk), x).reshape(ROWS, T, 16, d)
    np.testing.assert_array_equal(y0[:, :, :8], y1[:, :, :8])
    assert float(jnp.abs(y0[:, :, 8:] - y1[:, :, 8:]).max()) > 1e-4


def test_rope_rotates_the_first_elements_alone():
    x = jax.random.normal(jax.random.key(2), (ROWS, T, 3, 256))
    y = seq.rope(x, 1e7, 64)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    _close(y[..., :64], seq.rope(x[..., :64], 1e7))
    _close(y, ref.rope(x, 1e7, 64))
    assert float(jnp.abs(y[:, 1:, :, :64] - x[:, 1:, :, :64]).max()) > 0.1
    # the whole head when no count is given, as before
    _close(seq.rope(x, 1e5), ref.rope(x, 1e5, 256))


@pytest.mark.parametrize("platform,head,t,batched,form,reason", [
    ("cpu", 256, 32768, False, "xla", "platform"),
    ("tpu", 256, 32768, False, "splash", None),
    ("tpu", 256, 32768, True, "xla", "batched"),
    ("tpu", 16, 32768, False, "xla", "head_size"),
    ("tpu", 256, 1000, False, "xla", "row"),
    ("tpu", 128, 256, False, "splash", None)])
def test_attention_path_is_chosen_from_platform_and_shapes(
        platform, head, t, batched, form, reason):
    path = attention.attention_path(platform, head, t, batched)
    assert (path["form"], path.get("reason")) == (form, reason)
    if form == "splash":
        assert t % path["tiles"]["block_q"] == 0


def test_xla_attention_in_blocks_equals_one_block():
    q = jax.random.normal(jax.random.key(1), (ROWS, T, 2, 2, 16))
    k = jax.random.normal(jax.random.key(2), (ROWS, T, 2, 16))
    v = jax.random.normal(jax.random.key(3), (ROWS, T, 2, 16))
    _close(attention.core_xla(q, k, v, block=32),
           attention.core_xla(q, k, v, block=T))
    _close(attention.core_xla(q, k, v, block=32),
           ref.causal_attention(q * 4.0, k, v))


# -- the mixture of experts -------------------------------------------------

def test_the_shares_add_up():
    """The routed parts of both shares (experts 0-3 and 4-7 of 8) plus
    the shared expert counted once equal the uncut reference layer."""
    _, cfg, params, x = _unit("moe", experts_held=8)
    fw = cfg["->"]
    whole = ref.moe(x, params, fw, held=(0, 8))
    none = {n: params[n][:0] for n in ("w_gate", "w_up", "w_down")}
    shared_alone = ref.moe(x, dict(params, **none), fw, held=(0, 0))
    parts = []
    for first in (0, 4):
        unit, _, _, _ = _unit("moe", experts_held=4, first_held=first)
        mine = dict(params, **{n: params[n][first:first + 4]
                               for n in ("w_gate", "w_up", "w_down")})
        parts.append(unit.forward(mine, x))
        _close(parts[-1], ref.moe(x, mine, unit_fw(unit, fw)))
    _close(parts[0] + parts[1] - shared_alone, whole)


def unit_fw(unit, fw):
    return dict(fw, experts_held=unit.experts_held,
                first_held=unit.first_held)


def test_a_router_that_sends_every_token_here_drops_nothing():
    """Every token picks held experts alone (the worst routing for
    this share): the static buffers take all ``tokens * top_k`` rows,
    nothing is dropped, and the layer still equals the reference."""
    unit, cfg, params, x = _unit("moe")
    held = slice(unit.first_held, unit.first_held + unit.experts_held)
    x = jnp.abs(x)
    params = dict(params, router=params["router"].at[:, held].add(0.5))
    got = unit.probe(params, x)
    load = unit.report_probe(jax.device_get(got))
    assert load["local_assignments"] == ROWS * T * unit.top_k
    assert load["dropped"] == 0
    assert unit.share["rows"] == ROWS * T * unit.top_k
    assert telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).value == 0
    want, back = jax.vjp(lambda p, xx: ref.layer_forward(cfg, p, xx),
                         params, x)
    y, mine = jax.vjp(unit.forward, params, x)
    _close(y, want)
    err = jnp.ones_like(want)
    for a, b in zip(jax.tree.leaves(mine(err)),
                    jax.tree.leaves(back(err))):
        _close(a, b)


def test_moe_in_blocks_of_tokens_equals_one_block(monkeypatch):
    unit, cfg, params, x = _unit("moe")
    monkeypatch.setattr(moe, "DISPATCH_BUFFER_BYTES", 64 * 2 * HIDDEN * 2)
    assert moe.block_tokens(ROWS * T, unit.top_k, HIDDEN) == 64
    want, back = jax.vjp(lambda p, xx: ref.layer_forward(cfg, p, xx),
                         params, x)
    y, mine = jax.vjp(unit.forward, params, x)
    assert unit.share["blocks"] == ROWS * T // 64
    assert unit.share["rows"] == 64 * unit.top_k
    _close(y, want)
    err = jax.random.normal(jax.random.key(4), want.shape)
    for a, b in zip(jax.tree.leaves(mine(err)),
                    jax.tree.leaves(back(err))):
        _close(a, b)


# -- the compact dispatch buffers ---------------------------------------------

COMPACT = dict(experts_total=16, experts_held=2, top_k=4)


def _steered(params, x, pairs_held):
    """Router and input under which the first ``pairs_held // 2`` tokens
    of the ``ROWS * T`` choose experts 0-3 (two held pairs each) and the
    others experts 2-5 (none): the routing is read off the first 16
    input dimensions."""
    router = jnp.zeros_like(params["router"]).at[:16].set(
        10.0 * jnp.eye(16))
    here = jnp.zeros(16).at[:4].set(jnp.array([4.0, 3.0, 2.0, 1.0]))
    there = jnp.roll(here, 2)
    first = (jnp.arange(ROWS * T) < pairs_held // 2)[:, None]
    scores = jnp.where(first, here, there).reshape(ROWS, T, 16)
    return dict(params, router=router), x.at[..., :16].set(scores)


@pytest.mark.parametrize("case", ["fits", "every_block_overflows",
                                  "exactly_at_capacity",
                                  "one_pair_over_capacity"])
def test_a_layer_with_compact_buffers_equals_the_reference(
        case, monkeypatch):
    """2 of 16 experts held, top 4: a block's buffers hold 1.5 x the
    pairs it expects, not every pair.  A block whose held pairs fit —
    up to exactly ``capacity`` of them — goes through the buffers once;
    one that overflows walks its sorted pairs a bufferful at a time,
    and nothing is dropped: the layer equals the reference, forward and
    backward, whatever the routing."""
    unit, cfg, params, x = _unit("moe", **COMPACT)
    blocks, pieces = 1, 1
    if case == "every_block_overflows":
        monkeypatch.setattr(moe, "DISPATCH_BUFFER_BYTES",
                            64 * 4 * HIDDEN * 2)
        blocks, pieces = ROWS * T // 64, 3      # 128 pairs in 48 rows
        x = jnp.abs(x)
        params = dict(params, router=params["router"].at[:, :2].add(3.0))
    elif case != "fits":
        pieces = 1 + (case == "one_pair_over_capacity")
        params, x = _steered(params, x, 192 + 2 * (pieces - 1))
    load = unit.report_probe(jax.device_get(unit.probe(params, x)))
    capacity = unit.share["capacity"]
    assert (unit.share["rows"], unit.share["blocks"], capacity) == (
        ROWS * T * 4 // blocks, blocks, 192 // blocks)
    assert load["dropped"] == 0 and load["blocks"] == blocks
    assert load["over_capacity_blocks"] == (blocks if pieces > 1 else 0)
    assert telemetry.gauge(
        events.GAUGE_MOE_OVER_CAPACITY_BLOCKS).value \
        == load["over_capacity_blocks"]
    if case == "every_block_overflows":
        assert load["local_assignments"] == ROWS * T * 2
    elif case != "fits":
        assert load["local_assignments"] == capacity + 2 * (pieces - 1)
        # the pieces one at a time: what lies beyond the held pairs
        # adds nothing, and a piece is needed only past ``capacity``
        tokens = x.reshape(ROWS * T, HIDDEN)
        top_i, top_w = unit.route(params, tokens)
        whole = unit._routed_block(params, None, tokens, top_i, top_w)
        parts = [unit._compact_block(params, None, capacity,
                                     jnp.int32(piece), tokens, top_i,
                                     top_w) for piece in range(3)]
        _close(sum(parts[:pieces]), whole)
        assert float(jnp.abs(parts[pieces - 1]).max()) > 0.0
        assert float(jnp.abs(parts[pieces]).max()) == 0.0
    want, back = jax.vjp(lambda p, xx: ref.layer_forward(cfg, p, xx),
                         params, x)
    y, mine = jax.vjp(unit.forward, params, x)
    _close(y, want)
    err = jax.random.normal(jax.random.key(4), want.shape)
    for a, b in zip(jax.tree.leaves(mine(err)),
                    jax.tree.leaves(back(err))):
        _close(a, b)


@pytest.mark.parametrize("block,top_k,held,total,unit,batched,want", [
    (4096, 10, 32, 512, moe.GMM_ROWS, False, 4096),     # qwen3next's cell
    (4096, 8, 16, 64, moe.GMM_ROWS, False, 12288),      # mellum2's cell
    (4096, 8, 16, 64, moe.GMM_ROWS, True, None),        # under vmap
    (4096, 8, 64, 64, moe.GMM_ROWS, False, None),       # every expert held
    (256, 2, 4, 8, moe.SUBLANES, False, None),          # the tiny preset
    (256, 2, 2, 8, moe.SUBLANES, False, 192),           # mellum2's tiny one
    (4096, 8, 21, 64, moe.GMM_ROWS, False, 16384),      # half: still compact
    (4096, 8, 22, 64, moe.GMM_ROWS, False, None)])      # over half: whole
def test_capacity_is_read_from_shapes(block, top_k, held, total, unit,
                                      batched, want):
    assert moe.dispatch_capacity(block * top_k, held, total, unit,
                                 batched) == want
    if want is not None:
        assert want % unit == 0 and 2 * want <= block * top_k


def test_the_tiny_preset_keeps_the_whole_buffers():
    unit, _, _, x = _unit("moe")
    assert unit._share(ROWS * T, HIDDEN)["capacity"] is None
    assert unit._share(ROWS * T, HIDDEN, batched=True)["capacity"] is None
    compact, _, _, _ = _unit("moe", **COMPACT)
    assert compact._share(ROWS * T, HIDDEN)["capacity"] == 192
    assert compact._share(ROWS * T, HIDDEN, batched=True)[
        "capacity"] is None


@pytest.mark.parametrize("platform,rows,width,inner,batched,form", [
    ("cpu", 40960, 2048, 512, False, "ragged_dot"),
    ("tpu", 40960, 2048, 512, False, "gmm"),
    ("tpu", 40960, 2048, 512, True, "ragged_dot"),
    ("tpu", 512, 64, 32, False, "ragged_dot")])
def test_grouped_path_is_chosen_from_platform_and_shapes(
        platform, rows, width, inner, batched, form):
    path = moe.grouped_path(platform, rows, width, inner, batched)
    assert path["form"] == form
    assert ("tiles" in path) == (form == "gmm")


def test_the_cells_dispatch_block_is_sized_from_shapes():
    assert moe.block_tokens(32768, 10, 2048) == 4096
    assert moe.block_tokens(256, 2, 64) == 256


# -- the whole model through StandardWorkflow --------------------------------

def _workflow(rows, superstep=2, mb=ROWS, layers=LAYERS, epochs=1):
    prng.seed_all(11)
    return StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=mb, name="loader"),
        layers=layers, loss_function="next_byte",
        decision_config={"max_epochs": epochs}, superstep=superstep,
        name="qwen3next_tiny")


def _rows(n=4):
    return np.asarray(jax.random.randint(
        jax.random.key(5), (n, T), 0, TINY["vocab_held"]), np.int32)


def _run_two_steps(monkeypatch, limit=None, recompute=None):
    """2 SGD steps (one firing of superstep 2) from seeded weights;
    returns (workflow, w0, the rows of each step)."""
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


def test_the_layers_list_is_a_period_of_three_and_one():
    kinds = [[c["type"] for c in e.get("layers", [e])] for e in LAYERS]
    mixer = [k[1] for k in kinds if len(k) == 3]
    assert mixer == ["gated_delta_net"] * 3 + ["gated_attention"]
    assert [k for k in kinds if len(k) == 2] == [["rmsnorm", "moe"]] * 4
    assert kinds[0] == ["embedding"] and kinds[-1] == ["lm_head"]
    full = ref.flatten(qwen3next_layers())
    assert ref.param_count(qwen3next_layers()) == 625667136
    assert [c["type"] for c in full].count("gated_attention") == 1


def test_two_steps_through_standardworkflow_match_the_reference(
        monkeypatch):
    telemetry.reset()
    w, w0, fed = _run_two_steps(monkeypatch)
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
    assert [e["form"] for e in
            telemetry.recent_events(events.EV_GDN_PATH)] == ["chunked"] * 3
    ev = telemetry.recent_events(events.EV_ATTN_PATH)
    assert [(e["form"], e["reason"]) for e in ev] == [("xla", "platform")]
    shares = telemetry.recent_events(events.EV_MOE_SHARE)
    assert len(shares) == 4 and all(
        (e["experts_total"], e["experts_held"], e["first_held"],
         e["top_k"], e["rows"], e["form"]) ==
        (8, 4, 0, 2, ROWS * T * 2, "ragged_dot") for e in shares)
    # ... and after the first firing: every layer's load, nothing dropped
    loads = telemetry.recent_events(events.EV_MOE_LOAD)
    assert [e["unit"] for e in loads] == [
        f.name for f in w.forwards if isinstance(f, moe.MoE)]
    assert all(e["dropped"] == 0 and 0 < e["min_expert_rows"]
               <= e["max_expert_rows"] for e in loads)
    assert telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).value == 0
    # the reference reports what every token chose in every moe layer
    assert sorted(want["choices0"]) == [
        i for i, c in enumerate(FLAT) if c["type"] == "moe"]
    got = w.fused.probe_units(jnp.asarray(fed[0]))
    assert set(got) == {f.name for f in w.forwards
                        if isinstance(f, moe.MoE)}


def test_recomputation_on_and_off_give_the_same_step(monkeypatch):
    telemetry.reset()
    keep, _, _ = _run_two_steps(monkeypatch)
    ev = telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1]
    assert (ev["policy"], ev["reason"]) == ("keep", "no_limit")
    p_keep = jax.device_get(keep.fused._params)
    rec, _, _ = _run_two_steps(monkeypatch, limit=1 << 24)
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


def test_blocked_loss_and_error_equal_the_unblocked(monkeypatch):
    """The head's product, the loss and the head's backward a block of
    positions at a time — chosen from shapes against free memory —
    leave the step the whole logits leave."""
    telemetry.reset()
    whole, _, _ = _run_two_steps(monkeypatch, recompute=False)
    ev = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (ev["blocks"], ev["reason"]) == (0, "no_limit")
    acc_whole = np.asarray(whole.fused._acc)
    p_whole = jax.device_get(whole.fused._params)
    # free memory of 64 x the logits' arrays: whole they may take a
    # quarter of it... they fit
    state = whole.fused._state_bytes(jnp.float32)
    logits = 4 * 4 * ROWS * T * TINY["vocab_held"]
    _run_two_steps(monkeypatch, limit=state + 4 * logits,
                   recompute=False)
    ev = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (ev["blocks"], ev["reason"], ev["bytes_whole"]) == (
        0, "fits", logits)
    # ... of twice the logits' arrays: 8 blocks, a sixteenth each
    cut, _, _ = _run_two_steps(monkeypatch, limit=state + 2 * logits,
                               recompute=False)
    ev = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (ev["blocks"], ev["reason"]) == (8, "whole_exceeds_free")
    assert ev["bytes_block"] == logits // 8
    np.testing.assert_allclose(np.asarray(cut.fused._acc), acc_whole,
                               rtol=1e-6)
    p_cut = jax.device_get(cut.fused._params)
    for layer in p_whole:
        for name in p_whole[layer]:
            np.testing.assert_allclose(p_cut[layer][name],
                                       p_whole[layer][name],
                                       rtol=1e-5, atol=1e-6)
    # the evaluator's block against its whole, error included
    ev_unit = cut.evaluator
    out = jax.random.normal(jax.random.key(1),
                            (ROWS, T, 1, TINY["vocab_held"]))
    rows, mask = jnp.asarray(_rows()[:ROWS]), jnp.ones((ROWS,))
    m = ev_unit.metrics_fn(out, rows, mask)
    n = ev_unit.valid_count(out.shape, mask)
    assert float(n) == float(m["count"]) == ROWS * (T - 1)
    half = [ev_unit.block_metrics(out[:, lo:lo + T // 2], rows, mask,
                                  lo, n) for lo in (0, T // 2)]
    _close(jnp.concatenate([h["err_output"] for h in half], 1),
           m["err_output"])
    assert float(half[0]["loss_sum"] + half[1]["loss_sum"]) == \
        pytest.approx(float(m["loss_sum"]), rel=1e-6)
    assert float(half[0]["n_err"] + half[1]["n_err"]) == float(m["n_err"])


def test_blocked_loss_in_the_eval_step(monkeypatch):
    """A validation pass under a blocked head: the same metrics, and
    no whole output is kept."""
    def run(limit):
        if limit is not None:
            monkeypatch.setattr(FusedStepRunner, "_device_bytes_limit",
                                lambda self: limit)
        prng.seed_all(11)
        rows = _rows(8)
        w = StandardWorkflow(
            loader_factory=lambda wf: ArrayLoader(
                wf, train=(rows[:4],), valid=(rows[4:],),
                minibatch_size=ROWS, name="loader"),
            layers=LAYERS, loss_function="next_byte",
            decision_config={"max_epochs": 1}, superstep=2, name="q")
        w.initialize(device=make_device("cpu"))
        w.run()
        return w.decision.history
    whole, cut = run(None), run(1 << 21)
    assert telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]["blocks"]
    assert len(whole) == len(cut) >= 1
    for a, b in zip(whole, cut):
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)


def test_model_file_trains_and_the_loss_falls():
    prng.seed_all(7)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedTokensLoader(
            wf, name="loader", n_train=4, seq_len=T, minibatch_size=2,
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


def test_evabyte_traces_no_blocked_loss_and_none_of_the_new_ops(
        monkeypatch):
    """The other sequence configuration's step keeps the unblocked
    form it has, on a device of a real chip's size too."""
    monkeypatch.setattr(FusedStepRunner, "_device_bytes_limit",
                        lambda self: 16909336064)
    telemetry.reset()
    t = evabyte.TINY["seq_len"]
    rows = np.asarray(jax.random.randint(jax.random.key(5), (4, t), 0,
                                         320), np.int32)
    w = _workflow(rows, layers=evabyte.evabyte_layers(**evabyte.TINY))
    w.initialize(device=make_device("cpu"))
    ev = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (ev["blocks"], ev["reason"]) == (0, "fits")
    for name in (events.EV_GDN_PATH, events.EV_ATTN_PATH,
                 events.EV_MOE_SHARE, events.EV_MOE_LOAD):
        assert not telemetry.recent_events(name)
    fused = w.fused
    w.loader.run()
    fused._ensure_params()
    acc, conf = fused._fresh_acc()
    idx, mask = fused._superstep_arrays()
    text = jax.make_jaxpr(fused._train_step)(
        fused._params, fused._opt, acc, conf,
        w.loader.original_data.unmap(), fused._target_store(), idx,
        mask, fused._lr_rates_array(idx.shape[0]), fused._rng_counter
    ).pretty_print(name_stack=True)
    assert "fwd/fwd0_embedding" in text and "eva/local" in text
    for scope in ("gdn/", "attn/core", "moe/", "loss/block"):
        assert scope not in text, scope
    w.fused.run()
    assert not telemetry.recent_events(events.EV_MOE_LOAD)
    assert fused.probe_units(rows[:1]) == {}


def test_the_real_sizes_block_the_loss_and_evabytes_do_not():
    """The decision's arithmetic at the two cells' sizes on a v5e
    (16 909 336 064 B): 4 arrays of [1, 32768, 1, 18992] f32 are 9.96
    GB beside 6.26 GB of state — 16 blocks of 2048 positions; EvaByte's
    1.34 GB beside 8.21 GB fit whole."""
    limit = 16909336064

    def blocks(whole, state, t=32768):
        free = limit - state
        if 4 * whole <= free:
            return 0
        n = 2
        while 16 * whole > n * free and t % (2 * n) == 0:
            n *= 2
        return n

    assert blocks(16 * 32768 * 18992, 625667136 * 10) == 16
    assert blocks(16 * 32768 * 8 * 320, 821366784 * 10) == 0
