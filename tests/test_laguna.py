"""Laguna-S-2.1's layer types at the tiny preset, on XLA:CPU in f32:
``attention`` with a sigmoid gate a head and RoPE (YaRN) on part of
each head, a leading dense ``swiglu``, and ``moe`` whose routed
weights are scaled, beside a shared expert — against the plain
reference the benchmark also uses (``benchmarks/lib/
reference_laguna.py``: no ``veles_tpu`` import, matmul precision
"highest") and against the formulas written out here."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.lib import flops_lm_gate  # noqa: E402
from benchmarks.lib import reference_laguna as ref  # noqa: E402
from veles_tpu import events, prng, telemetry  # noqa: E402
from veles_tpu.backends import make_device  # noqa: E402
from veles_tpu.loader import ArrayLoader  # noqa: E402
from veles_tpu.models.laguna import (  # noqa: E402
    PUBLISHED, TINY, laguna_layers)
from veles_tpu.ops import moe  # noqa: E402
from veles_tpu.ops import sequence as seq  # noqa: E402
from veles_tpu.ops.registry import forward_registry  # noqa: E402
from veles_tpu.ops.standard_workflow import StandardWorkflow  # noqa: E402

T, ROWS = TINY["seq_len"], 2
HIDDEN = TINY["hidden_size"]
LAYERS = laguna_layers(**TINY)
FLAT = ref.flatten(LAYERS)
KINDS = [c["type"] for c in FLAT]
FULL = KINDS.index("attention")
WINDOW = next(i for i, c in enumerate(FLAT)
              if c["type"] == "attention" and c["->"]["window"])
SWIGLU = KINDS.index("swiglu")
MOE = KINDS.index("moe")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=2e-5):
    scale = max(1.0, float(np.abs(np.asarray(want)).max()))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


def _unit(i, rows=ROWS, **over):
    """(unit, its config, seeded parameters, an input) of flat layer i
    of the tiny model (``over``: other settings)."""
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


# -- the dense layer against the reference ------------------------------------

def test_the_dense_layer_forward_and_backward_match_the_reference():
    """(The attention and expert layers' gradients: every leaf's, in
    the one-firing test below.)"""
    unit, cfg, params, x = _unit(SWIGLU)
    assert unit.param_shapes(x.shape) == {
        k: tuple(v.shape) for k, v in params.items()}
    want = ref.layer_forward(cfg, params, x)
    got, back = unit.apply_fwd(params, x, train=True)
    _close(got, want)
    err = jax.random.normal(jax.random.key(99), want.shape, jnp.float32)
    gd = forward_registry[cfg["type"]][1](None, forward=unit, name="gd")
    err_in, grads = gd.backward_from_saved(params, back, err)
    want_p, want_x = jax.grad(lambda p, xx: jnp.sum(
        ref.layer_forward(cfg, p, xx) * err), argnums=(0, 1))(params, x)
    _close(err_in, want_x)
    assert set(grads) == set(params)
    for name in params:
        _close(grads[name], want_p[name])


# -- the gate and the partial YaRN, written out ------------------------------

def test_published_yarn_over_half_a_head_is_the_written_law():
    """64 of 128 elements rotate: ``e_j = 5e5^(-2j/64)``, ``p_j = e_j /
    128``, the ramp between ``low`` 9 and ``high`` 18, scale 1.4852."""
    spec = PUBLISHED["rope_parameters"]["full_attention"]
    inv, scale = seq.rope_frequencies(spec, 128)
    r, theta = 64, 5e5

    def dim(b):
        return r * math.log(8192 / (2 * math.pi * b)) / (2 * math.log(theta))

    low, high = max(math.floor(dim(32)), 0), min(math.ceil(dim(1)), r - 1)
    assert (low, high) == (9, 18)
    e = np.array([theta ** (-2 * j / r) for j in range(r // 2)])
    ramp = np.clip((np.arange(r // 2) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(inv, e / 128 * ramp + e * (1 - ramp),
                               rtol=1e-6)
    assert scale == 1.4852030263919618
    window = PUBLISHED["rope_parameters"]["sliding_attention"]
    inv, scale = seq.rope_frequencies(window, 128)
    np.testing.assert_allclose(inv, 1e4 ** (-np.arange(0, 128, 2) / 128),
                               rtol=1e-6)
    assert scale == 1.0
    with pytest.raises(ValueError, match="rotated elements"):
        seq.rope_frequencies(dict(spec, partial_rotary_factor=1.5), 128)


def test_full_layer_is_the_written_formula():
    """Numpy, with no reference in between: RoPE on the first r
    elements (scaled by a), the rest passed as they are; causal softmax
    over the key head a query head reads; the gate a head."""
    unit, cfg, p, x = _unit(FULL, rows=1)
    fw = cfg["->"]
    nh, nkv, d = fw["n_heads"], fw["n_kv_heads"], fw["head_size"]
    assert (unit.rotary, unit.gate_kind) == (8, "per_head")
    inv, a = seq.rope_frequencies(fw["rope"], d)
    r = unit.rotary
    u = np.asarray(x[0], np.float64)
    w = {k: np.asarray(v, np.float64) for k, v in p.items()}
    ang = np.arange(T)[:, None] * np.asarray(inv, np.float64)[None]
    cos = np.concatenate([np.cos(ang)] * 2, -1)[:, None]
    sin = np.concatenate([np.sin(ang)] * 2, -1)[:, None]

    def rot(h):
        h = h.reshape(T, -1, d)
        x1, x2 = h[..., :r // 2], h[..., r // 2:r]
        turned = a * (h[..., :r] * cos
                      + np.concatenate([-x2, x1], -1) * sin)
        return np.concatenate([turned, h[..., r:]], -1)

    q, k = rot(u @ w["wq"]), rot(u @ w["wk"])
    v = (u @ w["wv"]).reshape(T, nkv, d)
    gate = 1 / (1 + np.exp(-(u @ w["wg"])))
    out = np.zeros((T, nh, d))
    for h in range(nh):
        kv = h // (nh // nkv)
        s = q[:, h] @ k[:, kv].T / math.sqrt(d)
        s = np.where(np.tri(T, dtype=bool), s, -np.inf)
        pr = np.exp(s - s.max(-1, keepdims=True))
        out[:, h] = gate[:, h, None] * (pr / pr.sum(-1, keepdims=True)
                                        @ v[:, kv])
    _close(unit.forward(p, x)[0], out.reshape(T, nh * d), tol=1e-4)


# -- the mixture of experts ------------------------------------------------

def test_the_routed_scale_scales_the_routed_part_alone():
    unit, cfg, params, x = _unit(MOE)
    plain, _, _, _ = _unit(MOE, routed_scale=1.0)
    assert unit.routed_scale == 2.5
    tokens = x.reshape(-1, HIDDEN)
    (ids, w), (ids1, w1) = unit.route(params, tokens), \
        plain.route(params, tokens)
    np.testing.assert_array_equal(ids, ids1)
    _close(w, 2.5 * w1)
    _close(jnp.sum(w, -1), jnp.full(w.shape[:1], 2.5))
    shared = jax.nn.sigmoid(x @ params["s_mix"]) * ref.swiglu_mlp(
        x, params["s_gate"], params["s_up"], params["s_down"])
    _close(unit.forward(params, x) - shared,
           2.5 * (plain.forward(params, x) - shared))
    assert unit.share["routed_scale"] == 2.5


def test_the_four_shares_add_up():
    """The routed parts of the four shares (experts 0-1, 2-3, 4-5, 6-7
    of 8: the model's 0-7 ... 248-255 of 256) and the shared expert,
    which every share computes alike, counted once, sum to the uncut
    reference's layer."""
    _, cfg, params, x = _unit(MOE, experts_held=8)
    fw = cfg["->"]
    whole = ref.moe(x, params, fw, held=(0, 8))
    shared = jax.nn.sigmoid(x @ params["s_mix"]) * ref.swiglu_mlp(
        x, params["s_gate"], params["s_up"], params["s_down"])
    parts = []
    for first in (0, 2, 4, 6):
        unit, _, _, _ = _unit(MOE, experts_held=2, first_held=first)
        mine = dict(params, **{n: params[n][first:first + 2]
                               for n in ("w_gate", "w_up", "w_down")})
        parts.append(unit.forward(mine, x))
        _close(parts[-1], ref.moe(x, mine, dict(
            fw, experts_held=2, first_held=first)))
        assert float(jnp.abs(parts[-1] - shared).max()) > 1e-3
    _close(sum(parts) - 3 * shared, whole)


# -- what the layers without the new parameters trace ------------------------

@pytest.mark.parametrize("i", [WINDOW, MOE], ids=["attention", "moe"])
def test_without_the_new_parameters_the_trace_is_unchanged(i):
    """``gate`` False and a rope law over the whole head; ``routed_scale``
    1: the jaxpr of a layer built without those keys at all."""
    unit, cfg, params, x = _unit(i)
    fw = {k: v for k, v in cfg["->"].items() if k != "weights_stddev"}
    if cfg["type"] == "attention":
        assert fw["rope"]["partial_rotary_factor"] == 1
        bare = dict(fw, rope={k: v for k, v in fw["rope"].items()
                              if k != "partial_rotary_factor"})
        del bare["gate"]
        fw = dict(fw, gate=False)
        params = {k: v for k, v in params.items() if k != "wg"}
    else:
        bare = {k: v for k, v in fw.items() if k != "routed_scale"}
        fw = dict(fw, routed_scale=1.0)
    make = forward_registry[cfg["type"]][0]
    a, b = make(None, name="a", **fw), make(None, name="a", **bare)
    assert a.param_names == b.param_names

    def text(u):
        def step(p, xx):
            return jax.grad(lambda p, xx: jnp.sum(u.forward(p, xx)),
                            argnums=(0, 1))(p, xx)
        return jax.make_jaxpr(step)(params, x).pretty_print(
            name_stack=True)

    assert text(a) == text(b)


# -- the whole model through StandardWorkflow --------------------------------

def _norms(tree):
    return {f"{i}.{k}": float(jnp.sqrt(jnp.sum(jnp.square(v))))
            for i, d in enumerate(tree) for k, v in d.items()}


def test_one_firing_through_standardworkflow_matches_the_reference():
    telemetry.reset()
    prng.seed_all(11)
    rows = np.asarray(jax.random.randint(
        jax.random.key(5), (4, T), 0, TINY["vocab_held"]), np.int32)
    w = StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=ROWS, name="loader"),
        layers=LAYERS, loss_function="next_byte",
        decision_config={"max_epochs": 1}, superstep=2,
        name="laguna_tiny")
    w.initialize(device=make_device("cpu"))
    w0 = ref.init_params(3, LAYERS, 0.05)
    for f, p in zip(w.forwards, w0):
        assert set(f.param_vectors()) == set(p), f.name
        for name, vec in f.param_vectors().items():
            vec.mem = np.asarray(p[name])
    w.loader.run()
    fed = rows[np.array(w.loader.superstep_indices, copy=True)]
    w.fused.run()
    want = ref.follow(LAYERS, ref.init_params(3, LAYERS, 0.05), fed)
    _, loss_sum, count, _ = w.fused.take_class_metrics()
    assert count == 2 * ref.valid_count(ROWS, T)
    assert loss_sum == pytest.approx(want["loss_sum"], rel=1e-5)
    params = [w.fused._params[f.name] for f in w.forwards]
    update = _norms([{k: p[k] - z[k] for k in p}
                     for p, z in zip(params, w0)])
    momentum = _norms([w.fused._opt[g.name] for g in w.gds])
    assert set(update) == set(want["update"])
    floor = 1e-3 * np.median(list(want["grad0"].values()))
    for key in want["update"]:
        if want["grad0"][key] < floor:   # moves by round-off alone
            continue
        assert update[key] == pytest.approx(want["update"][key],
                                            rel=5e-4, abs=1e-9), key
        assert momentum[key] == pytest.approx(want["momentum"][key],
                                              rel=5e-4, abs=1e-9), key
    ev = telemetry.recent_events(events.EV_ATTN_PATH)
    assert [(e["window"], e["rope"], e["rotary"], e["gate"])
            for e in ev] == [(None, "yarn", 8, "per_head")] \
        + [(32, "default", 16, "per_head")] * 3 \
        + [(None, "yarn", 8, "per_head")]
    shares = telemetry.recent_events(events.EV_MOE_SHARE)
    assert [(e["shared"], e["routed_scale"]) for e in shares] \
        == [(True, 2.5)] * 4
    assert all(e["dropped"] == 0
               for e in telemetry.recent_events(events.EV_MOE_LOAD))
    fused = w.fused
    acc, conf = fused._fresh_acc()
    idx, mask = fused._superstep_arrays()
    text = jax.make_jaxpr(fused._train_step)(
        fused._params, fused._opt, acc, conf,
        w.loader.original_data.unmap(), fused._target_store(), idx,
        mask, fused._lr_rates_array(idx.shape[0]), fused._rng_counter
    ).pretty_print(name_stack=True)
    for scope in (f"fwd{FULL}_attention/jvp(attn/gate)",
                  f"fwd{FULL}_attention/transpose(jvp(attn/gate))",
                  f"fwd{SWIGLU}_swiglu/jvp(mlp/swiglu)",
                  f"fwd{SWIGLU}_swiglu/transpose(jvp(mlp/swiglu))",
                  f"fwd{WINDOW}_attention/jvp(attn/window)"):
        assert scope in text, scope


# -- the counts -------------------------------------------------------------

def test_the_flop_walk_counts_the_gate_and_the_dense_layer():
    """``flops_lm_gate`` on a two-layer list by hand, and on the tiny
    model as the program's own units count it."""
    small = [{"type": "embedding", "->": {"vocab_size": 16,
                                          "hidden_size": 8}},
             {"type": "attention", "->": {
                 "n_heads": 4, "n_kv_heads": 2, "head_size": 4,
                 "window": 3, "gate": True,
                 "rope": {"rope_type": "default", "rope_theta": 1e4}}},
             {"type": "dense", "->": {"output_size": 8}},
             {"type": "swiglu", "->": {"intermediate_size": 12}},
             {"type": "dense", "->": {"output_size": 8}},
             {"type": "lm_head", "->": {"vocab_size": 16,
                                        "n_pred_heads": 1}}]
    t = 5
    pairs = sum(min(n + 1, 3) for n in range(t))
    want = 2 * t * 8 * ((4 + 2 * 2) * 4 + 4) + 4 * 4 * 4 * pairs \
        + 2 * t * 16 * 8 + 4 * t * 8 * 12 + 2 * t * 12 * 8 \
        + 2 * t * 8 * 16
    assert flops_lm_gate.forward_flops_per_row(small, t) == want
    assert flops_lm_gate.train_flops_per_row(small, t) == 3 * want
    prng.seed_all(1)
    w = StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(np.zeros((2, T), np.int32),), minibatch_size=1,
            name="loader"),
        layers=LAYERS, loss_function="next_byte",
        decision_config={"max_epochs": 1}, name="count")
    w.initialize(device=make_device("cpu"))
    assert sum(f.mxu_flops_per_sample() for f in w.forwards) \
        == pytest.approx(flops_lm_gate.forward_flops_per_row(LAYERS, T))
    assert ref.param_count(laguna_layers()) == 811_029_504
    assert moe.MoE(None, name="m").routed_scale == 1.0
