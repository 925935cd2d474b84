"""ISSUE 28: the sequence op family, the residual chain with
recomputation, the next-byte loss and the integer resident store —
EvaByte at the tiny preset, on XLA:CPU in f32, against the plain
reference the benchmark also uses (``benchmarks/lib/
reference_evabyte.py``: no ``veles_tpu`` import, matmul precision
"highest")."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmarks.lib import reference_evabyte as ref  # noqa: E402
from veles_tpu import events, prng, telemetry  # noqa: E402
from veles_tpu.backends import make_device  # noqa: E402
from veles_tpu.engine import core as engine_core  # noqa: E402
from veles_tpu.loader import ArrayLoader  # noqa: E402
from veles_tpu.loader.synthetic import PackedBytesLoader  # noqa: E402
from veles_tpu.models.evabyte import TINY, evabyte_layers  # noqa: E402
from veles_tpu.ops import eva_pallas  # noqa: E402
from veles_tpu.ops import sequence as seq  # noqa: E402
from veles_tpu.ops.fused import FusedStepRunner  # noqa: E402
from veles_tpu.ops.registry import forward_registry  # noqa: E402
from veles_tpu.ops.standard_workflow import StandardWorkflow  # noqa: E402

T, ROWS = TINY["seq_len"], 2
LAYERS = evabyte_layers(**TINY)
FLAT = ref.flatten(LAYERS)
TYPES = ("embedding", "rmsnorm", "eva_attention", "dense", "swiglu",
         "lm_head")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _close(got, want, tol=1e-5):
    """Equal to ``tol`` of the array's own scale (f32 round-off grows
    with the largest term of a sum, not with each element)."""
    scale = max(1.0, float(np.abs(np.asarray(want)).max()))
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


def _unit(kind):
    """(unit, its config, parameter shapes, an input) of the first
    layer of that type in the tiny model."""
    i = next(i for i, c in enumerate(FLAT) if c["type"] == kind)
    cfg = FLAT[i]
    unit = forward_registry[kind][0](None, name=f"u_{kind}",
                                     **cfg["->"])
    shapes = ref.param_shapes(LAYERS)[i]
    key = jax.random.key(i)
    if kind == "embedding":
        x = jax.random.randint(key, (ROWS, T), 0, 320)
    else:
        width = {"rmsnorm": TINY["hidden_size"],
                 "eva_attention": TINY["hidden_size"],
                 "swiglu": TINY["hidden_size"],
                 "lm_head": TINY["hidden_size"],
                 "dense": TINY["n_heads"] * TINY["head_size"]}[kind]
        x = jax.random.normal(key, (ROWS, T, width), jnp.float32)
    params = {n: 0.3 * jax.random.normal(jax.random.fold_in(key, j), s)
              for j, (n, s) in enumerate(sorted(shapes.items()))}
    return unit, cfg, params, x


@pytest.mark.parametrize("kind", TYPES)
def test_layer_forward_and_backward_match_the_reference(kind):
    unit, cfg, params, x = _unit(kind)
    assert unit.param_shapes(x.shape) == {
        k: tuple(v.shape) for k, v in params.items()}
    want = ref.layer_forward(cfg, params, x)
    got, back = unit.apply_fwd(params, x, train=True)
    assert got.shape == unit.output_shape_for(x.shape)
    _close(got, want)
    # backward: the unit's closure against jax.grad of the reference
    err = jax.random.normal(jax.random.key(99), want.shape, jnp.float32)
    loss = lambda p, xx: jnp.sum(  # noqa: E731
        ref.layer_forward(cfg, p, xx) * err)
    gd = forward_registry[kind][1](None, forward=unit, name="gd")
    err_in, grads = gd.backward_from_saved(params, back, err)
    if kind == "embedding":
        want_p, want_x = jax.grad(loss)(params, x), None
        assert err_in is None
    else:
        want_p, want_x = jax.grad(loss, argnums=(0, 1))(params, x)
        _close(err_in, want_x)
    for name in params:
        _close(grads[name], want_p[name])


def _brute_force_rows(q, k, v, phi, mu, win, c):
    """Per query, in numpy float64: the local keys of its window up to
    itself, and the summaries of every chunk of every earlier window;
    q, k, v ``[rows, T, heads, d]``."""
    q, k, v, phi, mu = (np.asarray(a, np.float64)
                        for a in (q, k, v, phi, mu))
    b, t, nh, d = q.shape
    s = d ** -0.5
    out = np.zeros((b, t, nh, d))
    for r in range(b):
        for h in range(nh):
            ks, vs = [], []
            for j in range(t // c):
                kc, vc = k[r, j * c:(j + 1) * c, h], v[r, j * c:(j + 1) * c, h]
                a = np.exp(s * kc @ phi[h])
                a /= a.sum()
                ks.append(a @ kc + mu[h])
                vs.append(a @ vc)
            for n in range(t):
                w0 = (n // win) * win
                keys = [k[r, m, h] for m in range(w0, n + 1)] \
                    + ks[:w0 // c]
                vals = [v[r, m, h] for m in range(w0, n + 1)] \
                    + vs[:w0 // c]
                e = np.exp(s * np.asarray(keys) @ q[r, n, h])
                out[r, n, h] = (e / e.sum()) @ np.asarray(vals)
    return out


def _brute_force_eva(x, p, fw):
    """:func:`_brute_force_rows` behind the projections and RoPE."""
    x = np.asarray(x, np.float64)
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    b, t, _ = x.shape
    nh, d = fw["n_heads"], fw["head_size"]
    inv = fw["rope_theta"] ** (-np.arange(0, d, 2) / d)

    def rope(y):
        ang = np.arange(t)[:, None] * inv
        cos = np.concatenate([np.cos(ang)] * 2, -1)[None, :, None]
        sin = np.concatenate([np.sin(ang)] * 2, -1)[None, :, None]
        rot = np.concatenate([-y[..., d // 2:], y[..., :d // 2]], -1)
        return y * cos + rot * sin

    out = _brute_force_rows(
        rope((x @ p["wq"]).reshape(b, t, nh, d)),
        rope((x @ p["wk"]).reshape(b, t, nh, d)),
        (x @ p["wv"]).reshape(b, t, nh, d), p["phi"], p["mu"],
        min(fw["window_size"], t), fw["chunk_size"])
    return out.reshape(b, t, nh * d)


def test_eva_attention_matches_a_per_query_loop():
    unit, cfg, params, x = _unit("eva_attention")
    x = x[:1]
    got = unit.forward(params, x)
    np.testing.assert_allclose(got, _brute_force_eva(x, params, cfg["->"]),
                               rtol=2e-4, atol=2e-5)


def test_eva_attention_is_causal_softmax_inside_one_window():
    unit, cfg, params, x = _unit("eva_attention")
    t = TINY["window_size"]
    x = x[:, :t]
    nh, d = TINY["n_heads"], TINY["head_size"]
    heads = lambda w: (x @ w).reshape(ROWS, t, nh, d)  # noqa: E731
    q = seq.rope(heads(params["wq"]), TINY["rope_theta"])
    k = seq.rope(heads(params["wk"]), TINY["rope_theta"])
    score = jnp.einsum("bnhd,bmhd->bhnm", q, k) * d ** -0.5
    score = jnp.where(jnp.tril(jnp.ones((t, t), bool)), score, -jnp.inf)
    want = jnp.einsum("bhnm,bmhd->bnhd", jax.nn.softmax(score, -1),
                      heads(params["wv"])).reshape(ROWS, t, nh * d)
    np.testing.assert_allclose(unit.forward(params, x), want,
                               rtol=1e-5, atol=1e-5)


def test_eva_attention_is_causal():
    unit, _, params, x = _unit("eva_attention")
    n = 77                         # in the third window of four
    y0 = unit.forward(params, x)
    y1 = unit.forward(params, x.at[:, n].add(1.0))
    np.testing.assert_array_equal(y0[:, :n], y1[:, :n])
    assert float(jnp.abs(y0[:, n:] - y1[:, n:]).max()) > 1e-3


# -- the fused kernels (ISSUE 29), in Pallas interpret mode -----------------

FUSED_WIN, FUSED_CHUNK, FUSED_D = 256, 16, 128


def _attend(fused, tiles=None):
    """q, k, v, phi, mu -> the heads' outputs, by the kernels
    (interpret mode: the program itself never interprets) or by
    ``eva_rows`` (``eva_window``, a window at a time), as off the
    chip."""
    def attend(q, k, v, phi, mu):
        ks, vs = seq.eva_summaries(k, v, phi, mu, FUSED_CHUNK)
        if fused:
            return eva_pallas.eva_fused(q, k, v, ks, vs, FUSED_WIN,
                                        FUSED_CHUNK, tiles, True)
        return seq.eva_rows(q, k, v, ks, vs, FUSED_WIN, FUSED_CHUNK)
    return attend


@pytest.mark.parametrize("t,rows,tiles", [
    (256, 1, (128, 128, 16, 16)),     # the first window: no summaries
    (256, 2, (256, 128, 16, 16)),
    (1024, 1, (128, 128, 32, 16)),    # later windows: 16, 32, 48 of them
    (1024, 2, (256, 128, 32, 16)),    # ... a whole window a grid step
])
def test_fused_eva_matches_the_window_path_and_the_loop(t, rows, tiles):
    """Output and the gradients to q, k, v, phi, mu of the fused
    kernels against the ``eva_window`` path, and the output against
    the per-query loop: head size 128, windows of 256 = 1 or 2 query
    blocks of 1 or 2 key tiles, summaries in tiles of 32 with a tail
    of 16."""
    keys = jax.random.split(jax.random.key(t + rows), 6)
    q, k, v, err = (jax.random.normal(keys[i], (rows, t, 2, FUSED_D),
                                      jnp.float32) for i in range(4))
    phi, mu = (0.3 * jax.random.normal(keys[i], (2, FUSED_D),
                                       jnp.float32) for i in (4, 5))
    tiles = eva_pallas.Tiles(*tiles)
    got, back = jax.vjp(_attend(True, tiles), q, k, v, phi, mu)
    want, want_back = jax.vjp(_attend(False), q, k, v, phi, mu)
    _close(got, want)
    np.testing.assert_allclose(
        got, _brute_force_rows(q, k, v, phi, mu, FUSED_WIN, FUSED_CHUNK),
        rtol=2e-4, atol=2e-5)
    for name, g, w in zip(("q", "k", "v", "phi", "mu"), back(err),
                          want_back(err)):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        _close(g, w, 2e-5)


def test_fused_eva_refuses_tiles_that_do_not_tile():
    x = jnp.zeros((1, 512, 1, FUSED_D))
    s = jnp.zeros((1, 32, 1, FUSED_D))
    with pytest.raises(ValueError, match="do not tile"):
        eva_pallas.eva_fused(x, x, x, s, s, 256, 16,
                             eva_pallas.Tiles(128, 128, 48, 16), True)


@pytest.mark.parametrize("platform,head,win,chunk,t,batched,want", [
    ("cpu", 128, 2048, 16, 32768, False, ("xla", "platform")),
    ("tpu", 16, 32, 4, 128, False, ("xla", "head_size")),
    ("tpu", 128, 2048, 16, 32768, True, ("xla", "batched")),
    ("tpu", 128, 2048, 32, 32768, False, ("xla", "window")),
    ("tpu", 128, 96, 16, 192, False, ("xla", "window")),
    ("tpu", 128, 2048, 16, 32768, False, ("fused", None)),
    ("tpu", 128, 256, 2, 512, False, ("fused", None)),
])
def test_eva_path_is_chosen_from_platform_and_shapes(
        platform, head, win, chunk, t, batched, want):
    path = seq.eva_path(platform, head, win, chunk, t, batched)
    assert (path["path"], path.get("reason")) == want
    if want[0] == "fused":
        tiles = path["tiles"]
        assert tiles == eva_pallas.tiles_for(head, win, chunk, t)
        assert win % tiles.q == 0 and tiles.q % tiles.k == 0
        assert (win // chunk) % tiles.rs == 0 and tiles.r <= t // chunk
        if win == 2048:
            assert tiles == (2048, 512, 512, 128)


def test_eva_path_is_journaled_and_a_vmap_leaves_the_kernels():
    """On the CPU every unit journals ``xla`` / ``platform`` once at
    ``initialize``; a unit that believes it is on a TPU at head size
    128 takes the kernels, and under ``vmap`` (a cohort, an ensemble)
    falls back to the window path and says why."""
    from types import SimpleNamespace
    telemetry.reset()
    w = _workflow(_rows())
    w.initialize(device=make_device("cpu"))
    seen = telemetry.recent_events(events.EV_EVA_PATH)
    assert [(e["path"], e["reason"]) for e in seen] \
        == [("xla", "platform")] * TINY["n_layers"]
    assert len({e["unit"] for e in seen}) == TINY["n_layers"]
    assert telemetry.gauge(events.GAUGE_EVA_FUSED_LAYERS).value == 0
    w.stop()

    unit = forward_registry["eva_attention"][0](
        None, name="wide", n_heads=1, head_size=128, window_size=256,
        chunk_size=2)
    shapes = unit.param_shapes((1, 512, 8))
    params = {n: 0.3 * jax.random.normal(jax.random.key(j), (3,) + sh)
              for j, (n, sh) in enumerate(sorted(shapes.items()))}
    x = jax.random.normal(jax.random.key(9), (1, 512, 8), jnp.float32)
    one = lambda i: {n: p[i] for n, p in params.items()}  # noqa: E731
    want = jnp.stack([unit.forward(one(i), x) for i in range(3)])
    assert unit.path == {"path": "xla", "reason": "platform"}
    unit.device = SimpleNamespace(platform="tpu")
    assert unit._path(512)["path"] == "fused"
    got = jax.vmap(unit.forward, in_axes=(0, None))(params, x)
    _close(got, want)
    assert [(e["unit"], e["path"], e["reason"]) for e in
            telemetry.recent_events(events.EV_EVA_PATH)[-3:]] == [
        ("wide", "xla", "platform"), ("wide", "fused", None),
        ("wide", "xla", "batched")]
    assert seq.under_vmap(x) is False


# -- the whole model through StandardWorkflow -----------------------------

def _workflow(rows, superstep=2, mb=ROWS, layers=LAYERS, epochs=1):
    prng.seed_all(11)
    w = StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=mb, name="loader"),
        layers=layers, loss_function="next_byte",
        decision_config={"max_epochs": epochs}, superstep=superstep,
        name="evabyte_tiny")
    return w


def _rows(n=4):
    return np.asarray(jax.random.randint(jax.random.key(5), (n, T), 0,
                                         320), np.int32)


def _run_two_steps(monkeypatch, limit=None):
    """2 SGD steps (one firing of superstep 2) from seeded weights;
    returns (workflow, w0, the rows of each step)."""
    if limit is not None:
        monkeypatch.setattr(FusedStepRunner, "_device_bytes_limit",
                            lambda self: limit)
    rows = _rows()
    w = _workflow(rows)
    w.initialize(device=make_device("cpu"))
    w0 = ref.init_params(3, LAYERS, 0.05)
    for f, p in zip(w.forwards, w0):
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
    w, w0, fed = _run_two_steps(monkeypatch)
    chain = engine_core.chain_of(w.forwards)
    assert sum(isinstance(e, tuple) for e in chain) \
        == 2 * TINY["n_layers"]
    want = ref.follow(LAYERS, ref.init_params(3, LAYERS, 0.05), fed)
    _, loss_sum, count, _ = w.fused.take_class_metrics()
    assert count == 2 * ref.valid_count(ROWS, T, TINY["n_pred_heads"])
    assert loss_sum == pytest.approx(want["loss_sum"], rel=1e-5)
    params = [w.fused._params[f.name] for f in w.forwards]
    opt = [w.fused._opt[g.name] for g in w.gds]
    update = _norms([{k: p[k] - z[k] for k in p}
                     for p, z in zip(params, w0)])
    momentum = _norms(opt)
    assert set(update) == set(want["update"])
    for key in want["update"]:
        assert update[key] == pytest.approx(want["update"][key],
                                            rel=2e-4, abs=1e-9), key
        assert momentum[key] == pytest.approx(want["momentum"][key],
                                              rel=2e-4, abs=1e-9), key
    assert telemetry.counter(events.CTR_FUSED_TRAIN_TOKENS).value \
        == 2 * ROWS * T


def test_recomputation_on_and_off_give_the_same_step(monkeypatch):
    telemetry.reset()
    keep, _, _ = _run_two_steps(monkeypatch)
    ev = telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1]
    assert (ev["policy"], ev["reason"]) == ("keep", "no_limit")
    p_keep = jax.device_get(keep.fused._params)
    # a device so small that the kept residuals cannot fit
    rec, _, _ = _run_two_steps(monkeypatch, limit=1 << 20)
    ev = telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1]
    assert (ev["policy"], ev["reason"]) == ("recompute",
                                            "kept_exceeds_free")
    assert ev["blocks"] == 2 * TINY["n_layers"]
    assert 0 < ev["kept_bytes"] < ev["kept_bytes"] + ev["recomputed_bytes"]
    assert telemetry.gauge(
        events.GAUGE_FUSED_KEPT_ACTIVATION_BYTES).value == ev["kept_bytes"]
    p_rec = jax.device_get(rec.fused._params)
    for layer in p_keep:
        for name in p_keep[layer]:
            np.testing.assert_allclose(p_rec[layer][name],
                                       p_keep[layer][name],
                                       rtol=1e-6, atol=1e-8)
    # a roomy device keeps its residuals
    _run_two_steps(monkeypatch, limit=1 << 40)
    ev = telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1]
    assert (ev["policy"], ev["reason"]) == ("keep", "fits")


def test_model_file_trains_and_the_loss_falls():
    prng.seed_all(7)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedBytesLoader(
            wf, name="loader", n_train=4, seq_len=T, minibatch_size=2),
        layers=LAYERS, loss_function="next_byte",
        decision_config={"max_epochs": 4}, superstep=2, name="tiny")
    w.initialize(device=make_device("cpu"))
    w.run()
    losses = [h["loss"] for h in w.decision.history]
    assert len(losses) == 4 and losses[-1] < losses[0]
    assert all(np.isfinite(losses))
    assert telemetry.gauge(events.GAUGE_EVA_WINDOW).value == \
        TINY["window_size"]
    assert telemetry.gauge(events.GAUGE_EVA_SUMMARIES_PER_ROW).value == \
        T // TINY["chunk_size"]


def test_member_forward_walks_the_residual_chain():
    """The inference body of ensembles, the shadow and serving reads
    the skip paths off the units, and leaves ids 257...319 ids where
    the compute dtype is bf16 (which cannot hold them)."""
    w = _workflow(_rows())
    w.initialize(device=make_device("cpu"))
    w0 = ref.init_params(3, LAYERS, 0.05)
    params = {f.name: p for f, p in zip(w.forwards, w0)}
    ids = jnp.asarray(np.arange(ROWS * T, dtype=np.int32)
                      .reshape(ROWS, T) % 63 + 257)
    want = ref.model_logits(LAYERS, w0, ids)
    got = engine_core.build_member_forward(w.forwards, jnp.float32)(
        params, ids)
    np.testing.assert_allclose(got.reshape(want.shape), want,
                               rtol=1e-4, atol=1e-5)
    # in bf16 the ids must reach the embedding uncast: 257 and 258
    # are one bf16 number, so a cast row would read the same logits
    bf16 = engine_core.build_member_forward(w.forwards, jnp.bfloat16)(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16),
                               params), ids)
    np.testing.assert_allclose(bf16.reshape(want.shape), want,
                               atol=0.1)
    assert float(jnp.abs(bf16[:, 0] - bf16[:, 1]).max()) > 1e-3


def test_a_cohort_trains_the_residual_model():
    """``PopulationTrainEngine`` (the GA's cohort) walks the same
    chain and takes its targets from the rows: two members at the
    workflow's own rates read the single runner's error."""
    from veles_tpu.launcher import workflow_fitness
    from veles_tpu.ops.fused import PopulationTrainEngine

    rows = _rows()
    single = _workflow(rows, epochs=2)
    single.initialize(device=make_device("cpu"))
    single.run()
    want = workflow_fitness(single)
    single.stop()

    w = _workflow(rows, epochs=2)
    w.initialize(device=make_device("cpu"))
    one = np.asarray([[gd.learning_rate, gd.learning_rate_bias]
                      for gd in w.gds], np.float32)
    wd = np.asarray([[gd.weight_decay, gd.weight_decay_bias]
                     for gd in w.gds], np.float32)
    engine = PopulationTrainEngine(w, np.stack([one, 0.5 * one]),
                                   np.stack([wd, wd]))
    fits = engine.run()
    engine.release()
    w.stop()
    assert fits[0] == pytest.approx(want, rel=1e-5)
    assert np.isfinite(fits[1]) and fits[1] != fits[0]


def test_residual_entries_run_fused_only():
    w = _workflow(_rows())
    with pytest.raises(NotImplementedError, match="fused only"):
        w.initialize(device=None)


def test_native_export_refuses_a_residual_entry(tmp_path):
    from veles_tpu.export import export_model
    w = _workflow(_rows())
    with pytest.raises(ValueError, match="residual entry"):
        export_model(w, str(tmp_path / "m.vtpn"))


# -- the integer resident store -------------------------------------------

def test_integer_store_keeps_its_ids_through_reside_as():
    telemetry.reset()
    rows = np.tile(np.arange(257, 320, dtype=np.int32), 3)[:T][None] \
        .repeat(4, 0)
    w = _workflow(rows)
    w.fused.compute_dtype = jnp.bfloat16     # what a TPU resolves to
    w.initialize(device=make_device("cpu"))
    store = w.loader.original_data.devmem
    assert store.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(store), rows)
    ev = telemetry.recent_events(events.SPAN_LOADER_RESIDENT_DTYPE)[-1]
    assert ev["reason"] == "integer" and ev["from"] == ev["to"] == "int32"
    assert telemetry.counter(events.CTR_LOADER_RESIDENT_CASTS).value == 0
    # and the step's rows are the ids, not their bf16 roundings
    assert w.fused._target_store() is w.loader.original_data.devmem
    w.loader.run()
    w.fused.run()
    assert np.isfinite(w.fused.take_class_metrics()[1])


# -- a line stays the line --------------------------------------------------

def _parent_forward(forwards, seed, compute_dtype):
    """``build_forward`` as the parent commit had it (the line)."""
    mixed = compute_dtype != jnp.float32

    def forward_pass(params, x, rng_counter, train):
        residuals = []
        if mixed:
            with jax.named_scope("ingest"):
                x = x.astype(compute_dtype)
        for i, f in enumerate(forwards):
            with jax.named_scope("fwd/" + f.name):
                rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.key(seed),
                                       rng_counter), i) \
                    if f.stochastic else None
                x, res = f.apply_fwd(params[f.name], x, rng=rng,
                                     train=train)
            residuals.append(res)
        return x, residuals
    return forward_pass


def _parent_backward(forwards, gds, compute_dtype):
    """``build_backward`` as the parent commit had it."""
    n_fwd = len(forwards)
    first_gd = next((i for i, g in enumerate(gds) if g is not None), -1)
    mixed = compute_dtype != jnp.float32

    def backward_update(cparams, params, opt, residuals, err, lr,
                        wd=None):
        if mixed:
            err = err.astype(compute_dtype)
        new_params, new_opt = dict(params), dict(opt)
        for i in range(n_fwd - 1, -1, -1):
            f, gd = forwards[i], gds[i]
            if gd is None:
                continue
            with jax.named_scope("bwd/" + f.name):
                if i == first_gd and gd.can_skip_err_input:
                    _, grads = gd.backward_from_saved(
                        cparams[f.name], residuals[i], err,
                        need_err_input=False)
                    err_in = None
                else:
                    err_in, grads = gd.backward_from_saved(
                        cparams[f.name], residuals[i], err)
            if grads:
                with jax.named_scope("update/" + f.name):
                    p, v = gd.update_params(
                        params[f.name], grads, opt.get(gd.name, {}),
                        rates=(lr[i, 0], lr[i, 1]))
                new_params[f.name] = p
                if gd.name in opt:
                    new_opt[gd.name] = v
            err = err_in
        return new_params, new_opt
    return backward_update


@pytest.mark.parametrize("compute_dtype", [jnp.float32, jnp.bfloat16])
def test_tiny_alexnet_step_is_the_parents_program(monkeypatch,
                                                  compute_dtype):
    """The traced train step (jaxpr text) of a ``layers`` list with no
    residual entry, built by this chain and by the parent's."""
    from benchmarks.tests import tiny
    from veles_tpu.datasets import synthetic_classification

    def jaxpr_text(parent):
        if parent:
            monkeypatch.setattr(
                engine_core, "build_forward",
                lambda f, seed, cd, recompute=False, head_apart=False:
                _parent_forward(f, seed, cd))
            monkeypatch.setattr(
                engine_core, "build_backward",
                lambda f, g, cd, seed=0, exchange=None:
                _parent_backward(f, g, cd))
        prng.seed_all(4242)
        train, _, _ = synthetic_classification(
            64, 0, tuple(tiny.CFG["input_shape"]), n_classes=10, seed=5)
        w = StandardWorkflow(
            loader_factory=lambda wf: ArrayLoader(
                wf, train=train, minibatch_size=8, name="loader"),
            layers=tiny.CFG["layers"], loss_function="softmax",
            decision_config={"max_epochs": 1}, superstep=4, name="line")
        w.fused.compute_dtype = compute_dtype
        w.initialize(device=make_device("cpu"))
        assert not engine_core.has_residual(w.forwards)
        fused, ld = w.fused, w.loader
        ld.run()
        fused._ensure_params()
        acc, conf = fused._fresh_acc()
        k = ld.superstep_indices.shape[0]
        args = (fused._params, fused._opt, acc, conf,
                ld.original_data.unmap(), fused._target_store(),
                ld.superstep_indices, ld.superstep_mask,
                fused._lr_rates_array(k), 0)
        return str(jax.make_jaxpr(fused._train_step)(*args))

    ours = jaxpr_text(parent=False)
    theirs = jaxpr_text(parent=True)
    assert "optimization_barrier" not in ours
    assert ours == theirs
