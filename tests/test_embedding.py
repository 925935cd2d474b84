"""The token embedding's two forms (``ops/sequence.py``): the gather
(``gather_embed``: the rows taken, the table's gradient a sum of each
id's error rows by XLA's scatter-add or by the Pallas segment sum of
``ops/embed_pallas.py``, here in interpret mode) against the one-hot
product the parent ran, and the rule ``embed_path`` that chooses
between them from shapes."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from veles_tpu import events, prng, telemetry  # noqa: E402
from veles_tpu.backends import make_device  # noqa: E402
from veles_tpu.loader import ArrayLoader  # noqa: E402
from veles_tpu.models import evabyte  # noqa: E402
from veles_tpu.ops import embed_pallas  # noqa: E402
from veles_tpu.ops import sequence as seq  # noqa: E402
from veles_tpu.ops.standard_workflow import StandardWorkflow  # noqa: E402

V, H, ROWS, T = 300, 128, 2, 200
BACKWARDS = ("scatter", "segment_sum")


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _onehot(w, ids):
    return jnp.einsum("btv,vh->bth",
                      jax.nn.one_hot(ids, w.shape[0], dtype=w.dtype), w)


def _gather(backward):
    return lambda w, ids: seq.gather_embed(w, ids, backward,
                                           interpret=True)


def _both(fn, w, ids, err):
    out, back = jax.vjp(lambda w: fn(w, ids), w)
    return out, back(err)[0]


def _case(dtype, low=0, high=V, seed=0):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(low, high, (ROWS, T)), jnp.int32)
    w = jnp.asarray(rng.standard_normal((V, H)), dtype)
    err = jnp.asarray(rng.standard_normal((ROWS, T, H)), dtype)
    return w, ids, err


def _close_f32(got, want):
    """Equal within f32 rounding: a row is a sum in another order."""
    scale = float(np.abs(np.asarray(want)).max())
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6 * scale)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_lookup_is_the_one_hot_product_bit_for_bit(dtype):
    w, ids, _ = _case(dtype, -7, V + 7)
    want = _onehot(w, ids)
    got = seq.take_rows(w, ids)
    assert got.dtype == want.dtype
    assert bool(jnp.array_equal(got, want))


@pytest.mark.parametrize("backward", BACKWARDS)
def test_the_table_gradient_matches_the_one_hot_in_f32(backward):
    w, ids, err = _case(jnp.float32)
    want_y, want = _both(_onehot, w, ids, err)
    got_y, got = _both(_gather(backward), w, ids, err)
    assert bool(jnp.array_equal(got_y, want_y))
    assert got.dtype == jnp.float32
    _close_f32(got, want)


@pytest.mark.parametrize("backward", BACKWARDS)
def test_the_table_gradient_in_bf16_is_the_f32_sum_rounded(backward):
    """Both forms add a row's error rows in f32 and round once."""
    w, ids, err = _case(jnp.bfloat16, seed=1)
    _, want = _both(_onehot, w, ids, err)
    _, got = _both(_gather(backward), w, ids, err)
    assert got.dtype == jnp.bfloat16
    exact = np.zeros((V, H))
    np.add.at(exact, np.asarray(ids).reshape(-1),
              np.asarray(err, np.float64).reshape(-1, H))
    for a in (got, want):
        np.testing.assert_allclose(np.asarray(a, np.float64), exact,
                                   rtol=2 ** -8, atol=1e-6)


@pytest.mark.parametrize("backward", BACKWARDS)
def test_ids_outside_the_table_give_zero_rows_and_no_gradient(backward):
    w, ids, err = _case(jnp.float32, seed=2)
    ids = ids.at[0, :50].set(V).at[0, 50:90].set(V + 1000) \
        .at[1, :40].set(-1).at[1, 40:60].set(-V)
    y, dw = _both(_gather(backward), w, ids, err)
    outside = np.asarray((ids < 0) | (ids >= V))
    assert not np.asarray(y)[outside].any()
    inside = np.asarray(ids)[~outside]
    want = np.zeros((V, H))
    np.add.at(want, inside, np.asarray(err, np.float64)[~outside])
    _close_f32(dw, want)
    _, want_onehot = _both(_onehot, w, ids, err)
    _close_f32(dw, want_onehot)


@pytest.mark.parametrize("backward", BACKWARDS)
def test_all_ids_equal(backward):
    """The worst collisions: every error row lands on one table row."""
    w, _, err = _case(jnp.float32, seed=3)
    ids = jnp.full((ROWS, T), 257, jnp.int32)
    y, dw = _both(_gather(backward), w, ids, err)
    assert bool(jnp.array_equal(y, jnp.broadcast_to(w[257], y.shape)))
    _, want = _both(_onehot, w, ids, err)
    _close_f32(dw, want)
    others = np.delete(np.asarray(dw), 257, axis=0)
    assert not others.any()


@pytest.mark.parametrize("tiles", [embed_pallas.SegmentTiles(64, 128),
                                   embed_pallas.SegmentTiles(128, 256),
                                   embed_pallas.SegmentTiles(512, 128)])
def test_the_segment_sum_at_tiles_that_cut_the_table_and_the_rows(tiles):
    """A table of 300 rows is no whole number of blocks, and 400 ids
    no whole number of chunks: the kernel's output is cut back and its
    rows padded; skewed ids leave most blocks empty."""
    rng = np.random.default_rng(4)
    ids = jnp.asarray(np.concatenate([rng.integers(0, 8, 300),
                                      rng.integers(-3, V + 3, 100)]),
                      jnp.int32)
    err = jnp.asarray(rng.standard_normal((400, H)), jnp.float32)
    got = embed_pallas.embed_segment_sum(ids, err, V, tiles,
                                         interpret=True)
    _close_f32(got, seq.scatter_rows(ids, err, V))


def test_under_vmap_over_a_member_axis():
    """A GA cohort's members: a table each over the same ids, the
    gather form under ``vmap`` with the backward the rule gives it
    there (``scatter``: the kernel's schedule has no member axis)."""
    assert seq.embed_path(24576, 2304, 32768, "tpu", batched=True) == {
        "form": "gather", "backward": "scatter", "reason": "batched"}
    members = 3
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.standard_normal((members, V, H)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, (ROWS, T)), jnp.int32)
    err = jnp.asarray(rng.standard_normal((members, ROWS, T, H)),
                      jnp.float32)

    def one(fn):
        return jax.vmap(lambda w, e: _both(fn, w, ids, e))(w, err)

    want_y, want = one(_onehot)
    got_y, got = one(_gather("scatter"))
    assert bool(jnp.array_equal(got_y, want_y))
    _close_f32(got, want)


def test_the_unit_under_vmap_takes_the_gather_and_its_batched_rule(
        monkeypatch):
    """The unit itself under ``vmap``: on a TPU the rule would give the
    gather form the XLA backward (reason ``batched``), and the result
    is the one-hot's for every member."""
    monkeypatch.setattr(seq.SequenceUnit, "platform", lambda self: "tpu")
    vocab, hidden, rows, t = 4096, 8, 4, 512
    unit = seq.Embedding(None, name="emb", vocab_size=vocab,
                         hidden_size=hidden)
    rng = np.random.default_rng(6)
    w = jnp.asarray(rng.standard_normal((2, vocab, hidden)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, vocab, (rows, t)), jnp.int32)
    err = jnp.asarray(rng.standard_normal((2, rows, t, hidden)),
                      jnp.float32)

    def member(w, e):
        y, back = unit.apply_fwd({"weights": w}, ids)
        return y, back(e)[0]["weights"]

    y, dw = jax.vmap(member)(w, err)
    assert unit.path == {"form": "gather", "backward": "scatter",
                         "reason": "batched"}
    want_y, want = jax.vmap(lambda w, e: _both(_onehot, w, ids, e))(w, err)
    assert bool(jnp.array_equal(y, want_y))
    _close_f32(dw, want)


@pytest.mark.parametrize("vocab,hidden,n,form", [
    (320, 4096, 32768, "onehot"),        # EvaByte
    (24576, 2304, 32768, "gather"),      # mellum2
    (18992, 2048, 32768, "gather"),      # qwen3next
    (12544, 3072, 32768, "gather"),      # laguna
])
def test_the_rule_at_the_cells_shapes(vocab, hidden, n, form):
    path = seq.embed_path(vocab, hidden, n, "tpu")
    assert path["form"] == form, path
    assert path == ({"form": "onehot", "reason": "cost",
                     "backward": "onehot"} if form == "onehot" else
                    {"form": "gather", "backward": "segment_sum"})
    if form == "gather":
        assert seq.embed_path(vocab, hidden, n, "cpu") == {
            "form": "gather", "backward": "scatter", "reason": "platform"}


@pytest.mark.parametrize("vocab,hidden,n", [
    (8, 16, 64), (16, 32, 128), (64, 64, 256), (320, 64, 256)])
def test_the_tiny_presets_keep_the_one_hot(vocab, hidden, n):
    assert seq.embed_path(vocab, hidden, n, "tpu")["form"] == "onehot"


def _workflow(vocab, hidden, rows):
    prng.seed_all(44)
    gd = {"learning_rate": 0.5}
    return StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=rows.shape[0],
            name="loader"),
        layers=[{"type": "embedding",
                 "->": {"vocab_size": vocab, "hidden_size": hidden},
                 "<-": gd},
                {"type": "lm_head",
                 "->": {"vocab_size": vocab, "n_pred_heads": 1}, "<-": gd}],
        loss_function="next_byte", decision_config={"max_epochs": 1},
        superstep=2, name="embed_tiny")


def _trained(rows, vocab, hidden):
    telemetry.reset()
    w = _workflow(vocab, hidden, rows)
    w.initialize(device=make_device("cpu"))
    w.loader.run()
    w.fused.run()
    table = np.asarray(w.fused._params[w.forwards[0].name]["weights"])
    w.stop()
    return table


def test_a_step_journals_the_gather_and_trains_as_the_one_hot(
        monkeypatch):
    """Two SGD steps of an embedding + head over a table of 4 096 rows
    (the rule's gather at 2 048 ids a step): ``embed.path`` once, the
    gauge, and the table the one-hot form trains, within f32 rounding."""
    vocab, hidden = 4096, 8
    rows = np.asarray(np.random.default_rng(7).integers(
        0, vocab, (4, 512)), np.int32)
    got = _trained(rows, vocab, hidden)
    seen = telemetry.recent_events(events.EV_EMBED_PATH)
    assert len(seen) == 1, seen
    e = seen[0]
    assert (e["form"], e["backward"], e["reason"], e["vocab"], e["hidden"],
            e["rows"]) == ("gather", "scatter", "platform", vocab, hidden,
                           2048), e
    assert telemetry.gauge(events.GAUGE_EMBED_GATHER_LAYERS).value == 1
    monkeypatch.setattr(seq, "embed_path", lambda *a, **k: {
        "form": "onehot", "reason": "cost", "backward": "onehot"})
    want = _trained(rows, vocab, hidden)
    assert telemetry.gauge(events.GAUGE_EMBED_GATHER_LAYERS).value == 0
    _close_f32(got, want)


def test_evabytes_tiny_step_journals_the_one_hot():
    rows = np.asarray(np.random.default_rng(8).integers(
        0, 320, (2, evabyte.TINY["seq_len"])), np.int32)
    telemetry.reset()
    w = StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=2, name="loader"),
        layers=evabyte.evabyte_layers(**evabyte.TINY),
        loss_function="next_byte", decision_config={"max_epochs": 1},
        name="eva_tiny")
    w.initialize(device=make_device("cpu"))
    (e,) = telemetry.recent_events(events.EV_EMBED_PATH)
    assert (e["form"], e["reason"], e["backward"], e["vocab"]) == (
        "onehot", "cost", "onehot", 320), e
    assert telemetry.gauge(events.GAUGE_EMBED_GATHER_LAYERS).value == 0
    w.stop()
