"""ISSUE 34's layer types on the chip, at Mellum2's published widths in
bf16: the shipped flash kernel under a window of 1 024 keys on a row of
8 192 against the plain form every other platform runs (within the gap
that plain form itself keeps from an f32 run of the same mathematics:
the bf16 witness), forward and gradients, and the share of 16 of 64
experts on the shipped grouped matmul at 32 768-row buffers against
``ragged_dot``; then the cell's own sizes through ``StandardWorkflow``:
the journaled paths and one firing whose ``moe.load`` reads ``dropped``
0 at four rows.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu import events, prng, telemetry
from veles_tpu.models import mellum2
from veles_tpu.models.mellum2 import CUT, mellum2_layers
from veles_tpu.ops import attention, moe
from veles_tpu.ops.registry import forward_registry

T, WINDOW = CUT["seq_len"], 1024


def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_splash_core_under_a_window_matches_the_xla_core(tpu_device):
    keys = jax.random.split(jax.random.key(3434), 4)
    q = jax.random.normal(keys[0], (1, T, 4, 8, 128), jnp.float32) \
        / 128 ** 0.5
    k, v = (jax.random.normal(keys[i], (1, T, 4, 128), jnp.float32)
            for i in (1, 2))
    err = jax.random.normal(keys[3], q.shape, jnp.float32)

    def run(fn, dtype):
        @jax.jit
        def both(err, *args):
            out, back = jax.vjp(fn, *(a.astype(dtype) for a in args))
            return (out,) + back(err.astype(dtype))
        out = both(err, q, k, v)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        jax.block_until_ready(both(err, q, k, v))
        return out, time.perf_counter() - t0

    with jax.default_matmul_precision("highest"):
        exact, _ = run(lambda *a: attention.core_xla(*a, window=WINDOW),
                       jnp.float32)
    xla, _ = run(lambda *a: attention.core_xla(*a, window=WINDOW),
                 jnp.bfloat16)
    splash, t_window = run(
        lambda *a: attention.core_splash(*a, 512, WINDOW), jnp.bfloat16)
    for name, e, x, s in zip(("o", "dq", "dk", "dv"), exact, xla, splash):
        assert s.shape == x.shape and s.dtype == x.dtype, name
        assert np.isfinite(np.asarray(s, np.float32)).all(), name
        witness, ours = _gap(x, e), _gap(s, e)
        assert ours <= 1.5 * witness + 2e-3, (name, ours, witness)
    # the window is in the result: the full causal core differs
    full, t_full = run(lambda *a: attention.core_splash(*a, 512),
                       jnp.bfloat16)
    assert _gap(full[0][:, WINDOW:], splash[0][:, WINDOW:]) > 1e-2
    assert _gap(full[0][:, :WINDOW], splash[0][:, :WINDOW]) < 2e-2
    # blocks left of the window are not visited: 45 of 136
    print(f"splash forward + backward, one row: window {t_window:.4f} s, "
          f"full {t_full:.4f} s")
    assert t_window < 0.6 * t_full, (t_window, t_full)


def test_gmm_experts_at_the_cells_buffers_match_ragged_dot(tpu_device):
    flat = [c for e in mellum2_layers() for c in e.get("layers", [e])]
    fw = dict(next(c for c in flat if c["type"] == "moe")["->"])
    fw.pop("weights_stddev")
    unit = forward_registry["moe"][0](None, name="u_moe", **fw)
    unit.device = tpu_device
    x = jax.random.normal(jax.random.key(1),
                          (CUT["minibatch"], T, 2304), jnp.bfloat16)
    params = {n: (0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.key(2), i), s)).astype(jnp.bfloat16)
        for i, (n, s) in enumerate(unit.param_shapes(x.shape).items())}
    assert set(params) == {"router", "w_gate", "w_up", "w_down"}
    err = jax.random.normal(jax.random.key(3), x.shape, jnp.bfloat16)

    def both(params, x, err):
        out, back = jax.vjp(unit.forward, params, x)
        return (out,) + back(err)

    fast = jax.jit(both)(params, x, err)
    assert (unit.share["form"], unit.share["rows"], unit.share["blocks"],
            unit.share["shared"]) == ("gmm", 32768, 8, False)
    assert unit.share["tiles"] == {"in": (512, 1024, 512),
                                   "out": (512, 512, 1024)}
    plain = moe.grouped_path
    try:
        moe.grouped_path = lambda *a, **k: {"form": "ragged_dot",
                                            "reason": "test"}
        slow = jax.jit(lambda *a: both(*a))(params, x, err)
    finally:
        moe.grouped_path = plain
    assert unit.share["form"] == "ragged_dot"
    for a, b in zip(jax.tree.leaves(fast), jax.tree.leaves(slow)):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        assert _gap(a, b) <= 2e-2, _gap(a, b)
    got = jax.device_get(jax.jit(unit.probe)(params, x))
    assert int(got["dropped"]) == 0
    # 32 768 x 8 x 16 / 64 = 65 536 pairs expected on this share:
    # 4 096 rows an expert
    assert 50_000 < int(got["expert_rows"].sum()) < 80_000
    assert 2_000 < int(got["expert_rows"].min()) \
        and int(got["expert_rows"].max()) < 8_000


def test_compact_buffers_match_the_whole_buffers(tpu_device):
    """ISSUE 35: one dispatch block of 4 096 tokens at the cell's
    shapes through the compact buffers (12288 rows) against the
    whole ones (32768), forward + backward, equal to rounding (the
    operands are the same bf16 values, a token's slots are summed in
    f32 in another order), the two times printed; under a router that
    sends every token here the block overflows and walks its pairs a
    bufferful at a time: the layer without compaction's numbers."""
    import time
    flat = [c for e in mellum2_layers() for c in e.get("layers", [e])]
    fw = dict(next(c for c in flat if c["type"] == "moe")["->"])
    fw.pop("weights_stddev")
    unit = forward_registry["moe"][0](None, name="u_moe", **fw)
    unit.device = tpu_device
    x = jax.random.normal(jax.random.key(1), (1, 4096, 2304),
                          jnp.bfloat16)
    params = {n: (0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.key(2), i), s)).astype(jnp.bfloat16)
        for i, (n, s) in enumerate(unit.param_shapes(x.shape).items())}
    err = jax.random.normal(jax.random.key(3), x.shape, jnp.bfloat16)
    here = dict(params, router=params["router"].at[:, :16].add(1.0))

    def both(params, x, err):
        out, back = jax.vjp(unit.forward, params, x)
        return (out,) + back(err)

    def timed(f, *a):
        jax.block_until_ready(f(*a))
        t0 = time.perf_counter()
        for _ in range(10):
            out = f(*a)
        jax.block_until_ready(out)
        return out, (time.perf_counter() - t0) / 10

    compact, t_compact = timed(jax.jit(both), params, x, err)
    assert (unit.share["rows"], unit.share["blocks"],
            unit.share["capacity"]) == (32768, 1, 12288)
    over, t_over = timed(jax.jit(both), here, jnp.abs(x), err)
    load = unit.report_probe(jax.device_get(
        jax.jit(unit.probe)(here, jnp.abs(x))))
    assert (load["over_capacity_blocks"], load["blocks"],
            load["dropped"]) == (1, 1, 0)
    plain = moe.dispatch_capacity
    try:
        moe.dispatch_capacity = lambda *a, **k: None
        whole, t_whole = timed(jax.jit(lambda *a: both(*a)),
                               params, x, err)
        assert unit.share["capacity"] is None
        over_whole = jax.jit(lambda *a: both(*a))(here, jnp.abs(x), err)
    finally:
        moe.dispatch_capacity = plain
    print(f"one block forward + backward: compact {t_compact:.5f} s, "
          f"whole {t_whole:.5f} s, overflowing {t_over:.5f} s")
    for a, b in zip(jax.tree.leaves(compact), jax.tree.leaves(whole)):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        assert _gap(a, b) <= 1e-2, _gap(a, b)
    # (pieces' parts are added in f32, a gradient's in bf16)
    for a, b in zip(jax.tree.leaves(over), jax.tree.leaves(over_whole)):
        assert _gap(a, b) <= 2e-2, _gap(a, b)
    assert t_compact < t_whole, (t_compact, t_whole)


def test_the_cells_sizes_take_the_chip_forms_and_drop_nothing(
        tpu_device):
    """``veles_tpu/models/mellum2.py`` as the cell runs it: 4 layers,
    16 of 64 experts, 24 576 ids, four rows of 8 192; one firing."""
    telemetry.reset()
    prng.seed_all(34)

    class Launcher:
        workflow = None

    w = mellum2.create_workflow(
        Launcher(), loader=dict(mellum2.DEFAULTS["loader"], n_train=4),
        superstep=1, decision={"max_epochs": 1})
    w.initialize(device=tpu_device)
    seen = telemetry.recent_events(events.EV_ATTN_PATH)
    assert [(e["form"], e["window"], e["rope"], e["kv_blocks"])
            for e in seen] == [("splash", 1024, "default", 3)] * 3 + [
        ("splash", None, "yarn", 16)]
    assert telemetry.gauge(events.GAUGE_ATTN_WINDOW_LAYERS).value == 3
    shares = telemetry.recent_events(events.EV_MOE_SHARE)
    assert len(shares) == 4 and all(
        (e["form"], e["experts_total"], e["experts_held"], e["top_k"],
         e["rows"], e["blocks"], e["capacity"], e["shared"]) == (
            "gmm", 64, 16, 8, 32768, 8, 12288, False) for e in shares)
    blocked = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (blocked["blocks"], blocked["reason"]) == (
        32, "whole_exceeds_free")
    print(telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1])
    w.loader.run()
    w.fused.run()
    jax.block_until_ready(w.fused._params)
    loads = telemetry.recent_events(events.EV_MOE_LOAD)
    assert len(loads) == 4
    for e in loads:
        assert e["dropped"] == 0
        # 32 768 x 8 / 4 = 65 536 expected; never all, never none
        assert 40_000 < e["local_assignments"] < 100_000, e
        # 8 192 pairs a block expected in buffers of 12 288: a seed's
        # router may send a block over (it then takes the whole ones)
        assert e["blocks"] == 8 and 0 <= e["over_capacity_blocks"] <= 8
        print(e)
    assert telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).value == 0
    _, loss_sum, count, _ = w.fused.take_class_metrics()
    assert count == CUT["minibatch"] * (CUT["seq_len"] - 1)
    assert abs(loss_sum / count - np.log(CUT["vocab_held"])) < 0.5
    w.stop()
