"""The three layer types of ISSUE 32 on the chip, at Qwen3-Next's
published widths in bf16: each TPU form (the chunked delta rule, its
chunk products by the XLA form and by the fused kernels of ISSUE 33,
its scan over chunks by a ``lax.scan`` and by the kernels of ISSUE 38;
the shipped flash kernel under ``gated_attention``; the shipped grouped
matmul under ``moe``) against the plain form every other platform runs,
each within the gap that plain form itself keeps from an f32 run of the
same mathematics (the bf16 witness); and the cell's own sizes through
``StandardWorkflow``: the journaled paths, the blocked loss, and one
firing whose ``moe.load`` reads ``dropped`` 0.
"""

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu import events, prng, telemetry
from veles_tpu.models import qwen3next
from veles_tpu.models.qwen3next import CUT, PUBLISHED, qwen3next_layers
from veles_tpu.ops import attention, deltanet, moe
from veles_tpu.ops.registry import forward_registry

T = 4096


def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _unit(kind):
    flat = [c for e in qwen3next_layers() for c in e.get("layers", [e])]
    fw = dict(next(c for c in flat if c["type"] == kind)["->"])
    fw.pop("weights_stddev")
    return forward_registry[kind][0](None, name="u_" + kind, **fw)


def test_splash_core_matches_the_xla_core(tpu_device):
    keys = jax.random.split(jax.random.key(3232), 4)
    q = jax.random.normal(keys[0], (1, T, 2, 8, 256), jnp.float32) / 16.0
    k, v = (jax.random.normal(keys[i], (1, T, 2, 256), jnp.float32)
            for i in (1, 2))
    err = jax.random.normal(keys[3], q.shape, jnp.float32)

    def run(fn, dtype):
        @jax.jit
        def both(err, *args):
            out, back = jax.vjp(fn, *(a.astype(dtype) for a in args))
            return (out,) + back(err.astype(dtype))
        return both(err, q, k, v)

    with jax.default_matmul_precision("highest"):
        exact = run(attention.core_xla, jnp.float32)
    xla = run(attention.core_xla, jnp.bfloat16)
    splash = run(lambda *a: attention.core_splash(*a, 512), jnp.bfloat16)
    for name, e, x, s in zip(("o", "dq", "dk", "dv"), exact, xla, splash):
        assert s.shape == x.shape and s.dtype == x.dtype, name
        assert np.isfinite(np.asarray(s, np.float32)).all(), name
        witness, ours = _gap(x, e), _gap(s, e)
        assert ours <= 1.5 * witness + 2e-3, (name, ours, witness)


def test_gmm_experts_match_ragged_dot(tpu_device):
    unit = _unit("moe")
    unit.device = tpu_device
    x = jax.random.normal(jax.random.key(1), (1, T, 2048), jnp.bfloat16)
    params = {n: (0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.key(2), i), s)).astype(jnp.bfloat16)
        for i, (n, s) in enumerate(unit.param_shapes(x.shape).items())}
    err = jax.random.normal(jax.random.key(3), x.shape, jnp.bfloat16)

    def both(params, x, err):
        out, back = jax.vjp(unit.forward, params, x)
        return (out,) + back(err)

    fast = jax.jit(both)(params, x, err)
    assert unit.share["form"] == "gmm" and unit.share["rows"] == T * 10
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
    # about 4096 x 10 x 32 / 512 = 2560 pairs land on this share
    assert 1500 < int(got["expert_rows"].sum()) < 4000


def test_compact_buffers_match_the_whole_buffers(tpu_device):
    """ISSUE 35: one dispatch block of 4 096 tokens at the cell's
    shapes through the compact buffers (4096 rows) against the
    whole ones (40960), forward + backward, equal to rounding (the
    operands are the same bf16 values, a token's slots are summed in
    f32 in another order), the two times printed; under a router that
    sends every token here the block overflows and walks its pairs a
    bufferful at a time: the layer without compaction's numbers."""
    import time
    unit = _unit("moe")
    unit.device = tpu_device
    x = jax.random.normal(jax.random.key(1), (1, 4096, 2048),
                          jnp.bfloat16)
    params = {n: (0.02 * jax.random.normal(
        jax.random.fold_in(jax.random.key(2), i), s)).astype(jnp.bfloat16)
        for i, (n, s) in enumerate(unit.param_shapes(x.shape).items())}
    err = jax.random.normal(jax.random.key(3), x.shape, jnp.bfloat16)
    here = dict(params, router=params["router"].at[:, :32].add(1.0))

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
            unit.share["capacity"]) == (40960, 1, 4096)
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


def _rule_inputs(seed):
    """q, k (unit, q scaled), v, g, beta of one row at the published
    widths, f32."""
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda a: a / jnp.linalg.norm(  # noqa: E731
        a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, T, 16, 128))) / 128 ** 0.5
    k = unit(jax.random.normal(ks[1], (1, T, 16, 128)))
    v = jax.random.normal(ks[2], (1, T, 32, 128))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, T, 32))) * 0.2
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, T, 32)))
    return q, k, v, g, beta


def test_chunked_rule_matches_the_recurrence(tpu_device):
    q, k, v, g, beta = _rule_inputs(7)
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(deltanet.rule_recurrent)(q, k, v, g, beta)
    path = deltanet.products_path("tpu", 64, 128, 128, 2)
    assert path["products"] == "fused", path
    gaps = {}
    for name, tiles in (("xla", None), ("fused", path["tiles"])):
        got = jax.jit(lambda *a, tiles=tiles: deltanet.rule_chunked(
            *a, 64, jnp.bfloat16, tiles))(q, k, v, g, beta)
        assert np.isfinite(np.asarray(got, np.float32)).all(), name
        gaps[name] = _gap(got, exact)
    print("chunked rule against the recurrence:", gaps)
    assert gaps["xla"] <= 3e-2 and gaps["fused"] <= 3e-2, gaps
    # the kernels make the same three-pass products: no further off
    assert gaps["fused"] <= 1.25 * gaps["xla"] + 1e-3, gaps


def test_fused_chunk_products_match_the_xla_form(tpu_device):
    """The two kernels of ``ops/deltanet_pallas.py`` at the published
    widths against ``_chunk_products`` and ``jax.vjp`` of it, both in
    bf16 on the chip: the four results and the five cotangents within
    the rounding of their dtype."""
    parts = deltanet.chunk_parts(*_rule_inputs(33), 64, jnp.bfloat16)
    tiles = deltanet.products_path("tpu", 64, 128, 128, 2)["tiles"]

    def both(tiles):
        @jax.jit
        def run(parts, cots):
            out, back = jax.vjp(
                lambda *a: deltanet.products_of(a, tiles), *parts)
            return out, back(cots)
        return run

    shapes = jax.eval_shape(lambda *a: deltanet.products_of(a), *parts)
    cots = tuple(jax.random.normal(jax.random.key(40 + i), s.shape, s.dtype)
                 for i, s in enumerate(shapes))
    want, mine = both(None)(parts, cots), both(tiles)(parts, cots)
    names = ("u", "w", "a_qk", "gsum", "dq", "dk", "dv", "dg", "dbeta")
    for name, a, b in zip(names, jax.tree.leaves(mine),
                          jax.tree.leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        limit = 1e-4 if a.dtype == jnp.float32 else 2e-2
        assert _gap(a, b) <= limit, (name, _gap(a, b))


def test_scan_kernels_match_the_xla_scan(tpu_device):
    """ISSUE 38: ``gdn_scan_fwd`` / ``gdn_scan_bwd`` at the published
    widths against the XLA scan and ``jax.vjp`` of it, both in bf16 on
    the chip, over the fused products of the same row: o and the six
    cotangents within the rounding of bf16 (the XLA
    transposition rounds its products' results to bf16, the kernels
    accumulate them in f32), and the whole rule both ways against the
    f32 recurrence no further off than the XLA scan's."""
    q, k, v, g, beta = _rule_inputs(38)
    parts = deltanet.chunk_parts(q, k, v, g, beta, 64, jnp.bfloat16)
    tiles = deltanet.products_path("tpu", 64, 128, 128, 2)["tiles"]
    scan = deltanet.scan_path("tpu", 64, 128, 128, 16, 2)
    assert scan["scan"] == "fused" and scan["scan_tiles"].heads == 16
    made = jax.jit(lambda *a: deltanet.products_of(a, tiles))(*parts)
    args = tuple(made) + parts[:2]
    do = jax.random.normal(jax.random.key(5), made[0].shape, jnp.bfloat16)

    def both(scan_tiles):
        @jax.jit
        def run(args, do):
            out, back = jax.vjp(lambda *a: deltanet.scan_of(
                a[:4], a[4], a[5], scan_tiles), *args)
            return (out,) + back(do)
        return run

    want, mine = both(None)(args, do), both(scan["scan_tiles"])(args, do)
    lower = jnp.tril(jnp.ones((64, 64), bool))
    for name, a, b in zip(("o", "du", "dw", "da_qk", "dgsum", "dq", "dk"),
                          mine, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        if name == "da_qk":
            b = jnp.where(lower, b, 0)
        assert _gap(a, b) <= 2e-2, (name, _gap(a, b))
    with jax.default_matmul_precision("highest"):
        exact = jax.jit(deltanet.rule_recurrent)(q, k, v, g, beta)
    gaps = {}
    for name, s in (("xla", None), ("fused", scan["scan_tiles"])):
        got = jax.jit(lambda *a, s=s: deltanet.rule_chunked(
            *a, 64, jnp.bfloat16, tiles, scan=s))(q, k, v, g, beta)
        gaps[name] = _gap(got, exact)
    print("chunked rule against the recurrence, scan by:", gaps)
    assert gaps["fused"] <= 1.25 * gaps["xla"] + 1e-3, gaps


def test_the_cells_sizes_take_the_chip_forms_and_drop_nothing(
        tpu_device):
    """``veles_tpu/models/qwen3next.py`` as the cell runs it: 4 layers,
    32 of 512 experts, 18 992 ids, a row of 32 768; one firing."""
    telemetry.reset()
    prng.seed_all(32)

    class Launcher:
        workflow = None

    w = qwen3next.create_workflow(
        Launcher(), loader=dict(qwen3next.DEFAULTS["loader"], n_train=2),
        superstep=1, decision={"max_epochs": 1})
    w.initialize(device=tpu_device)
    assert [(e["form"], e["products"], e["scan"], e["scan_tiles"])
            for e in telemetry.recent_events(events.EV_GDN_PATH)] == [
        ("chunked", "fused", "fused", {"heads": 16, "unroll": 2})] * 3
    assert telemetry.gauge(events.GAUGE_GDN_FUSED_LAYERS).value == 3
    assert telemetry.gauge(events.GAUGE_GDN_FUSED_SCANS).value == 3
    seen = telemetry.recent_events(events.EV_ATTN_PATH)
    assert [(e["form"], e["tiles"]["block_q"]) for e in seen] == [
        ("splash", 512)]
    embeds = telemetry.recent_events(events.EV_EMBED_PATH)
    assert [(e["form"], e["backward"], e["vocab"], e["hidden"], e["rows"])
            for e in embeds] == [("gather", "segment_sum", 18992, 2048,
                                  32768)]
    assert telemetry.gauge(events.GAUGE_EMBED_GATHER_LAYERS).value == 1
    shares = telemetry.recent_events(events.EV_MOE_SHARE)
    assert len(shares) == 4 and all(
        (e["form"], e["experts_total"], e["experts_held"], e["top_k"],
         e["rows"], e["blocks"], e["capacity"]) == (
            "gmm", 512, 32, 10, 40960, 8, 4096) for e in shares)
    blocked = telemetry.recent_events(events.EV_LOSS_BLOCKED)[-1]
    assert (blocked["blocks"], blocked["reason"]) == (
        16, "whole_exceeds_free")
    assert telemetry.recent_events(events.EV_FUSED_RECOMPUTE)[-1][
        "policy"] == "recompute"
    w.loader.run()
    w.fused.run()
    jax.block_until_ready(w.fused._params)
    loads = telemetry.recent_events(events.EV_MOE_LOAD)
    assert len(loads) == 4
    for e in loads:
        assert e["dropped"] == 0
        # 32 768 x 10 / 16 = 20 480 expected; never all, never none
        assert 10_000 < e["local_assignments"] < 40_000, e
        # 2 560 pairs a block expected in buffers of 4 096
        assert (e["over_capacity_blocks"], e["blocks"]) == (0, 8), e
    assert telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).value == 0
    assert telemetry.gauge(
        events.GAUGE_MOE_OVER_CAPACITY_BLOCKS).value == 0
    _, loss_sum, count, _ = w.fused.take_class_metrics()
    assert count == CUT["seq_len"] - 1
    assert abs(loss_sum / count - np.log(CUT["vocab_held"])) < 0.5
    assert PUBLISHED["num_experts"] == 512
    w.stop()
