"""Laguna-S-2.1's attention layers on the chip, at the published widths
in bf16 on one row of 8 192: a window layer (72 query heads over 8 key
heads, a window of 512 keys: the band kernels at group 9) and a full
layer (48 over 8, YaRN on half of each head: the shipped flash kernel
at group 6), each with
its gate a head, forward and gradients, against the plain form every
other platform runs — within the gap that plain form itself keeps from
an f32 run of the same mathematics (the bf16 witness); and the
embedding at the cell's table and four rows of 8 192 ids takes the
gather form.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu import events, telemetry
from veles_tpu.models.laguna import CUT, laguna_layers
from veles_tpu.ops import attention
from veles_tpu.ops.registry import forward_registry

T = CUT["seq_len"]
FLAT = [c for e in laguna_layers() for c in e.get("layers", [e])]


def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("window", [512, None], ids=["window", "full"])
def test_gated_layer_on_the_kernel_matches_the_xla_core(tpu_device,
                                                         monkeypatch,
                                                         window):
    fw = dict(next(c["->"] for c in FLAT if c["type"] == "attention"
                   and c["->"]["window"] == window))
    fw.pop("weights_stddev")
    unit = forward_registry["attention"][0](None, name="u_attn", **fw)
    unit.device = tpu_device
    group = fw["n_heads"] // fw["n_kv_heads"]
    assert (group, unit.rotary, unit.gate_kind) == (
        (9, 128) if window else (6, 64)) + ("per_head",)
    x = jax.random.normal(jax.random.key(1), (1, T, 3072), jnp.float32)
    params = {n: 0.02 * jax.random.normal(jax.random.fold_in(
        jax.random.key(2), i), s, jnp.float32)
        for i, (n, s) in enumerate(unit.param_shapes(x.shape).items())}
    assert set(params) == {"wq", "wk", "wv", "wg"}
    err = jax.random.normal(jax.random.key(3), (1, T, fw["n_heads"] * 128),
                            jnp.float32)

    def run(dtype):
        @jax.jit
        def both(params, x, err):
            cast = lambda a: a.astype(dtype)  # noqa: E731
            out, back = jax.vjp(unit.forward, jax.tree.map(cast, params),
                                cast(x))
            return (out,) + back(cast(err))
        out = both(params, x, err)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        jax.block_until_ready(both(params, x, err))
        return jax.tree.leaves(out), time.perf_counter() - t0

    splash, t_splash = run(jnp.bfloat16)
    assert unit.path["form"] == ("band" if window else "splash") \
        and unit.path["window"] == window
    if window:
        assert unit.path["tiles"] == {"block_q": 256, "slab": 768}
        assert unit.path["visited"] == 1.5
    else:
        assert unit.path["kv_blocks"] == T // 512
    plain = attention.attention_path
    monkeypatch.setattr(attention, "attention_path",
                        lambda *a, **k: dict(plain("cpu", *a[1:], **k)))
    with jax.default_matmul_precision("highest"):
        exact, _ = run(jnp.float32)
    xla, t_xla = run(jnp.bfloat16)
    assert unit.path["form"] == "xla"
    for i, (e, x_, s) in enumerate(zip(exact, xla, splash)):
        assert s.shape == x_.shape and s.dtype == x_.dtype, i
        assert np.isfinite(np.asarray(s, np.float32)).all(), i
        witness, ours = _gap(x_, e), _gap(s, e)
        assert ours <= 1.5 * witness + 2e-3, (i, ours, witness)
    print(f"gated attention layer, one row, forward + backward: kernel "
          f"{t_splash:.4f} s, xla {t_xla:.4f} s (group {group}, window "
          f"{window})")


def test_the_embedding_at_the_cells_sizes_takes_the_gather(tpu_device):
    fw = dict(FLAT[0]["->"])
    assert FLAT[0]["type"] == "embedding"
    fw.pop("weights_stddev")
    telemetry.reset()
    unit = forward_registry["embedding"][0](None, name="u_embed", **fw)
    unit.device = tpu_device
    unit._path(CUT["minibatch"] * T)
    (e,) = telemetry.recent_events(events.EV_EMBED_PATH)
    assert (e["form"], e["backward"], e["vocab"], e["hidden"]) == (
        "gather", "segment_sum", 12544, 3072), e
    assert telemetry.gauge(events.GAUGE_EMBED_GATHER_LAYERS).value == 1
