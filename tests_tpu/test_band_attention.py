"""The window core's band kernels (``ops/attention_pallas.py``) on the
chip, at both benchmark cells' shapes in bf16 on one row of 8 192:
``laguna``'s window of 512 keys (72 query heads over 8 key heads) and
``mellum2``'s of 1 024 (32 over 4), forward and all three gradients,
against the plain form every other platform runs — within the gap that
plain form itself keeps from an f32 run of the same mathematics (the
bf16 witness) — and faster than the shipped splash kernel under a
``LocalMask`` at the same shapes.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import attention, attention_pallas

T = 8192


def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("nkv,group,window", [(8, 9, 512), (4, 8, 1024)],
                         ids=["laguna", "mellum2"])
def test_band_kernels_match_the_xla_core_and_beat_splash(tpu_device, nkv,
                                                         group, window):
    keys = jax.random.split(jax.random.key(4343), 4)
    q = jax.random.normal(keys[0], (1, T, nkv, group, 128), jnp.float32) \
        / 128 ** 0.5
    k, v = (jax.random.normal(keys[i], (1, T, nkv, 128), jnp.float32)
            for i in (1, 2))
    err = jax.random.normal(keys[3], q.shape, jnp.float32)
    path = attention.attention_path("tpu", 128, T, window=window,
                                    group=group)
    assert path["form"] == "band", path
    tiles = attention_pallas.BandTiles(**path["tiles"])

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
        exact, _ = run(lambda *a: attention.core_xla(*a, window=window),
                       jnp.float32)
    xla, _ = run(lambda *a: attention.core_xla(*a, window=window),
                 jnp.bfloat16)
    band, t_band = run(lambda *a: attention_pallas.band_attention(
        *a, window, tiles), jnp.bfloat16)
    for name, e, x, b in zip(("o", "dq", "dk", "dv"), exact, xla, band):
        assert b.shape == x.shape and b.dtype == x.dtype, name
        assert np.isfinite(np.asarray(b, np.float32)).all(), name
        witness, ours = _gap(x, e), _gap(b, e)
        assert ours <= 1.5 * witness + 2e-3, (name, ours, witness)
    splash, t_splash = run(
        lambda *a: attention.core_splash(*a, 512, window), jnp.bfloat16)
    print(f"window core forward + backward, one row, group {group}, "
          f"window {window}: band {path['tiles']} {t_band:.4f} s, splash "
          f"512 x 512 {t_splash:.4f} s")
    assert t_band < t_splash, (t_band, t_splash)
