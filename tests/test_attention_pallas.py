"""The window core's band kernels (``ops/attention_pallas.py``) in
Pallas interpret mode against the XLA core they replace on the chip
(``ops/attention.py`` ``core_xla``), forward and all three gradients,
in f32 at small shapes; the ring of blocks the kernels' pieces hold;
and the choice of form ``attention_path`` makes from shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu.ops import attention, attention_pallas as band

D, NKV, GROUP = 128, 2, 3


def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def _both(core, q, k, v, err):
    out, back = jax.vjp(core, q, k, v)
    return (out,) + back(err)


@pytest.mark.parametrize("t,window,bq", [
    (1024, 256, 128), (1024, 256, 256),
    # every step's slab clamped: at the row's start in the forward and
    # dq, at its end in dkv
    (384, 256, 128)])
def test_kernels_match_the_xla_core(t, window, bq):
    ks = jax.random.split(jax.random.key(t + bq), 4)
    q = jax.random.normal(ks[0], (1, t, NKV, GROUP, D)) / D ** 0.5
    k, v = (jax.random.normal(kk, (1, t, NKV, D)) for kk in ks[1:3])
    err = jax.random.normal(ks[3], q.shape)
    tiles = band.BandTiles(bq, window + bq)
    with jax.default_matmul_precision("highest"):
        want = _both(lambda *a: attention.core_xla(*a, window=window),
                     q, k, v, err)
        got = _both(lambda *a: band.band_attention(
            *a, window, tiles, interpret=True), q, k, v, err)
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _gap(a, b) < 1e-5, (name, _gap(a, b))
    # the queries at the row's start read fewer keys than the window
    assert _gap(got[0][:, :window], want[0][:, :window]) < 1e-5
    assert _gap(got[2][:, -window:], want[2][:, -window:]) < 1e-5


def test_kernels_keep_the_compute_dtype_and_the_window():
    t, window, bq = 512, 128, 128
    ks = jax.random.split(jax.random.key(5), 3)
    q = (jax.random.normal(ks[0], (1, t, NKV, GROUP, D)) / D ** 0.5
         ).astype(jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, t, NKV, D)).astype(jnp.bfloat16)
            for kk in ks[1:])
    o = band.band_attention(q, k, v, window, band.BandTiles(bq, 256),
                            interpret=True)
    assert o.dtype == jnp.bfloat16
    xla = attention.core_xla(q, k, v, window=window)
    assert _gap(o, xla) < 2e-2
    # a key that leaves the window leaves the query's output
    k2 = k.at[:, 100].add(1.0)
    o2 = band.band_attention(q, k2, v, window, band.BandTiles(bq, 256),
                             interpret=True)
    moved = jnp.abs(o2.astype(jnp.float32) - o.astype(jnp.float32)).max(
        axis=(0, 2, 3, 4))
    assert float(moved[:100].max()) == 0.0
    assert float(moved[100:100 + window].min()) > 0.0
    assert float(moved[100 + window:].max()) == 0.0


def test_a_shape_the_blocks_do_not_tile_is_refused():
    q = jnp.zeros((1, 512, 1, 2, D))
    k = jnp.zeros((1, 512, 1, D))
    with pytest.raises(ValueError, match="tile"):
        band.band_attention(q, k, k, 192, band.BandTiles(128, 320))


@pytest.mark.parametrize("blocks,pieces", [(8, 3), (5, 5), (32, 9)])
def test_the_ring_holds_the_slab_and_moves_one_piece_a_step(blocks, pieces):
    held_k, held_q = [], []
    for t in range(blocks):
        keys = [int(band._keys_of(j, t, pieces)) for j in range(pieces)]
        queries = [int(band._queries_of(j, t, pieces, blocks))
                   for j in range(pieces)]
        first = max(0, t - pieces + 1)
        assert sorted(keys) == list(range(first, first + pieces)), t
        last = min(blocks, t + pieces)
        assert sorted(queries) == list(range(last - pieces, last)), t
        # a block sits in the piece of its index mod the piece count
        assert [b % pieces for b in keys] == list(range(pieces))
        assert [b % pieces for b in queries] == list(range(pieces))
        held_k.append(keys)
        held_q.append(queries)
    for a, b in zip(held_k, held_k[1:]):
        assert sum(x != y for x, y in zip(a, b)) <= 1
    for a, b in zip(held_q, held_q[1:]):
        assert sum(x != y for x, y in zip(a, b)) <= 1


def test_band_is_chosen_from_the_window_and_the_shapes():
    laguna = attention.attention_path("tpu", 128, 8192, window=512,
                                      group=9)
    assert laguna == {"form": "band", "window": 512, "visited": 1.5,
                      "tiles": {"block_q": 256, "slab": 768}}
    assert attention.attention_path("tpu", 128, 8192, window=512)[
        "form"] == "band"
    mellum2 = attention.attention_path("tpu", 128, 8192, window=1024,
                                       group=8)
    assert (mellum2["tiles"], mellum2["visited"]) == (
        {"block_q": 256, "slab": 1280}, 1.25)
    # a window the blocks do not tile, or wider than the row allows,
    # keeps the shipped kernel; the CPU and a batched trace keep xla
    for window in (1000, 64, 8192):
        path = attention.attention_path("tpu", 128, 8192, window=window,
                                        group=9)
        assert path["form"] == "splash" and "kv_blocks" in path, window
    assert attention.attention_path("cpu", 128, 8192, window=512) == {
        "form": "xla", "reason": "platform", "window": 512}
    assert attention.attention_path("tpu", 128, 8192, True, 512)[
        "reason"] == "batched"
    assert attention.attention_path("tpu", 64, 8192, window=512)[
        "reason"] == "head_size"
    # ragged rows the band tiles still take it
    assert attention.attention_path("tpu", 128, 640, window=256)[
        "form"] == "band"
    assert attention.attention_path("tpu", 128, 640)["reason"] == "row"


def test_the_block_is_the_cheaper_one_by_the_rule():
    """More keys scored a key read at the larger block, fewer grid
    steps: the fixed cost of a step decides."""
    def tiles(step):
        old = band.STEP_SECONDS
        band.STEP_SECONDS = step
        try:
            return band.band_tiles(8192, 512, 9, 128).block_q
        finally:
            band.STEP_SECONDS = old
    assert tiles(0.0) == 128
    assert tiles(1e-5) == 256
    assert band.band_tiles(8192, 512, 9, 100) is None
    assert band.band_tiles(8192, 384, 9, 128).block_q == 128
    assert band.band_tiles(256, 256, 9, 128) is None


@pytest.mark.parametrize("t,head,window,batched", [
    (8192, 128, None, False), (32768, 256, None, False),
    (8192, 128, None, True), (1000, 128, None, False),
    (256, 128, None, False), (8192, 16, None, False),
    (8192, 128, 1000, False), (8192, 128, 1024, True)])
def test_every_other_layer_keeps_the_parents_path(t, head, window,
                                                  batched):
    """Full layers, and windows the band does not take, get exactly
    the dict they got before the band kernels existed."""
    block = min(attention.KERNEL_BLOCK, t)
    reason = "batched" if batched else "head_size" if head % 128 \
        else "row" if t % block or block % 128 else None
    want = {"form": "xla", "reason": reason, "window": window} if reason \
        else {"form": "splash", "window": window,
              "tiles": {"block_q": block, "block_kv": block},
              "kv_blocks": max(
                  i - attention.first_key(i * block, window) // block + 1
                  for i in range(t // block))}
    for group in (1, 6, 9):
        assert attention.attention_path("tpu", head, t, batched, window,
                                        group) == want
