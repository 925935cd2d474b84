"""The grouped products' tiles (``ops/moe.py`` ``GroupedTiles``): each
kernel call — a product's forward, its input gradient, its weight
gradient, the segment sum — takes contraction and column tiles from its
own ``(m, k, n)``, as pure functions of shapes; and the kernels under a
chooser give the numbers ``ragged_dot`` gives (Pallas interpret mode).
"""

import json
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu import events, telemetry
from veles_tpu.ops import moe

#: (hidden, expert width) of published mixtures of experts: the two
#: cells' (Mellum2, Qwen3-Next), then DeepSeek-V2-Lite (1 408 = 11 x
#: 128), Qwen3-30B-A3B, Qwen3-235B-A22B, DeepSeek-V3, Mixtral 8x7B,
#: OLMoE, and small and odd sides
SHAPES = [(2304, 896), (2048, 512), (2048, 1408), (2048, 768),
          (4096, 1536), (7168, 2048), (4096, 14336), (2048, 1024),
          (1408, 1408), (2560, 384), (640, 1408), (128, 128), (256, 384)]


def _by_product(product, width, inner):
    """The rule before each call had tiles of its own: one tuple a
    product, for its three calls; the segment sum took ``out``'s
    columns."""
    if product == "in":
        return (moe.GMM_ROWS, min(width, 1024), min(inner, 512))
    return (moe.GMM_ROWS, min(inner, 512), min(width, 1024))


def _work(tiles, k, n):
    return moe._padded(k, tiles[1]) * moe._padded(n, tiles[2])


@pytest.mark.parametrize("width,inner,capacity", [
    (2304, 896, 12288), (2048, 512, 4096)])
def test_the_cells_call_kinds_take_exact_tiles(width, inner, capacity):
    got = moe.call_tiles(moe.GroupedTiles(), capacity, width, inner,
                         moe.FOLD_TOKENS)
    assert all(p == 1.0 for product in ("in", "out")
               for p in got["padding"][product].values())
    assert got["padding"]["fold"] == 1.0
    if width == 2304:
        assert got["tiles"]["in"] == {"forward": (512, 1152, 896),
                                      "input_grad": (512, 896, 1152),
                                      "weight_grad": (512, 1152, 896)}
        assert got["tiles"]["fold"] == (512, 512, 1152)
    else:
        # forward and weight gradient as one tuple a product had them;
        # the input gradients no longer put 1 024 over a 512 side
        for product in ("in", "out"):
            for call in ("forward", "weight_grad"):
                assert got["tiles"][product][call] == _by_product(
                    product, width, inner)
        assert got["tiles"]["in"]["input_grad"] == (512, 512, 1024)
        assert got["tiles"]["out"]["input_grad"] == (512, 1024, 512)
        assert got["tiles"]["fold"] == (512, 512, 1024)


@pytest.mark.parametrize("k,n,want", [
    (1408, 512, (1408, 512)),       # 11 x 128 whole, not 11 steps of 128
    (1408, 896, (768, 896)),        # 1 408 x 896 would not fit: 1.09 x
    (2304, 896, (1152, 896)), (896, 2304, (896, 1152)),
    (2304, 2304, (768, 1152)),      # exact both ways; the wider columns
    (128, 2304, (128, 2304)), (256, 896, (256, 896)),
    (384, 2304, (384, 1152)), (640, 896, (640, 896))])
def test_a_side_takes_whole_lanes_never_below_512(k, n, want):
    got = moe.GroupedTiles()(4096, k, n)
    assert got == (moe.GMM_ROWS,) + want
    for side, tile in zip((k, n), want):
        assert tile % moe.LANES == 0
        assert tile >= min(side, moe.GMM_LEAST_TILE)


@pytest.mark.parametrize("width,inner", SHAPES)
@pytest.mark.parametrize("itemsize", [2, 4])
def test_every_choice_fits_the_vmem_budget(width, inner, itemsize):
    tiles = moe.GroupedTiles(itemsize)
    for _, _, k, n in moe.product_calls(width, inner):
        got = tiles(8192, k, n)
        assert got[0] == moe.GMM_ROWS
        assert moe.gmm_vmem_bytes(*got, itemsize) <= moe.GMM_VMEM_BYTES
    assert moe.gmm_vmem_bytes(*tiles(8192, moe.FOLD_TOKENS, width),
                              itemsize) <= moe.GMM_VMEM_BYTES


@pytest.mark.parametrize("width,inner", SHAPES)
def test_no_shape_pads_more_than_one_tuple_a_product(width, inner):
    tiles = moe.GroupedTiles()
    for product, call, k, n in moe.product_calls(width, inner):
        assert _work(tiles(4096, k, n), k, n) \
            <= _work(_by_product(product, width, inner), k, n), (product, call)
    fold = tiles(4096, moe.FOLD_TOKENS, width)
    assert _work(fold, moe.FOLD_TOKENS, width) <= _work(
        (512, 512, min(width, 1024)), moe.FOLD_TOKENS, width)


def test_one_tuple_a_product_pads_mellum2_half_again():
    got = {(product, call): round(moe.padding(
        _by_product(product, 2304, 896), k, n), 2)
        for product, call, k, n in moe.product_calls(2304, 896)}
    assert got[("in", "forward")] == got[("in", "weight_grad")] == 1.52
    assert got[("in", "input_grad")] == 1.27
    assert round(moe.padding((512, 512, 1024), 512, 2304), 2) == 1.33


def test_the_chooser_is_one_static_argument_and_the_journal_numbers():
    path = moe.grouped_path("tpu", 32768, 2304, 896)
    assert path == {"form": "gmm", "tiles": moe.GroupedTiles(2)}
    assert hash(path["tiles"]) == hash(moe.GroupedTiles(2))
    assert moe.grouped_path("tpu", 32768, 2304, 896, itemsize=4)[
        "tiles"] != path["tiles"]
    telemetry.reset()
    unit = moe.MoE(None, name="m", experts_total=64, experts_held=16,
                   top_k=8, expert_size=896, shared_size=0)
    unit.platform = lambda: "tpu"
    share, tiles = unit._share(4 * 8192, 2304)
    assert tiles == moe.GroupedTiles(2)
    assert (share["form"], share["capacity"]) == ("gmm", 12288)
    assert share["tiles"]["out"]["forward"] == (512, 896, 1152)
    assert share["padding"]["fold"] == 1.0
    journaled = telemetry.recent_events(events.EV_MOE_SHARE)
    assert len(journaled) == 1 and "GroupedTiles" not in json.dumps(
        journaled[0])
    # the same share again is not journaled again
    unit._share(4 * 8192, 2304)
    assert len(telemetry.recent_events(events.EV_MOE_SHARE)) == 1
    # under vmap: ragged_dot, no tiles
    share, tiles = unit._share(4 * 8192, 2304, batched=True)
    assert tiles is None and "tiles" not in share


def test_the_kernels_under_a_chooser_match_ragged_dot():
    """Forward, input gradient and weight gradient of ``ops.gmm`` with
    the chooser as its tiling (Pallas interpret mode on the CPU), and
    the segment sum's ``tgmm``: each call takes its own tiles."""
    from jax.experimental.pallas.ops.tpu.megablox import ops
    keys = jax.random.split(jax.random.key(41), 4)
    lhs = jax.random.normal(keys[0], (1024, 384), jnp.float32)
    rhs = jax.random.normal(keys[1], (3, 384, 640), jnp.float32)
    err = jax.random.normal(keys[2], (1024, 640), jnp.float32)
    sizes = jnp.array([300, 500, 224], jnp.int32)
    tiles = moe.GroupedTiles(4)
    assert tiles(1024, 384, 640) == (512, 384, 640)
    assert tiles(1024, 640, 384) == (512, 640, 384)
    mine = jax.vjp(lambda a, b: ops.gmm(a, b, sizes, jnp.float32, tiles,
                                        None, None, False, True), lhs, rhs)
    want = jax.vjp(lambda a, b: jax.lax.ragged_dot(
        a, b, sizes, precision="highest"), lhs, rhs)
    with jax.default_matmul_precision("highest"):
        for a, b in zip((mine[0],) + mine[1](err),
                        (want[0],) + want[1](err)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-3)
    pairs = jax.random.permutation(keys[3], 1024).astype(jnp.int32)
    by_token = jnp.argsort(pairs).astype(jnp.int32)
    y = jax.random.normal(keys[2], (1024, 640), jnp.float32)
    plain = moe.token_sums(y, pairs, by_token, 512, 2)

    real = ops.backend.tgmm
    ops.backend.tgmm = partial(real, interpret=True)
    try:
        fused = moe.token_sums(y, pairs, by_token, 512, 2, tiles)
    finally:
        ops.backend.tgmm = real
    np.testing.assert_allclose(np.asarray(fused), np.asarray(plain),
                               rtol=1e-5, atol=1e-5)
