"""Pallas TPU kernel of the embedding table's gradient
(``ops/sequence.py`` ``Embedding``, gather form): ``embed_segment_sum``
— row ``v`` of the result is the f32 sum of the error rows of every id
equal to ``v``, returned in the rows' dtype; ids outside ``[0, V)``
add nothing.

The ids are sorted once (with their positions) and the error rows
gathered into that order, so the ids of a block of ``block_v`` table
rows are one contiguous run of the sorted rows.  One grid step is
(table block, chunk of ``block_n`` sorted rows) and adds ``one_hot(ids
- first row of the block)^T . rows`` to the block's f32 accumulator in
VMEM: the one-hot is exact in the rows' dtype and the MXU accumulates
in f32, so a table row is the f32 sum of its error rows, as the
transposed one-hot product over the whole table gives it.  An id of
another block, or outside the table, matches no column of the step's
one-hot and adds nothing, so where a run starts or ends inside a chunk
nothing has to be masked.  The steps are a flat list made from the
sorted ids (scalar prefetch): each block visits the chunks its run
spans, at least one (an empty block writes zeros), in order of
blocks, so a block's accumulator lives across its consecutive steps
and is written once, at its last.  The list's length is bounded from
shapes alone (``chunks + 2 x blocks``); steps past its real end repeat
the last step's blocks (nothing is fetched again) and add nothing.

Work: every visited chunk costs ``2 x block_v x block_n x hidden``
MXU FLOPs — about ``(1 + block_n / ids a block) x 2 N block_v H`` in
all, against ``2 N V H`` for the whole one-hot product.
``ops/sequence.py`` ``scatter_rows`` (XLA's scatter-add) is the oracle
(tests/test_embedding.py in interpret mode, tests_tpu/
test_mellum2_layers.py on the chip).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

#: scoped VMEM the kernel may use (the default is 16 MiB of a v5e's 128)
VMEM_LIMIT = 64 * 1024 * 1024


class SegmentTiles(NamedTuple):
    """``block_v`` table rows a grid step accumulates, ``block_n``
    sorted error rows it reads."""
    block_v: int = 256
    block_n: int = 256


def _kernel(blk_ref, chunk_ref, total_ref, ids_ref, rows_ref, out_ref,
            acc_ref, *, block_v, steps):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    g = pl.program_id(0)
    b = blk_ref[g]

    @pl.when((g == 0) | (blk_ref[jnp.maximum(g - 1, 0)] != b))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(g < total_ref[0])
    def _():
        rows = rows_ref[...]
        local = ids_ref[...] - b * block_v                 # [1, block_n]
        hot = (lax.broadcasted_iota(jnp.int32, (block_v, rows.shape[0]), 0)
               == local).astype(rows.dtype)
        acc_ref[...] += jnp.dot(
            hot, rows, preferred_element_type=jnp.float32,
            precision=lax.Precision.HIGHEST
            if rows.dtype == jnp.float32 else None)

    @pl.when((g == steps - 1)
             | (blk_ref[jnp.minimum(g + 1, steps - 1)] != b))
    def _():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _schedule(sorted_ids, blocks: int, chunks: int, tiles: SegmentTiles):
    """(block, chunk) of every grid step, and the count of real steps,
    from the sorted ids: block ``b`` visits the chunks of its run
    ``[lo_b, hi_b)`` of the sorted rows, at least one."""
    import jax.numpy as jnp
    bv, bn = tiles
    edges = jnp.searchsorted(
        sorted_ids, jnp.arange(blocks + 1, dtype=jnp.int32) * bv,
        side="left").astype(jnp.int32)
    first = jnp.minimum(edges[:-1] // bn, chunks - 1)
    last = jnp.maximum(first, (edges[1:] + bn - 1) // bn - 1)
    visits = last - first + 1
    ends = jnp.cumsum(visits)
    steps = chunks + 2 * blocks
    g = jnp.arange(steps, dtype=jnp.int32)
    blk = jnp.minimum(jnp.searchsorted(ends, g, side="right"),
                      blocks - 1).astype(jnp.int32)
    chunk = jnp.minimum(first[blk] + g - (ends[blk] - visits[blk]),
                        last[blk]).astype(jnp.int32)
    return blk, chunk, ends[-1:].astype(jnp.int32), steps


def _segment_sum(ids, rows, vocab: int, tiles: SegmentTiles,
                 interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    n, h = rows.shape
    bv, bn = tiles
    blocks = -(-vocab // bv)
    pad = -n % bn
    # ids outside the table sort past every block and match none
    ids = ids.astype(jnp.int32)
    key = jnp.where((ids >= 0) & (ids < vocab), ids, blocks * bv)
    if pad:
        key = jnp.concatenate([key, jnp.full((pad,), blocks * bv,
                                             jnp.int32)])
        rows = jnp.concatenate([rows, jnp.zeros((pad, h), rows.dtype)])
    chunks = (n + pad) // bn
    key, order = lax.sort_key_val(
        key, lax.iota(jnp.int32, n + pad), is_stable=False)
    rows = jnp.take(rows, order, axis=0)
    blk, chunk, total, steps = _schedule(key, blocks, chunks, tiles)
    out = pl.pallas_call(
        functools.partial(_kernel, block_v=bv, steps=steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(steps,),
            in_specs=[
                pl.BlockSpec((1, bn), lambda g, bk, ck, tt: (0, ck[g])),
                pl.BlockSpec((bn, h), lambda g, bk, ck, tt: (ck[g], 0))],
            out_specs=pl.BlockSpec((bv, h),
                                   lambda g, bk, ck, tt: (bk[g], 0)),
            scratch_shapes=[pltpu.VMEM((bv, h), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((blocks * bv, h), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name="embed_segment_sum")(
            blk, chunk, total, key.reshape(1, -1), rows)
    return out if blocks * bv == vocab else out[:vocab]


@functools.lru_cache(maxsize=None)
def _jitted():
    import jax
    return jax.jit(_segment_sum, static_argnums=(2, 3, 4))


def embed_segment_sum(ids: Any, rows: Any, vocab: int,
                      tiles: SegmentTiles = SegmentTiles(),
                      interpret: bool = False) -> Any:
    """``[vocab, hidden]`` in ``rows``' dtype: row ``v`` the f32 sum of
    the rows ``rows[i]`` (``[N, hidden]``) with ``ids[i] == v``
    (``[N]`` integers); ids outside ``[0, vocab)`` dropped.
    ``interpret``: Pallas interpret mode, off the chip."""
    return _jitted()(ids.reshape(-1), rows.reshape(-1, rows.shape[-1]),
                     int(vocab), tiles, interpret)
