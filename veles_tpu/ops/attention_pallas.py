"""Pallas TPU kernels of the sliding-window attention core
(``ops/attention.py``): ``band_fwd``, and the backward's ``band_bwd_dq``
and ``band_bwd_dkv`` — a query reads the ``window`` keys ending at
itself, ``n - window < m <= n``.

One grid step is (row, key head, block of ``bq`` queries) and holds the
block of EVERY query head of the key head's group against the one slab
of keys the window reads: the ``window / bq + 1`` key blocks from the
block ``window / bq`` before the query block up to the query block
itself (clamped at the row's start: the first blocks read ``[0, window
+ bq)``, the keys right of a query masked).  The step scores ``group x
bq x (window + bq)`` pairs, so keys scored a key read are ``(window +
bq) / window`` — 1.5 / 1.25 at a window of 512 and ``bq`` 256 / 128,
where 512 x 512 tiles score 2.0 — and its fixed cost is paid once for
every head of the group.  The whole window is in VMEM: the softmax is
taken in one pass, no running rescale.

The slab is ``window / bq + 1`` pieces, each the same array under its
own index map, and a key block stays in the piece of its index mod the
piece count while the slab slides (a ring): between two consecutive
query blocks one piece changes, so each key block is fetched once a
(row, key head), however many query blocks read it.  Positions, and so
the mask ``0 <= n - m < window``, are made from the block a piece holds.
The backward's ``dkv`` step holds one key block and the ring of query
blocks that read it, ``[s, s + bq + window)`` (clamped at the row's
end); the ``dq`` step the forward's ring of keys.

A tile is ``[queries, keys]`` in the forward and ``dq`` — every
product then a plain one or one against a transpose the MXU takes as it
is, and ``o`` comes out in its own layout — and ``[keys, queries]`` in
``dkv``, for the same reason.  The forward writes ``o`` and the
log-sum-exp (f32 ``[rows, key heads, G, T]``: ROWS of a query block, ``G``
the group in whole sublane tiles); ``dq`` re-makes the probabilities
from q, k and it, makes ``sum(o * do)`` from the blocks it holds and
writes it beside ``dq`` for ``dkv`` (a step's statistics turn between
rows and columns by one transpose).  Operands of the products in the
inputs' dtype (probabilities cast before the products with v and do),
scores, softmax and accumulation in f32, as ``ops/attention.py``
``core_xla`` — the oracle (tests/test_attention_pallas.py in
interpret mode, tests_tpu/test_band_attention.py on the chip).

Arrays are ``[rows, T, heads * d]``, the projections' own layout: a key
head's group is a ``group * d`` lane block and a head a 128-lane slice
of it, so nothing is transposed on the way in or out.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

#: lanes of a vreg: a head is whole multiples of it
LANES = 128
#: query blocks a grid step may hold
BLOCKS = (128, 256)
#: the fixed cost of one grid step, seconds, beside its MXU work: the
#: forward's times at both blocks fitted to ``steps x (cost + work /
#: rate)`` by scripts/attn_window_chip.py on a TPU v5e, 2.46 us at the
#: laguna window's shapes and 2.73 us at mellum2's
STEP_SECONDS = 2.6e-6
#: bf16 FLOP/s of a v5e's MXU, the unit of a step's work in the rule
MXU_FLOPS = 197e12
#: scoped VMEM the kernels may use (the default is 16 MiB of a v5e's
#: 128); a step's blocks must fit half of it
VMEM_LIMIT = 64 * 1024 * 1024


class BandTiles(NamedTuple):
    """``block_q`` queries (in ``dkv``: keys) a grid step; ``slab``:
    the keys a query block reads, ``window + block_q``."""
    block_q: int
    slab: int


def _stat_rows(group: int) -> int:
    """Rows of a key head's statistics block: the group in whole
    sublane tiles."""
    return -(-group // 8) * 8


def _step_bytes(bq: int, window: int, group: int, head_size: int) -> int:
    """VMEM of one ``dkv`` grid step, the largest of the three: its
    blocks in and out, both buffers, at 4 B an element (an f32 compute
    dtype fits too), and a few f32 ``[bq, bq]`` tiles."""
    pieces = window // bq + 1
    rows = _stat_rows(group)
    blocks = pieces * (2 * bq * group * head_size + 2 * rows * bq) \
        + 4 * bq * head_size
    return 4 * (2 * blocks + 6 * bq * bq + 2 * bq * head_size)


def band_tiles(t: int, window: int, group: int,
               head_size: int) -> Optional[BandTiles]:
    """The kernels' tiling for these sizes, or None where they do not
    tile: a head of whole 128-lane columns, a window and a row of whole
    query blocks, a slab within the row, a step within the VMEM.  Of
    the blocks that tile, the one of least cost, ``(window + bq) /
    window x (1 + STEP_SECONDS / a step's MXU time)``: keys scored a
    key read against the fixed cost a step pays."""
    if head_size % LANES or group > LANES:
        return None
    fits = [bq for bq in BLOCKS
            if window % bq == 0 and t % bq == 0 and window + bq <= t
            and _step_bytes(bq, window, group, head_size)
            <= VMEM_LIMIT // 2]
    if not fits:
        return None

    def cost(bq):
        work = 4.0 * group * bq * (window + bq) * head_size / MXU_FLOPS
        return (window + bq) / window * (1.0 + STEP_SECONDS / work)

    bq = min(fits, key=cost)
    return BandTiles(bq, window + bq)


_NT = (((1,), (1,)), ((), ()))      # a . b^T


def _keys_of(j: int, t: Any, pieces: int) -> Any:
    """The key block piece ``j`` holds for query block ``t``: the one
    of the slab ``[t - pieces + 1, t]`` (``[0, pieces - 1]`` at the
    row's start) whose index is ``j`` mod ``pieces``."""
    import jax.numpy as jnp
    from jax import lax
    b = t - lax.rem(t + pieces - j, pieces)
    return jnp.where(b < 0, b + pieces, b)


def _queries_of(j: int, t: Any, pieces: int, blocks: int) -> Any:
    """The query block piece ``j`` holds for key block ``t``: the one
    of ``[t, t + pieces - 1]`` (``[blocks - pieces, blocks - 1]`` at
    the row's end) whose index is ``j`` mod ``pieces``."""
    import jax.numpy as jnp
    from jax import lax
    b = t + lax.rem(j + pieces - lax.rem(t, pieces), pieces)
    return jnp.where(b >= blocks, b - pieces, b)


def _biases(bq: int, window: int, offsets, keys_first: bool) -> list:
    """Additive masks (0 where a query reads the key, -inf elsewhere) of
    the ``[bq, bq]`` tiles whose query block starts ``offset`` positions
    right of their key block, ``[keys, queries]`` (``keys_first``) or
    ``[queries, keys]``: query n reads key m where ``0 <= n - m <
    window``.  Made once a grid step, added to every head's scores."""
    import jax.numpy as jnp
    from jax import lax
    rows = lax.broadcasted_iota(jnp.int32, (bq, bq), 0)
    cols = lax.broadcasted_iota(jnp.int32, (bq, bq), 1)
    gap = cols - rows if keys_first else rows - cols
    biases = []
    for off in offsets:
        diff = gap + off
        biases.append(jnp.where((diff >= 0) & (diff < window), 0.0,
                                -jnp.inf).astype(jnp.float32))
    return biases


def _slab(k_refs, v_refs, window: int):
    """What every head of a query block's step reads: the additive
    masks of its ``[queries, keys]`` tiles, the slab's keys and values
    (a piece each)."""
    from jax.experimental import pallas as pl
    pieces, bq = len(k_refs), k_refs[0].shape[0]
    t = pl.program_id(2)
    return (_biases(bq, window, [(t - _keys_of(j, t, pieces)) * bq
                                 for j in range(pieces)], False),
            [r[...] for r in k_refs], [r[...] for r in v_refs])


def _put(acc, g: int, column):
    """``acc`` with its column ``g`` replaced by ``column``."""
    import jax.numpy as jnp
    from jax import lax
    at = lax.broadcasted_iota(jnp.int32, acc.shape, 1) == g
    return jnp.where(at, column, acc)


def _fwd_kernel(q_ref, *refs, window, pieces):
    """Every query head of the step against the slab, tiles ``[queries,
    keys]``: ``o``, and the log-sum-exp as columns turned into the rows
    the backward reads by one transpose a step."""
    import jax.numpy as jnp
    from jax import lax
    k_refs, v_refs = refs[:pieces], refs[pieces:2 * pieces]
    o_ref, lse_ref = refs[2 * pieces:]
    bq, d = k_refs[0].shape
    f32 = jnp.float32
    biases, keys, values = _slab(k_refs, v_refs, window)
    lses = jnp.zeros((bq, LANES), f32)
    for g in range(q_ref.shape[1] // d):
        q = q_ref[:, g * d:(g + 1) * d]
        s = [lax.dot_general(q, k, _NT, preferred_element_type=f32) + b
             for k, b in zip(keys, biases)]
        m = functools.reduce(jnp.maximum,
                             [jnp.max(x, axis=1, keepdims=True) for x in s])
        p = [jnp.exp(x - m) for x in s]
        total = sum(jnp.sum(x, axis=1, keepdims=True) for x in p)
        acc = sum(jnp.dot(x.astype(v.dtype), v, preferred_element_type=f32)
                  for v, x in zip(values, p))            # [queries, d]
        o_ref[:, g * d:(g + 1) * d] = (acc * (1.0 / total)).astype(
            o_ref.dtype)
        lses = _put(lses, g, m + jnp.log(total))
    lse_ref[...] = jnp.transpose(lses)[:lse_ref.shape[0]]


def _dq_kernel(q_ref, o_ref, do_ref, lse_ref, *refs, window, pieces):
    """``dq`` of every query head of the step over the slab it read,
    tiles ``[queries, keys]`` (every product a plain one or against a
    transpose the MXU takes as it is), and each query's ``sum(o *
    do)`` for ``dkv``: the statistics as columns, turned from and into
    the rows the other kernels keep by one transpose a step."""
    import jax.numpy as jnp
    from jax import lax
    k_refs, v_refs = refs[:pieces], refs[pieces:2 * pieces]
    dq_ref, di_ref = refs[2 * pieces:]
    bq, d = k_refs[0].shape
    f32 = jnp.float32
    rows = lse_ref.shape[0]
    biases, keys, values = _slab(k_refs, v_refs, window)
    lses = jnp.transpose(jnp.concatenate(
        [lse_ref[...], jnp.zeros((LANES - rows, bq), f32)]))  # [bq, 128]
    lane = lax.broadcasted_iota(jnp.int32, lses.shape, 1)
    dis = jnp.zeros(lses.shape, f32)
    for g in range(q_ref.shape[1] // d):
        head = slice(g * d, (g + 1) * d)
        q, do = q_ref[:, head], do_ref[:, head]
        di = jnp.sum(o_ref[:, head].astype(f32) * do.astype(f32), axis=1,
                     keepdims=True)                      # [bq, 1]
        lse = jnp.sum(jnp.where(lane == g, lses, 0.0), axis=1,
                      keepdims=True)
        dq = jnp.zeros((bq, d), f32)
        for b, k, v in zip(biases, keys, values):
            p = jnp.exp(lax.dot_general(q, k, _NT,
                                        preferred_element_type=f32)
                        + b - lse)
            dp = lax.dot_general(do, v, _NT, preferred_element_type=f32)
            ds = (p * (dp - di)).astype(k.dtype)
            dq = dq + jnp.dot(ds, k, preferred_element_type=f32)
        dq_ref[:, head] = dq.astype(dq_ref.dtype)
        dis = _put(dis, g, di)
    di_ref[...] = jnp.transpose(dis)[:rows]


def _dkv_kernel(k_ref, v_ref, *refs, window, pieces):
    """``dk``, ``dv`` of one key block over every query head of the
    group in the query blocks that read it, tiles ``[keys,
    queries]``."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl
    q_refs, do_refs = refs[:pieces], refs[pieces:2 * pieces]
    lse_refs, di_refs = refs[2 * pieces:3 * pieces], refs[3 * pieces:
                                                          4 * pieces]
    dk_ref, dv_ref = refs[4 * pieces:]
    bk, d = k_ref.shape
    f32 = jnp.float32
    t, blocks = pl.program_id(2), pl.num_programs(2)
    biases = _biases(bk, window, [(_queries_of(j, t, pieces, blocks) - t)
                                  * bk for j in range(pieces)], True)
    k, v = k_ref[...], v_ref[...]
    dk = jnp.zeros((bk, d), f32)
    dv = jnp.zeros((bk, d), f32)
    for g in range(q_refs[0].shape[1] // d):
        head = slice(g * d, (g + 1) * d)
        for j, b in enumerate(biases):
            q, do = q_refs[j][:, head], do_refs[j][:, head]
            p = jnp.exp(lax.dot_general(k, q, _NT,
                                        preferred_element_type=f32)
                        + b - lse_refs[j][g:g + 1, :])
            dv = dv + jnp.dot(p.astype(do.dtype), do,
                              preferred_element_type=f32)
            dp = lax.dot_general(v, do, _NT, preferred_element_type=f32)
            ds = (p * (dp - di_refs[j][g:g + 1, :])).astype(q.dtype)
            dk = dk + jnp.dot(ds, q, preferred_element_type=f32)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _call(kernel, name, window, pieces, grid, in_specs, out_specs,
          out_shape, interpret, *args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(kernel, window=window, pieces=pieces),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the block axis in order: a ring's piece then changes once
            # a step
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name)(*args)


class _Specs(NamedTuple):
    """Block specs over ``[rows, T, heads * d]`` arrays and the
    ``[rows, key heads, G, T]`` statistics, for the grid (row, key
    head, block): the step's own block of queries (``group``) or of
    keys (``head``), its statistics (``stat``), and the ring's pieces
    of key blocks (``keys``), of query blocks (``queries``) and of
    their statistics (``query_stats``)."""
    group: Any
    head: Any
    stat: Any
    keys: list
    queries: list
    query_stats: list


def _specs(bq: int, d: int, group: int, blocks: int, pieces: int):
    from jax.experimental import pallas as pl
    rows = _stat_rows(group)

    def key(j):
        return pl.BlockSpec((None, bq, d), lambda i, h, t: (
            i, _keys_of(j, t, pieces), h))

    def query(j):
        return pl.BlockSpec((None, bq, group * d), lambda i, h, t: (
            i, _queries_of(j, t, pieces, blocks), h))

    def query_stat(j):
        return pl.BlockSpec((None, None, rows, bq), lambda i, h, t: (
            i, h, 0, _queries_of(j, t, pieces, blocks)))

    return _Specs(
        pl.BlockSpec((None, bq, group * d), lambda i, h, t: (i, t, h)),
        pl.BlockSpec((None, bq, d), lambda i, h, t: (i, t, h)),
        pl.BlockSpec((None, None, rows, bq), lambda i, h, t: (i, h, 0, t)),
        [key(j) for j in range(pieces)],
        [query(j) for j in range(pieces)],
        [query_stat(j) for j in range(pieces)])


def _plan(q, k, window: int, tiles: BandTiles, d: int):
    """(grid, group, pieces, block specs) of a call over ``q [rows, T,
    heads * d]``, ``k [rows, T, key heads * d]``."""
    b, t, width = q.shape
    nkv = k.shape[2] // d
    group, bq = width // (nkv * d), tiles.block_q
    pieces = window // bq + 1
    return ((b, nkv, t // bq), group, pieces,
            _specs(bq, d, group, t // bq, pieces))


def _forward(q, k, v, window, tiles, d, interpret):
    """(o like q, the log-sum-exp ``[rows, key heads, G, T]`` f32)."""
    import jax
    import jax.numpy as jnp
    grid, group, pieces, sp = _plan(q, k, window, tiles, d)
    return _call(
        _fwd_kernel, "band_fwd", window, pieces, grid,
        [sp.group] + sp.keys + sp.keys, [sp.group, sp.stat],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(grid[:2] + (_stat_rows(group), q.shape[1]),
                              jnp.float32)],
        interpret, q, *[k] * pieces, *[v] * pieces)


def _backward(q, k, v, o, lse, do, window, tiles, d, interpret):
    """(dq, dk, dv): ``dq`` (and each query's ``sum(o * do)``) by the
    first kernel, ``dk``, ``dv`` by the second."""
    import jax
    grid, _, pieces, sp = _plan(q, k, window, tiles, d)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    dq, di = _call(
        _dq_kernel, "band_bwd_dq", window, pieces, grid,
        [sp.group, sp.group, sp.group, sp.stat] + sp.keys + sp.keys,
        [sp.group, sp.stat], [like(q), like(lse)], interpret,
        q, o, do, lse, *[k] * pieces, *[v] * pieces)
    dk, dv = _call(
        _dkv_kernel, "band_bwd_dkv", window, pieces, grid,
        [sp.head, sp.head] + sp.queries + sp.queries + sp.query_stats
        + sp.query_stats, [sp.head, sp.head], [like(k), like(v)],
        interpret, k, v, *[q] * pieces, *[do] * pieces, *[lse] * pieces,
        *[di] * pieces)
    return dq, dk, dv


@functools.lru_cache(maxsize=None)
def _op():
    """The differentiable call, made once.  Each kernel call is a
    ``jax.jit`` of its own, so that layers of the same shapes, and the
    same layer traced again (the plan's walks, the step, the entry's
    re-run), reuse its traced kernel body."""
    import jax
    forward = jax.jit(_forward, static_argnums=(3, 4, 5, 6))
    backward = jax.jit(_backward, static_argnums=(6, 7, 8, 9))

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
    def op(q, k, v, window, tiles, d, interpret):
        return forward(q, k, v, window, tiles, d, interpret)[0]

    def fwd(q, k, v, window, tiles, d, interpret):
        o, lse = forward(q, k, v, window, tiles, d, interpret)
        return o, (q, k, v, o, lse)

    def bwd(window, tiles, d, interpret, saved, do):
        return backward(*saved, do, window, tiles, d, interpret)

    op.defvjp(fwd, bwd)
    return op


def band_attention(q: Any, k: Any, v: Any, window: int, tiles: BandTiles,
                   interpret: bool = False) -> Any:
    """``ops/attention.py`` ``core_xla`` under ``window`` by the
    kernels: q ``[rows, T, nkv, group, d]`` (already scaled), k, v
    ``[rows, T, nkv, d]`` -> o like q, in q's dtype.  Differentiable
    in all three (``jax.custom_vjp``: the residuals are q, k, v, o and
    the log-sum-exp; the backward is the ``dq`` kernel, then
    ``dkv``).  ``interpret``: Pallas interpret mode, off the chip."""
    b, t, nkv, group, d = q.shape
    if d % LANES or window % tiles.block_q or t % tiles.block_q \
            or tiles.slab != window + tiles.block_q or tiles.slab > t:
        raise ValueError(f"T {t}, window {window}, head size {d} do not "
                         f"tile as {tiles}")
    o = _op()(q.reshape(b, t, nkv * group * d), k.reshape(b, t, nkv * d),
              v.reshape(b, t, nkv * d), window, tiles, d, interpret)
    return o.reshape(q.shape)
