"""Pallas TPU kernels for EVA attention (``ops/sequence.py``
``EvaAttention``): a row's queries against the keys of their own
window (causal) AND the summaries of every earlier window under ONE
running softmax, flash-attention style — a tile of scores is made in
VMEM, exponentiated against the running row maximum, multiplied into
the f32 accumulator and dropped; no score is ever written to HBM.

Two kernels, one grid step a (row, head, block of ``q`` queries).  In
both a tile is ``[keys, queries]`` — the running maximum and sum, the
saved log-sum-exp and ``sum(o * do)`` are ROWS ``[1, q]``, so a tile's
statistics are reductions over sublanes and broadcast along them (the
usual ``[queries, keys]`` orientation with ``(q, 1)`` columns ran the
forward at half the speed on a v5e: lane reductions on every tile).

- forward: the window's keys and values (``[W, d]``) and the row's
  summaries (``[S, d]``) stay resident in VMEM while the query blocks
  of the window pass; an in-kernel loop walks the visible tiles — the
  local tiles below the diagonal unmasked, the tiles the diagonal
  crosses under the causal mask and cut to the queries that can see
  them, then the ``w * W / chunk`` summaries visible to window ``w`` in
  tiles of ``r`` and at most one tile each of ``r/2``, ``r/4`` ...
  ``rs`` (a window's worth of summaries is whole ``rs`` tiles, so no
  remote tile is ever masked and none that is invisible is computed).
  It writes ``o`` and the row's log-sum-exp (``[rows, heads, 1, T]``
  f32: 4 bytes a query).
- backward, fused: the same walk; a tile's probabilities are re-made
  once from q, k and the log-sum-exp, and the one pass yields dq
  (accumulated over the tiles of the step), dk / dv (f32 VMEM
  accumulators over the query blocks of a window, written as the
  window ends) and the summaries' dks / dvs (accumulators over the
  whole row, written as it ends) — which is why the query-block axis
  of its grid is sequential.

Scores, softmax and every accumulation are f32; the two products of
the forward and the five of the backward take their operands in the
inputs' dtype (probabilities cast before ``p . v``), as the XLA form
does.  ``ops/sequence.py`` ``eva_rows`` is the oracle
(tests/test_sequence.py in interpret mode, tests_tpu/test_eva_kernel.py
on the chip).  Arrays are ``[rows, T, heads * d]`` — the projections'
own layout, a head a 128-lane column block, so nothing is transposed
on the way in or out.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

#: lanes of a vreg: the head size and every lane-side tile is whole
#: multiples of it
LANES = 128
#: scoped VMEM the kernels may use: the backward keeps 4 MB of f32
#: accumulators, 8 MB of double-buffered blocks and a few 4 MB tiles
#: (the default scoped limit is 16 MiB of a v5e's 128)
VMEM_LIMIT = 64 * 1024 * 1024


class Tiles(NamedTuple):
    """Tile sizes of one call: ``q`` queries a grid step, ``k`` local
    keys a tile, ``r`` summaries a tile and ``rs`` the least tile of
    their tail (``r / rs`` a power of two)."""
    q: int
    k: int
    r: int
    rs: int


def tiles_for(head_size: int, window: int, chunk: int,
              t: int) -> Optional[Tiles]:
    """The tiling of the compiled kernels for these sizes, or None
    where they do not tile: the head is whole 128-lane columns, the
    window whole query blocks, a window's summaries whole lane
    tiles."""
    if head_size % LANES or t % window or window % chunk:
        return None
    per_window = window // chunk
    q = next((n for n in (2048, 1024, 512, 256, 128)
              if window % n == 0), None)
    if q is None or per_window % LANES:
        return None
    rs = LANES
    most = min(4, (t // chunk) // rs)       # r / rs is a power of two
    return Tiles(q=q, k=min(q, 512), r=rs << (most.bit_length() - 1),
                 rs=rs)


def _check(t: int, window: int, chunk: int, tiles: Tiles) -> None:
    per_window = window // chunk
    if (t % window or window % chunk or window % tiles.q
            or tiles.q % tiles.k or per_window % tiles.rs
            or tiles.r % tiles.rs or tiles.r > t // chunk
            or (tiles.r // tiles.rs) & (tiles.r // tiles.rs - 1)):
        raise ValueError(f"a row of {t}, windows of {window} and chunks "
                         f"of {chunk} do not tile as {tiles}")


_NT = (((1,), (1,)), ((), ()))      # a . b^T
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _walk(i, tile, k_ref, v_ref, ks_ref, vs_ref, *, window, chunk,
          tiles):
    """Call ``tile(keys, values, mask, summary, off, n, q0)`` for every
    tile visible to query block ``i`` — ``n`` keys from ``off`` (of
    the window, or of the row's summaries) against the block's queries
    from ``q0`` on: local tiles first (the first one shows every query
    a key, so the running maximum is finite from then on), then the
    summaries.  A tile is ``[keys, queries]``."""
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    bq, bk, rb, rs = tiles
    per_window = window // bq
    w, il = i // per_window, i % per_window

    def local(off, mask, q0=0):
        tile(k_ref[pl.ds(off, bk), :], v_ref[pl.ds(off, bk), :], mask,
             False, off, bk, q0)

    def full(c, carry):
        local(pl.multiple_of(c * bk, bk), None)
        return carry

    lax.fori_loop(0, il * (bq // bk), full, None)
    for c in range(bq // bk):
        # the tiles the diagonal crosses: the queries before the
        # tile's first key see none of it and are left out
        shape = (bk, bq - c * bk)
        key = lax.broadcasted_iota(jnp.int32, shape, 0)
        query = lax.broadcasted_iota(jnp.int32, shape, 1)
        local(pl.multiple_of(il * bq + c * bk, bk), key <= query,
              c * bk)

    def remote(off, n):
        tile(ks_ref[pl.ds(off, n), :], vs_ref[pl.ds(off, n), :], None,
             True, off, n, 0)

    def big(c, carry):
        remote(pl.multiple_of(c * rb, rb), rb)
        return carry

    # window w sees the w * W / chunk summaries before it: whole tiles
    # of r, then at most one tile each of r/2, r/4 ... rs
    visible = w * (window // chunk)
    lax.fori_loop(0, visible // rb, big, None)
    n = rb // 2
    while n >= rs:
        @pl.when((visible // n) % 2 == 1)
        def _(n=n):
            remote(pl.multiple_of((visible // (2 * n)) * (2 * n),
                                  2 * n), n)
        n //= 2


def _fwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, window, chunk, tiles):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    # running maximum and sum a query as ROWS [1, q], the weighted sum
    # transposed [d, q]: a tile's statistics are then reductions over
    # sublanes and broadcast along them (no cross-lane traffic)
    m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def tile(keys, values, mask, summary, off, n, q0):
        s = scale * lax.dot_general(keys, q_ref[q0:, :], _NT,
                                    preferred_element_type=jnp.float32)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_ref[:, q0:]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_ref[:, q0:] = alpha * l_ref[:, q0:] \
            + jnp.sum(p, axis=0, keepdims=True)
        acc_ref[:, q0:] = alpha * acc_ref[:, q0:] + lax.dot_general(
            values, p.astype(values.dtype), _TN,
            preferred_element_type=jnp.float32)
        m_ref[:, q0:] = m_next

    _walk(pl.program_id(2), tile, k_ref, v_ref, ks_ref, vs_ref,
          window=window, chunk=chunk, tiles=tiles)
    total = l_ref[...]
    o_ref[...] = jnp.transpose(acc_ref[...] / total).astype(o_ref.dtype)
    lse_ref[...] = m_ref[...] + jnp.log(total)


def _bwd_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, do_ref, lse_ref,
                di_ref, dq_ref, dk_ref, dv_ref, dks_ref, dvs_ref,
                dq_acc, dk_acc, dv_acc, dks_acc, dvs_acc, *, scale,
                window, chunk, tiles):
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    i, n_blocks = pl.program_id(2), pl.num_programs(2)
    per_window = window // tiles.q
    il = i % per_window

    @pl.when(i == 0)
    def _():
        dks_acc[...] = jnp.zeros(dks_acc.shape, jnp.float32)
        dvs_acc[...] = jnp.zeros(dvs_acc.shape, jnp.float32)

    @pl.when(il == 0)
    def _():
        dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
        dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    dq_acc[...] = jnp.zeros(dq_acc.shape, jnp.float32)

    def tile(keys, values, mask, summary, off, n, q0):
        q, do = q_ref[q0:, :], do_ref[q0:, :]
        s = scale * lax.dot_general(keys, q, _NT,
                                    preferred_element_type=jnp.float32)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[:, q0:])                # [keys, q]
        dp = lax.dot_general(values, do, _NT,
                             preferred_element_type=jnp.float32)
        ds = (p * (dp - di_ref[:, q0:])).astype(q.dtype)
        dk_to, dv_to = (dks_acc, dvs_acc) if summary \
            else (dk_acc, dv_acc)
        dv_to[pl.ds(off, n), :] += jnp.dot(
            p.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dk_to[pl.ds(off, n), :] += jnp.dot(
            ds, q, preferred_element_type=jnp.float32)
        dq_acc[q0:, :] += lax.dot_general(
            ds, keys, _TN, preferred_element_type=jnp.float32)

    _walk(i, tile, k_ref, v_ref, ks_ref, vs_ref, window=window,
          chunk=chunk, tiles=tiles)
    dq_ref[...] = (scale * dq_acc[...]).astype(dq_ref.dtype)

    @pl.when(il == per_window - 1)
    def _():
        dk_ref[...] = (scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(i == n_blocks - 1)
    def _():
        dks_ref[...] = (scale * dks_acc[...]).astype(dks_ref.dtype)
        dvs_ref[...] = dvs_acc[...].astype(dvs_ref.dtype)


def _specs(d: int, t: int, n_sum: int, window: int, bq: int):
    """Block specs over ``[rows, T | S, heads * d]`` arrays and the
    ``[rows, heads, 1, T]`` statistics, for the grid (row, head, query
    block): a query block, its window, the row's summaries, a row of
    statistics."""
    from jax.experimental import pallas as pl
    per_window = window // bq
    block = pl.BlockSpec((None, bq, d), lambda b, h, i: (b, i, h))
    win = pl.BlockSpec((None, window, d),
                       lambda b, h, i: (b, i // per_window, h))
    summ = pl.BlockSpec((None, n_sum, d), lambda b, h, i: (b, 0, h))
    stat = pl.BlockSpec((None, None, 1, bq),
                        lambda b, h, i: (b, h, 0, i))
    return block, win, summ, stat


def _params(sequential: bool):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel",
                             "arbitrary" if sequential else "parallel"),
        vmem_limit_bytes=VMEM_LIMIT)


def _flat(a):
    """``[rows, n, heads, d]`` as the kernels' ``[rows, n, heads * d]``."""
    return a.reshape(a.shape[0], a.shape[1], -1)


def _forward(q, k, v, ks, vs, window, chunk, tiles, interpret):
    """(o ``[rows, T, heads, d]``, log-sum-exp ``[rows, heads, 1, T]``)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, nh, d = q.shape
    n_sum = ks.shape[1]
    _check(t, window, chunk, tiles)
    block, win, summ, stat = _specs(d, t, n_sum, window, tiles.q)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=d ** -0.5, window=window,
                          chunk=chunk, tiles=tiles),
        grid=(b, nh, t // tiles.q),
        in_specs=[block, win, win, summ, summ],
        out_specs=[block, stat],
        out_shape=[jax.ShapeDtypeStruct((b, t, nh * d), v.dtype),
                   jax.ShapeDtypeStruct((b, nh, 1, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((1, tiles.q), jnp.float32),
                        pltpu.VMEM((1, tiles.q), jnp.float32),
                        pltpu.VMEM((d, tiles.q), jnp.float32)],
        compiler_params=_params(sequential=False),
        interpret=interpret, name="eva_fused_fwd",
    )(_flat(q), _flat(k), _flat(v), _flat(ks), _flat(vs))
    return o.reshape(b, t, nh, d), lse


def _backward(q, k, v, ks, vs, o, lse, do, window, chunk, tiles,
              interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, nh, d = q.shape
    n_sum = ks.shape[1]
    block, win, summ, stat = _specs(d, t, n_sum, window, tiles.q)
    # sum_keys p dp of a query = o . do: one number a query, a row
    di = jnp.einsum("bthd,bthd->bht", o.astype(jnp.float32),
                    do.astype(jnp.float32))[:, :, None, :]
    like = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        (b, a.shape[1], nh * d), a.dtype)
    f32 = lambda n: pltpu.VMEM((n, d), jnp.float32)  # noqa: E731
    grads = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=d ** -0.5, window=window,
                          chunk=chunk, tiles=tiles),
        grid=(b, nh, t // tiles.q),
        in_specs=[block, win, win, summ, summ, block, stat, stat],
        out_specs=[block, win, win, summ, summ],
        out_shape=[like(q), like(k), like(v), like(ks), like(vs)],
        scratch_shapes=[f32(tiles.q), f32(window), f32(window),
                        f32(n_sum), f32(n_sum)],
        compiler_params=_params(sequential=True),
        interpret=interpret, name="eva_fused_bwd",
    )(_flat(q), _flat(k), _flat(v), _flat(ks), _flat(vs), _flat(do), lse,
      di)
    return tuple(g.reshape(b, g.shape[1], nh, d) for g in grads)


@functools.lru_cache(maxsize=None)
def _op():
    """The differentiable call, made once (jax is imported on use)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
    def op(q, k, v, ks, vs, window, chunk, tiles, interpret):
        return _forward(q, k, v, ks, vs, window, chunk, tiles,
                        interpret)[0]

    def fwd(q, k, v, ks, vs, window, chunk, tiles, interpret):
        o, lse = _forward(q, k, v, ks, vs, window, chunk, tiles,
                          interpret)
        return o, (q, k, v, ks, vs, o, lse)

    def bwd(window, chunk, tiles, interpret, saved, do):
        return _backward(*saved, do, window, chunk, tiles, interpret)

    op.defvjp(fwd, bwd)
    return op


def eva_fused(q: Any, k: Any, v: Any, ks: Any, vs: Any, window: int,
              chunk: int, tiles: Tiles, interpret: bool = False) -> Any:
    """EVA attention of whole rows: q, k, v ``[rows, T, heads, d]``,
    the summaries ks, vs ``[rows, T / chunk, heads, d]`` of
    ``eva_summaries``; the heads' outputs ``[rows, T, heads, d]``.
    Differentiable in all five (``jax.custom_vjp``: the backward is
    the fused kernel, from q, k, v, the summaries, o and the
    log-sum-exp)."""
    return _op()(q, k, v, ks, vs, window, chunk, tiles, interpret)
