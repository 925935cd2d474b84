"""Pallas TPU kernels for the chunk products of the gated delta rule
(``ops/deltanet.py`` ``_chunk_products``): what the scan over chunks
reads — ``u = T (beta V)``, ``w = T (beta Gamma K)``, the decayed ``q
k^T`` and ``log Gamma``, with ``T = (I + M)^-1`` — made for a chunk
inside ONE kernel, every ``[C, C]`` matrix (the decay, ``k k^T``, ``q
k^T``, ``P = -M``, its powers, ``T``) living in VMEM from birth to last
use.  HBM sees the chunked q, k, v (compute dtype), g, beta (f32) and
the four results, nothing between.

Two kernels, one grid step a group of ``Tiles.pairs`` (chunk, row, key
head) pairs.  A *system* is ``Tiles.pack`` value heads' ``[C, C]``
matrices side by side on the diagonal of one ``[R, R]`` block, ``R =
pack * C``: two chunks of 64 fill the 128 lanes (and the MXU's 128
columns) that a lone 64-wide matrix would half use; off the diagonal
the decay is masked to zero before anything is made from it, so the
blocks never mix and ``P`` stays nilpotent of order C.

- forward: ``log Gamma`` of all the step's systems as one product with
  a triangular matrix of ones; their column forms (``beta_i``, ``log
  Gamma_i`` down the sublanes) by ONE transpose; then a system at a
  time ``decay = exp(mask(log Gamma_i - log Gamma_j))`` (masked BEFORE
  the exp), ``k k^T``, ``q k^T`` (compute-dtype operands, f32
  accumulation), ``P``, the inverse as the same doubling product as the
  XLA form — ``S <- S (I + Q)``, ``Q <- Q Q`` — with the two products
  of a round sharing their right operand in one ``[2R, R] x [R, R]``
  call, then ``[u | w] = T [beta V | beta Gamma K]`` in one call.
- backward (``jax.custom_vjp``; the residuals are the kernel's inputs
  alone): re-makes ``T`` exactly as the forward does, then, with ``A =
  I + M``, ``u = T b_v``, ``w = T b_k``,

      d b_v = T^T du,  d b_k = T^T dw,
      dA = -(d b_v) u^T - (d b_k) w^T   (strictly lower; rest masked),

  two ``[R, R] x [R, dk + dv]`` products and one ``[R, dk + dv] x [dk +
  dv, R]``, then the elementwise chain back to k, beta, g and the ``q
  k^T`` branch of the decayed scores; no transpose of the ten-product
  chain exists.  Reductions over a row land in columns; they are
  gathered a lane a system and turned into rows by one transpose a
  grid step.

Precision: every product of f32 operands is three bf16 passes — the
operands split ``x = hi + lo`` and ``hi hi + hi lo + lo hi``
accumulated in f32 in one call over a three-fold contraction — which is
XLA's ``Precision.HIGH``, what the XLA form asks for (Mosaic offers
DEFAULT and HIGHEST alone); the cumulative sums take three-way splits
against exact ones and are f32-exact.  State, decays and the triangular
system never leave f32.  ``ops/deltanet.py`` ``_chunk_products`` is the
oracle (tests/test_qwen3next.py in interpret mode, tests_tpu/
test_hybrid_layers.py on the chip).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple, Optional

#: lanes of a vreg: a head is whole multiples of it, a system fills it
LANES = 128
#: (chunk, row, key head) pairs a grid step
PAIRS = 8
#: scoped VMEM the kernels may use: the backward's double-buffered
#: blocks are 7 MB and a system's live f32 matrices a few more (the
#: default scoped limit is 16 MiB of a v5e's 128)
VMEM_LIMIT = 48 * 1024 * 1024


class Tiles(NamedTuple):
    """``pairs`` (chunk, row, key head) pairs a grid step; ``pack``
    value heads' systems on the diagonal of one ``[pack * C, pack *
    C]`` block."""
    pairs: int
    pack: int


def tiles_for(chunk: int, key_head_size: int, value_head_size: int,
              heads_a_key: int) -> Optional[Tiles]:
    """The tiling of the compiled kernels for these sizes, or None
    where they do not tile: heads of whole 128-lane columns, a chunk of
    64 (two value heads of a key head fill the lanes together, else one
    half fills them) or 128."""
    if key_head_size % LANES or value_head_size % LANES \
            or chunk not in (LANES // 2, LANES):
        return None
    pack = 2 if chunk < LANES and heads_a_key % 2 == 0 else 1
    # the step's columns are made by one [R, R] transpose: beta and
    # log Gamma of every system of the step fit its R rows
    if 2 * PAIRS * (heads_a_key // pack) > pack * chunk:
        return None
    return Tiles(PAIRS, pack)


_NT = (((1,), (1,)), ((), ()))      # a . b^T
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def _split(x, ways: int = 2):
    """f32 ``x`` as ``ways`` bf16 terms, largest first, that sum to it
    (to 16 bits of mantissa for two, all 24 for three)."""
    import jax.numpy as jnp
    terms = []
    for _ in range(ways):
        terms.append(x.astype(jnp.bfloat16))
        x = x - terms[-1].astype(jnp.float32)
    return terms


def _dot3(a, b, dims=None):
    """``a . b`` (``dims``: a ``dot_general`` contraction over the LAST
    axis of ``a``) of f32 operands given as their :func:`_split`
    terms: ``hi hi + hi lo + lo hi`` in one call over a three-fold
    contraction, f32 accumulation — three bf16 passes,
    ``Precision.HIGH``."""
    import jax.numpy as jnp
    from jax import lax
    (ah, al), (bh, bl) = a, b
    a3 = jnp.concatenate([ah, ah, al], axis=1)
    if dims is None:
        return jnp.dot(a3, jnp.concatenate([bh, bl, bh], axis=0),
                       preferred_element_type=jnp.float32)
    return lax.dot_general(a3, jnp.concatenate([bh, bl, bh], axis=1),
                           dims, preferred_element_type=jnp.float32)


def _high(a, b, dims=None):
    """:func:`_dot3` of f32 ``a`` and ``b``."""
    return _dot3(_split(a), _split(b), dims)


def _sums(x, ones):
    """``x . ones`` for a matrix of exact zeros and ones: f32-exact
    (three bf16 terms of ``x``, each product exact)."""
    import jax.numpy as jnp
    return jnp.dot(jnp.concatenate(_split(x, 3), axis=1),
                   jnp.concatenate([ones.astype(jnp.bfloat16)] * 3,
                                   axis=0),
                   preferred_element_type=jnp.float32)


def _inverse(p, eye, rounds: int):
    """``(I - p)^-1 = (I + p)(I + p^2)(I + p^4)...`` for a nilpotent
    ``p``: ``rounds`` squarings, as the XLA form makes it; the two
    products of a round share their right operand in one call."""
    import jax.numpy as jnp
    s = eye + p
    if not rounds:
        return s
    r = p.shape[0]
    q = _split(p)
    q = _dot3(q, q)
    for _ in range(rounds - 1):
        qs, ss = _split(q), _split(s)
        both = _dot3([jnp.concatenate(t, axis=0) for t in zip(qs, ss)],
                     qs)
        q, s = both[:r], s + both[r:]
    return s + _high(s, q)


class _Step(NamedTuple):
    """What both kernels make first for a grid step."""
    gsum: Any       # log Gamma of the step's systems, rows [G, R]
    cols: Any       # [R, R]: lane i log Gamma, lane G + i beta of system i
    eye: Any
    lower: Any      # same value head and i >= j
    strict: Any     # ... i > j
    same: Any       # same value head (the ones of a cumulative sum)


def _step(g_ref, b_ref, chunk: int) -> _Step:
    import jax.numpy as jnp
    from jax import lax
    n, r = g_ref.shape
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    col = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    same = (row // chunk) == (col // chunk)
    gsum = _sums(g_ref[...], same & (row <= col))
    rows = [gsum, b_ref[...]]
    if r > 2 * n:
        rows.append(jnp.zeros((r - 2 * n, r), jnp.float32))
    return _Step(gsum, jnp.transpose(jnp.concatenate(rows, axis=0)),
                 (row == col).astype(jnp.float32), same & (row >= col),
                 same & (row > col), same)


class _System(NamedTuple):
    """One system's matrices, as both kernels make them."""
    be: Any         # beta, a column [R, 1]
    gam: Any        # Gamma, a column
    decay: Any      # Gamma_i / Gamma_j for j <= i of a value head, else 0
    low: Any        # ... for j < i
    t: Any          # (I + M)^-1
    b: Any          # [beta V | beta Gamma K], f32 [R, dv + dk]


def _system(st: _Step, i: int, kk, k2, v, chunk: int) -> _System:
    import jax.numpy as jnp
    n = st.gsum.shape[0]
    gs, be = st.cols[:, i:i + 1], st.cols[:, n + i:n + i + 1]
    # masked BEFORE the exp: above the diagonal the difference is
    # positive and may overflow
    decay = jnp.exp(jnp.where(st.lower, gs - st.gsum[i:i + 1, :],
                              -jnp.inf))
    low = jnp.where(st.strict, decay, 0.0)
    rounds = max(0, (chunk - 1).bit_length() - 1)
    t = _inverse(-(be * kk) * low, st.eye, rounds)
    gam = jnp.exp(gs)
    b = jnp.concatenate([be * v.astype(jnp.float32),
                         (be * gam) * k2.astype(jnp.float32)], axis=1)
    return _System(be, gam, decay, low, t, b)


def _tile(a, pack: int, axis: int):
    """``a`` once a value head of a system along ``axis``: a pair's
    ``[C, d]`` rows as a system's ``[R, d]``; a system's ``[R, C]``
    side by side as ``[R, R]`` (off the diagonal it meets a zero
    decay)."""
    import jax.numpy as jnp
    return jnp.concatenate([a] * pack, axis=axis) if pack > 1 else a


def _scores(q_ref, k_ref, p: int, pack: int):
    """Pair ``p``'s k and q as a system's rows, ``k k^T`` and ``q
    k^T`` (compute-dtype operands, f32 accumulation)."""
    import jax.numpy as jnp
    from jax import lax
    k2, q2 = _tile(k_ref[p], pack, 0), _tile(q_ref[p], pack, 0)
    return k2, q2, lax.dot_general(
        k2, k2, _NT, preferred_element_type=jnp.float32), lax.dot_general(
        q2, k2, _NT, preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, u_ref, w_ref, a_ref,
                gs_ref, *, chunk, pack):
    import jax.numpy as jnp
    from jax import lax

    st = _step(g_ref, b_ref, chunk)
    gs_ref[...] = st.gsum
    pairs, per_pair = v_ref.shape[:2]
    dv = v_ref.shape[-1]
    top = lax.broadcasted_iota(jnp.int32, (pack * chunk, chunk), 0) < chunk
    for p in range(pairs):
        k2, _, kk, qk = _scores(q_ref, k_ref, p, pack)
        for j in range(per_pair):
            s = _system(st, p * per_pair + j, kk, k2, v_ref[p, j], chunk)
            uw = _high(s.t, s.b)
            u_ref[p, j] = uw[:, :dv]
            w_ref[p, j] = uw[:, dv:].astype(w_ref.dtype)
            a = qk * s.decay
            if pack > 1:        # the blocks on the diagonal alone
                a = jnp.where(top, a[:, :chunk], a[:, chunk:])
            a_ref[p, j] = a.astype(a_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, du_ref, dw_ref,
                da_ref, dgs_ref, dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                *, chunk, pack):
    import jax.numpy as jnp
    from jax import lax

    st = _step(g_ref, b_ref, chunk)
    pairs, per_pair = v_ref.shape[:2]
    dv = v_ref.shape[-1]
    cd = q_ref.dtype
    r = pack * chunk
    lane = lax.broadcasted_iota(jnp.int32, (r, r), 1)
    d_be = jnp.zeros((r, r), jnp.float32)   # a lane a system, as st.cols
    d_gs = jnp.zeros((r, r), jnp.float32)
    d_gs_rows = []

    def fold(x):                # the value heads of a system share k, q
        return sum(x[n * chunk:(n + 1) * chunk] for n in range(pack))

    for p in range(pairs):
        k2, q2, kk, qk = _scores(q_ref, k_ref, p, pack)
        dk2 = jnp.zeros(k2.shape, jnp.float32)
        dq2 = jnp.zeros(q2.shape, jnp.float32)
        for j in range(per_pair):
            i = p * per_pair + j
            v = v_ref[p, j]
            s = _system(st, i, kk, k2, v, chunk)
            uw = _high(s.t, s.b)                    # u | w, in f32
            db = _high(jnp.transpose(s.t), jnp.concatenate(
                [du_ref[p, j], dw_ref[p, j].astype(jnp.float32)], axis=1))
            # dA = -(d b_v) u^T - (d b_k) w^T, its strictly lower part
            dm = jnp.where(st.strict, -_high(db, uw, _NT), 0.0)
            da = _tile(da_ref[p, j].astype(jnp.float32), pack, 1)
            md = dm * s.low
            # M = beta (k k^T) decay; a_qk = (q k^T) decay
            dd = (s.be * (dm * kk) + da * qk) * s.decay     # d log-decay
            dkk = (s.be * md).astype(cd)
            dqk = (da * s.decay).astype(cd)
            dbv, dbk = db[:, :dv], db[:, dv:]
            key = jnp.sum(dbk * k2.astype(jnp.float32), axis=1,
                          keepdims=True)
            bcol = jnp.sum(md * kk, axis=1, keepdims=True) \
                + jnp.sum(dbv * v.astype(jnp.float32), axis=1,
                          keepdims=True) + s.gam * key
            bg = s.be * s.gam
            gcol = jnp.sum(dd, axis=1, keepdims=True) + bg * key
            d_be = jnp.where(lane == i, bcol, d_be)
            d_gs = jnp.where(lane == i, gcol, d_gs)
            d_gs_rows.append(jnp.sum(dd, axis=0, keepdims=True))
            dv_ref[p, j] = (s.be * dbv).astype(dv_ref.dtype)
            dk2 = dk2 + bg * dbk \
                + jnp.dot(dkk, k2, preferred_element_type=jnp.float32) \
                + lax.dot_general(dkk, k2, _TN,
                                  preferred_element_type=jnp.float32) \
                + lax.dot_general(dqk, q2, _TN,
                                  preferred_element_type=jnp.float32)
            dq2 = dq2 + jnp.dot(dqk, k2,
                                preferred_element_type=jnp.float32)
        dk_ref[p] = fold(dk2).astype(dk_ref.dtype)
        dq_ref[p] = fold(dq2).astype(dq_ref.dtype)
    n = st.gsum.shape[0]
    db_ref[...] = jnp.transpose(d_be)[:n]
    # log Gamma_i moved the decay of its row (+) and of its column (-),
    # Gamma_i itself, and the result that hands it to the scan; g_m
    # moves every log Gamma_i of its value head from m on
    d_gsum = jnp.transpose(d_gs)[:n] \
        - jnp.concatenate(d_gs_rows, axis=0) + dgs_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (r, r), 0)
    dg_ref[...] = _sums(d_gsum, st.same & (row >= lane))


def _flat(qc, kc, vc, gc, bc, tiles: Tiles):
    """The arrays of ``_chunk_products`` as the kernels block them:
    pairs first (padded to whole grid steps), a pair's value heads in
    systems of ``pack``."""
    import jax.numpy as jnp
    c, dk = qc.shape[-2:]
    r, dv = vc.shape[-3], vc.shape[-1]
    pairs = qc.size // (c * dk)
    per_pair, rows = r // tiles.pack, tiles.pack * c
    pad = -pairs % tiles.pairs

    def flat(a, *shape):
        a = a.reshape((pairs,) + shape)
        return jnp.pad(a, ((0, pad),) + ((0, 0),) * len(shape)) \
            if pad else a

    return (flat(qc, c, dk), flat(kc, c, dk),
            flat(vc, per_pair, rows, dv),
            flat(gc, per_pair, rows).reshape(-1, rows),
            flat(bc, per_pair, rows).reshape(-1, rows))


def _specs(tiles: Tiles, per_pair: int, rows: int):
    """Block specs of a grid step: a pair's rows, a system's rows, the
    step's vectors."""
    from jax.experimental import pallas as pl

    def pair(c, d):
        return pl.BlockSpec((tiles.pairs, c, d), lambda n: (n, 0, 0))

    def system(d):
        return pl.BlockSpec((tiles.pairs, per_pair, rows, d),
                            lambda n: (n, 0, 0, 0))

    vector = pl.BlockSpec((tiles.pairs * per_pair, rows),
                          lambda n: (n, 0))
    return pair, system, vector


def _call(kernel, name, tiles: Tiles, chunk: int, steps: int, in_specs,
          out_specs, out_shape, interpret, *args):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.pallas_call(
        functools.partial(kernel, chunk=chunk, pack=tiles.pack),
        grid=(steps,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret, name=name)(*args)


def _forward(q, k, v, g, b, tiles, interpret):
    """(u, w, a_qk, gsum) of the flat arrays."""
    import jax
    import jax.numpy as jnp
    n, c, dk = q.shape
    per_pair, rows, dv = v.shape[1:]
    pair, system, vector = _specs(tiles, per_pair, rows)
    shape = jax.ShapeDtypeStruct
    return _call(
        _fwd_kernel, "gdn_products_fwd", tiles, c, n // tiles.pairs,
        [pair(c, dk), pair(c, dk), system(dv), vector, vector],
        [system(dv), system(dk), system(c), vector],
        [shape(v.shape, jnp.float32),
         shape((n, per_pair, rows, dk), q.dtype),
         shape((n, per_pair, rows, c), q.dtype),
         shape(g.shape, jnp.float32)],
        interpret, q, k, v, g, b)


def _backward(q, k, v, g, b, du, dw, da, dgs, tiles, interpret):
    """(dq, dk, dv, dg, dbeta) of the flat arrays."""
    import jax
    n, c, dk = q.shape
    per_pair, rows, dv = v.shape[1:]
    pair, system, vector = _specs(tiles, per_pair, rows)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    return _call(
        _bwd_kernel, "gdn_products_bwd", tiles, c, n // tiles.pairs,
        [pair(c, dk), pair(c, dk), system(dv), vector, vector,
         system(dv), system(dk), system(c), vector],
        [pair(c, dk), pair(c, dk), system(dv), vector, vector],
        [like(q), like(k), like(v), like(g), like(b)],
        interpret, q, k, v, g, b, du, dw, da, dgs)


@functools.lru_cache(maxsize=None)
def _op():
    """The differentiable call, made once (jax is imported on use)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
    def op(q, k, v, g, b, tiles, interpret):
        return tuple(_forward(q, k, v, g, b, tiles, interpret))

    def fwd(q, k, v, g, b, tiles, interpret):
        return op(q, k, v, g, b, tiles, interpret), (q, k, v, g, b)

    def bwd(tiles, interpret, saved, grads):
        return tuple(_backward(*saved, *grads, tiles, interpret))

    op.defvjp(fwd, bwd)
    return op


def chunk_products(qc: Any, kc: Any, vc: Any, gc: Any, bc: Any,
                   tiles: Tiles, interpret: bool = False):
    """``ops/deltanet.py`` ``_chunk_products`` by the kernels: qc, kc
    ``[n, rows, Hk, C, dk]``, vc ``[n, rows, Hk, r, C, dv]`` in the
    compute dtype, gc, bc ``[n, rows, Hk, r, C]`` f32 -> (u f32, w,
    a_qk in the compute dtype, log Gamma f32), shaped as there.
    Differentiable in all five (``jax.custom_vjp``: the residuals are
    these five arrays, the backward is the second kernel)."""
    c, dk = qc.shape[-2:]
    dv = vc.shape[-1]
    if vc.shape[-3] % tiles.pack:
        raise ValueError(f"{vc.shape[-3]} value heads a key head do not "
                         f"tile as {tiles}")
    u, w, a, gsum = _op()(*_flat(qc, kc, vc, gc, bc, tiles), tiles,
                          interpret)
    pairs = qc.size // (c * dk)
    return (u[:pairs].reshape(vc.shape[:-1] + (dv,)),
            w[:pairs].reshape(vc.shape[:-1] + (dk,)),
            a[:pairs].reshape(vc.shape[:-1] + (c,)),
            gsum[:gc.size // (tiles.pack * c)].reshape(gc.shape))
