"""``moe``: a sparse mixture-of-experts MLP of which this device holds
a share, beside one shared expert where the model has one
(``shared_size`` 0: none — no ``s_*`` parameters, no op under
``moe/shared``) — a layer type of the sequence op family
(``ops/sequence.py``).

``p = softmax(u W_r)`` over ALL ``experts_total`` experts in f32; the
``top_k`` largest, ``w_e = s p_e / sum_topk p`` (``s`` the
``routed_scale``, 1 unless the model scales its routed experts); ``y =
sum over e in topk AND held of w_e W_d,e(silu(W_g,e u) * W_u,e u) +
sigmoid(u . w_s) Shared(u)``, the shared expert a SwiGLU.  The layer
is told which experts it holds (``experts_held`` of them from
``first_held``): only those contribute, and that partial sum goes on
— in a deployment the other shares' parts arrive by an exchange
between chips; on one chip there is none, and no code stands in for
the absent chips.

**No token is dropped whatever the imbalance.**  A row is cut into
blocks of tokens.  Inside a block the (token, slot) pairs are sorted by
expert (the pairs of experts held elsewhere last), so one expert's
rows are contiguous and the three products are grouped products over
the held experts.  The buffers the sorted rows go through come in two
sizes, both static:

- **whole**: ``block * top_k`` rows — every token of the block may
  choose ``top_k`` held experts: safe for the worst routing.  Gather
  and combine are row gathers (the sort is a permutation: the backward
  of one is the other), the combine adds a token's slots in f32; the
  rows beyond the held pairs are never multiplied, but they are moved.
- **compact**: ``capacity`` rows (:func:`dispatch_capacity`, from
  shapes alone) — the held pairs a block EXPECTS, ``block * top_k *
  experts_held / experts_total``, times ``DISPATCH_HEADROOM``, rounded
  up to the grouped product's row unit: 4 096 of 40 960 rows where 32
  of 512 experts are held at top 10, 12 288 of 32 768 where 16 of 64
  at top 8.  Everything after the sort works on the first ``capacity``
  sorted pairs: the gather takes ``capacity`` rows, the products and
  their selects run on ``[capacity, ...]``, and the combine is a
  segment sum (:func:`token_sums`: the rows permuted to token order,
  then a grouped product of a one-hot against them, a group a tile of
  tokens — 1.0 x a row accumulated in f32, exact as the whole path's
  sum).  Gather and combine stay each other's backward.

Where the capacity is over half of the whole buffer (every expert
held; the tiny presets) or the trace is batched, the layer has the
whole buffers alone.  Otherwise it has the compact ones alone, and a
block goes through them as many times as its held pairs fill them
(:meth:`MoE._capped_block`: a ``while_loop`` whose trip count the
device reads off the routing, forward and backward): once where they
fit, and a block that overflows walks its sorted pairs a bufferful at
a time and adds the parts up — so every pair of a held expert is
multiplied whatever the routing, in one traced body and within the
compact buffers' memory.  A block keeps only its inputs for the
backward (its buffers live once, not once a block), which walks the
pieces again and re-makes each.

Two forms of the grouped product, chosen by :func:`grouped_path` from
platform and shapes and journaled with the share (``moe.share``):
``gmm`` — on a TPU where the shapes tile, the Pallas grouped matmul
that ships with jax (``jax.experimental.pallas.ops.tpu.megablox``),
which visits only the tiles that hold rows — and ``ragged_dot``
(``jax.lax.ragged_dot``) everywhere else.  Under ``gmm`` every kernel
call — a product's forward, its input gradient (contraction and
columns trade places), its weight gradient (``tgmm``), and the
segment sum's — takes its contraction and column tiles from its own
``(m, k, n)`` (:class:`GroupedTiles`): the fewest padded elements a
side, then the fewest grid steps, within the scoped VMEM the kernels
get; rows are ``GMM_ROWS`` a tile.  The share journals each call's
tiles and ``padding`` (the MXU work of those tiles over the product's
own).

Device ops carry ``moe/router``, ``moe/dispatch``, ``moe/experts``,
``moe/shared``.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

from veles_tpu import events, telemetry
from veles_tpu.ops.sequence import SequenceUnit, under_vmap

LANES = 128
SUBLANES = 8
#: bytes one block's gathered rows may take (the block is the largest
#: power-of-two part of the row under it)
DISPATCH_BUFFER_BYTES = 256 << 20
#: rows of a grouped product's tile
GMM_ROWS = 512
#: a compact dispatch buffer holds this many times the held pairs a
#: block expects
DISPATCH_HEADROOM = 1.5
#: tokens whose rows one group of the compact combine's grouped product
#: sums
FOLD_TOKENS = 512
#: scoped VMEM a grouped-product kernel may take: megablox sets no
#: ``vmem_limit_bytes``, so it is the compiler's default scoped limit,
#: 16 MiB on a v5e (its refusal of a kernel that asks more reads
#: "limit 16.00M"; ``scripts/moe_kernel_chip.py`` reads it on the chip)
GMM_VMEM_BYTES = 16 << 20
#: the least tile of a side, where the side is that long: a side of 11
#: x 128 never falls to 128-wide steps
GMM_LEAST_TILE = 512
#: the three calls of one grouped product: its forward, its input
#: gradient (``transpose_rhs``: contraction and columns trade places)
#: and its weight gradient (``tgmm``)
GMM_CALLS = ("forward", "input_grad", "weight_grad")


def gmm_vmem_bytes(tm: int, tk: int, tn: int, itemsize: int) -> int:
    """Scoped VMEM one grid step of either kernel takes, at most: its
    three blocks ``(tm, tk)``, ``(tk, tn)``, ``(tm, tn)`` double-
    buffered at ``itemsize``, and an f32 working copy of each — the
    accumulator (``(tm, tn)`` in ``gmm``, ``(tk, tn)`` in ``tgmm``) is
    one, the product's and the masked loads' values the others.  Above
    the least limit each kernel compiles under, for every tiling tried
    (11.2 / 13.1 MiB at 512 x 1 152 x 896 in bf16, where this reads
    15.9)."""
    return (2 * itemsize + 4) * (tm * tk + tk * tn + tm * tn)


def _side_tiles(d: int):
    """A side's candidate tiles: multiples of ``LANES`` from
    ``min(d, GMM_LEAST_TILE)`` up to the side rounded up to them."""
    least = -(-min(d, GMM_LEAST_TILE) // LANES) * LANES
    return range(least, -(-d // LANES) * LANES + 1, LANES)


def _padded(d: int, t: int) -> int:
    return -(-d // t) * t


@dataclasses.dataclass(frozen=True)
class GroupedTiles:
    """The tiling of one grouped-product kernel call from its own
    ``(m, k, n)`` — what megablox's ``gmm`` and ``tgmm`` call when
    handed a function: ``GMM_ROWS`` rows, and the ``(tk, tn)`` of the
    fewest padded elements ``ceil(k / tk) tk x ceil(n / tn) tn``, then
    the fewest grid steps, then the widest columns, among tiles of
    whole lanes no shorter than ``min(side, GMM_LEAST_TILE)`` whose
    step fits ``GMM_VMEM_BYTES`` (:func:`gmm_vmem_bytes`) at operands of
    ``itemsize`` bytes.  Hashable, equal by value: the kernels' jit
    takes it as a static argument."""
    itemsize: int = 2

    def __call__(self, m: int, k: int, n: int) -> Tuple[int, int, int]:
        del m       # rows are GMM_ROWS a tile whatever the buffer
        _, tk, tn = min(
            ((_padded(k, tk) * _padded(n, tn),
              -(-k // tk) * -(-n // tn), -(-n // tn)), tk, tn)
            for tk in _side_tiles(k) for tn in _side_tiles(n)
            if gmm_vmem_bytes(GMM_ROWS, tk, tn, self.itemsize)
            <= GMM_VMEM_BYTES)
        return GMM_ROWS, tk, tn


def padding(tiles, k: int, n: int) -> float:
    """The MXU work of ``tiles`` over a ``[·, k] x [k, n]`` product's
    own, rows aside (1.0: nothing padded)."""
    return _padded(k, tiles[1]) * _padded(n, tiles[2]) / float(k * n)


def product_calls(width: int, inner: int):
    """``(product, call, k, n)`` of every kernel call of the experts'
    two products: ``in`` (gate and up, ``[·, width] x [width, inner]``)
    and ``out`` (down, ``[·, inner] x [inner, width]``)."""
    for product, (k, n) in (("in", (width, inner)),
                            ("out", (inner, width))):
        for call, shape in zip(GMM_CALLS, ((k, n), (n, k), (k, n))):
            yield (product, call) + shape


def fold_tile(tokens: int) -> int:
    """Tokens a group of the segment sum (:func:`token_sums`) sums."""
    return FOLD_TOKENS if tokens % FOLD_TOKENS == 0 else tokens


def call_tiles(tiles: GroupedTiles, rows: int, width: int, inner: int,
               fold_tokens: Optional[int] = None) -> Dict[str, Any]:
    """The tiles ``tiles`` gives each kernel call of the experts'
    products over ``rows``-row buffers and, given ``fold_tokens``, the
    segment sum's (``[fold_tokens, rows] x [rows, width]``), and each
    call's ``padding``: numbers alone, for the journal."""
    calls: Dict[str, Any] = {"in": {}, "out": {}}
    pads: Dict[str, Any] = {"in": {}, "out": {}}
    for product, call, k, n in product_calls(width, inner):
        calls[product][call] = tiles(rows, k, n)
        pads[product][call] = round(padding(calls[product][call], k, n), 4)
    if fold_tokens is not None:
        calls["fold"] = (GMM_ROWS, fold_tokens,
                         tiles(rows, fold_tokens, width)[2])
        pads["fold"] = round(padding(calls["fold"], fold_tokens, width), 4)
    return {"tiles": calls, "padding": pads}


def block_tokens(tokens: int, top_k: int, width: int,
                 itemsize: int = 2) -> int:
    """Tokens of one dispatch block: the whole row, halved while its
    ``tokens * top_k`` gathered rows pass ``DISPATCH_BUFFER_BYTES``."""
    block = tokens
    while block % 2 == 0 and \
            block * top_k * width * itemsize > DISPATCH_BUFFER_BYTES:
        block //= 2
    return block


def grouped_path(platform: str, rows: int, width: int, inner: int,
                 batched: bool = False, itemsize: int = 2
                 ) -> Dict[str, Any]:
    """Which form the grouped products take, from what the code
    observes; under ``gmm``, ``tiles``: the ONE chooser every kernel
    call asks for its (rows, contraction, columns) tiles, from that
    call's own ``(m, k, n)`` (operands of ``itemsize`` bytes)."""
    if platform != "tpu":
        return {"form": "ragged_dot", "reason": "platform"}
    if batched:
        return {"form": "ragged_dot", "reason": "batched"}
    if rows % GMM_ROWS or width % LANES or inner % LANES:
        return {"form": "ragged_dot", "reason": "shapes"}
    return {"form": "gmm", "tiles": GroupedTiles(itemsize)}


def dispatch_capacity(rows: int, experts_held: int, experts_total: int,
                      unit: int, batched: bool = False) -> Optional[int]:
    """Rows of a block's compact dispatch buffer: the held pairs the
    block expects (``rows * experts_held / experts_total``) times
    ``DISPATCH_HEADROOM``, rounded up to ``unit`` (the grouped
    product's row unit).  None — no compaction, the whole ``rows``-row
    buffer — where that is over half of ``rows`` or the trace is
    batched (under ``vmap`` every row would walk as many pieces as the
    fullest)."""
    if batched:
        return None
    want = math.ceil(rows * experts_held * DISPATCH_HEADROOM
                     / experts_total)
    capacity = -(-want // unit) * unit
    return capacity if 2 * capacity <= rows else None


def grouped_matmul(lhs, rhs, group_sizes, tiles=None):
    """``lhs[rows of group e] @ rhs[e]``: lhs ``[m, k]`` sorted by
    group, rhs ``[groups, k, n]``; rows beyond the groups come out
    zero.  ``tiles`` given (a :class:`GroupedTiles`, asked by each of
    the forward's and the backward's kernel calls): the Pallas kernel;
    else ``lax.ragged_dot``.

    The kernel never writes the tiles it does not visit — forward (the
    product's rows beyond the groups) and backward (the same rows of
    the gradient it hands back for ``lhs``) are whatever the memory
    held.  Both sides are therefore SELECTED to zero there (a select,
    not a product: garbage may be NaN): the output here, and through
    the input's select its gradient."""
    import jax.numpy as jnp
    from jax import lax
    live = (jnp.arange(lhs.shape[0]) < jnp.sum(group_sizes))[:, None]
    lhs = jnp.where(live, lhs, jnp.zeros((), lhs.dtype))
    if tiles is None:
        out = lax.ragged_dot(lhs, rhs, group_sizes)
    else:
        from jax.experimental.pallas.ops.tpu.megablox import ops
        out = ops.gmm(lhs, rhs, group_sizes, lhs.dtype, tiles)
    return jnp.where(live, out, jnp.zeros((), out.dtype))


def _take(a, idx):
    import jax.numpy as jnp
    return jnp.take(a, idx, axis=0)


def _permute():
    """``a[order]`` whose backward is ``g[inverse]`` — a gather both
    ways where autodiff would scatter-add."""
    import jax

    @jax.custom_vjp
    def permute(a, order, inverse):
        return _take(a, order)

    def fwd(a, order, inverse):
        return _take(a, order), (order, inverse)

    def bwd(res, g):
        return _take(g, res[1]), None, None

    permute.defvjp(fwd, bwd)
    return permute


def _dispatch(top_k: int):
    """(gather, combine) over a sort ``order`` of the ``tokens * top_k``
    (token, slot) pairs and its ``inverse``: ``gather(x)`` = the row of
    each pair's token, sorted; ``combine(y)`` = each token's sorted rows
    back and its slots added in f32.  Each is the other's backward."""
    import jax
    import jax.numpy as jnp

    def spread(x, order):
        return _take(x, order // top_k)

    def fold(y, inverse, dtype):
        y = _take(y, inverse)
        return jnp.sum(y.reshape((-1, top_k) + y.shape[1:]).astype(
            jnp.float32), axis=1).astype(dtype)

    @jax.custom_vjp
    def gather(x, order, inverse):
        return spread(x, order)

    def gather_fwd(x, order, inverse):
        return spread(x, order), inverse

    def gather_bwd(inverse, g):
        return fold(g, inverse, g.dtype), None, None

    gather.defvjp(gather_fwd, gather_bwd)

    @partial(jax.custom_vjp, nondiff_argnums=(3,))
    def combine(y, order, inverse, dtype):
        return fold(y, inverse, jnp.float32)

    def combine_fwd(y, order, inverse, dtype):
        return fold(y, inverse, jnp.float32), order

    def combine_bwd(dtype, order, g):
        return spread(g.astype(dtype), order), None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return gather, combine


def token_sums(y, pairs, by_token, tokens: int, top_k: int, tiles=None):
    """f32 ``[tokens, h]``: row ``j`` of ``y`` added to token ``pairs[j]
    // top_k`` — a segment sum as a grouped product.  The rows go to
    token order (``by_token``: the argsort of ``pairs``), then a one-hot
    (row -> its token inside a tile of ``FOLD_TOKENS`` tokens) is
    multiplied into them, a group a tile of tokens: 1.0 x the row
    accumulated in f32, so the sum is exact.  ``tiles`` given (a
    :class:`GroupedTiles`: the column tile is its choice for this
    call): the Pallas kernel (it visits every group, so every tile of
    the result is written); else ``lax.ragged_dot_general``."""
    import jax.numpy as jnp
    from jax import lax
    tile = fold_tile(tokens)
    token = _take(pairs, by_token) // top_k
    y = _take(y, by_token)
    sizes = jnp.sum(token[:, None] // tile == jnp.arange(tokens // tile),
                    axis=0, dtype=jnp.int32)
    if tiles is None:
        onehot = token[:, None] % tile == jnp.arange(tile)
        out = lax.ragged_dot_general(
            onehot.astype(y.dtype), y, sizes,
            lax.RaggedDotDimensionNumbers(
                (([0], [0]), ([], [])), [0], []),
            preferred_element_type=jnp.float32)
    else:
        # (``ops.backend``: the module of the kernels behind ``ops.gmm``)
        from jax.experimental.pallas.ops.tpu.megablox import ops
        onehot = jnp.arange(tile)[:, None] == token % tile
        out = ops.backend.tgmm(
            onehot.astype(y.dtype), y, sizes, jnp.float32,
            (GMM_ROWS, tile, tiles(y.shape[0], tile, y.shape[1])[2]))
    return out.reshape(tokens, -1)


def _compact_dispatch(top_k: int, tokens: int, tiles=None):
    """``_dispatch`` over the first ``capacity`` sorted pairs alone
    (``pairs``, and ``by_token``: their argsort): ``gather(x)`` =
    ``capacity`` rows, ``combine(y)`` = :func:`token_sums`.  Each is the
    other's backward."""
    import jax

    def spread(x, pairs):
        return _take(x, pairs // top_k)

    fold = partial(token_sums, tokens=tokens, top_k=top_k, tiles=tiles)

    @jax.custom_vjp
    def gather(x, pairs, by_token):
        return spread(x, pairs)

    def gather_fwd(x, pairs, by_token):
        return spread(x, pairs), (pairs, by_token)

    def gather_bwd(res, g):
        return fold(g, *res).astype(g.dtype), None, None

    gather.defvjp(gather_fwd, gather_bwd)

    @partial(jax.custom_vjp, nondiff_argnums=(3,))
    def combine(y, pairs, by_token, dtype):
        return fold(y, pairs, by_token)

    def combine_fwd(y, pairs, by_token, dtype):
        return fold(y, pairs, by_token), pairs

    def combine_bwd(dtype, pairs, g):
        return spread(g.astype(dtype), pairs), None, None

    combine.defvjp(combine_fwd, combine_bwd)
    return gather, combine


class MoE(SequenceUnit):
    """Router + the held experts' grouped SwiGLUs + the shared expert
    over ``[rows, T, hidden]`` -> ``[rows, T, hidden]``."""

    ROUTED = ("router", "w_gate", "w_up", "w_down")
    SHARED = ("s_gate", "s_up", "s_down", "s_mix")

    def __init__(self, workflow=None, experts_total: int = 8,
                 experts_held: int = 4, first_held: int = 0,
                 top_k: int = 2, expert_size: int = 32,
                 shared_size: int = 32, routed_scale: float = 1.0,
                 **kwargs: Any) -> None:
        # (before the base makes a Vector a name)
        self.matrix_names = self.param_names = self.ROUTED + (
            self.SHARED if shared_size else ())
        super().__init__(workflow, **kwargs)
        if not 0 <= first_held <= experts_total - experts_held \
                or not 0 < top_k <= experts_total:
            raise ValueError(
                f"{self.name}: experts {first_held}..{first_held}+"
                f"{experts_held} of {experts_total}, top {top_k}")
        self.experts_total, self.experts_held = experts_total, experts_held
        self.first_held, self.top_k = first_held, top_k
        self.expert_size, self.shared_size = expert_size, shared_size
        self.routed_scale = float(routed_scale)
        #: the last share journaled (``moe.share``)
        self.share: Dict[str, Any] = {}
        #: the last load journaled (``moe.load``)
        self.load: Dict[str, Any] = {}

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self, input_shape):
        h, e = int(input_shape[-1]), self.experts_held
        n, s = self.expert_size, self.shared_size
        shapes = {"router": (h, self.experts_total),
                  "w_gate": (e, h, n), "w_up": (e, h, n),
                  "w_down": (e, n, h)}
        if s:
            shapes.update({"s_gate": (h, s), "s_up": (h, s),
                           "s_down": (s, h), "s_mix": (h, 1)})
        return shapes

    # -- the share, and how it is laid out --------------------------------

    def _share(self, tokens: int, width: int, batched: bool = False,
               itemsize: int = 2
               ) -> Tuple[Dict[str, Any], Optional[GroupedTiles]]:
        """How ``tokens`` tokens of a row are dispatched — journaled
        (``moe.share``) whenever it differs from the last one; under
        ``gmm`` with each kernel call's ``tiles`` and ``padding`` — and
        the chooser those tiles come from (None: ``ragged_dot``)."""
        block = block_tokens(tokens, self.top_k, width)
        rows = block * self.top_k
        path = grouped_path(self.platform(), rows, width,
                            self.expert_size, batched, itemsize)
        tiles = path.pop("tiles", None)
        capacity = dispatch_capacity(
            rows, self.experts_held, self.experts_total,
            GMM_ROWS if tiles else SUBLANES, batched)
        share = {"experts_total": self.experts_total,
                 "experts_held": self.experts_held,
                 "first_held": self.first_held, "top_k": self.top_k,
                 "rows": rows, "blocks": tokens // block,
                 "capacity": capacity,
                 "shared": bool(self.shared_size),
                 "routed_scale": self.routed_scale, **path}
        if tiles:
            share.update(call_tiles(
                tiles, capacity or rows, width, self.expert_size,
                capacity and fold_tile(block)))
        if share != self.share:
            self.share = share
            telemetry.event(events.EV_MOE_SHARE, unit=self.name, **share)
        return share, tiles

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        from veles_tpu.ops.batching import resolve_compute_dtype
        shape = self.input.shape
        self._share(int(shape[0]) * int(shape[1]), int(shape[2]),
                    itemsize=resolve_compute_dtype(None,
                                                   self.device).itemsize)

    # -- pure compute ------------------------------------------------------

    def route(self, params, tokens):
        """(ids, weights) ``[tokens, top_k]``: each token's chosen
        experts among all ``experts_total``, and their renormalised
        weights times ``routed_scale`` (f32)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        with jax.named_scope(events.SCOPE_MOE_ROUTER):
            prob = jax.nn.softmax(jnp.einsum(
                "th,he->te", tokens, params["router"],
                preferred_element_type=jnp.float32), axis=-1)
            top_p, top_i = lax.top_k(prob, self.top_k)
            top_w = top_p / jnp.sum(top_p, -1, keepdims=True)
            if self.routed_scale != 1.0:
                top_w = top_w * self.routed_scale
            return top_i, top_w

    def _held(self, top_i):
        """(local id or ``experts_held`` where held elsewhere, held?)"""
        import jax.numpy as jnp
        local = top_i - self.first_held
        held = (local >= 0) & (local < self.experts_held)
        return jnp.where(held, local, self.experts_held), held

    def _experts(self, params, tiles, rows, sizes, weight):
        """The held experts' SwiGLUs over sorted ``rows`` (``sizes`` of
        them an expert), each row's output times its routing
        ``weight``."""
        import jax
        import jax.numpy as jnp
        with jax.named_scope(events.SCOPE_MOE_EXPERTS):
            gate = grouped_matmul(rows, params["w_gate"], sizes, tiles)
            up = grouped_matmul(rows, params["w_up"], sizes, tiles)
            # the routing weight on the narrow side of the down product
            act = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)
                   * weight[:, None]).astype(rows.dtype)
            return grouped_matmul(act, params["w_down"], sizes, tiles)

    def _routed_block(self, params, tiles, x, top_i, top_w):
        """One block: x ``[n, hidden]``, its routing ``[n, top_k]`` ->
        the held experts' part of the routed sum ``[n, hidden]`` f32."""
        import jax
        import jax.numpy as jnp
        gather, combine = _dispatch(self.top_k)
        with jax.named_scope(events.SCOPE_MOE_DISPATCH):
            key, held = self._held(top_i)
            key = key.reshape(-1)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            inverse = jnp.argsort(order).astype(jnp.int32)
            bounds = jnp.searchsorted(
                _take(key, order), jnp.arange(self.experts_held + 1),
                side="left").astype(jnp.int32)
            sizes = bounds[1:] - bounds[:-1]
            weight = _permute()(jnp.where(held, top_w, 0.0).reshape(-1),
                                order, inverse)
            rows = gather(x, order, inverse)
        out = self._experts(params, tiles, rows, sizes, weight)
        with jax.named_scope(events.SCOPE_MOE_DISPATCH):
            return combine(out, order, inverse, out.dtype)

    def _compact_block(self, params, tiles, capacity, piece, x, top_i,
                       top_w):
        """``_routed_block`` through buffers of ``capacity`` rows: the
        ``piece``-th ``capacity`` sorted pairs of the block (a traced
        index) and the part of the routed sum they make.  The pieces'
        parts add up to ``_routed_block``'s; where the block's held
        pairs fit the buffers, piece 0 is all of it."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        gather, combine = _compact_dispatch(self.top_k, x.shape[0], tiles)
        with jax.named_scope(events.SCOPE_MOE_DISPATCH):
            key, held = self._held(top_i)
            key = key.reshape(-1)
            order = jnp.argsort(key, stable=True).astype(jnp.int32)
            # (padded to whole pieces: the last one's tail lies beyond
            # every held pair, so its rows are selected to zero)
            pairs = lax.dynamic_slice(
                jnp.pad(order, (0, -order.shape[0] % capacity)),
                (piece * capacity,), (capacity,))
            by_token = jnp.argsort(pairs).astype(jnp.int32)
            bounds = jnp.clip(
                jnp.sum(key[:, None] < jnp.arange(self.experts_held + 1),
                        axis=0, dtype=jnp.int32) - piece * capacity,
                0, capacity)
            sizes = bounds[1:] - bounds[:-1]
            weight = _take(jnp.where(held, top_w, 0.0).reshape(-1), pairs)
            rows = gather(x, pairs, by_token)
        out = self._experts(params, tiles, rows, sizes, weight)
        with jax.named_scope(events.SCOPE_MOE_DISPATCH):
            return combine(out, pairs, by_token, out.dtype)

    def _capped_block(self, tiles, capacity):
        """``block(weights, x, top_i, top_w)``: the sum of
        ``_compact_block`` over as many pieces as the block's held
        pairs fill — counted on the device, forward and backward, so
        nothing is dropped; one piece unless the block overflows.  Only
        the inputs are kept for the backward (as under
        ``jax.checkpoint``), which walks the pieces again and re-makes
        each: a loop whose trip count the device decides has no
        derivative of its own."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        def summed(top_i, part, zero):
            """``part(piece)`` added up over the block's pieces."""
            with jax.named_scope(events.SCOPE_MOE_DISPATCH):
                pieces = -(-jnp.sum(self._held(top_i)[1], dtype=jnp.int32)
                           // capacity)
            return lax.while_loop(
                lambda c: c[0] < pieces,
                lambda c: (c[0] + 1, jax.tree.map(jnp.add, c[1],
                                                  part(c[0]))),
                (jnp.int32(0), zero))[1]

        @jax.custom_vjp
        def block(w, x, top_i, top_w):
            return summed(top_i, lambda piece: self._compact_block(
                w, tiles, capacity, piece, x, top_i, top_w),
                jnp.zeros(x.shape, jnp.float32))

        def fwd(w, x, top_i, top_w):
            return block(w, x, top_i, top_w), (w, x, top_i, top_w)

        def bwd(res, g):
            w, x, top_i, top_w = res

            def back(piece):
                return jax.vjp(jax.checkpoint(
                    lambda w, x, tw: self._compact_block(
                        w, tiles, capacity, piece, x, top_i, tw)),
                    w, x, top_w)[1](g)

            d_w, d_x, d_tw = summed(top_i, back, jax.tree.map(
                jnp.zeros_like, (w, x, top_w)))
            return d_w, d_x, None, d_tw

        block.defvjp(fwd, bwd)
        return block

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        from jax import lax
        b, t, h = x.shape
        tokens = x.reshape(b * t, h)
        top_i, top_w = self.route(params, tokens)
        share, tiles = self._share(b * t, h, under_vmap(x),
                                   x.dtype.itemsize)
        n, k = share["blocks"], self.top_k
        if share["capacity"] is None:
            block = jax.checkpoint(
                partial(self._routed_block, params, tiles))
        else:
            block = partial(
                self._capped_block(tiles, share["capacity"]),
                {name: params[name] for name in self.ROUTED[1:]})
        if n == 1:
            routed = block(tokens, top_i, top_w)
        else:
            _, routed = lax.scan(
                lambda c, xs: (c, block(*xs)), None,
                (tokens.reshape(n, -1, h), top_i.reshape(n, -1, k),
                 top_w.reshape(n, -1, k)))
            routed = routed.reshape(b * t, h)
        if not self.shared_size:
            return routed.astype(x.dtype).reshape(b, t, h)
        with jax.named_scope(events.SCOPE_MOE_SHARED):
            hid = jax.nn.silu(jnp.einsum(
                "th,hk->tk", tokens, params["s_gate"]).astype(
                    jnp.float32)) \
                * jnp.einsum("th,hk->tk", tokens,
                             params["s_up"]).astype(jnp.float32)
            shared = jnp.einsum("tk,kh->th", hid.astype(x.dtype),
                                params["s_down"],
                                preferred_element_type=jnp.float32)
            mix = jax.nn.sigmoid(jnp.einsum(
                "th,hk->tk", tokens, params["s_mix"],
                preferred_element_type=jnp.float32))
            y = routed + mix * shared
        return y.astype(x.dtype).reshape(b, t, h)

    # -- what the load probe reads -----------------------------------------

    def probe(self, params, x):
        """What the routing of ``x`` puts on this share: every token's
        choice, the rows of each held expert, the rows that would not
        fit the dispatch buffers (0: a block that overflows the compact
        buffers goes through them a piece at a time) and the blocks
        that overflow."""
        import jax.numpy as jnp
        b, t, h = x.shape
        top_i, _ = self.route(params, x.reshape(b * t, h))
        key, held = self._held(top_i)
        share, _ = self._share(b * t, h, under_vmap(x), x.dtype.itemsize)
        per_block = jnp.sum(held.reshape(share["blocks"], -1), axis=1)
        return {"choice": top_i.reshape(b, t, self.top_k),
                "blocks": share["blocks"],
                "over_capacity": jnp.sum(
                    per_block > (share["capacity"] or share["rows"])),
                "expert_rows": jnp.sum(
                    key[..., None] == jnp.arange(self.experts_held),
                    axis=(0, 1)),
                "dropped": jnp.sum(jnp.maximum(
                    per_block - share["rows"], 0))}

    def report_probe(self, got) -> Dict[str, Any]:
        """Journal one probed minibatch's load (``moe.load``) and,
        over all the workflow's layers probed so far, the rows dropped
        (gauge ``moe.dropped_rows``) and the blocks that overflowed
        the compact buffers (gauge ``moe.over_capacity_blocks``)."""
        import numpy as np
        rows = np.asarray(got["expert_rows"])
        self.load = {"local_assignments": int(rows.sum()),
                     "max_expert_rows": int(rows.max()),
                     "min_expert_rows": int(rows.min()),
                     "dropped": int(got["dropped"]),
                     "over_capacity_blocks": int(got["over_capacity"]),
                     "blocks": int(got["blocks"])}
        telemetry.event(events.EV_MOE_LOAD, unit=self.name, **self.load)
        peers = [f for f in getattr(self.workflow, "forwards", None)
                 or [self] if isinstance(f, MoE)]
        for gauge, field in (
                (events.GAUGE_MOE_DROPPED_ROWS, "dropped"),
                (events.GAUGE_MOE_OVER_CAPACITY_BLOCKS,
                 "over_capacity_blocks")):
            telemetry.gauge(gauge).set(sum(
                f.load.get(field, 0) for f in peers))
        return self.load

    def mxu_flops_per_sample(self) -> float:
        t, h = int(self.input.shape[1]), int(self.input.shape[2])
        # router; shared expert; the held experts' expected share of
        # each token's top_k
        held = self.top_k * self.experts_held / float(self.experts_total)
        return 2.0 * t * h * self.experts_total \
            + 6.0 * t * h * self.shared_size \
            + 6.0 * t * h * self.expert_size * held
