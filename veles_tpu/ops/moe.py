"""``moe``: a sparse mixture-of-experts MLP of which this device holds
a share, beside one shared expert where the model has one
(``shared_size`` 0: none — no ``s_*`` parameters, no op under
``moe/shared``) — a layer type of the sequence op family
(``ops/sequence.py``).

``p = softmax(u W_r)`` over ALL ``experts_total`` experts in f32; the
``top_k`` largest, ``w_e = p_e / sum_topk p``; ``y = sum over e in topk
AND held of w_e W_d,e(silu(W_g,e u) * W_u,e u) + sigmoid(u . w_s)
Shared(u)``, the shared expert a SwiGLU.  The layer is told which
experts it holds (``experts_held`` of them from ``first_held``): only
those contribute, and that partial sum goes on — in a deployment the
other shares' parts arrive by an exchange between chips; on one chip
there is none, and no code stands in for the absent chips.

**No token is dropped whatever the imbalance.**  A row is cut into
blocks of tokens; a block's dispatch buffer has ``block * top_k`` rows —
every token of the block may choose ``top_k`` held experts — so its
shape is static and safe for the worst routing.  Inside a block the
(token, slot) pairs are sorted by expert (the pairs of experts held
elsewhere last), so one expert's rows are contiguous and the three
products are grouped products over the held experts; the rows beyond
the held pairs are never multiplied.  Gather and combine are both
row gathers (the sort is a permutation: the backward of one is the
other), the combine adds a token's slots in f32.  A block is
``jax.checkpoint``-ed: its buffers live once, not once a block.

Two forms of the grouped product, chosen by :func:`grouped_path` from
platform and shapes and journaled with the share (``moe.share``):
``gmm`` — on a TPU where the shapes tile, the Pallas grouped matmul
that ships with jax (``jax.experimental.pallas.ops.tpu.megablox``),
which visits only the tiles that hold rows — and ``ragged_dot``
(``jax.lax.ragged_dot``) everywhere else.

Device ops carry ``moe/router``, ``moe/dispatch``, ``moe/experts``,
``moe/shared``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

from veles_tpu import events, telemetry
from veles_tpu.ops.sequence import SequenceUnit, under_vmap

LANES = 128
#: bytes one block's gathered rows may take (the block is the largest
#: power-of-two part of the row under it)
DISPATCH_BUFFER_BYTES = 256 << 20
#: rows of a grouped product's tile
GMM_ROWS = 512


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
                 batched: bool = False) -> Dict[str, Any]:
    """Which form the grouped products take, from what the code
    observes; ``tiles`` are (rows, contraction, columns) of the two
    shapes of product."""
    if platform != "tpu":
        return {"form": "ragged_dot", "reason": "platform"}
    if batched:
        return {"form": "ragged_dot", "reason": "batched"}
    if rows % GMM_ROWS or width % LANES or inner % LANES:
        return {"form": "ragged_dot", "reason": "shapes"}
    return {"form": "gmm",
            "tiles": {"in": (GMM_ROWS, min(width, 1024), min(inner, 512)),
                      "out": (GMM_ROWS, min(inner, 512),
                              min(width, 1024))}}


def grouped_matmul(lhs, rhs, group_sizes, tiles=None):
    """``lhs[rows of group e] @ rhs[e]``: lhs ``[m, k]`` sorted by
    group, rhs ``[groups, k, n]``; rows beyond the groups come out
    zero.  ``tiles`` given: the Pallas kernel; else ``lax.ragged_dot``.

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
        out = ops.gmm(lhs, rhs, group_sizes, lhs.dtype, tuple(tiles))
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


class MoE(SequenceUnit):
    """Router + the held experts' grouped SwiGLUs + the shared expert
    over ``[rows, T, hidden]`` -> ``[rows, T, hidden]``."""

    ROUTED = ("router", "w_gate", "w_up", "w_down")
    SHARED = ("s_gate", "s_up", "s_down", "s_mix")

    def __init__(self, workflow=None, experts_total: int = 8,
                 experts_held: int = 4, first_held: int = 0,
                 top_k: int = 2, expert_size: int = 32,
                 shared_size: int = 32, **kwargs: Any) -> None:
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

    def _share(self, tokens: int, width: int, batched: bool = False
               ) -> Dict[str, Any]:
        """How ``tokens`` tokens of a row are dispatched, journaled
        (``moe.share``) whenever it differs from the last one."""
        block = block_tokens(tokens, self.top_k, width)
        rows = block * self.top_k
        share = {"experts_total": self.experts_total,
                 "experts_held": self.experts_held,
                 "first_held": self.first_held, "top_k": self.top_k,
                 "rows": rows, "blocks": tokens // block,
                 "shared": bool(self.shared_size),
                 **grouped_path(self.platform(), rows, width,
                                self.expert_size, batched)}
        if share != self.share:
            self.share = share
            telemetry.event(events.EV_MOE_SHARE, unit=self.name, **share)
        return share

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        shape = self.input.shape
        self._share(int(shape[0]) * int(shape[1]), int(shape[2]))

    # -- pure compute ------------------------------------------------------

    def route(self, params, tokens):
        """(ids, weights) ``[tokens, top_k]``: each token's chosen
        experts among all ``experts_total``, and their renormalised
        weights (f32)."""
        import jax
        import jax.numpy as jnp
        from jax import lax
        with jax.named_scope(events.SCOPE_MOE_ROUTER):
            prob = jax.nn.softmax(jnp.einsum(
                "th,he->te", tokens, params["router"],
                preferred_element_type=jnp.float32), axis=-1)
            top_p, top_i = lax.top_k(prob, self.top_k)
            return top_i, top_p / jnp.sum(top_p, -1, keepdims=True)

    def _held(self, top_i):
        """(local id or ``experts_held`` where held elsewhere, held?)"""
        import jax.numpy as jnp
        local = top_i - self.first_held
        held = (local >= 0) & (local < self.experts_held)
        return jnp.where(held, local, self.experts_held), held

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
        with jax.named_scope(events.SCOPE_MOE_EXPERTS):
            t_in, t_out = (tiles["in"], tiles["out"]) if tiles \
                else (None, None)
            gate = grouped_matmul(rows, params["w_gate"], sizes, t_in)
            up = grouped_matmul(rows, params["w_up"], sizes, t_in)
            # the routing weight on the narrow side of the down product
            act = (jax.nn.silu(gate.astype(jnp.float32))
                   * up.astype(jnp.float32)
                   * weight[:, None]).astype(x.dtype)
            out = grouped_matmul(act, params["w_down"], sizes, t_out)
        with jax.named_scope(events.SCOPE_MOE_DISPATCH):
            return combine(out, order, inverse, out.dtype)

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        from jax import lax
        b, t, h = x.shape
        tokens = x.reshape(b * t, h)
        top_i, top_w = self.route(params, tokens)
        share = self._share(b * t, h, under_vmap(x))
        n, k = share["blocks"], self.top_k
        block = jax.checkpoint(
            partial(self._routed_block, params, share.get("tiles")))
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
        choice, the rows of each held expert, and the rows that would
        not fit the dispatch buffers (0: they are sized for the worst
        routing)."""
        import jax.numpy as jnp
        b, t, h = x.shape
        top_i, _ = self.route(params, x.reshape(b * t, h))
        key, held = self._held(top_i)
        share = self._share(b * t, h, under_vmap(x))
        per_block = jnp.sum(held.reshape(share["blocks"], -1), axis=1)
        return {"choice": top_i.reshape(b, t, self.top_k),
                "expert_rows": jnp.sum(
                    key[..., None] == jnp.arange(self.experts_held),
                    axis=(0, 1)),
                "dropped": jnp.sum(jnp.maximum(
                    per_block - share["rows"], 0))}

    def report_probe(self, got) -> Dict[str, Any]:
        """Journal one probed minibatch's load (``moe.load``) and the
        rows dropped over all the workflow's layers probed so far
        (gauge ``moe.dropped_rows``)."""
        import numpy as np
        rows = np.asarray(got["expert_rows"])
        self.load = {"local_assignments": int(rows.sum()),
                     "max_expert_rows": int(rows.max()),
                     "min_expert_rows": int(rows.min()),
                     "dropped": int(got["dropped"])}
        telemetry.event(events.EV_MOE_LOAD, unit=self.name, **self.load)
        peers = getattr(self.workflow, "forwards", None) or [self]
        telemetry.gauge(events.GAUGE_MOE_DROPPED_ROWS).set(sum(
            f.load.get("dropped", 0) for f in peers
            if isinstance(f, MoE)))
        return self.load

    def mxu_flops_per_sample(self) -> float:
        t, h = int(self.input.shape[1]), int(self.input.shape[2])
        # router; shared expert; the held experts' expected share of
        # each token's top_k
        held = self.top_k * self.experts_held / float(self.experts_total)
        return 2.0 * t * h * self.experts_total \
            + 6.0 * t * h * self.shared_size \
            + 6.0 * t * h * self.expert_size * held
