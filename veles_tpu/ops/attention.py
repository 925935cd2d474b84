"""``gated_attention`` and ``attention``: causal grouped-query softmax
attention, with per-head norms, partial RoPE and a sigmoid output gate
(``gated_attention``) or plain, under a window of keys where the layer
has one and a RoPE law of its own (``attention``) — layer types of the
sequence op family (``ops/sequence.py``) over ONE core.

From ``u [rows, T, hidden]``: ``u Wq [T, nh, 2 d]`` splits a head into
query and gate; ``k, v [T, nkv, d]``; ``q <- N(q)``, ``k <- N(k)`` (an
RMS norm a head, ``1 + g``); rotate-half RoPE on the first
``rotary_size`` elements of each head, the rest untouched; ``o =
softmax(q k^T / sqrt(d) + causal) v`` with scores and softmax in f32,
query head h reading key head ``h // (nh / nkv)``; ``y = o *
sigmoid(gate)``.  The out-projection is the ``dense`` layer that
follows.  A row's whole ``[nh, T, T]`` scores never exist.

``attention`` is the same without the gate and the norms: ``q = u Wq
[T, nh, d]``, ``k, v [T, nkv, d]``; rotate-half RoPE over the whole
head with the inverse frequencies and the scale of the layer's ``rope``
specification (``ops/sequence.py`` ``rope_frequencies``: ``default`` or
``yarn``); with ``window`` W a query at n reads the keys ``n - W < m
<= n`` (W keys, its own among them), without one every ``m <= n``.

Two forms of the core (scores, softmax, weighted sums), chosen by
:func:`attention_path` from platform and shapes and journaled
(``attn.path``):

- ``splash`` — on a TPU where the shapes tile: the Pallas flash
  attention that ships with jax (``jax.experimental.pallas.ops.tpu.
  splash_attention``), its multi-query kernel mapped over the key
  heads: a tile of scores lives in VMEM under a running softmax,
  blocks above the diagonal and blocks left of the window are never
  visited, forward and backward (its own ``custom_vjp``);
- ``xla`` — everywhere else (XLA:CPU, ragged shapes, under ``vmap``):
  plain XLA ops, a block of queries against the keys from the window's
  left edge up to the block's end (``jax.checkpoint`` round a block),
  the kernel's oracle.

Device ops of the core carry ``attn/core`` — ``attn/window`` where the
layer has a window — forward and backward.
"""

from __future__ import annotations

from functools import lru_cache
from typing import Any, Dict, Optional

from veles_tpu import events, telemetry
from veles_tpu.ops.sequence import (SequenceUnit, rms_norm, rope,
                                    rope_frequencies, under_vmap)

LANES = 128
#: queries and keys a kernel tile holds; queries the XLA form scores
#: at once
KERNEL_BLOCK = 512
XLA_BLOCK = 512


def attention_path(platform: str, head_size: int, t: int,
                   batched: bool = False, window: Optional[int] = None
                   ) -> Dict[str, Any]:
    """Which form of the core runs, from what the code observes; under
    the kernel form ``kv_blocks``, the most key blocks a query block
    visits (the causal triangle's widest row, or the window's)."""
    block = min(KERNEL_BLOCK, t)
    reason = "platform" if platform != "tpu" else "batched" if batched \
        else "head_size" if head_size % LANES \
        else "row" if t % block or block % LANES else None
    if reason:
        return {"form": "xla", "reason": reason, "window": window}
    return {"form": "splash", "window": window,
            "tiles": {"block_q": block, "block_kv": block},
            "kv_blocks": max(
                i - first_key(i * block, window) // block + 1
                for i in range(t // block))}


def first_key(query: int, window: Optional[int]) -> int:
    """The leftmost key a query reads: its ``window`` keys end at the
    query itself."""
    return 0 if window is None else max(0, query - window + 1)


@lru_cache(maxsize=8)
def _splash_kernel(t: int, group: int, block: int,
                   window: Optional[int] = None):
    """The multi-query kernel of ``group`` query heads over one key
    head, causal over rows of ``t`` and, with ``window``, that many
    keys wide.  Its mask tables are made on the host once a shape — as
    concrete arrays, whatever trace asks first: they are kept, and a
    tracer kept would leak into the next trace."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as mask)
    sizes = kernel.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block,
        block_kv_dkv_compute=block, block_q_dq=block, block_kv_dq=block)
    one = mask.CausalMask((t, t)) if window is None \
        else mask.LocalMask((t, t), (window - 1, 0), 0)
    with telemetry.span(events.SPAN_ATTN_MASK_TABLES), \
            jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            mask.MultiHeadMask([one] * group), block_sizes=sizes)


def core_splash(q, k, v, block: int, window: Optional[int] = None):
    """q ``[rows, T, nkv, group, d]`` (already scaled), k, v ``[rows,
    T, nkv, d]`` -> o like q, by the shipped kernel."""
    import jax
    import jax.numpy as jnp
    t, group = q.shape[1], q.shape[3]
    one = _splash_kernel(t, group, block, window)  # [g, T, d], [T, d]
    o = jax.vmap(jax.vmap(one))(
        jnp.transpose(q, (0, 2, 3, 1, 4)), jnp.transpose(k, (0, 2, 1, 3)),
        jnp.transpose(v, (0, 2, 1, 3)))
    return jnp.transpose(o, (0, 3, 1, 2, 4))


def core_xla(q, k, v, block: int = XLA_BLOCK,
             window: Optional[int] = None):
    """The same by plain XLA ops: a block of queries at a time against
    the keys from the window's left edge (the row's start without one)
    up to the block's end, scores and softmax in f32."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    t = q.shape[1]
    block = block if 0 < block < t and t % block == 0 else t

    @jax.checkpoint
    def one(qb, kb, vb):
        n, m = qb.shape[1], kb.shape[1]
        s = jnp.einsum("bnhgd,bmhd->bhgnm", qb, kb,
                       preferred_element_type=jnp.float32)
        # the block's keys end where its queries end: query j is
        # key m - n + j
        at = (m - n + jnp.arange(n))[:, None]
        causal = at >= jnp.arange(m)[None]
        if window is not None:
            causal &= at - window < jnp.arange(m)[None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhgnm,bmhd->bnhgd", p.astype(vb.dtype), vb)

    out = []
    for lo in range(0, t, block):
        qb = q[:, lo:lo + block]
        if out:
            # one block's f32 scores at a time, forward and backward
            qb, out[-1] = lax.optimization_barrier((qb, out[-1]))
        keys = slice(first_key(lo, window), lo + block)
        out.append(one(qb, k[:, keys], v[:, keys]))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


class GroupedQueryAttention(SequenceUnit):
    """What the attention layer types share: query heads over key
    heads, the journaled path, and the ONE core — causal, under
    ``window`` keys where the layer has a window."""

    #: the layer's RoPE law as the journal names it
    rope_kind = "default"

    def __init__(self, workflow=None, n_heads: int = 4,
                 n_kv_heads: int = 2, head_size: int = 16,
                 window: Optional[int] = None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        if n_heads % n_kv_heads:
            raise ValueError(f"{self.name}: {n_heads} query heads over "
                             f"{n_kv_heads} key heads")
        self.n_heads, self.n_kv_heads = n_heads, n_kv_heads
        self.head_size, self.window = head_size, window
        #: the last :func:`attention_path` journaled
        self.path: Dict[str, Any] = {}

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.n_heads * self.head_size,)

    def _path(self, t: int, batched: bool = False) -> Dict[str, Any]:
        """:func:`attention_path` of this unit, journaled whenever it
        differs from the last one journaled."""
        path = attention_path(self.platform(), self.head_size, t, batched,
                              self.window)
        if path != self.path:
            self.path = path
            telemetry.event(events.EV_ATTN_PATH, unit=self.name,
                            rope=self.rope_kind, **path)
        return path

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self._path(int(self.input.shape[1]))
        peers = getattr(self.workflow, "forwards", None) or [self]
        telemetry.gauge(events.GAUGE_ATTN_WINDOW_LAYERS).set(sum(
            f.window is not None for f in peers
            if isinstance(f, GroupedQueryAttention)))

    def core(self, q, k, v):
        """q ``[rows, T, nkv, group, d]`` (already scaled), k, v
        ``[rows, T, nkv, d]`` -> o like q, in the form
        :func:`attention_path` chooses."""
        import jax
        path = self._path(q.shape[1], under_vmap(q, k, v))
        with jax.named_scope(events.SCOPE_ATTN_CORE if self.window is None
                             else events.SCOPE_ATTN_WINDOW):
            if path["form"] == "splash":
                return core_splash(q, k, v, path["tiles"]["block_q"],
                                   self.window)
            return core_xla(q, k, v, window=self.window)

    def core_flops(self, t: int) -> float:
        """Scores and weighted sums of one row: a query reads the keys
        up to itself, ``window`` of them at most."""
        w = t if self.window is None else min(self.window, t)
        pairs = w * (w + 1) / 2.0 + (t - w) * w
        return 4.0 * self.n_heads * self.head_size * pairs


class GatedAttention(GroupedQueryAttention):
    """Norms + partial RoPE + causal grouped-query attention + output
    gate over ``[rows, T, hidden]``; the heads' outputs side by side
    ``[rows, T, n_heads * head_size]``."""

    matrix_names = ("wq", "wk", "wv")
    param_names = ("wq", "wk", "wv", "q_gain", "k_gain")

    def __init__(self, workflow=None, rotary_size: int = 4,
                 rope_theta: float = 1e7, eps: float = 1e-6,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.rotary_size = rotary_size
        self.rope_theta, self.eps = rope_theta, eps

    def param_shapes(self, input_shape):
        h, d = int(input_shape[-1]), self.head_size
        return {"wq": (h, self.n_heads * 2 * d),
                "wk": (h, self.n_kv_heads * d),
                "wv": (h, self.n_kv_heads * d),
                "q_gain": (d,), "k_gain": (d,)}

    def fill_special(self, name, shape, gen):
        if name in ("q_gain", "k_gain"):
            name = "gain"
        return super().fill_special(name, shape, gen)

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        b, t, _ = x.shape
        nh, nkv, d = self.n_heads, self.n_kv_heads, self.head_size

        def heads(w, n):
            return jnp.einsum("bth,hk->btk", x, w).reshape(b, t, n, d)

        def normed(a, gain, scale=1.0):
            af = rms_norm(a, params[gain], self.eps)
            return (rope(af, self.rope_theta, self.rotary_size)
                    * scale).astype(x.dtype)

        # a head's columns of wq are its query, then its gate: the
        # WEIGHT is split, so no [T, heads, 2 d] activation (nor its
        # gradient, padded back from two halves) is ever made
        wq = params["wq"].reshape(-1, nh, 2, d)
        q = normed(heads(wq[:, :, 0].reshape(-1, nh * d), nh),
                   "q_gain", d ** -0.5)
        gate = heads(wq[:, :, 1].reshape(-1, nh * d), nh)
        k = normed(heads(params["wk"], nkv), "k_gain")
        v = heads(params["wv"], nkv)
        o = self.core(q.reshape(b, t, nkv, nh // nkv, d), k, v)
        y = o.reshape(b, t, nh, d).astype(jnp.float32) \
            * jax.nn.sigmoid(gate.astype(jnp.float32))
        return y.astype(x.dtype).reshape(b, t, nh * d)

    def mxu_flops_per_sample(self) -> float:
        t, h = int(self.input.shape[1]), int(self.input.shape[2])
        nh, nkv, d = self.n_heads, self.n_kv_heads, self.head_size
        # projections; a query scores the keys up to itself
        return 2.0 * t * h * (2 * nh * d + 2 * nkv * d) \
            + self.core_flops(t)


class Attention(GroupedQueryAttention):
    """RoPE by the layer's own law + causal grouped-query attention,
    ``window`` keys wide where it has a window, over ``[rows, T,
    hidden]``; no gate, no per-head norm, no bias."""

    matrix_names = param_names = ("wq", "wk", "wv")

    def __init__(self, workflow=None,
                 rope: Optional[Dict[str, Any]] = None,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.rope = dict(rope or {"rope_type": "default",
                                  "rope_theta": 1e4})
        self.rope_kind = self.rope.get("rope_type", "default")
        #: made once, on the host
        self.inv_freq, self.rope_scale = rope_frequencies(
            self.rope, self.head_size)

    def param_shapes(self, input_shape):
        h, d = int(input_shape[-1]), self.head_size
        return {"wq": (h, self.n_heads * d),
                "wk": (h, self.n_kv_heads * d),
                "wv": (h, self.n_kv_heads * d)}

    def forward(self, params, x):
        import jax.numpy as jnp
        b, t, _ = x.shape
        nh, nkv, d = self.n_heads, self.n_kv_heads, self.head_size

        def rotated(w, n, scale=1.0):
            a = jnp.einsum("bth,hk->btk", x, w).reshape(b, t, n, d)
            return (rope(a.astype(jnp.float32), inv_freq=self.inv_freq,
                         scale=self.rope_scale) * scale).astype(x.dtype)

        q = rotated(params["wq"], nh, d ** -0.5)
        k = rotated(params["wk"], nkv)
        v = jnp.einsum("bth,hk->btk", x, params["wv"]).reshape(
            b, t, nkv, d)
        o = self.core(q.reshape(b, t, nkv, nh // nkv, d), k, v)
        return o.reshape(b, t, nh * d)

    def mxu_flops_per_sample(self) -> float:
        t, h = int(self.input.shape[1]), int(self.input.shape[2])
        nh, nkv, d = self.n_heads, self.n_kv_heads, self.head_size
        return 2.0 * t * h * (nh * d + 2 * nkv * d) + self.core_flops(t)
