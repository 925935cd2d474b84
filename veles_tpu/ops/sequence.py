"""The sequence op family: the layer types a decoder over rows of
token ids is made of — ``embedding``, ``rmsnorm``, ``dense`` (per
position, no flattening of the sample, no bias), ``swiglu``,
``eva_attention`` and ``lm_head`` here; ``gated_delta_net``
(``ops/deltanet.py``), ``gated_attention`` (``ops/attention.py``) and
``moe`` (``ops/moe.py``) in modules of their own on this one's base.

Every unit here is ONE pure ``forward(params, x)`` over ``[rows, T,
width]`` activations (the embedding: ``[rows, T]`` integer ids); its
backward is ``jax.vjp`` of that function (ROADMAP D2's default, as
conv / pooling / deconv do): ``apply_fwd`` in train mode returns the
vjp closure as the layer's residual — a pytree of what the backward
needs — and :class:`GDSequence` calls it.  Matrices take the
``weights`` rate and decay of the layer's ``<-`` config, vectors (norm
gains, EVA's ``phi`` / ``mu``) the bias's.

``eva_attention`` is EVA chunked linearized attention (Zheng, Yuan,
Wang, Kong: Efficient Attention via Control Variates, ICLR 2023,
arXiv:2302.04542, section 4) as ``veles_tpu/models/evabyte.py`` states
it: a query scores the keys of its own window exactly (causal) and one
learned summary of every chunk of every earlier window, under one
softmax.  Two forms of the one algorithm, chosen by
:func:`eva_path` from what the unit observes — the platform and the
shapes, never a setting:

- on a TPU, where the shapes tile (head size whole 128-lane columns,
  the window whole query blocks, a window's summaries whole lane
  tiles): ``ops/eva_pallas.py`` — one fused kernel over the whole row,
  forward and backward (``jax.custom_vjp``): a tile of scores lives in
  VMEM under a running softmax and is never written to HBM; the
  forward keeps ``o`` and the row's log-sum-exp, the backward re-makes
  a tile's probabilities from q, k and that number.  No
  ``jax.checkpoint``, no tie between windows;
- everywhere else (XLA:CPU, small or ragged shapes, under ``vmap``):
  plain XLA ops, one window's scores at a time (``eva_window``;
  ``jax.checkpoint`` round a window: its backward re-makes the scores
  from q, k, v instead of keeping sixteen windows' worth).  It is the
  oracle the kernel is tested against.

No masked-out remote block is computed in either — the remote set of a
window is whole earlier windows.  Scores and softmax in f32, products
in the compute dtype.  Device ops carry ``eva/summaries``,
``eva/local``, ``eva/remote`` in their metadata, forward and backward
alike; the fused kernels, which hold local and remote under their one
softmax, run under ``eva/local``.  Which form a unit took is journaled
once at ``initialize`` (``eva.path``; gauge ``eva.fused_layers``).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import numpy as np

from veles_tpu import events, prng, telemetry
from veles_tpu.memory import Vector
from veles_tpu.ops import embed_pallas, eva_pallas
from veles_tpu.ops.nn_units import ForwardUnit, GradientUnit


class SequenceUnit(ForwardUnit):
    """Base of the family: parameters by name (each a Vector attribute),
    gaussian fill, vjp-derived backward."""

    #: parameter names that are matrices (the weights' rate and decay)
    matrix_names: Tuple[str, ...] = ()
    #: every parameter name, in a fixed order
    param_names: Tuple[str, ...] = ()
    #: True when ``forward`` returns float32 whatever the compute dtype
    f32_output = False

    def __init__(self, workflow=None, **kwargs: Any) -> None:
        kwargs.setdefault("include_bias", False)
        kwargs.setdefault("weights_filling", "gaussian")
        super().__init__(workflow, **kwargs)
        for name in self.param_names:
            if name not in ("weights", "bias"):
                setattr(self, name, Vector(name=f"{self.name}.{name}"))

    def param_vectors(self) -> Dict[str, Vector]:
        return {name: getattr(self, name) for name in self.param_names
                if getattr(self, name)}

    def fill_params(self, input_shape) -> None:
        gen = prng.get("weights").numpy
        std = self.weights_stddev
        for name, shape in self.param_shapes(input_shape).items():
            arr = self.fill_special(name, shape, gen)
            if arr is None:
                s = std if std is not None else \
                    1.0 / np.sqrt(shape[0] or 1)
                arr = gen.standard_normal(shape, dtype=np.float32)
                arr *= np.float32(s)
            getattr(self, name).mem = arr

    def fill_special(self, name: str, shape, gen) -> Optional[np.ndarray]:
        """The fill of a parameter that is no gaussian matrix (None:
        it is one); ``gen`` is the weights stream's numpy generator."""
        if name == "gain":            # the norm multiplies by 1 + gain
            return np.zeros(shape, np.float32)
        return None

    def platform(self) -> str:
        """The platform this unit's ops will run on, as far as it can
        observe: its device's, or JAX's default when it is walked
        without ``initialize``."""
        if self.device is None:
            import jax
            return jax.default_backend()
        return getattr(self.device, "platform", None) \
            or self.device.backend_name

    # -- pure compute --------------------------------------------------

    def forward(self, params: Dict[str, Any], x: Any) -> Any:
        raise NotImplementedError

    def apply(self, params, inputs, rng=None) -> Dict[str, Any]:
        return {"output": self.forward(params, inputs["input"])}

    def apply_fwd(self, params, x, rng=None, train: bool = True):
        """(output, the backward as a closure).  Ids take no gradient:
        an integer input's closure returns None for it."""
        if not train:
            return self.forward(params, x), None
        import jax
        if np.issubdtype(np.dtype(x.dtype), np.integer):
            return jax.vjp(lambda p: self.forward(p, x), params)
        return jax.vjp(self.forward, params, x)

    # -- what the program's own MFU counts -------------------------------

    def mxu_flops_per_sample(self) -> float:
        """Forward matmul FLOPs of one row (``profiling.py``)."""
        return 0.0


class GDSequence(GradientUnit):
    """Backward of any unit of the family: the saved vjp closure."""

    can_skip_err_input = True

    @property
    def weight_names(self) -> Tuple[str, ...]:
        return self.forward.matrix_names

    def backward_from_saved(self, params, saved, err_output,
                            need_err_input: bool = True):
        if self.forward.f32_output:
            err_output = err_output.astype(np.float32)
        out = saved(err_output)
        grads = out[0]
        err_input = out[1] if len(out) > 1 and need_err_input else None
        return err_input, grads


#: bf16 FLOP/s of a v5e's MXU and bytes/s of its HBM: the units of
#: :func:`embed_path`'s two prices
MXU_FLOPS = 197e12
HBM_BYTES_PER_S = 819e9


def embed_path(vocab: int, hidden: int, n: int, platform: str,
               batched: bool = False) -> Dict[str, Any]:
    """Which form of the embedding runs, from what the code observes:
    ``onehot`` (reason ``cost``) where the one-hot form's two products,
    lookup and gradient, take no longer on the MXU (``4 N V H /
    MXU_FLOPS``, ``N`` ids a step) than the gather form moves its bytes
    through HBM (``(6 N H + 2 V H)`` bf16 elements: the rows looked up
    read and written, the error rows read, sorted and read again, the
    gradient written); else ``gather``, whose ``backward`` is
    ``segment_sum`` (``ops/embed_pallas.py``) on a TPU and ``scatter``
    (XLA's scatter-add) where it is not (``reason`` ``platform``) or
    under ``vmap`` (``batched``: the kernel's schedule has no member
    axis)."""
    onehot_s = 4.0 * n * vocab * hidden / MXU_FLOPS
    gather_s = 2.0 * (6.0 * n + 2.0 * vocab) * hidden / HBM_BYTES_PER_S
    if onehot_s <= gather_s:
        return {"form": "onehot", "reason": "cost", "backward": "onehot"}
    reason = "platform" if platform != "tpu" else \
        "batched" if batched else None
    if reason:
        return {"form": "gather", "backward": "scatter", "reason": reason}
    return {"form": "gather", "backward": "segment_sum"}


def take_rows(w, ids):
    """Rows ``w[ids]`` (``[..., hidden]``); an id outside ``[0, V)``
    gives a row of zeros, as its one-hot does."""
    import jax.numpy as jnp
    v = w.shape[0]
    ids = ids.astype(jnp.int32)
    return jnp.take(w, jnp.where((ids >= 0) & (ids < v), ids, v), axis=0,
                    mode="fill", fill_value=0)


def scatter_rows(ids, err, vocab: int):
    """``[vocab, hidden]`` in ``err``'s dtype: row ``v`` the f32 sum of
    the error rows of the ids equal to ``v`` (XLA's scatter-add); ids
    outside ``[0, vocab)`` dropped."""
    import jax.numpy as jnp
    h = err.shape[-1]
    ids = ids.reshape(-1).astype(jnp.int32)
    ids = jnp.where((ids >= 0) & (ids < vocab), ids, vocab)
    return jnp.zeros((vocab, h), jnp.float32).at[ids].add(
        err.reshape(-1, h).astype(jnp.float32), mode="drop").astype(
            err.dtype)


@functools.lru_cache(maxsize=None)
def _gather_op():
    """The gather form, differentiable in the table alone
    (``jax.custom_vjp``; the residual is the ids)."""
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
    def op(w, ids, vocab, dtype, backward, interpret):
        return take_rows(w, ids)

    def fwd(w, ids, vocab, dtype, backward, interpret):
        return take_rows(w, ids), ids

    def bwd(vocab, dtype, backward, interpret, ids, err):
        if backward == "segment_sum":
            dw = embed_pallas.embed_segment_sum(ids, err, vocab,
                                                interpret=interpret)
        else:
            dw = scatter_rows(ids, err, vocab)
        return dw.astype(dtype), np.zeros(ids.shape, jax.dtypes.float0)

    op.defvjp(fwd, bwd)
    return op


def gather_embed(w, ids, backward: str = "scatter",
                 interpret: bool = False):
    """The gather form of the embedding: :func:`take_rows`, whose
    gradient in ``w`` is the f32 sum of each id's error rows in ``w``'s
    dtype — by ``backward`` ``segment_sum`` (the Pallas kernel;
    ``interpret``: its interpret mode, off the chip) or ``scatter``."""
    return _gather_op()(w, ids, int(w.shape[0]), np.dtype(w.dtype),
                        backward, interpret)


class Embedding(SequenceUnit):
    """ids ``[rows, T]`` -> ``[rows, T, hidden]``: row ``id`` of the
    table, an id outside the table a row of zeros.  Two forms of the
    one lookup, chosen by :func:`embed_path` from the shapes: the
    one-hot product (the MXU's form of a gather; the table's gradient
    is the transposed product with f32 accumulation) where the table
    is small, the gather and a segment sum of the error rows
    (:func:`gather_embed`) where the one-hot's FLOPs would cost more
    than the rows' bytes.  Which form a unit took is journaled once at
    ``initialize`` (``embed.path``; gauge ``embed.gather_layers``)."""

    matrix_names = param_names = ("weights",)

    def __init__(self, workflow=None, vocab_size: int = 320,
                 hidden_size: int = 64, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.vocab_size, self.hidden_size = vocab_size, hidden_size
        #: the last :func:`embed_path` journaled ({} before the first)
        self.path: Dict[str, Any] = {}

    def output_shape_for(self, input_shape):
        return tuple(input_shape) + (self.hidden_size,)

    def param_shapes(self, input_shape):
        return {"weights": (self.vocab_size, self.hidden_size)}

    def _path(self, n: int, batched: bool = False) -> Dict[str, Any]:
        """:func:`embed_path` of this unit for ``n`` ids a step,
        journaled whenever it differs from the last one journaled."""
        path = embed_path(self.vocab_size, self.hidden_size, n,
                          self.platform(), batched)
        if path != self.path:
            self.path = path
            telemetry.event(events.EV_EMBED_PATH, unit=self.name,
                            vocab=self.vocab_size, hidden=self.hidden_size,
                            rows=n, **path)
            peers = getattr(self.workflow, "forwards", None) or [self]
            telemetry.gauge(events.GAUGE_EMBED_GATHER_LAYERS).set(sum(
                isinstance(f, Embedding) and f.path.get("form") == "gather"
                for f in peers))
        return path

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self._path(int(np.prod(self.input.shape)))

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        w = params["weights"]
        path = self._path(int(np.prod(x.shape)), under_vmap(w, x))
        if path["form"] == "gather":
            return gather_embed(w, x, path["backward"])
        return jnp.einsum("btv,vh->bth",
                          jax.nn.one_hot(x, self.vocab_size,
                                         dtype=w.dtype), w)


def rms_norm(x, gain, eps: float):
    """``x / sqrt(mean(x^2) + eps) * (1 + gain)`` over the last axis,
    in f32 (returned in f32)."""
    import jax.numpy as jnp
    from jax import lax
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return xf * lax.rsqrt(ms + eps) * (1.0 + gain.astype(jnp.float32))


class RMSNorm(SequenceUnit):
    """``x / sqrt(mean(x^2) + eps) * (1 + gain)`` over the last axis,
    in f32 (the published ``norm_add_unit_offset``)."""

    param_names = ("gain",)

    def __init__(self, workflow=None, eps: float = 1e-5,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.eps = eps

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self, input_shape):
        return {"gain": (int(input_shape[-1]),)}

    def forward(self, params, x):
        return rms_norm(x, params["gain"], self.eps).astype(x.dtype)


class Dense(SequenceUnit):
    """``x W`` at every position: ``[rows, T, in] -> [rows, T, out]``."""

    matrix_names = param_names = ("weights",)

    def __init__(self, workflow=None, output_size: int = 64,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.output_size = output_size

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.output_size,)

    def param_shapes(self, input_shape):
        return {"weights": (int(input_shape[-1]), self.output_size)}

    def forward(self, params, x):
        import jax.numpy as jnp
        return jnp.einsum("bth,hk->btk", x, params["weights"])

    def mxu_flops_per_sample(self) -> float:
        t, n_in = self.input.shape[1], self.input.shape[2]
        return 2.0 * t * n_in * self.output_size


class SwiGLU(SequenceUnit):
    """``silu(x W_gate) * (x W_up)``; the down projection is the
    ``dense`` layer that follows.  Its device ops carry ``mlp/swiglu``,
    forward and backward."""

    matrix_names = param_names = ("w_gate", "w_up")

    def __init__(self, workflow=None, intermediate_size: int = 96,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.intermediate_size = intermediate_size

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.intermediate_size,)

    def param_shapes(self, input_shape):
        shape = (int(input_shape[-1]), self.intermediate_size)
        return {"w_gate": shape, "w_up": shape}

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        with jax.named_scope(events.SCOPE_MLP_SWIGLU):
            gate = jnp.einsum("bth,hk->btk", x, params["w_gate"])
            up = jnp.einsum("bth,hk->btk", x, params["w_up"])
            y = jax.nn.silu(gate.astype(jnp.float32)) \
                * up.astype(jnp.float32)
            return y.astype(x.dtype)

    def mxu_flops_per_sample(self) -> float:
        t, n_in = self.input.shape[1], self.input.shape[2]
        return 2.0 * 2.0 * t * n_in * self.intermediate_size


class LMHead(SequenceUnit):
    """Prediction heads over a vocabulary of token ids (bytes, or any
    other): one ``hidden -> n_pred_heads * vocab`` product, logits
    ``[rows, T, n_pred_heads, vocab]`` in f32 (head j at position n
    predicts id n + 1 + j).  It acts on each position alone, so where
    the whole logits would not fit beside the state the fused step
    makes them, the loss and the head's backward a block of positions
    at a time (``engine/core.py`` ``build_blocked_head``)."""

    matrix_names = param_names = ("weights",)
    f32_output = True
    #: ``forward`` of a block of positions is that block of ``forward``
    per_position = True

    def __init__(self, workflow=None, vocab_size: int = 320,
                 n_pred_heads: int = 8, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.vocab_size, self.n_pred_heads = vocab_size, n_pred_heads

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.n_pred_heads,
                                          self.vocab_size)

    def param_shapes(self, input_shape):
        return {"weights": (int(input_shape[-1]),
                            self.n_pred_heads * self.vocab_size)}

    def forward(self, params, x):
        import jax.numpy as jnp
        logits = jnp.einsum("bth,hk->btk", x, params["weights"],
                            preferred_element_type=jnp.float32)
        return logits.reshape(x.shape[:2] + (self.n_pred_heads,
                                             self.vocab_size))

    def mxu_flops_per_sample(self) -> float:
        t, n_in = self.input.shape[1], self.input.shape[2]
        return 2.0 * t * n_in * self.n_pred_heads * self.vocab_size


def rope_frequencies(spec: Dict[str, Any], head_size: int
                     ) -> Tuple[np.ndarray, float]:
    """(inverse frequencies ``[d / 2]`` f32, the scale on cos and sin)
    of a layer's ``rope`` specification, made once on the host, over
    the ``d = head_size * partial_rotary_factor`` elements of a head
    that are rotated (the factor 1 where the specification gives none:
    the whole head).  ``rope_type`` ``default``: ``theta^(-2j/d)``,
    scale 1.  ``yarn`` (as ``transformers`` computes it): ``e_j =
    theta^(-2j/d)``,
    ``p_j = e_j / factor``; ``dim(r) = d ln(original / (2 pi r)) / (2 ln
    theta)``, ``low = max(floor(dim(beta_fast)), 0)``, ``high =
    min(ceil(dim(beta_slow)), d - 1)``; ``ramp_j = clip((j - low) /
    (high - low), 0, 1)``; ``f_j = p_j ramp_j + e_j (1 - ramp_j)``; the
    scale is ``attention_factor`` (``0.1 ln(factor) + 1`` where the
    specification gives none)."""
    kind = spec.get("rope_type", "default")
    theta = float(spec["rope_theta"])
    d = int(round(head_size * float(spec.get("partial_rotary_factor", 1))))
    if d % 2 or not 0 < d <= head_size:
        raise ValueError(f"rope: {d} rotated elements of a head of "
                         f"{head_size}")
    e = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if kind == "default":
        return e.astype(np.float32), 1.0
    if kind != "yarn":
        raise ValueError(f"rope: unknown rope_type {kind!r}")
    factor = float(spec["factor"])
    original = float(spec["original_max_position_embeddings"])

    def dim(rotations: float) -> float:
        return d * np.log(original / (rotations * 2.0 * np.pi)) \
            / (2.0 * np.log(theta))

    low = max(np.floor(dim(float(spec.get("beta_fast", 32.0)))), 0.0)
    high = min(np.ceil(dim(float(spec.get("beta_slow", 1.0)))), d - 1.0)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    scale = spec.get("attention_factor")
    if scale is None:
        scale = 0.1 * np.log(factor) + 1.0 if factor > 1.0 else 1.0
    return (e / factor * ramp + e * (1.0 - ramp)).astype(np.float32), \
        float(scale)


def rope(x, theta: Optional[float] = None, rotary: Optional[int] = None,
         inv_freq=None, scale: float = 1.0):
    """Rotate-half RoPE in f32; x ``[rows, T, heads, d]``.  Over the
    whole head (``rotary`` None), angle ``n * theta^(-2i/d)``, or over
    the first ``rotary`` elements of each head alone (a partial rotary
    factor: angle ``n * theta^(-2i/rotary)``), the rest untouched.
    ``inv_freq`` ``[rotated / 2]`` given (:func:`rope_frequencies`):
    angle ``n * inv_freq_i`` in ``theta``'s place, cos and sin times
    ``scale`` — on the rotated elements alone."""
    import jax.numpy as jnp
    if rotary is not None and rotary < x.shape[-1]:
        return jnp.concatenate(
            [rope(x[..., :rotary], theta, inv_freq=inv_freq, scale=scale),
             x[..., rotary:]], -1)
    t, d = x.shape[1], x.shape[-1]
    if inv_freq is None:
        inv = jnp.float32(theta) ** (
            -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        inv = jnp.asarray(inv_freq, jnp.float32)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * jnp.float32(scale), sin * jnp.float32(scale)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    y = xf * cos + jnp.concatenate([-x2, x1], -1) * sin
    return y.astype(x.dtype)


def eva_summaries(k, v, phi, mu, chunk: int):
    """(ks, vs) ``[rows, T/chunk, heads, d]``: every chunk's pooled
    key (+ mu) and value, pooled with ``softmax_m(s k_m . phi)``."""
    import jax
    import jax.numpy as jnp
    b, t, nh, d = k.shape
    with jax.named_scope(events.SCOPE_EVA_SUMMARIES):
        kc = k.reshape(b, t // chunk, chunk, nh, d)
        vc = v.reshape(b, t // chunk, chunk, nh, d)
        logit = d ** -0.5 * jnp.einsum(
            "bjmhd,hd->bjmh", kc, phi,
            preferred_element_type=jnp.float32)
        a = jax.nn.softmax(logit, axis=2).astype(k.dtype)
        vs = jnp.einsum("bjmh,bjmhd->bjhd", a, vc)
        ks = jnp.einsum("bjmh,bjmhd->bjhd", a, kc) + mu
    return ks, vs


def eva_window(q, k, v, ks, vs):
    """One window of queries ``[rows, W, heads, d]`` against its own
    keys (causal) and the summaries ``[rows, R, heads, d]`` of every
    earlier window (R may be 0), under one softmax.  The two score
    blocks are never joined: each is exponentiated against the common
    row maximum and the two weighted sums are added."""
    import jax
    import jax.numpy as jnp
    w, d = q.shape[1], q.shape[-1]
    s = d ** -0.5
    remote = ks.shape[1] > 0
    with jax.named_scope(events.SCOPE_EVA_LOCAL):
        sl = s * jnp.einsum("bnhd,bmhd->bhnm", q, k,
                            preferred_element_type=jnp.float32)
        sl = jnp.where(jnp.tril(jnp.ones((w, w), bool)), sl, -jnp.inf)
        top = jnp.max(sl, axis=-1, keepdims=True)
    if remote:
        with jax.named_scope(events.SCOPE_EVA_REMOTE):
            sr = s * jnp.einsum("bnhd,bjhd->bhnj", q, ks,
                                preferred_element_type=jnp.float32)
            top = jnp.maximum(top, jnp.max(sr, axis=-1, keepdims=True))
            pr = jnp.exp(sr - top)
            zr = jnp.sum(pr, axis=-1)
            o_r = jnp.einsum("bhnj,bjhd->bnhd", pr.astype(v.dtype), vs,
                             preferred_element_type=jnp.float32)
    with jax.named_scope(events.SCOPE_EVA_LOCAL):
        pl = jnp.exp(sl - top)
        z = jnp.sum(pl, axis=-1)
        o = jnp.einsum("bhnm,bmhd->bnhd", pl.astype(v.dtype), v,
                       preferred_element_type=jnp.float32)
        if remote:
            z, o = z + zr, o + o_r
        # z [rows, heads, W] against o [rows, W, heads, d]
        o = o / jnp.swapaxes(z, 1, 2)[..., None]
    return o.astype(v.dtype)


def eva_rows(q, k, v, ks, vs, win: int, chunk: int):
    """Whole rows ``[rows, T, heads, d]`` by :func:`eva_window`, a
    window at a time: the form every platform but the TPU's fused
    kernels runs, and their oracle."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    window = jax.checkpoint(eva_window)
    out = []
    for lo in range(0, q.shape[1], win):
        r = lo // chunk        # every chunk of every earlier window
        qw = q[:, lo:lo + win]
        if out:
            # one window at a time, forward and backward: without
            # the tie the scheduler holds several windows' f32
            # scores at once (0.5 GB each at the published sizes)
            qw, out[-1] = lax.optimization_barrier((qw, out[-1]))
        out.append(window(qw, k[:, lo:lo + win], v[:, lo:lo + win],
                          ks[:, :r], vs[:, :r]))
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


def eva_path(platform: str, head_size: int, window: int, chunk: int,
             t: int, batched: bool = False) -> Dict[str, Any]:
    """Which form of EVA attention runs, from what the code observes:
    ``{"path": "fused", "tiles": Tiles}`` on a TPU where the shapes
    tile, else ``{"path": "xla", "reason": ...}`` — ``platform`` (not
    a TPU), ``batched`` (under ``vmap``: the kernels' accumulators
    have no member axis), ``head_size`` (not whole 128-lane columns),
    ``window`` (the window or its summaries are not whole tiles)."""
    if platform != "tpu":
        return {"path": "xla", "reason": "platform"}
    if batched:
        return {"path": "xla", "reason": "batched"}
    tiles = eva_pallas.tiles_for(head_size, window, chunk, t)
    if tiles is None:
        return {"path": "xla", "reason": "head_size"
                if head_size % eva_pallas.LANES else "window"}
    return {"path": "fused", "tiles": tiles}


def under_vmap(*arrays) -> bool:
    """True where one of ``arrays`` is traced under ``jax.vmap``
    (directly or inside a ``jvp`` / ``vjp`` of it)."""
    import jax
    for a in arrays:
        while isinstance(a, jax.core.Tracer):
            if hasattr(a, "batch_dim"):     # vmap's tracer alone
                return True
            a = getattr(a, "primal", None)  # through jvp / linearize
    return False


class EvaAttention(SequenceUnit):
    """RoPE + EVA attention over ``[rows, T, hidden]``; the heads'
    outputs side by side ``[rows, T, heads * head_size]`` (the output
    projection is the ``dense`` layer that follows)."""

    matrix_names = ("wq", "wk", "wv")
    param_names = ("wq", "wk", "wv", "phi", "mu")

    def __init__(self, workflow=None, n_heads: int = 4,
                 head_size: int = 16, window_size: int = 32,
                 chunk_size: int = 4, rope_theta: float = 1e5,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.n_heads, self.head_size = n_heads, head_size
        self.window_size, self.chunk_size = window_size, chunk_size
        self.rope_theta = rope_theta
        #: the last :func:`eva_path` journaled ({} before the first)
        self.path: Dict[str, Any] = {}

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (self.n_heads * self.head_size,)

    def param_shapes(self, input_shape):
        h, nh, d = int(input_shape[-1]), self.n_heads, self.head_size
        return {"wq": (h, nh * d), "wk": (h, nh * d), "wv": (h, nh * d),
                "phi": (nh, d), "mu": (nh, d)}

    def _window(self, t: int) -> int:
        win = min(self.window_size, t)
        if t % win or win % self.chunk_size:
            raise ValueError(
                f"{self.name}: a row of {t} positions is not whole "
                f"windows of {win} made of chunks of {self.chunk_size}")
        return win

    def _path(self, t: int, batched: bool = False) -> Dict[str, Any]:
        """:func:`eva_path` of this unit for rows of ``t`` positions,
        journaled (``eva.path``) whenever it differs from the last
        one journaled: once at ``initialize``, and again only where a
        later trace must leave it (a ``vmap``)."""
        path = eva_path(self.platform(), self.head_size, self._window(t),
                        self.chunk_size, t, batched)
        if path != self.path:
            self.path = path
            tiles = path.get("tiles")
            telemetry.event(
                events.EV_EVA_PATH, unit=self.name, path=path["path"],
                reason=path.get("reason"),
                tiles=tiles and dict(tiles._asdict()))
        return path

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        t = int(self.input.shape[1])
        telemetry.gauge(events.GAUGE_EVA_WINDOW).set(self._window(t))
        telemetry.gauge(events.GAUGE_EVA_CHUNK).set(self.chunk_size)
        telemetry.gauge(events.GAUGE_EVA_SUMMARIES_PER_ROW).set(
            t // self.chunk_size)
        self._path(t)
        # units initialize in order: the last one sets the whole count
        peers = getattr(self.workflow, "forwards", None) or [self]
        telemetry.gauge(events.GAUGE_EVA_FUSED_LAYERS).set(sum(
            isinstance(f, EvaAttention) and f.path.get("path") == "fused"
            for f in peers))

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        from jax import lax
        b, t, _ = x.shape
        nh, d = self.n_heads, self.head_size
        win, chunk = self._window(t), self.chunk_size

        def heads(w):
            return jnp.einsum("bth,hk->btk", x, w).reshape(b, t, nh, d)

        q = rope(heads(params["wq"]), self.rope_theta)
        k = rope(heads(params["wk"]), self.rope_theta)
        v = heads(params["wv"])
        ks, vs = eva_summaries(k, v, params["phi"], params["mu"], chunk)
        path = self._path(t, under_vmap(q, k, v))
        if path["path"] == "fused":
            # the whole row in one call: local and remote under their
            # one softmax, no score in HBM, nothing to checkpoint
            with jax.named_scope(events.SCOPE_EVA_LOCAL):
                o = eva_pallas.eva_fused(q, k, v, ks, vs, win, chunk,
                                         path["tiles"])
            return o.reshape(b, t, nh * d)
        return eva_rows(q, k, v, ks, vs, win, chunk).reshape(b, t, nh * d)

    def mxu_flops_per_sample(self) -> float:
        t, h = int(self.input.shape[1]), int(self.input.shape[2])
        nh, d = self.n_heads, self.head_size
        win = self._window(t)
        # keys a query scores, summed over the row: its place in its
        # window, and one summary a chunk of every earlier window
        keys = t * ((win + 1) / 2.0
                    + (win // self.chunk_size) * (t // win - 1) / 2.0)
        return 2.0 * t * h * 3 * nh * d + 4.0 * nh * d * keys \
            + 6.0 * t * nh * d
