"""The plain reference of configuration ``qwen3next``: a decoder whose
period of four layers is three Gated DeltaNet layers (a causal
depthwise convolution, then a gated delta-rule recurrence over a
``[d_k, d_v]`` state a head) and one gated grouped-query softmax
attention layer, every layer followed by a mixture of experts of which
this chip holds a share, beside one shared expert; next-token
cross-entropy and SGD with momentum — plain ``jax.numpy`` float32 at
matmul precision "highest", an interpreter of the configuration's
``layers`` list.  It imports nothing of the program and nothing of the
other references; the CPU tests import its layer functions, the
benchmark its ``follow``.

What it computes, per row of ``T`` token ids (``layers`` names the
sizes):

- ``x0 = E[id]``; a ``residual`` entry is ``x + f(x)``; ``rmsnorm`` is
  ``x / sqrt(mean(x^2) + eps) * (1 + g)``; ``dense`` is ``x W``;
- ``gated_delta_net``: ``q, k [T, Hk, dk]``, ``v, z [T, Hv, dv]``,
  ``b, a [T, Hv]`` by bias-free projections; ``(q, k, v) <-
  silu(conv(concat(q, k, v)))``, the convolution causal and depthwise
  (``y_t = sum_j c_j x_{t - K + 1 + j}``, zeros left of the row);
  ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
  q and k L2-normalised over the head (eps 1e-6), ``q <- q / sqrt(dk)``;
  value head h reads key head ``h // (Hv / Hk)``.  A head, from ``S =
  0 [dk, dv]``, **token by token**: ``S <- exp(g_t) S``; ``delta_t =
  beta_t (v_t - S^T k_t)``; ``S <- S + k_t delta_t^T``; ``o_t = S^T
  q_t``.  Out: ``y_t = w * o_t / sqrt(mean(o_t^2) + eps) * silu(z_t)``
  a head (``w`` a plain gain); the out-projection is the ``dense``
  layer that follows;
- ``gated_attention``: ``x Wq [T, nh, 2 d]`` splits a head into query
  and gate; ``k, v [T, nkv, d]``; ``q <- N(q)``, ``k <- N(k)`` (per
  head, ``1 + g``); rotate-half RoPE on the first ``rotary_size``
  elements of each head; ``o = softmax(q k^T / sqrt(d) + causal) v``,
  query head h reading key head ``h // (nh / nkv)``, a block of
  queries at a time; ``y = o * sigmoid(gate)``;
- ``moe``: ``p = softmax(x W_r)`` over ALL ``experts_total``; the
  ``top_k`` largest, ``w_e = p_e / sum_topk p``; ``y = sum over e in
  topk AND held of w_e W_d,e(silu(W_g,e x) * W_u,e x)`` — a loop over
  the held experts with a 0 / w mask a token, nothing sorted, nothing
  grouped, the experts this chip does not hold contribute nothing —
  ``+ sigmoid(x . w_s) Shared(x)``, the shared expert a SwiGLU;
- ``lm_head``: logits ``[T, 1, V] = x W`` in f32; position n predicts
  token n + 1; the loss is the mean cross-entropy over the valid
  positions of the minibatch.

``precision`` selects what stands in the program's place for the
control: "f32" is the reference; "bf16" / "fp8" round the operands of
every matmul to bfloat16 / float8_e4m3 as plain casts.  ``fault``
plants a fault into the same arithmetic: "no_shared" (the shared
expert left out), "no_renorm" (the top-k weights not renormalised),
"beta_one" (beta fixed at 1), "no_decay" (``exp(g)`` left out),
"no_gate" (the attention's output gate left out), "rope_whole" (RoPE
over the whole head), "state_unchanged" (the state returned as given).

``follow`` walks the top-level entries back one at a time with
``jax.vjp`` (entries whose layers act on each position alone, and the
head with its loss, in blocks of ``seq_block`` positions), keeps the
weights, the momentum and the entries' inputs on the host — only the
entry being walked is on the device — and applies an entry's update as
soon as its gradient exists.  Inside an entry the recurrence is a
``lax.scan`` over positions, ``jax.checkpoint`` a block of positions;
the attention a ``lax.map`` over blocks of queries, each checkpointed;
the experts a ``lax.scan`` over the held experts, each checkpointed.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST

#: parameters that take the weights' rate and decay; every other one
#: (gains, the convolution, A_log, dt_bias) takes the bias's
MATRICES = {
    "embedding": ("weights",), "rmsnorm": (), "dense": ("weights",),
    "lm_head": ("weights",),
    "gated_delta_net": ("wq", "wk", "wv", "wz", "wb", "wa"),
    "gated_attention": ("wq", "wk", "wv"),
    "moe": ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up",
            "s_down", "s_mix")}
#: layer types that act on each position alone
POINTWISE = ("rmsnorm", "dense", "moe")
FAULTS = ("no_shared", "no_renorm", "beta_one", "no_decay", "no_gate",
          "rope_whole")

WEIGHT_STREAM = 2
#: positions of the recurrence kept between checkpoints, queries of
#: the attention scored at once
RULE_BLOCK = 256
QUERY_BLOCK = 256


def stream_seed(seed: int, stream: int) -> int:
    """A 31-bit seed of one of the benchmark's streams (``--seed`` may
    need more than 32 signed bits)."""
    return (int(seed) * 2654435761 + stream * 40503) % (2 ** 31 - 1)


# -- the layers list --------------------------------------------------

def flatten(layers: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The layers in order, the inner ones of every ``residual`` entry
    in its place: flat index i is the program's unit ``fwd<i>_<type>``
    and the key of its parameters."""
    out: List[Dict[str, Any]] = []
    for cfg in layers:
        if cfg["type"] == "residual":
            out.extend(flatten(cfg["layers"]))
        else:
            out.append(cfg)
    return out


def entries(layers) -> List[Tuple[str, List[int]]]:
    """[(kind, flat indices)] of the top-level entries: "residual" for
    a skip round its inner layers, else "plain"."""
    out, i = [], 0
    for cfg in layers:
        n = len(flatten([cfg]))
        out.append(("residual" if cfg["type"] == "residual" else "plain",
                    list(range(i, i + n))))
        i += n
    return out


def param_shapes(layers) -> List[Dict[str, Tuple[int, ...]]]:
    """Parameter shapes of every flat layer (empty where it has none)."""
    out, width = [], None
    for cfg in flatten(layers):
        kind, fw = cfg["type"], cfg.get("->", {})
        if kind == "embedding":
            width = int(fw["hidden_size"])
            p = {"weights": (int(fw["vocab_size"]), width)}
        elif kind == "rmsnorm":
            p = {"gain": (width,)}
        elif kind == "dense":
            p = {"weights": (width, int(fw["output_size"]))}
            width = int(fw["output_size"])
        elif kind == "gated_delta_net":
            hk, hv = int(fw["n_key_heads"]), int(fw["n_value_heads"])
            dk, dv = int(fw["key_head_size"]), int(fw["value_head_size"])
            p = {"wq": (width, hk * dk), "wk": (width, hk * dk),
                 "wv": (width, hv * dv), "wz": (width, hv * dv),
                 "wb": (width, hv), "wa": (width, hv),
                 "conv": (int(fw["conv_kernel"]), 2 * hk * dk + hv * dv),
                 "a_log": (hv,), "dt_bias": (hv,), "norm": (dv,)}
            width = hv * dv
        elif kind == "gated_attention":
            nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
            d = int(fw["head_size"])
            p = {"wq": (width, nh * 2 * d), "wk": (width, nkv * d),
                 "wv": (width, nkv * d), "q_gain": (d,), "k_gain": (d,)}
            width = nh * d
        elif kind == "moe":
            held, n = int(fw["experts_held"]), int(fw["expert_size"])
            s = int(fw["shared_size"])
            p = {"router": (width, int(fw["experts_total"])),
                 "w_gate": (held, width, n), "w_up": (held, width, n),
                 "w_down": (held, n, width),
                 "s_gate": (width, s), "s_up": (width, s),
                 "s_down": (s, width), "s_mix": (width, 1)}
        elif kind == "lm_head":
            p = {"weights": (width, int(fw["n_pred_heads"])
                             * int(fw["vocab_size"]))}
        else:
            raise ValueError(f"reference: unknown layer type {kind!r}")
        out.append(p)
    return out


def param_count(layers) -> int:
    return sum(int(np.prod(s)) for p in param_shapes(layers)
               for s in p.values())


def init_leaf(seed: int, index: int, name: str, shape, std: float):
    """One parameter of the seed's weights: matrices N(0, std^2);
    ``1 + g`` gains zero; the gated norm's plain gain and ``dt_bias``
    one; ``A_log`` the log of a uniform on [1, 16]; the convolution
    uniform on +-1/sqrt(kernel)."""
    if name in ("gain", "q_gain", "k_gain"):
        return jnp.zeros(shape, jnp.float32)
    if name in ("norm", "dt_bias"):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(stream_seed(seed, WEIGHT_STREAM)), index),
        sum(map(ord, name)))
    shape = tuple(shape)
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                          1.0, 16.0))
    if name == "conv":
        bound = float(shape[0]) ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return std * jax.random.normal(key, shape, jnp.float32)


def init_params(seed: int, layers, std: float) -> List[Dict[str, Any]]:
    """The configuration's initial weights from ``--seed``: one dict a
    flat layer; a leaf is a function of (seed, layer, name) alone."""
    return [{name: init_leaf(seed, i, name, shape, std)
             for name, shape in p.items()}
            for i, p in enumerate(param_shapes(layers))]


# -- the arithmetic ---------------------------------------------------

def _q(x, precision):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    raise ValueError(precision)


def _ein(spec, a, b, precision="f32"):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HI)


def rmsnorm(x, gain, eps: float):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * (1.0 + gain)


def l2norm(x, eps: float = 1e-6):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + eps)


def causal_conv(x, kernel):
    """Depthwise causal convolution: x [B, T, C], kernel [K, C];
    ``y_t = sum_j kernel_j x_{t - K + 1 + j}``, zeros left of the row."""
    k, t = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t] * kernel[j] for j in range(k))


def delta_rule(q, k, v, g, beta, precision="f32", block: int = RULE_BLOCK):
    """The gated delta rule, token by token.  q, k [B, T, Hk, dk] (as
    the rule uses them: normalised, q scaled); v [B, T, Hv, dv]; g,
    beta [B, T, Hv].  Returns o [B, T, Hv, dv]."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk
    # time first; value heads as (key head, its r value heads)
    qs = jnp.moveaxis(q, 1, 0)
    ks = jnp.moveaxis(k, 1, 0)
    vs = jnp.moveaxis(v, 1, 0).reshape(t, b, hk, r, dv)
    gs = jnp.moveaxis(g, 1, 0).reshape(t, b, hk, r)
    bs = jnp.moveaxis(beta, 1, 0).reshape(t, b, hk, r)

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        d_t = b_t[..., None] * (
            v_t - _ein("bhrkv,bhk->bhrv", s, k_t, precision))
        s = s + _ein("bhk,bhrv->bhrkv", k_t, d_t, precision)
        return s, _ein("bhrkv,bhk->bhrv", s, q_t, precision)

    blk = block if 0 < block < t and t % block == 0 else t

    @jax.checkpoint
    def run_block(s, xs):
        return lax.scan(step, s, xs)

    s0 = jnp.zeros((b, hk, r, dk, dv), jnp.float32)
    xs = jax.tree.map(
        lambda a: a.reshape((t // blk, blk) + a.shape[1:]),
        (qs, ks, vs, gs, bs))
    _, o = lax.scan(run_block, s0, xs)
    return jnp.moveaxis(o.reshape(t, b, hv, dv), 0, 1)


def gdn_inputs(x, p, fw, precision="f32", fault=None):
    """(q, k, v, g, beta, z) of a Gated DeltaNet layer as the rule
    reads them."""
    b, t, _ = x.shape
    hk, hv = int(fw["n_key_heads"]), int(fw["n_value_heads"])
    dk, dv = int(fw["key_head_size"]), int(fw["value_head_size"])
    proj = lambda w: _ein("bth,hk->btk", x, p[w], precision)  # noqa: E731
    # depthwise: the convolution of the concatenation is the
    # concatenation of the convolutions, each with its own columns
    kw = hk * dk
    q, k, v = (jax.nn.silu(causal_conv(proj(w), p["conv"][:, lo:hi]))
               for w, lo, hi in (("wq", 0, kw), ("wk", kw, 2 * kw),
                                 ("wv", 2 * kw, None)))
    q = l2norm(q.reshape(b, t, hk, dk)) * dk ** -0.5
    k = l2norm(k.reshape(b, t, hk, dk))
    v = v.reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(proj("wb"))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(proj("wa") + p["dt_bias"])
    if fault == "beta_one":
        beta = jnp.ones_like(beta)
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    z = proj("wz").reshape(b, t, hv, dv)
    return q, k, v, g, beta, z


def gated_delta_net(x, p, fw, precision="f32", fault=None):
    """x [B, T, H] -> [B, T, Hv * dv]."""
    # (checkpoints: what the backward holds at one time is one stage's)
    q, k, v, g, beta, z = jax.checkpoint(
        partial(gdn_inputs, fw=fw, precision=precision, fault=fault))(x, p)
    o = delta_rule(q, k, v, g, beta, precision)
    eps = float(fw.get("eps", 1e-6))

    @jax.checkpoint
    def gate_norm(gain, o, z):
        ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
        return gain * o * lax.rsqrt(ms + eps) * jax.nn.silu(z)

    return gate_norm(p["norm"], o, z).reshape(x.shape[:2] + (-1,))


def rope(x, theta: float, rotary: int):
    """Rotate-half RoPE on the first ``rotary`` elements of each head;
    x [B, T, heads, d], angle ``n * theta^(-2i / rotary)``."""
    t = x.shape[1]
    inv = jnp.float32(theta) ** (
        -jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    xr, rest = x[..., :rotary], x[..., rotary:]
    x1, x2 = xr[..., :rotary // 2], xr[..., rotary // 2:]
    xr = xr * cos + jnp.concatenate([-x2, x1], -1) * sin
    return jnp.concatenate([xr, rest], -1)


def causal_attention(q, k, v, precision="f32", block: int = QUERY_BLOCK):
    """q [B, T, nkv, r, d] against k, v [B, T, nkv, d]: plain causal
    softmax attention, a block of queries at a time."""
    b, t, nkv, r, d = q.shape
    blk = block if 0 < block < t and t % block == 0 else t

    @jax.checkpoint
    def one(args):
        qb, lo = args
        s = d ** -0.5 * _ein("bnhrd,bmhd->bhrnm", qb, k, precision)
        mask = (lo + jnp.arange(blk))[:, None] >= jnp.arange(t)[None, :]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return _ein("bhrnm,bmhd->bnhrd", p, v, precision)

    qs = jnp.moveaxis(q.reshape(b, t // blk, blk, nkv, r, d), 1, 0)
    o = lax.map(one, (qs, jnp.arange(0, t, blk)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, nkv, r, d)


def gated_attention(x, p, fw, precision="f32", fault=None):
    """x [B, T, H] -> [B, T, nh * d]."""
    b, t, _ = x.shape
    nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
    d, eps = int(fw["head_size"]), float(fw.get("eps", 1e-6))
    rotary = d if fault == "rope_whole" else int(fw["rotary_size"])
    theta = float(fw["rope_theta"])
    qg = _ein("bth,hk->btk", x, p["wq"], precision).reshape(
        b, t, nh, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = _ein("bth,hk->btk", x, p["wk"], precision).reshape(b, t, nkv, d)
    v = _ein("bth,hk->btk", x, p["wv"], precision).reshape(b, t, nkv, d)
    q = rope(rmsnorm(q, p["q_gain"], eps), theta, rotary)
    k = rope(rmsnorm(k, p["k_gain"], eps), theta, rotary)
    o = causal_attention(q.reshape(b, t, nkv, nh // nkv, d), k, v,
                         precision).reshape(b, t, nh, d)
    if fault != "no_gate":
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(b, t, nh * d)


def moe_routing(x, p, fw, precision="f32", fault=None):
    """(ids, weights) [B, T, top_k]: each token's chosen experts among
    ALL ``experts_total`` and their renormalised weights."""
    prob = jax.nn.softmax(_ein("bth,he->bte", x, p["router"], precision),
                          axis=-1)
    top_p, top_i = lax.top_k(prob, int(fw["top_k"]))
    if fault != "no_renorm":
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    return top_i, top_p


def swiglu_mlp(x, w_gate, w_up, w_down, precision="f32"):
    h = jax.nn.silu(_ein("bth,hk->btk", x, w_gate, precision)) \
        * _ein("bth,hk->btk", x, w_up, precision)
    return _ein("btk,kh->bth", h, w_down, precision)


def moe(x, p, fw, precision="f32", fault=None, held=None):
    """x [B, T, H] -> [B, T, H]: the held experts' part of the routed
    sum (``held`` = (first, count) overrides the layer's own share:
    the shares-add-up test), plus the shared expert."""
    first, count = held if held is not None else (
        int(fw["first_held"]), int(fw["experts_held"]))
    top_i, top_w = moe_routing(x, p, fw, precision, fault)

    @jax.checkpoint
    def one(y, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        return y + w_e[..., None] * swiglu_mlp(x, wg, wu, wd,
                                               precision), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (first + jnp.arange(count), p["w_gate"], p["w_up"],
                     p["w_down"]))
    if fault != "no_shared":
        mix = jax.nn.sigmoid(_ein("bth,hk->btk", x, p["s_mix"],
                                  precision))
        y = y + mix * swiglu_mlp(x, p["s_gate"], p["s_up"], p["s_down"],
                                 precision)
    return y


def layer_forward(cfg, p, x, precision="f32", fault=None):
    """One flat layer of the list on x (ids [B, T] for the embedding,
    else [B, T, width])."""
    kind, fw = cfg["type"], cfg.get("->", {})
    if kind == "embedding":
        return p["weights"][x]
    if kind == "rmsnorm":
        return rmsnorm(x, p["gain"], float(fw.get("eps", 1e-6)))
    if kind == "dense":
        return _ein("bth,hk->btk", x, p["weights"], precision)
    if kind == "gated_delta_net":
        return gated_delta_net(x, p, fw, precision, fault)
    if kind == "gated_attention":
        return gated_attention(x, p, fw, precision, fault)
    if kind == "moe":
        return moe(x, p, fw, precision, fault)
    if kind == "lm_head":
        logits = _ein("bth,hk->btk", x, p["weights"], precision)
        return logits.reshape(x.shape[:2] + (int(fw["n_pred_heads"]),
                                             int(fw["vocab_size"])))
    raise ValueError(f"reference: unknown layer type {kind!r}")


def targets_of(ids, n_pred: int):
    """(targets, valid) [B, T, P]: head j at position n predicts token
    n + 1 + j; valid where that token exists."""
    t = ids.shape[1]
    pos = jnp.arange(t)[:, None] + 1 + jnp.arange(n_pred)[None, :]
    valid = pos < t
    return ids[:, jnp.minimum(pos, t - 1)], \
        jnp.broadcast_to(valid, ids.shape[:1] + valid.shape)


def loss_sum(logits, targets, valid):
    """Summed cross-entropy of the valid (row, position, head)."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0))


def entry_forward(flat, idx, kind, params, x, precision="f32",
                  fault=None):
    """One top-level entry: its layers in order, and the skip."""
    y = x
    for i in idx:
        y = layer_forward(flat[i], params[i], y, precision, fault)
    return x + y if kind == "residual" else y


def model_logits(layers, params, ids, precision="f32", fault=None):
    """Logits [B, T, P, V] of the whole model (small sizes: the tests)."""
    flat, x = flatten(layers), ids
    for kind, idx in entries(layers):
        x = entry_forward(flat, idx, kind, {i: params[i] for i in idx},
                          x, precision, fault)
    return x


def model_loss(layers, params, ids, precision="f32", fault=None):
    """(summed loss, count of valid predictions) of rows ``ids``."""
    logits = model_logits(layers, params, ids, precision, fault)
    targets, valid = targets_of(ids, logits.shape[2])
    return loss_sum(logits, targets, valid), jnp.sum(valid)


# -- training, an entry at a time ---------------------------------------

def rates_of(cfg) -> Tuple[Dict[str, Tuple[float, float]], float]:
    """({parameter: (rate, decay)}, momentum) of one flat layer."""
    bw = cfg.get("<-", {})
    lr = bw.get("learning_rate", 0.01)
    weights = (lr, bw.get("weight_decay", 0.0))
    bias = (bw.get("learning_rate_bias", lr),
            bw.get("weight_decay_bias", 0.0))
    return {n: weights if n in MATRICES[cfg["type"]] else bias
            for p in param_shapes([cfg]) for n in p}, \
        bw.get("gradient_moment", 0.0)


@partial(jax.jit, static_argnames=("lr", "wd", "moment"),
         donate_argnums=(0, 1))
def _sgd(w, v, g, lr, wd, moment):
    v = moment * v - lr * (g + wd * w)
    return w + v, v


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


class _Walker:
    """The jitted pieces of one configuration: an entry's forward, its
    vjp (whole, or summed over blocks of positions), and the head with
    its loss."""

    def __init__(self, layers, precision, fault, seq_block):
        self.flat = flatten(layers)
        self.entries = entries(layers)
        self.precision, self.fault = precision, fault
        self.seq_block = int(seq_block)
        self._fwd: Dict[Any, Any] = {}
        self._bwd: Dict[Any, Any] = {}

    def _key(self, e):
        kind, idx = self.entries[e]
        return kind, tuple(repr(self.flat[i].get("->")) +
                           self.flat[i]["type"] for i in idx)

    def _fn(self, e):
        kind, idx = self.entries[e]
        flat, prec, fault = self.flat, self.precision, self.fault

        def fn(ps, x):
            # parameters by position in the entry, so that entries of
            # one shape share one compiled program
            return entry_forward(flat, idx, kind, dict(zip(idx, ps)),
                                 x, prec, fault)
        return fn

    def pointwise(self, e) -> bool:
        return all(self.flat[i]["type"] in POINTWISE
                   for i in self.entries[e][1])

    def _block(self, e, t) -> int:
        blk = self.seq_block
        return blk if self.pointwise(e) and 0 < blk < t else t

    def forward(self, e, ps, x):
        key = self._key(e)
        if key not in self._fwd:
            self._fwd[key] = jax.jit(self._fn(e))
        t = x.shape[1]
        blk = self._block(e, t)
        if blk == t:
            return self._fwd[key](ps, x)
        return jnp.concatenate(
            [self._fwd[key](ps, x[:, lo:lo + blk])
             for lo in range(0, t, blk)], axis=1)

    def backward(self, e, ps, x, err):
        """(d parameters, d input) of entry e at input x."""
        key = self._key(e)
        if key not in self._bwd:
            fn = self._fn(e)

            def bwd(ps, x, err):
                if jnp.issubdtype(x.dtype, jnp.integer):
                    # ids take no gradient
                    _, vjp = jax.vjp(lambda ps: fn(ps, x), ps)
                    return vjp(err)[0], None
                _, vjp = jax.vjp(fn, ps, x)
                return vjp(err)
            self._bwd[key] = jax.jit(bwd)
        bwd = self._bwd[key]
        t = x.shape[1]
        blk = self._block(e, t)
        if blk == t:
            return bwd(ps, x, err)
        dps, dxs = None, []
        for lo in range(0, t, blk):
            dp, dx = bwd(ps, x[:, lo:lo + blk], err[:, lo:lo + blk])
            dps = dp if dps is None else jax.tree.map(jnp.add, dps, dp)
            dxs.append(dx)
        return dps, jnp.concatenate(dxs, axis=1)

    def head(self, ps, x, ids, count):
        """(summed loss, d parameters, d input) of the tail — the last
        norm and the head — under the mean loss over ``count``."""
        flat, prec = self.flat, self.precision
        idx = list(range(self.tail_start(), len(flat)))
        n_pred = int(flat[-1]["->"]["n_pred_heads"])

        if "head" not in self._bwd:
            def head_loss(ps, x, targets, valid, count):
                y = x
                for i, p in zip(idx, ps):
                    y = layer_forward(flat[i], p, y, prec)
                s = loss_sum(y, targets, valid)
                return s / count, s

            def bwd(ps, x, targets, valid, count):
                (_, s), g = jax.value_and_grad(
                    head_loss, argnums=(0, 1), has_aux=True)(
                        ps, x, targets, valid, count)
                return s, g[0], g[1]
            self._bwd["head"] = jax.jit(bwd)
        targets, valid = targets_of(ids, n_pred)
        t = x.shape[1]
        blk = self.seq_block if 0 < self.seq_block < t else t
        total, dps, dxs = 0.0, None, []
        for lo in range(0, t, blk):
            s, dp, dx = self._bwd["head"](
                ps, x[:, lo:lo + blk], targets[:, lo:lo + blk],
                valid[:, lo:lo + blk], jnp.float32(count))
            total = total + s
            dps = dp if dps is None else jax.tree.map(jnp.add, dps, dp)
            dxs.append(dx)
        return total, dps, jnp.concatenate(dxs, axis=1)

    def tail_start(self) -> int:
        """Flat index where the tail begins: the trailing plain
        entries that act on each position alone, ending in the head."""
        start = len(self.flat)
        for kind, idx in reversed(self.entries):
            if kind != "plain" or self.flat[idx[0]]["type"] not in \
                    ("rmsnorm", "dense", "lm_head"):
                break
            start = idx[0]
        return start

    def choices(self, e, ps, x):
        """{flat index: ids [B, T, top_k]} of the ``moe`` layers of
        entry e at input x: what each token chose."""
        kind, idx = self.entries[e]
        flat, prec, fault = self.flat, self.precision, self.fault
        if not any(flat[i]["type"] == "moe" for i in idx):
            return {}
        key = ("choices",) + self._key(e)
        if key not in self._fwd:
            def fn(ps, x):
                out, y = [], x
                for i, p in zip(idx, ps):
                    if flat[i]["type"] == "moe":
                        out.append(moe_routing(y, p, flat[i]["->"], prec,
                                               fault)[0])
                    y = layer_forward(flat[i], p, y, prec, fault)
                return out
            self._fwd[key] = jax.jit(fn)
        t = x.shape[1]
        blk = self._block(e, t)
        got = [self._fwd[key](ps, x[:, lo:lo + blk])
               for lo in range(0, t, blk)]
        where = [i for i in idx if flat[i]["type"] == "moe"]
        return {i: np.concatenate([np.asarray(g[j]) for g in got], 1)
                for j, i in enumerate(where)}


def valid_count(n_rows: int, seq_len: int, n_pred: int = 1) -> int:
    return n_rows * sum(max(seq_len - 1 - j, 0) for j in range(n_pred))


def follow(layers, params0, rows, precision: str = "f32",
           fault: Optional[str] = None, seq_block: int = 0,
           make_w0=None) -> Dict[str, Any]:
    """Drive the reference through ``len(rows)`` SGD steps from
    ``params0`` (zero momentum; it is consumed).  ``rows`` [k, mb, T]
    holds each step's minibatch of ids.  Returns what the comparison
    reads: the steps' summed losses, per-leaf norms ("<flat
    index>.<name>") of the momentum and of the parameters' change after
    the last step, and of the first step's gradient; ``choices0``: what
    each token of the first step chose in every ``moe`` layer.
    ``make_w0(i, name)`` gives an initial leaf again (so that no second
    copy of the weights is held); without it a copy is kept."""
    wk = _Walker(layers, precision, fault, seq_block)
    flat, ents = wk.flat, wk.entries
    # the state lives on the HOST; only the entry being walked is on
    # the device (beside an entry's f32 backward at a 32 k row there is
    # no room for 5 GB of weights and momentum)
    params = [{k: np.asarray(v) for k, v in p.items()} for p in params0]
    del params0
    w0 = None if make_w0 is not None else \
        [{k: np.array(v) for k, v in p.items()} for p in params]
    vel = [{k: np.zeros_like(v) for k, v in p.items()} for p in params]
    n_pred = int(flat[-1]["->"]["n_pred_heads"])
    tail0 = wk.tail_start()
    body = [e for e, (_, idx) in enumerate(ents) if idx[0] < tail0]
    tail_idx = [i for _, idx in ents for i in idx if i >= tail0]
    losses, grad0, choices0 = [], {}, {}

    def on_device(idx):
        return [{k: jnp.asarray(v) for k, v in params[i].items()}
                for i in idx]

    def update(i, grads, first):
        rates, moment = rates_of(flat[i])
        for name, g in grads.items():
            if first:
                grad0[f"{i}.{name}"] = _norm(g)
            if fault == "state_unchanged":
                continue
            lr, wd = rates[name]
            w, v = _sgd(jnp.asarray(params[i][name]),
                        jnp.asarray(vel[i][name]), g, lr=float(lr),
                        wd=float(wd), moment=float(moment))
            params[i][name], vel[i][name] = np.asarray(w), np.asarray(v)

    for t, ids in enumerate(np.asarray(rows)):
        ids = jnp.asarray(ids, jnp.int32)
        count = float(valid_count(ids.shape[0], ids.shape[1], n_pred))
        x, inputs = ids, []
        for e in body:
            inputs.append(np.asarray(x))      # kept on the host
            ps = on_device(ents[e][1])
            if t == 0:
                choices0.update(wk.choices(e, ps, x))
            x = wk.forward(e, ps, x)
            del ps
        s, dps, err = wk.head(on_device(tail_idx), x, ids, count)
        losses.append(float(s))
        del x
        for i, dp in zip(tail_idx, dps):
            update(i, dp, t == 0)
        del dps
        for e in reversed(body):
            idx = ents[e][1]
            x_in = jnp.asarray(inputs.pop())
            dps, err = wk.backward(e, on_device(idx), x_in, err)
            for i, dp in zip(idx, dps):
                update(i, dp, t == 0)
            del dps, x_in
        del err
    out = {"loss_sum": float(np.sum(losses)), "losses": losses,
           "momentum": {}, "update": {}, "grad0": {},
           "choices0": choices0}
    for i, p in enumerate(params):
        for name, a in p.items():
            z = make_w0(i, name) if make_w0 is not None else w0[i][name]
            out["update"][f"{i}.{name}"] = _norm(jnp.asarray(a) - z)
            out["momentum"][f"{i}.{name}"] = _norm(
                jnp.asarray(vel[i][name]))
    for key in ("momentum", "update"):
        out[key] = {k: float(v) for k, v in
                    jax.device_get(out[key]).items()}
    out["grad0"] = {k: float(v) for k, v in
                    jax.device_get(grad0).items()}
    return out
