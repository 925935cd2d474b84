"""The plain reference of configuration ``evabyte``: a byte-level
decoder with EVA chunked linearized attention (Zheng, Yuan, Wang,
Kong: Efficient Attention via Control Variates, ICLR 2023,
arXiv:2302.04542, section 4: EVA with the exact set E = the local
window), multi-byte prediction heads, next-byte cross-entropy and SGD
with momentum — plain ``jax.numpy`` float32 at matmul precision
"highest", an interpreter of the configuration's ``layers`` list.  It
imports nothing of the program; the CPU tests import its layer
functions, the benchmark its ``follow``.

What it computes, per row of ``T`` byte ids (``layers`` names the
sizes; H hidden, nh heads of size d, window W, chunk c, P heads):

- ``x0 = E[id]``; a ``residual`` entry is ``x + f(x)`` with the add in
  f32; ``rmsnorm`` is ``x / sqrt(mean(x^2) + eps) * (1 + g)``;
- ``eva_attention``: ``q, k = RoPE(x Wq), RoPE(x Wk)`` (rotate-half
  over the whole head, angle ``n * theta^(-2i/d)``), ``v = x Wv``;
  chunk j = positions [jc, jc + c) has the summary ``a_jm =
  softmax_m(s k_m . phi)``, ``vs_j = sum_m a_jm v_m``, ``ks_j = sum_m
  a_jm k_m + mu`` (s = d^-1/2; phi, mu one learned vector a head).
  Query n of window w = n // W scores its local keys {m : wW <= m <=
  n} exactly and the summaries of every chunk of every earlier window
  (128 w of them); one softmax over both sets; the output is the
  weighted sum of the local v and the remote vs;
- ``dense`` is ``x W``; ``swiglu`` is ``silu(x Wg) * (x Wu)``;
- ``lm_head``: logits ``[T, P, V] = x W`` in f32; head j at position
  n predicts byte n + 1 + j; the loss is the mean cross-entropy over
  the valid (n, j) of the minibatch.

``precision`` selects what stands in the program's place for the
control: "f32" is the reference; "bf16" / "fp8" round the operands of
every matmul (projections, scores, weighted sums) to bfloat16 /
float8_e4m3 as plain casts — the program's own recipe one step down.
``fault`` plants a fault into the same arithmetic: "no_remote" leaves
the remote summaries out, "seven_heads" the last prediction head,
"state_unchanged" returns the state as given.

At the published widths a step does not fit a chip as one ``jax.grad``:
``follow`` walks the top-level entries back one at a time with
``jax.vjp`` (entries whose layers act on each position alone, and the
head with its loss, in blocks of ``seq_block`` positions; an entry with
attention in stages — projections, chunk summaries, one window of
``head_block`` heads at a time, output projection and skip — so that
one window's f32 scores are all that is ever held), keeps the entries'
inputs on the host, and applies the update of an entry as soon as its
gradient exists.
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
#: (gains, phi, mu) takes the bias's
MATRICES = {"embedding": ("weights",), "rmsnorm": (),
            "eva_attention": ("wq", "wk", "wv"), "dense": ("weights",),
            "swiglu": ("w_gate", "w_up"), "lm_head": ("weights",)}
#: layer types that act on each position alone
POINTWISE = ("rmsnorm", "dense", "swiglu")

WEIGHT_STREAM = 2


def stream_seed(seed: int, stream: int) -> int:
    """A 31-bit seed of one of the benchmark's streams (as
    ``lib/seeded.py``; ``--seed`` may need more than 32 signed bits)."""
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
        if cfg["type"] == "residual":
            n = len(flatten(cfg["layers"]))
            out.append(("residual", list(range(i, i + n))))
            i += n
        else:
            out.append(("plain", [i]))
            i += 1
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
        elif kind == "eva_attention":
            nh, d = int(fw["n_heads"]), int(fw["head_size"])
            p = {"wq": (width, nh * d), "wk": (width, nh * d),
                 "wv": (width, nh * d), "phi": (nh, d), "mu": (nh, d)}
            width = nh * d
        elif kind == "dense":
            p = {"weights": (width, int(fw["output_size"]))}
            width = int(fw["output_size"])
        elif kind == "swiglu":
            n = int(fw["intermediate_size"])
            p = {"w_gate": (width, n), "w_up": (width, n)}
            width = n
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
    """One parameter of the seed's weights: N(0, std^2), norm gains
    zero (the norm multiplies by 1 + g)."""
    if name == "gain":
        return jnp.zeros(shape, jnp.float32)
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.key(stream_seed(seed, WEIGHT_STREAM)), index),
        sum(map(ord, name)))
    return std * jax.random.normal(key, tuple(shape), jnp.float32)


def init_params(seed: int, layers, std: float
                ) -> List[Dict[str, Any]]:
    """The configuration's initial weights from ``--seed``: one dict a
    flat layer.  A leaf is a function of (seed, layer, name) alone, so
    any one of them can be made again without the others."""
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


def _ein(spec, a, b, precision):
    return jnp.einsum(spec, _q(a, precision), _q(b, precision),
                      precision=HI)


def rmsnorm(x, gain, eps: float):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(ms + eps) * (1.0 + gain)


def rope(x, theta: float, offset=0):
    """Rotate-half RoPE over the whole head; x [B, T, nh, d], position
    n = offset + row."""
    t, d = x.shape[1], x.shape[-1]
    inv = jnp.float32(theta) ** (
        -jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = (offset + jnp.arange(t, dtype=jnp.float32))[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def summaries(k, v, phi, mu, chunk: int, precision="f32"):
    """(ks, vs) [B, T/c, nh, d]: each chunk's pooled key (+ mu) and
    value, pooled with softmax_m(s k_m . phi)."""
    b, t, nh, d = k.shape
    kc = k.reshape(b, t // chunk, chunk, nh, d)
    vc = v.reshape(b, t // chunk, chunk, nh, d)
    logit = d ** -0.5 * _ein("bjmhd,hd->bjmh", kc, phi, precision)
    a = jax.nn.softmax(logit, axis=2)
    vs = _ein("bjmh,bjmhd->bjhd", a, vc, precision)
    ks = _ein("bjmh,bjmhd->bjhd", a, kc, precision) + mu
    return ks, vs


def window_attention(q, k, v, ks, vs, precision="f32"):
    """One window: q, k, v [B, W, nh, d] (causal among themselves),
    ks, vs [B, R, nh, d] the summaries of every earlier window (R may
    be 0).  One softmax over the local keys and the summaries."""
    w, d = q.shape[1], q.shape[-1]
    s = d ** -0.5
    score = s * _ein("bnhd,bmhd->bhnm", q, k, precision)
    score = jnp.where(jnp.tril(jnp.ones((w, w), bool)), score, -jnp.inf)
    if ks.shape[1]:
        remote = s * _ein("bnhd,bjhd->bhnj", q, ks, precision)
        score = jnp.concatenate([score, remote], axis=-1)
    p = jax.nn.softmax(score, axis=-1)
    o = _ein("bhnm,bmhd->bnhd", p[..., :w], v, precision)
    if ks.shape[1]:
        o = o + _ein("bhnj,bjhd->bnhd", p[..., w:], vs, precision)
    return o


def eva_qkv(x, p, fw, precision="f32", offset=0):
    """(q, k, v) [B, T, nh, d]: the projections, q and k rotated (row
    r of x is position offset + r)."""
    b, t, _ = x.shape
    nh, d = int(fw["n_heads"]), int(fw["head_size"])
    theta = float(fw["rope_theta"])
    heads = lambda y: y.reshape(b, t, nh, d)  # noqa: E731
    q = rope(heads(_ein("bth,hk->btk", x, p["wq"], precision)), theta,
             offset)
    k = rope(heads(_ein("bth,hk->btk", x, p["wk"], precision)), theta,
             offset)
    v = heads(_ein("bth,hk->btk", x, p["wv"], precision))
    return q, k, v


def eva_windows(fw, t: int, fault=None):
    """[(first position, one past the last, summaries it sees)] of the
    windows of a row of ``t`` positions."""
    win = min(int(fw["window_size"]), t)
    chunk = int(fw["chunk_size"])
    assert t % win == 0 and win % chunk == 0, (t, win, chunk)
    return [(lo, lo + win, 0 if fault == "no_remote" else lo // chunk)
            for lo in range(0, t, win)]


def eva_attention(x, p, fw, precision="f32", fault=None):
    """x [B, T, H] -> [B, T, nh * d], the heads' outputs side by side
    (the output projection is the ``dense`` layer that follows)."""
    b, t, _ = x.shape
    q, k, v = eva_qkv(x, p, fw, precision)
    ks, vs = summaries(k, v, p["phi"], p["mu"], int(fw["chunk_size"]),
                       precision)
    out = [window_attention(q[:, lo:hi], k[:, lo:hi], v[:, lo:hi],
                            ks[:, :r], vs[:, :r], precision)
           for lo, hi, r in eva_windows(fw, t, fault)]
    return jnp.concatenate(out, axis=1).reshape(b, t, -1)


def layer_forward(cfg, p, x, precision="f32", fault=None):
    """One flat layer of the list on x (ids [B, T] for the embedding,
    else [B, T, width])."""
    kind, fw = cfg["type"], cfg.get("->", {})
    if kind == "embedding":
        return p["weights"][x]
    if kind == "rmsnorm":
        return rmsnorm(x, p["gain"], float(fw.get("eps", 1e-5)))
    if kind == "eva_attention":
        return eva_attention(x, p, fw, precision, fault)
    if kind == "dense":
        return _ein("bth,hk->btk", x, p["weights"], precision)
    if kind == "swiglu":
        return jax.nn.silu(_ein("bth,hk->btk", x, p["w_gate"], precision)) \
            * _ein("bth,hk->btk", x, p["w_up"], precision)
    if kind == "lm_head":
        logits = _ein("bth,hk->btk", x, p["weights"], precision)
        return logits.reshape(x.shape[:2] + (int(fw["n_pred_heads"]),
                                             int(fw["vocab_size"])))
    raise ValueError(f"reference: unknown layer type {kind!r}")


def targets_of(ids, n_pred: int):
    """(targets, valid) [B, T, P]: head j at position n predicts byte
    n + 1 + j; valid where that byte exists."""
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
    n_pred = logits.shape[2] - (1 if fault == "seven_heads" else 0)
    targets, valid = targets_of(ids, logits.shape[2])
    valid = valid & (jnp.arange(logits.shape[2]) < n_pred)
    return loss_sum(logits, targets, valid), jnp.sum(valid)


# -- training, an entry at a time ---------------------------------------

def rates_of(cfg) -> Tuple[Dict[str, Tuple[float, float]], float]:
    """({parameter: (rate, decay)}, momentum) of one flat layer."""
    bw = cfg.get("<-", {})
    lr = bw.get("learning_rate", 0.01)
    weights = (lr, bw.get("weight_decay", 0.0))
    bias = (bw.get("learning_rate_bias", lr),
            bw.get("weight_decay_bias", 0.0))
    return {name: weights if name in MATRICES[cfg["type"]] else bias
            for name in ("weights", "gain", "wq", "wk", "wv", "phi",
                         "mu", "w_gate", "w_up")}, \
        bw.get("gradient_moment", 0.0)


@partial(jax.jit, static_argnames=("lr", "wd", "moment"),
         donate_argnums=(0, 1))
def _sgd(w, v, g, lr, wd, moment):
    v = moment * v - lr * (g + wd * w)
    return w + v, v


def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


class _Walker:
    """The jitted pieces of one configuration: an entry forward, an
    entry's vjp (whole, or summed over blocks of positions), the
    attention entries in stages, and the head with its loss."""

    def __init__(self, layers, precision, fault, seq_block,
                 head_block=0):
        self.layers = layers
        self.flat = flatten(layers)
        self.entries = entries(layers)
        self.precision, self.fault = precision, fault
        self.seq_block = int(seq_block)
        self.head_block = int(head_block)
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

    def _attention_at(self, e) -> Optional[int]:
        """Place within entry e of its attention layer, if it has one."""
        types = [self.flat[i]["type"] for i in self.entries[e][1]]
        return types.index("eva_attention") \
            if "eva_attention" in types else None

    def forward(self, e, ps, x):
        if self._attention_at(e) is not None:
            return self._attention_entry(e, ps, x, None)
        key = self._key(e)
        if key not in self._fwd:
            self._fwd[key] = jax.jit(self._fn(e))
        return self._fwd[key](ps, x)

    def backward(self, e, ps, x, err):
        """(d parameters, d input) of entry e at input x."""
        if self._attention_at(e) is not None:
            return self._attention_entry(e, ps, x, err)
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
        blk = self.seq_block
        t = x.shape[1]
        if not self.pointwise(e) or not 0 < blk < t:
            return bwd(ps, x, err)
        dps, dxs = None, []
        for lo in range(0, t, blk):
            dp, dx = bwd(ps, x[:, lo:lo + blk], err[:, lo:lo + blk])
            dps = dp if dps is None else jax.tree.map(jnp.add, dps, dp)
            dxs.append(dx)
        return dps, jnp.concatenate(dxs, axis=1)

    # -- an entry with attention, in stages --------------------------------
    # before: the layers up to the projections -> (q, k, v); pool: the
    # chunk summaries; window: one window of some of the heads; after:
    # the layers behind the attention, and the skip.  Forward, and —
    # given ``err`` — backward, a window and ``head_block`` heads at a
    # time, so that one window's f32 scores are all that is ever held.

    def _stages(self, e):
        key = self._key(e)
        if key in self._fwd:
            return self._fwd[key]
        kind, idx = self.entries[e]
        at = self._attention_at(e)
        flat, prec = self.flat, self.precision
        fw = flat[idx[at]]["->"]

        def before(ps, x, offset):
            # acts on each position alone (given where the block of
            # positions starts: RoPE), so its vjp can go in blocks
            y = x
            for i, p in zip(idx[:at], ps[:at]):
                y = layer_forward(flat[i], p, y, prec)
            return eva_qkv(y, ps[at], fw, prec, offset)

        def pool(p, k, v):
            return summaries(k, v, p["phi"], p["mu"],
                             int(fw["chunk_size"]), prec)

        def window(q, k, v, ks, vs):
            return window_attention(q, k, v, ks, vs, prec)

        def after(ps, o, x):
            y = o.reshape(o.shape[:2] + (-1,))
            for i, p in zip(idx[at + 1:], ps[at + 1:]):
                y = layer_forward(flat[i], p, y, prec)
            return x + y if kind == "residual" else y

        def vjp_of(fn):
            def bwd(err, *args):
                return jax.vjp(fn, *args)[1](err)
            return jax.jit(bwd)

        def d_before(err, ps, x, offset):
            return jax.vjp(lambda ps, x: before(ps, x, offset),
                           ps, x)[1](err)

        st = {"fw": fw, "at": at,
              "before": jax.jit(before), "pool": jax.jit(pool),
              "window": jax.jit(window), "after": jax.jit(after),
              "d_before": jax.jit(d_before), "d_pool": vjp_of(pool),
              "d_window": vjp_of(window), "d_after": vjp_of(after)}
        self._fwd[key] = st
        return st

    def _attention_entry(self, e, ps, x, err):
        """Output of entry e (``err`` None), else (d parameters, d
        input)."""
        st = self._stages(e)
        at, nh = st["at"], int(st["fw"]["n_heads"])
        hb = self.head_block if 0 < self.head_block < nh else nh
        q, k, v = st["before"](ps, x, 0)
        ks, vs = st["pool"](ps[at], k, v)
        windows = eva_windows(st["fw"], x.shape[1], self.fault)

        def pieces(lo, hi, r, h):
            """One window's q, k, v and summaries, ``hb`` heads."""
            return (q[:, lo:hi, h:h + hb], k[:, lo:hi, h:h + hb],
                    v[:, lo:hi, h:h + hb], ks[:, :r, h:h + hb],
                    vs[:, :r, h:h + hb])

        # (in the backward the attention's output is made again: the
        # forward walk keeps no entry's insides)
        o = jnp.concatenate([jnp.concatenate(
            [st["window"](*pieces(lo, hi, r, h))
             for h in range(0, nh, hb)], axis=2)
            for lo, hi, r in windows], axis=1)
        if err is None:
            return st["after"](ps, o, x)
        d_post, d_o, d_skip = st["d_after"](err, ps, o, x)
        d_o = d_o.reshape(o.shape)
        del o
        dks, dvs = jnp.zeros_like(ks), jnp.zeros_like(vs)
        dq, dk, dv = [], [], []
        for lo, hi, r in windows:
            row = [st["d_window"](d_o[:, lo:hi, h:h + hb],
                                  *pieces(lo, hi, r, h))
                   for h in range(0, nh, hb)]
            dq.append(jnp.concatenate([g[0] for g in row], axis=2))
            dk.append(jnp.concatenate([g[1] for g in row], axis=2))
            dv.append(jnp.concatenate([g[2] for g in row], axis=2))
            if r:
                dks = dks.at[:, :r].add(
                    jnp.concatenate([g[3] for g in row], axis=2))
                dvs = dvs.at[:, :r].add(
                    jnp.concatenate([g[4] for g in row], axis=2))
        del d_o
        dq, dk, dv = (jnp.concatenate(g, axis=1) for g in (dq, dk, dv))
        d_pool, dk2, dv2 = st["d_pool"]((dks, dvs), ps[at], k, v)
        del q, k, v, ks, vs, dks, dvs
        dk, dv = dk + dk2, dv + dv2
        del dk2, dv2
        dps = list(d_post)
        dps[at] = jax.tree.map(jnp.add, dps[at], d_pool)
        t = x.shape[1]
        blk = self.seq_block if 0 < self.seq_block < t else t
        d_xs = []
        for lo in range(0, t, blk):
            d_pre, d_x = st["d_before"](
                (dq[:, lo:lo + blk], dk[:, lo:lo + blk],
                 dv[:, lo:lo + blk]), ps, x[:, lo:lo + blk], lo)
            dps = [jax.tree.map(jnp.add, a, b)
                   for a, b in zip(dps, d_pre)]
            d_xs.append(d_x)
        return dps, jnp.concatenate(d_xs, axis=1) + d_skip

    def head(self, ps, x, ids, count):
        """(summed loss, d parameters, d input) of the tail — the last
        norm and the head — under the mean loss over ``count``."""
        flat, prec = self.flat, self.precision
        idx = list(range(self.tail_start(), len(flat)))
        n_pred = int(flat[-1]["->"]["n_pred_heads"])
        keep = n_pred - (1 if self.fault == "seven_heads" else 0)

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
        valid = valid & (jnp.arange(n_pred) < keep)
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
                    POINTWISE + ("lm_head",):
                break
            start = idx[0]
        return start


def valid_count(n_rows: int, seq_len: int, n_pred: int) -> int:
    return n_rows * sum(max(seq_len - 1 - j, 0) for j in range(n_pred))


def follow(layers, params0, rows, precision: str = "f32",
           fault: Optional[str] = None, seq_block: int = 0,
           make_w0=None, head_block: int = 0) -> Dict[str, Any]:
    """Drive the reference through ``len(rows)`` SGD steps from
    ``params0`` (zero momentum; it is consumed).  ``rows`` [k, mb, T]
    holds each step's minibatch of ids.  Returns what the comparison
    reads: the steps' summed losses, per-leaf norms ("<flat
    index>.<name>") of the momentum and of the parameters' change after
    the last step, and of the first step's gradient.  ``make_w0(i,
    name)`` gives an initial leaf again (so that no second copy of the
    weights is held); without it a copy is kept."""
    wk = _Walker(layers, precision, fault, seq_block, head_block)
    flat, ents = wk.flat, wk.entries
    params = [dict(p) for p in params0]
    w0 = None if make_w0 is not None else \
        [{k: jnp.array(v) for k, v in p.items()} for p in params0]
    vel = [{k: jnp.zeros_like(v) for k, v in p.items()} for p in params]
    n_pred = int(flat[-1]["->"]["n_pred_heads"])
    keep = n_pred - (1 if fault == "seven_heads" else 0)
    tail0 = wk.tail_start()
    body = [e for e, (_, idx) in enumerate(ents) if idx[0] < tail0]
    tail_idx = [i for _, idx in ents for i in idx if i >= tail0]
    losses, grad0 = [], {}

    def update(i, grads, first):
        rates, moment = rates_of(flat[i])
        for name, g in grads.items():
            if first:
                grad0[f"{i}.{name}"] = _norm(g)
            if fault == "state_unchanged":
                continue
            lr, wd = rates[name]
            params[i][name], vel[i][name] = _sgd(
                params[i][name], vel[i][name], g, lr=float(lr),
                wd=float(wd), moment=float(moment))

    for t, ids in enumerate(np.asarray(rows)):
        ids = jnp.asarray(ids, jnp.int32)
        count = float(valid_count(ids.shape[0], ids.shape[1], keep))
        x, inputs = ids, []
        for e in body:
            inputs.append(np.asarray(x))      # kept on the host
            x = wk.forward(e, [params[i] for i in ents[e][1]], x)
        s, dps, err = wk.head([params[i] for i in tail_idx], x, ids,
                              count)
        losses.append(float(s))
        del x
        for i, dp in zip(tail_idx, dps):
            update(i, dp, t == 0)
        for e in reversed(body):
            idx = ents[e][1]
            x_in = jnp.asarray(inputs.pop())
            dps, err = wk.backward(e, [params[i] for i in idx], x_in,
                                   err)
            for i, dp in zip(idx, dps):
                update(i, dp, t == 0)
        del err
    out = {"loss_sum": float(np.sum(losses)), "losses": losses,
           "momentum": {}, "update": {}, "grad0": {}}
    for i, p in enumerate(params):
        for name, a in p.items():
            z = make_w0(i, name) if make_w0 is not None else w0[i][name]
            out["update"][f"{i}.{name}"] = _norm(a - z)
            out["momentum"][f"{i}.{name}"] = _norm(vel[i][name])
    for key in ("momentum", "update"):
        out[key] = {k: float(v) for k, v in
                    jax.device_get(out[key]).items()}
    out["grad0"] = {k: float(v) for k, v in
                    jax.device_get(grad0).items()}
    return out
