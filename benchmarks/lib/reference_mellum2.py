"""The plain reference of configuration ``mellum2``: a decoder whose
period of four layers is three sliding-window attention layers and one
full causal attention layer — grouped-query, no gate, no per-head norm,
each kind with a RoPE law of its own (YaRN on the full layers) — every
layer followed by a mixture of experts of which this chip holds a
share, with NO shared expert; next-token cross-entropy and SGD with
momentum — plain ``jax.numpy`` float32 at matmul precision "highest",
an interpreter of the configuration's ``layers`` list.  It imports
nothing of the program.  What is the same mathematics as in
``reference_qwen3next.py`` is imported from there (the list's
flattening, the seeded leaves, the rounding of a control's operands,
the norm, the router, a SwiGLU, the loss, the SGD step); what this
architecture adds is written out here.

What it computes, per row of ``T`` token ids (``layers`` names the
sizes):

- ``x0 = E[id]``; a ``residual`` entry is ``x + f(x)``; ``rmsnorm`` is
  ``x / sqrt(mean(x^2) + eps) * (1 + g)`` (g from 0: the published
  ``* g`` from 1); ``dense`` is ``x W``;
- ``attention``: ``q = x Wq [T, nh, d]``, ``k, v [T, nkv, d]``;
  rotate-half RoPE over the whole head, ``q, k <- a (x cos(n f) +
  rot(x) sin(n f))``, with ``(f, a)`` of the layer's ``rope``
  (:func:`rope_law`): ``default``: ``f_j = theta^(-2j/d)``, ``a = 1``;
  ``yarn``: ``e_j = theta^(-2j/d)``, ``p_j = e_j / factor``, ``dim(r) =
  d ln(original / (2 pi r)) / (2 ln theta)``, ``low = max(floor(dim(
  beta_fast)), 0)``, ``high = min(ceil(dim(beta_slow)), d - 1)``,
  ``ramp_j = clip((j - low) / (high - low), 0, 1)``, ``f_j = p_j ramp_j
  + e_j (1 - ramp_j)``, ``a = attention_factor``.  ``o_n = softmax_m(q_n
  . k_m / sqrt(d)) v_m`` over the keys the mask allows: ``m <= n``, and
  with ``window`` W also ``m > n - W`` — the mask written out from that
  definition over ALL keys, a block of queries at a time; query head h
  reads key head ``h // (nh / nkv)``;
- ``moe``: ``p = softmax(x W_r)`` over ALL ``experts_total``; the
  ``top_k`` largest, ``w_e = p_e / sum_topk p``; ``y = sum over e in
  topk AND held of w_e W_d,e(silu(W_g,e x) * W_u,e x)`` — a loop over
  the held experts with a 0 / w mask a token; nothing else is added;
- ``lm_head``: logits ``[T, 1, V] = x W`` in f32; position n predicts
  token n + 1; the loss is the mean cross-entropy over the valid
  positions of the minibatch.

``precision`` selects what stands in the program's place for the
control: "f32" is the reference; "bf16" / "fp8" round the operands of
every matmul to bfloat16 / float8_e4m3 as plain casts.  ``fault``
plants a fault into the same arithmetic: "no_window" (the window left
out of the window layers), "yarn_default" (the full layers' YaRN
frequencies replaced by the default law), "no_rope_scale" (YaRN's
scale on cos and sin left out), "no_renorm" (the top-k weights not
renormalised), "expert_out" (the last held expert left out),
"half_batch" (the second half of every minibatch's rows left out, the
mean taken over the rest), "state_unchanged" (the state returned as
given).

``follow`` walks the top-level entries back one at a time with
``jax.vjp`` (entries whose layers act on each position alone, and the
head with its loss, in blocks of ``seq_block`` positions), keeps the
weights, the momentum and the entries' inputs on the host — only the
entry being walked is on the device — and applies an entry's update as
soon as its gradient exists.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .reference_qwen3next import (  # noqa: F401  (re-exported)
    _ein, _norm, _sgd, entries, flatten, init_leaf, loss_sum,
    moe_routing, rmsnorm, stream_seed, swiglu_mlp, targets_of,
    valid_count)

#: parameters that take the weights' rate and decay; gains the bias's
MATRICES = {
    "embedding": ("weights",), "rmsnorm": (), "dense": ("weights",),
    "lm_head": ("weights",), "attention": ("wq", "wk", "wv"),
    "moe": ("router", "w_gate", "w_up", "w_down")}
#: layer types that act on each position alone
POINTWISE = ("rmsnorm", "dense", "moe")
FAULTS = ("no_window", "yarn_default", "no_rope_scale", "no_renorm",
          "expert_out", "half_batch")

#: queries of the attention scored at once
QUERY_BLOCK = 256


# -- the layers list --------------------------------------------------

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
        elif kind == "attention":
            nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
            d = int(fw["head_size"])
            p = {"wq": (width, nh * d), "wk": (width, nkv * d),
                 "wv": (width, nkv * d)}
            width = nh * d
        elif kind == "moe":
            if int(fw.get("shared_size", 0)):
                raise ValueError("reference: this configuration's "
                                 "experts have no shared one")
            held, n = int(fw["experts_held"]), int(fw["expert_size"])
            p = {"router": (width, int(fw["experts_total"])),
                 "w_gate": (held, width, n), "w_up": (held, width, n),
                 "w_down": (held, n, width)}
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


def init_params(seed: int, layers, std: float) -> List[Dict[str, Any]]:
    """The configuration's initial weights from ``--seed``: one dict a
    flat layer; a leaf is a function of (seed, layer, name) alone."""
    return [{name: init_leaf(seed, i, name, shape, std)
             for name, shape in p.items()}
            for i, p in enumerate(param_shapes(layers))]


# -- the arithmetic ---------------------------------------------------

def rope_law(spec: Dict[str, Any], d: int, fault: Optional[str] = None):
    """(inverse frequencies [d / 2], scale a) of a ``rope``
    specification, from the formulas of the module's docstring."""
    theta = float(spec["rope_theta"])
    e = [theta ** (-2.0 * j / d) for j in range(d // 2)]
    if spec.get("rope_type", "default") == "default" \
            or fault == "yarn_default":
        return np.asarray(e, np.float32), 1.0
    factor = float(spec["factor"])
    original = float(spec["original_max_position_embeddings"])

    def dim(r):
        return d * math.log(original / (2.0 * math.pi * r)) \
            / (2.0 * math.log(theta))

    low = max(math.floor(dim(float(spec["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(spec["beta_slow"]))), d - 1)
    f = []
    for j in range(d // 2):
        ramp = min(max((j - low) / float(high - low), 0.0), 1.0)
        f.append(e[j] / factor * ramp + e[j] * (1.0 - ramp))
    a = 1.0 if fault == "no_rope_scale" \
        else float(spec["attention_factor"])
    return np.asarray(f, np.float32), a


def rotate(x, inv_freq, scale: float):
    """Rotate-half RoPE over the whole head; x [B, T, heads, d], angle
    ``n * inv_freq_j``, cos and sin times ``scale``."""
    t, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv_freq, jnp.float32)
    cos = scale * jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None]
    sin = scale * jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def allowed(queries, keys, window: Optional[int]):
    """The mask from its definition: query n reads key m where ``m <=
    n`` and, under a window of W keys, ``m > n - W``."""
    n, m = queries[:, None], keys[None, :]
    ok = m <= n
    if window is not None:
        ok = ok & (m > n - window)
    return ok


def masked_attention(q, k, v, window: Optional[int] = None,
                     precision="f32", block: int = QUERY_BLOCK):
    """q [B, T, nkv, r, d] against k, v [B, T, nkv, d]: plain softmax
    attention under :func:`allowed`, a block of queries at a time
    against all the keys."""
    b, t, nkv, r, d = q.shape
    blk = block if 0 < block < t and t % block == 0 else t

    @jax.checkpoint
    def one(args):
        qb, lo = args
        s = d ** -0.5 * _ein("bnhrd,bmhd->bhrnm", qb, k, precision)
        ok = allowed(lo + jnp.arange(blk), jnp.arange(t), window)
        p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
        return _ein("bhrnm,bmhd->bnhrd", p, v, precision)

    qs = jnp.moveaxis(q.reshape(b, t // blk, blk, nkv, r, d), 1, 0)
    o = lax.map(one, (qs, jnp.arange(0, t, blk)))
    return jnp.moveaxis(o, 0, 1).reshape(b, t, nkv, r, d)


def attention(x, p, fw, precision="f32", fault=None):
    """x [B, T, H] -> [B, T, nh * d]."""
    b, t, _ = x.shape
    nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
    d = int(fw["head_size"])
    window = None if fault == "no_window" else fw.get("window")
    inv_freq, scale = rope_law(fw["rope"], d, fault)

    def heads(w, n):
        return _ein("bth,hk->btk", x, p[w], precision).reshape(b, t, n, d)

    q = rotate(heads("wq", nh), inv_freq, scale)
    k = rotate(heads("wk", nkv), inv_freq, scale)
    o = masked_attention(q.reshape(b, t, nkv, nh // nkv, d), k,
                         heads("wv", nkv), window, precision)
    return o.reshape(b, t, nh * d)


def moe(x, p, fw, precision="f32", fault=None, held=None):
    """x [B, T, H] -> [B, T, H]: the held experts' part of the routed
    sum (``held`` = (first, count) overrides the layer's own share: the
    shares-add-up test); there is no shared expert."""
    first, count = held if held is not None else (
        int(fw["first_held"]), int(fw["experts_held"]))
    if fault == "expert_out":
        count -= 1
    top_i, top_w = moe_routing(x, p, fw, precision, fault)

    @jax.checkpoint
    def one(y, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(top_i == e, top_w, 0.0), -1)
        return y + w_e[..., None] * swiglu_mlp(x, wg, wu, wd,
                                               precision), None

    y, _ = lax.scan(one, jnp.zeros_like(x),
                    (first + jnp.arange(count), p["w_gate"][:count],
                     p["w_up"][:count], p["w_down"][:count]))
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
    if kind == "attention":
        return attention(x, p, fw, precision, fault)
    if kind == "moe":
        return moe(x, p, fw, precision, fault)
    if kind == "lm_head":
        logits = _ein("bth,hk->btk", x, p["weights"], precision)
        return logits.reshape(x.shape[:2] + (int(fw["n_pred_heads"]),
                                             int(fw["vocab_size"])))
    raise ValueError(f"reference: unknown layer type {kind!r}")


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
    names = ("gain",) if cfg["type"] == "rmsnorm" \
        else MATRICES[cfg["type"]]
    return {n: weights if n in MATRICES[cfg["type"]] else bias
            for n in names}, bw.get("gradient_moment", 0.0)


class _Walker:
    """The jitted pieces of one configuration: an entry's forward and
    its vjp (whole, or over blocks of positions), what the tokens of
    an entry's ``moe`` layers chose, and the tail — the last norm and
    the head — with its loss.  Entries of one shape share one compiled
    program: parameters go in by position."""

    def __init__(self, layers, precision, fault, seq_block):
        self.flat = flatten(layers)
        self.entries = entries(layers)
        self.precision, self.fault = precision, fault
        self.seq_block = int(seq_block)
        self._jitted: Dict[Any, Any] = {}
        start = len(self.flat)
        for kind, idx in reversed(self.entries):
            if kind != "plain" or self.flat[idx[0]]["type"] not in \
                    ("rmsnorm", "dense", "lm_head"):
                break
            start = idx[0]
        #: flat index where the tail begins
        self.tail_start = start

    def _cached(self, what, e, make):
        kind, idx = self.entries[e]
        key = (what, kind) + tuple(
            self.flat[i]["type"] + repr(self.flat[i].get("->"))
            for i in idx)
        if key not in self._jitted:
            self._jitted[key] = jax.jit(make(kind, idx))
        return self._jitted[key]

    def _blocks(self, e, t):
        """Slices of positions an entry is walked in."""
        pointwise = all(self.flat[i]["type"] in POINTWISE
                        for i in self.entries[e][1])
        blk = self.seq_block if pointwise and 0 < self.seq_block < t \
            else t
        return [slice(lo, lo + blk) for lo in range(0, t, blk)]

    def _forward_of(self, kind, idx):
        return lambda ps, x: entry_forward(
            self.flat, idx, kind, dict(zip(idx, ps)), x,
            self.precision, self.fault)

    def forward(self, e, ps, x):
        fn = self._cached("fwd", e, self._forward_of)
        return jnp.concatenate(
            [fn(ps, x[:, s]) for s in self._blocks(e, x.shape[1])], 1)

    def backward(self, e, ps, x, err):
        """(d parameters, d input) of entry e at input x."""
        def make(kind, idx):
            fn = self._forward_of(kind, idx)

            def bwd(ps, x, err):
                if jnp.issubdtype(x.dtype, jnp.integer):
                    # ids take no gradient
                    _, vjp = jax.vjp(lambda ps: fn(ps, x), ps)
                    return vjp(err)[0], jnp.zeros((), jnp.float32)
                _, vjp = jax.vjp(fn, ps, x)
                return vjp(err)
            return bwd
        bwd = self._cached("bwd", e, make)
        dps, dxs = None, []
        for s in self._blocks(e, x.shape[1]):
            dp, dx = bwd(ps, x[:, s], err[:, s])
            dps = dp if dps is None else jax.tree.map(jnp.add, dps, dp)
            dxs.append(dx)
        return dps, (jnp.concatenate(dxs, 1) if dxs[0].ndim else None)

    def choices(self, e, ps, x):
        """{flat index: ids [B, T, top_k]} of the ``moe`` layers of
        entry e at input x: what each token chose."""
        where = [i for i in self.entries[e][1]
                 if self.flat[i]["type"] == "moe"]
        if not where:
            return {}

        def make(kind, idx):
            def fn(ps, x):
                out, y = [], x
                for i, p in zip(idx, ps):
                    if i in where:
                        out.append(moe_routing(
                            y, p, self.flat[i]["->"], self.precision,
                            self.fault)[0])
                    y = layer_forward(self.flat[i], p, y,
                                      self.precision, self.fault)
                return out
            return fn
        fn = self._cached("choices", e, make)
        got = [fn(ps, x[:, s]) for s in self._blocks(e, x.shape[1])]
        return {i: np.concatenate([np.asarray(g[j]) for g in got], 1)
                for j, i in enumerate(where)}

    def head(self, ps, x, ids, count):
        """(summed loss, d parameters, d input) of the tail under the
        mean loss over ``count``, in blocks of positions."""
        flat, prec = self.flat, self.precision
        idx = list(range(self.tail_start, len(flat)))
        if "head" not in self._jitted:
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
            self._jitted["head"] = jax.jit(bwd)
        targets, valid = targets_of(
            ids, int(flat[-1]["->"]["n_pred_heads"]))
        t = x.shape[1]
        blk = self.seq_block if 0 < self.seq_block < t else t
        total, dps, dxs = 0.0, None, []
        for lo in range(0, t, blk):
            s, dp, dx = self._jitted["head"](
                ps, x[:, lo:lo + blk], targets[:, lo:lo + blk],
                valid[:, lo:lo + blk], jnp.float32(count))
            total = total + s
            dps = dp if dps is None else jax.tree.map(jnp.add, dps, dp)
            dxs.append(dx)
        return total, dps, jnp.concatenate(dxs, axis=1)


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
    rows = np.asarray(rows)
    if fault == "half_batch":
        rows = rows[:, :rows.shape[1] // 2]
    # the state lives on the HOST; only the entry being walked is on
    # the device
    params = [{k: np.asarray(v) for k, v in p.items()} for p in params0]
    del params0
    w0 = None if make_w0 is not None else \
        [{k: np.array(v) for k, v in p.items()} for p in params]
    vel = [{k: np.zeros_like(v) for k, v in p.items()} for p in params]
    n_pred = int(flat[-1]["->"]["n_pred_heads"])
    body = [e for e, (_, idx) in enumerate(ents)
            if idx[0] < wk.tail_start]
    tail_idx = [i for _, idx in ents for i in idx if i >= wk.tail_start]
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

    for t, ids in enumerate(rows):
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
