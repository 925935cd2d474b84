"""The plain reference: an interpreter of a ``layers`` list in
``jax.numpy`` float32 at matmul precision "highest" — forward, softmax
cross-entropy, gradients by ``jax.grad``, SGD with momentum and weight
decay.  It serves every configuration whose layer types it knows and
imports nothing of the program.

``precision`` selects what stands in the program's place for the
control: "f32" is the reference itself; "fp8" casts the operands of
every convolution and matmul, and the gradient that flows back through
them, to float8_e4m3 as they are — the program's own recipe (plain
casts; the error signal of the MEAN loss, at 1/minibatch scale) with
float8 where the configurations state bfloat16, which is what flipping
the program's ``compute_dtype`` would compute.  "bf16" rounds the same
operands to bfloat16 (a witness on the program's side), "fp8_scaled"
is an fp8 recipe with per-tensor scales (e4m3 operands, e5m2
gradients); PERF.md has what each reads.  ``fault`` plants
one of the faults a training cell can have into the same arithmetic.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import flops, seeded


def _scaled_cast(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8_scaled(x):
    return _scaled_cast(x, jnp.float8_e4m3fn, 448.0)


_fp8_scaled.defvjp(
    lambda x: (_fp8_scaled(x), None),
    lambda _, g: (_scaled_cast(g, jnp.float8_e5m2, 57344.0),))


def _q(x, precision):
    if precision == "f32":
        return x
    if precision == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        # the same program with float8 where bfloat16 stood: the cast's
        # transpose rounds the gradient that flows back to e4m3 too
        return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    if precision == "fp8_scaled":
        return _fp8_scaled(x)
    raise ValueError(precision)


def forward(layers, params, x, masks, precision="f32"):
    """Probabilities (B, n_classes) of the net on ``x`` (B, H, W, C);
    ``masks[i]`` is dropout layer i's scaled keep mask."""
    hi = lax.Precision.HIGHEST
    for i, cfg in enumerate(layers):
        kind, fw, p = cfg["type"], cfg.get("->", {}), params[i]
        if kind == "conv_relu":
            py, px = flops.pair(fw.get("padding", 0))
            x = lax.conv_general_dilated(
                _q(x, precision), _q(p["weights"], precision),
                window_strides=flops.pair(fw.get("sliding", 1)),
                padding=((py, py), (px, px)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=hi) + p["bias"]
            x = jnp.maximum(x, 0.0)
        elif kind == "norm":
            n, half = int(fw.get("n", 5)), int(fw.get("n", 5)) // 2
            sq = jnp.pad(x * x, ((0, 0),) * 3 + ((half, n - 1 - half),))
            c = x.shape[-1]
            win = sum(sq[..., j:j + c] for j in range(n))
            x = x * (fw.get("k", 2.0) + fw.get("alpha", 1e-4) * win) \
                ** (-fw.get("beta", 0.75))
        elif kind == "max_pooling":
            ky, kx = int(fw.get("ky", 2)), int(fw.get("kx", 2))
            sy, sx = flops.pair(fw["sliding"]) if fw.get("sliding") \
                is not None else (ky, kx)
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, ky, kx, 1),
                                  (1, sy, sx, 1), "VALID")
        elif kind in ("all2all_relu", "softmax"):
            x = jnp.dot(_q(x.reshape(x.shape[0], -1), precision),
                        _q(p["weights"], precision),
                        precision=hi) + p["bias"]
            x = jnp.maximum(x, 0.0) if kind == "all2all_relu" \
                else jax.nn.softmax(x, axis=-1)
        elif kind == "dropout":
            x = x * masks[i]
        else:
            raise ValueError(f"reference: unknown layer type {kind!r}")
    return x


def _loss_sum(layers, params, x, y, masks, precision):
    p = forward(layers, params, x, masks, precision)
    py = jnp.take_along_axis(p, y[:, None], axis=-1)[:, 0]
    return -jnp.sum(jnp.log(jnp.maximum(py, 1e-12)))


def _rates(cfg):
    bw = cfg.get("<-", {})
    lr = bw.get("learning_rate", 0.01)
    return {"weights": (lr, bw.get("weight_decay", 0.0)),
            "bias": (bw.get("learning_rate_bias", lr),
                     bw.get("weight_decay_bias", 0.0))}, \
        bw.get("gradient_moment", 0.0)


def make_step(layers, seed: int, precision="f32",
              fault: Optional[str] = None, block_rows: int = 0):
    """``step(params, vel, x, y, t) -> (params, vel, loss_sum, grads)``
    for one minibatch at optimiser step ``t``: mean cross-entropy
    gradient, ``g += wd * w``, ``v = m v - lr g``, ``w += v``.

    ``block_rows`` > 0 sums the gradient over blocks of that many rows
    so the f32 activations fit beside nothing else.  ``fault``:
    "half_batch" leaves the second half of the rows out and takes the
    mean over the rest; "state_unchanged" returns the state as given.
    """
    drops = {i: (1.0 - cfg["->"].get("dropout_ratio", 0.5))
             for i, cfg in enumerate(layers) if cfg["type"] == "dropout"}

    def mean_loss(p, x, y, m, n_rows):
        # the configuration's loss is the MEAN over the minibatch, so
        # the error signal flows back at 1/n_rows scale, as it does in
        # the program; in f32 that is the same gradient to the bit
        # where n_rows is a power of two
        s = _loss_sum(layers, p, x, y, m, precision)
        return s / n_rows, s

    vg = jax.value_and_grad(mean_loss, has_aux=True)

    def one_step(params, vel, x, y, t):
        mb = x.shape[0]
        n_rows = mb // 2 if fault == "half_batch" else mb
        # dropout masks of the WHOLE minibatch, as the configuration
        # states them; row blocks slice them
        widths = _dropout_widths(layers, x.shape[1:])
        masks = {i: seeded.dropout_mask(seed, t, i, keep,
                                        (mb,) + widths[i])
                 for i, keep in drops.items()}
        blk = block_rows if 0 < block_rows < n_rows else n_rows
        loss = jnp.float32(0.0)
        grads = jax.tree.map(jnp.zeros_like, params)
        for lo in range(0, n_rows, blk):
            hi = min(lo + blk, n_rows)
            (_, l), g = vg(params, x[lo:hi], y[lo:hi],
                           {i: m[lo:hi] for i, m in masks.items()},
                           n_rows)
            loss = loss + l
            grads = jax.tree.map(jnp.add, grads, g)
        if fault == "state_unchanged":
            return params, vel, loss, grads
        new_p, new_v = [], []
        for cfg, p, v, g in zip(layers, params, vel, grads):
            rates, moment = _rates(cfg)
            np_, nv = {}, {}
            for name in p:
                lr, wd = rates[name]
                gg = g[name] + wd * p[name]
                nv[name] = moment * v[name] - lr * gg
                np_[name] = p[name] + nv[name]
            new_p.append(np_)
            new_v.append(nv)
        return new_p, new_v, loss, grads

    return jax.jit(one_step)


def _dropout_widths(layers, sample_shape):
    return {r["index"]: tuple(r["out"])
            for r in flops.layer_shapes(layers, sample_shape)
            if r["kind"] == "dropout"}


def leaf_norms(tree) -> Dict[str, float]:
    """{"<layer>.<name>": l2 norm} of a list-of-dicts pytree."""
    out = {}
    for i, d in enumerate(tree):
        for name, a in d.items():
            out[f"{i}.{name}"] = jnp.sqrt(jnp.sum(jnp.square(
                a.astype(jnp.float32))))
    return {k: float(v) for k, v in jax.device_get(out).items()}


def follow(layers, seed: int, params0, xs, ys, precision="f32",
           fault=None, block_rows: int = 0) -> Dict[str, Any]:
    """Drive the reference through ``len(xs)`` steps from ``params0``
    (zero momentum).  Returns the readings the comparison uses: the sum
    of the steps' loss sums, per-leaf norms of the momentum and of the
    parameters' change after the last step, and of the first step's
    gradient."""
    step = make_step(layers, seed, precision, fault, block_rows)
    params = params0
    vel = jax.tree.map(jnp.zeros_like, params0)
    losses, g0 = [], None
    for t in range(len(xs)):
        params, vel, loss, grads = step(params, vel, xs[t], ys[t],
                                        jnp.int32(t))
        losses.append(float(loss))
        if t == 0:
            g0 = leaf_norms(grads)
        del grads
    delta = jax.tree.map(jnp.subtract, params, params0)
    return {"loss_sum": float(np.sum(losses)), "losses": losses,
            "momentum": leaf_norms(vel), "update": leaf_norms(delta),
            "grad0": g0}
