"""What the benchmark makes from ``--seed``: the dataset and the
initial weights, each in ONE jitted call on the device.  Both are
pure functions of the seed, so the reference regenerates the very same
values after the program's state is freed — nothing the program made
reaches the reference.

Rows are addressable: row ``r`` is a function of ``fold_in(key(seed),
r)`` alone, so ``dataset_rows`` gives any subset without making the
whole set again.
"""

from __future__ import annotations

from functools import partial
from math import prod, sqrt
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp

ROW_BLOCK = 256
DATA_STREAM, WEIGHT_STREAM, DROPOUT_STREAM = 1, 2, 3


def stream_seed(seed: int, stream: int) -> int:
    """A 31-bit seed of one of the benchmark's streams (``--seed`` may
    need more than 32 signed bits; every key is built from this)."""
    return (int(seed) * 2654435761 + stream * 40503) % (2 ** 31 - 1)


def _templates(key, n_classes, shape):
    h, w, c = shape
    coarse = jax.random.normal(
        key, (n_classes, max(2, h // 4), max(2, w // 4), c), jnp.float32)
    return jax.image.resize(coarse, (n_classes, h, w, c), "bilinear")


@partial(jax.jit, static_argnames=("shape", "n_classes", "noise",
                                   "max_shift"))
def _rows(seed, rows, shape, n_classes, noise, max_shift):
    key = jax.random.key(seed)
    h, w, c = shape
    # an image is made as (H, W*C): with C = 3 innermost the chip's
    # (8, 128) tiles are 3/128 full, and 8192 AlexNet rows took 7.1 s
    # instead of 0.32 s (chip run, PR 24)
    templates = _templates(jax.random.fold_in(key, 2 ** 30), n_classes,
                           shape).reshape(n_classes, h, w * c)

    def one(r):
        ky, ks, kn = jax.random.split(jax.random.fold_in(key, r), 3)
        y = jax.random.randint(ky, (), 0, n_classes, jnp.int32)
        sh = jax.random.randint(ks, (2,), -max_shift, max_shift + 1)
        img = jnp.roll(templates[y], (sh[0], sh[1] * c), axis=(0, 1))
        g = jax.random.normal(kn, (h, w * c), jnp.float32)
        return jax.nn.sigmoid(img + jnp.float32(noise) * g), y

    # in blocks written into one buffer in place: a vmap over every
    # row at once holds a rolled copy of the whole set beside it
    # (10 GB at AlexNet's 8192 rows), and the set-up's peak would
    # hide the train step's
    n = rows.shape[0]
    blk = max(b for b in range(1, min(n, ROW_BLOCK) + 1) if n % b == 0)

    def body(i, out):
        x, y = jax.vmap(one)(jax.lax.dynamic_slice(rows, (i * blk,),
                                                   (blk,)))
        return (jax.lax.dynamic_update_slice(out[0], x, (i * blk, 0, 0)),
                jax.lax.dynamic_update_slice(out[1], y, (i * blk,)))

    x, y = jax.lax.fori_loop(
        0, n // blk, body,
        (jnp.zeros((n, h, w * c), jnp.float32),
         jnp.zeros((n,), jnp.int32)))
    return x.reshape((n,) + tuple(shape)), y


def dataset_rows(seed: int, rows, shape: Sequence[int], n_classes: int,
                 noise: float, max_shift: int):
    """(data f32 (len(rows), *shape) in (0, 1), labels int32) of the
    given row numbers of the seed's dataset."""
    return _rows(stream_seed(seed, DATA_STREAM),
                 jnp.asarray(rows, jnp.int32), tuple(shape),
                 int(n_classes), float(noise), int(max_shift))


def dataset(seed: int, n: int, shape, n_classes, noise, max_shift):
    return dataset_rows(seed, jnp.arange(n, dtype=jnp.int32), shape,
                        n_classes, noise, max_shift)


def _init(seed, spec):
    key = jax.random.key(seed)
    out = []
    for i, layer in enumerate(spec):
        p = {}
        for name, shape in layer:
            if name == "weights":
                std = sqrt(2.0 / prod(shape[:-1]))
                p[name] = std * jax.random.normal(
                    jax.random.fold_in(key, i), shape, jnp.float32)
            else:
                p[name] = jnp.zeros(shape, jnp.float32)
        out.append(p)
    return out


_init_jit = jax.jit(_init, static_argnums=1)


def init_params(seed: int, layer_rows: List[Dict[str, Any]]
                ) -> List[Dict[str, Any]]:
    """He-normal weights (std sqrt(2 / fan_in), fan_in = every axis but
    the last) and zero biases for the rows of ``flops.layer_shapes``, f32, one dict per
    layer (empty where the layer has no parameters).  One jitted call;
    the outputs are committed to the default device."""
    spec = tuple(tuple(sorted((k, tuple(v)) for k, v in
                              r["params"].items()))
                 for r in layer_rows)
    dev = jax.devices()[0]
    params = _init_jit(jnp.uint32(stream_seed(seed, WEIGHT_STREAM)),
                       spec)
    return jax.device_put(params, dev)


def dropout_seed(seed: int) -> int:
    """The dropout stream's seed: ONE for every ``--seed``.  The program
    bakes its stream's seed into the step program as a constant
    (``engine/core.py`` ``jax.random.key(seed)``), so a stream that
    followed ``--seed`` would compile the step anew in every run (25 s,
    chip run of PR 24) and never find it in the cache.  Weights, data
    and row order follow ``--seed``; the masks are a function of the
    step and the layer alone."""
    del seed
    return stream_seed(0, DROPOUT_STREAM)


def dropout_mask(seed: int, step, layer_index: int, keep: float,
                 shape) -> Any:
    """The mask the configuration states for dropout layer
    ``layer_index`` at optimiser step ``step`` (counted from 0):
    ``bernoulli(fold_in(fold_in(key(dropout_seed), step), layer), keep)``
    scaled by 1/keep — the repo-wide dropout contract written in
    ``engine/core.py``; the harness hands the program ``dropout_seed``
    as its ``fused`` stream seed."""
    k = jax.random.fold_in(
        jax.random.fold_in(jax.random.key(dropout_seed(seed)), step),
        layer_index)
    return jax.random.bernoulli(k, keep, tuple(shape)) \
        .astype(jnp.float32) / keep
