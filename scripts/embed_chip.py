"""Time the token embedding's forms on the chip, alone, at the three LM
cells' shapes (32 768 ids a step; the table f32, looked up in bf16):

    python scripts/embed_chip.py [mellum2|qwen3next|laguna ...]  # on a TPU

Prints one JSON line a shape and part: milliseconds (median of 7 after
a warm call) of the lookup (``onehot``: the one-hot product, as the
parent ran it; ``gather``: ``ops/sequence.py`` ``take_rows``), of the
table's gradient from the ids and the bf16 error rows (``onehot``: the
transposed product; ``scatter``: XLA's scatter-add in f32;
``segment_sum``: ``ops/embed_pallas.py`` at each of ``TILES``, and at
the first of them with every id the same), and of a whole step of each
form (lookup, gradient, the f32 table's SGD update), with the
compiler's temporaries of that step and the worst gap of a form's
gradient from the one-hot's, relative to its largest element.  A tool
for choosing the form; the benchmark's own metric of the step is
``lm*.step_mfu_pct``.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from veles_tpu.engine.core import donating_jit  # noqa: E402
from veles_tpu.ops import embed_pallas, sequence  # noqa: E402

IDS = 4 * 8192
#: (vocabulary rows held, hidden) of each cell
SHAPES = {"mellum2": (24576, 2304), "qwen3next": (18992, 2048),
          "laguna": (12544, 3072)}
TILES = [embed_pallas.SegmentTiles(256, 256),
         embed_pallas.SegmentTiles(256, 128),
         embed_pallas.SegmentTiles(512, 256),
         embed_pallas.SegmentTiles(128, 512)]


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(7):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(ms), out


def gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def onehot(w, ids):
    return jnp.einsum("btv,vh->bth",
                      jax.nn.one_hot(ids, w.shape[0], dtype=w.dtype), w)


def onehot_grad(ids, err, vocab):
    return jax.vjp(lambda w: onehot(w, ids),
                   jnp.zeros((vocab, err.shape[-1]), err.dtype))[1](err)[0]


def step_of(form):
    def step(table, ids, err):
        w = table.astype(jnp.bfloat16)
        f = (lambda w: onehot(w, ids)) if form == "onehot" else \
            (lambda w: sequence.gather_embed(w, ids, form))
        out, back = jax.vjp(f, w)
        (dw,) = back(err)
        return out, table - 0.01 * dw.astype(jnp.float32)
    return donating_jit(step, donate=(0,))


def main(names):
    for name in names:
        vocab, hidden = SHAPES[name]
        key = jax.random.key(44)
        table = jax.random.normal(key, (vocab, hidden), jnp.float32)
        ids = jax.random.randint(jax.random.fold_in(key, 1), (4, 8192), 0,
                                 vocab, jnp.int32)
        err = jax.random.normal(jax.random.fold_in(key, 2),
                                (4, 8192, hidden), jnp.bfloat16)
        w = table.astype(jnp.bfloat16)
        line = {"shape": name, "vocab": vocab, "hidden": hidden,
                "ids": IDS, "path": sequence.embed_path(
                    vocab, hidden, IDS, jax.default_backend())}
        ms1, y1 = timed(jax.jit(onehot), w, ids)
        ms2, y2 = timed(jax.jit(sequence.take_rows), w, ids)
        line["lookup_ms"] = {"onehot": ms1, "gather": ms2}
        line["lookup_equal"] = bool(jnp.array_equal(y1, y2))
        print(json.dumps(line), flush=True)

        grads = {"part": "gradient", "shape": name}
        ms, want = timed(jax.jit(onehot_grad, static_argnums=2), ids,
                         err, vocab)
        grads["onehot_ms"] = ms
        ms, got = timed(jax.jit(sequence.scatter_rows, static_argnums=2),
                        ids, err, vocab)
        grads["scatter_ms"], grads["scatter_gap"] = ms, gap(got, want)
        for tiles in TILES:
            ms, got = timed(lambda i, e: embed_pallas.embed_segment_sum(
                i, e, vocab, tiles), ids, err)
            grads["segment_sum_%d_%d_ms" % tiles] = ms
            grads["segment_sum_%d_%d_gap" % tiles] = gap(got, want)
        same = jnp.full_like(ids, vocab // 3)
        ms, got = timed(lambda i, e: embed_pallas.embed_segment_sum(
            i, e, vocab, TILES[0]), same, err)
        want = onehot_grad(same, err, vocab)
        grads["segment_sum_all_equal_ms"] = ms
        grads["segment_sum_all_equal_gap"] = gap(got, want)
        print(json.dumps(grads), flush=True)

        steps = {"part": "step", "shape": name}
        for form in ("onehot", "scatter", "segment_sum"):
            fn = step_of(form)
            temp = fn.lower(table, ids, err).compile().memory_analysis() \
                .temp_size_in_bytes
            out = jax.block_until_ready(fn(table.copy(), ids, err))
            ms = []
            t = out[1]
            for _ in range(7):
                t0 = time.perf_counter()
                t = jax.block_until_ready(fn(t, ids, err)[1])
                ms.append(1e3 * (time.perf_counter() - t0))
            steps[form] = {"ms": statistics.median(ms), "temp_bytes": temp}
        print(json.dumps(steps), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:] or list(SHAPES))
