"""What the benchmark makes from ``--seed`` for a sequence
configuration: the rows of packed byte documents, in one jitted call on
the device, a pure function of the seed — the reference regenerates
the very same rows after the program's state is freed.  (The weights
are ``reference_evabyte.init_params``: a leaf is a function of seed,
layer and name.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .reference_evabyte import stream_seed

DATA_STREAM = 1
LANES = 64


@partial(jax.jit, static_argnames=("n_rows", "seq_len", "median_len",
                                   "separator"))
def _rows(seed, n_rows, seq_len, median_len, sigma, separator):
    key = jax.random.key(seed)
    k_perm, k_step, k_start, k_len = jax.random.split(key, 4)
    total = n_rows * seq_len
    assert total % LANES == 0, (total, LANES)
    # x[t+1] = (perm[x[t]] + e[t]) mod 256, e geometric(0.35): an
    # order-1 Markov chain over 256 values with a few likely successors
    # a byte.  LANES chains side by side, each a stretch of the stream
    # (the chain restarts there), so the scan is total / LANES steps
    perm = jax.random.permutation(k_perm, 256)
    u = jax.random.uniform(k_step, (total // LANES, LANES),
                           minval=1e-7, maxval=1.0)
    steps = jnp.minimum(jnp.floor(jnp.log(u) / jnp.log(0.65)),
                        255).astype(jnp.int32)
    start = jax.random.randint(k_start, (LANES,), 0, 256)

    def body(x, e):
        return (perm[x] + e) & 255, x

    _, stream = jax.lax.scan(body, start, steps)
    stream = stream.T.reshape(total)
    # documents with log-normal lengths, one separator id after each
    n_docs = max(8, 4 * total // median_len)
    lengths = jnp.maximum(1, jnp.rint(jnp.exp(
        jnp.log(float(median_len))
        + sigma * jax.random.normal(k_len, (n_docs,))))).astype(jnp.int32)
    ends = jnp.cumsum(lengths + 1) - 1
    stream = stream.at[ends].set(separator, mode="drop")
    return stream.reshape(n_rows, seq_len).astype(jnp.int32)


def dataset(seed: int, n_rows: int, seq_len: int, median_len: int = 2048,
            sigma: float = 1.2, separator: int = 256):
    """int32 ``[n_rows, seq_len]``: a stream of documents (log-normal
    lengths, median ``median_len`` bytes, at sigma 1.2 one in a hundred
    past 32 kB; bytes from a seeded order-1 Markov chain over 256
    values; one ``separator`` id >= 256 after each) cut into rows with
    no regard to document boundaries."""
    return _rows(jnp.uint32(stream_seed(seed, DATA_STREAM)), int(n_rows),
                 int(seq_len), int(median_len), float(sigma),
                 int(separator))
