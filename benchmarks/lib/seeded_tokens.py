"""What the benchmark makes from ``--seed`` for a configuration over a
vocabulary of token ids: the rows of packed documents, in one jitted
call on the device, a pure function of the seed — the reference
regenerates the very same rows after the program's state is freed.
The chain is ``lib/seeded_seq.py``'s, over ``n_values`` ids instead of
256 bytes.  (The weights are ``reference_qwen3next.init_params``: a
leaf is a function of seed, layer and name.)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .reference_qwen3next import stream_seed

DATA_STREAM = 1
LANES = 64


@partial(jax.jit, static_argnames=("n_rows", "seq_len", "n_values",
                                   "median_len", "separator"))
def _rows(seed, n_rows, seq_len, n_values, median_len, sigma, separator):
    key = jax.random.key(seed)
    k_perm, k_step, k_start, k_len = jax.random.split(key, 4)
    total = n_rows * seq_len
    assert total % LANES == 0, (total, LANES)
    # x[t+1] = (perm[x[t]] + e[t]) mod n_values, e geometric(0.35): an
    # order-1 Markov chain with a few likely successors an id.  LANES
    # chains side by side, each a stretch of the stream (the chain
    # restarts there), so the scan is total / LANES steps
    perm = jax.random.permutation(k_perm, n_values)
    u = jax.random.uniform(k_step, (total // LANES, LANES),
                           minval=1e-7, maxval=1.0)
    steps = jnp.minimum(jnp.floor(jnp.log(u) / jnp.log(0.65)),
                        n_values - 1).astype(jnp.int32)
    start = jax.random.randint(k_start, (LANES,), 0, n_values)

    def body(x, e):
        return (perm[x] + e) % n_values, x

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


def dataset(seed: int, n_rows: int, seq_len: int, n_values: int,
            separator: int, median_len: int = 1024, sigma: float = 1.2):
    """int32 ``[n_rows, seq_len]``: a stream of documents (log-normal
    lengths, median ``median_len`` tokens; ids ``0 .. n_values - 1``
    from a seeded order-1 Markov chain; one ``separator`` id after
    each) cut into rows with no regard to document boundaries."""
    return _rows(jnp.uint32(stream_seed(seed, DATA_STREAM)), int(n_rows),
                 int(seq_len), int(n_values), int(median_len),
                 float(sigma), int(separator))
