"""Named, seeded pseudo-random generator streams.

Reference parity: veles/prng/random_generator.py — ``prng.get(name)``
returns a named deterministic stream; seeds come from the CLI so runs
are reproducible.

TPU-first design: each stream owns BOTH a numpy ``Generator`` (for
host-side work: shuffling, weight init on the numpy backend) and a JAX
PRNG key chain (for traced stochastic ops: dropout, stochastic pooling).
``stream.next_key()`` splits deterministically, and the key counter is
part of snapshot state so resume continues the exact stream.

``jax`` is imported inside the key methods, not here: the fleet
router, the GA parent and the supervisor reach this module through
``veles_tpu.launcher`` and must stay off the device — a process that
never loads jax cannot take the chip from the child that needs it.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np


class RandomStream:
    def __init__(self, name: str, seed: int) -> None:
        self.name = name
        self.seed = seed
        self.numpy: np.random.Generator = np.random.default_rng(seed)
        self._key_counter = 0

    def next_key(self) -> "jax.Array":
        """Deterministic JAX key #N of this stream (N increments)."""
        import jax
        k = jax.random.fold_in(jax.random.key(self.seed), self._key_counter)
        self._key_counter += 1
        return k

    def key_at(self, counter: int) -> "jax.Array":
        """Key for an explicit counter (used inside jitted steps where the
        counter is threaded as traced state)."""
        import jax
        return jax.random.fold_in(jax.random.key(self.seed), counter)

    # -- snapshot support ---------------------------------------------

    def __getstate__(self) -> dict:
        return {
            "name": self.name,
            "seed": self.seed,
            "numpy_state": self.numpy.bit_generator.state,
            "key_counter": self._key_counter,
        }

    def __setstate__(self, state: dict) -> None:
        self.name = state["name"]
        self.seed = state["seed"]
        self.numpy = np.random.default_rng(self.seed)
        self.numpy.bit_generator.state = state["numpy_state"]
        self._key_counter = state["key_counter"]


_streams: Dict[str, RandomStream] = {}
_default_seed = 1234


def seed_all(seed: int) -> None:
    """Set the base seed and reset every existing stream (CLI --seed)."""
    global _default_seed
    _default_seed = seed
    names = list(_streams)
    _streams.clear()
    for n in names:
        get(n)


def get(name: str = "default", seed: Optional[int] = None) -> RandomStream:
    """The named stream, created on first use.

    Per-stream seeds derive from the base seed and the stream name, so
    streams are independent but fully determined by (base seed, name).
    """
    if name not in _streams:
        if seed is None:
            h = 14695981039346656037
            for ch in name.encode():
                h = ((h ^ ch) * 1099511628211) % (2**64)
            seed = (_default_seed ^ h) % (2**63)
        _streams[name] = RandomStream(name, seed)
    return _streams[name]


def snapshot_state() -> Dict[str, dict]:
    return {n: s.__getstate__() for n, s in _streams.items()}


def restore_state(state: Dict[str, dict]) -> None:
    for n, st in state.items():
        s = RandomStream.__new__(RandomStream)
        s.__setstate__(st)
        _streams[n] = s
