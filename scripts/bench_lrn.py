"""LRN implementation shootout on the real chip: XLA banded-matmul
form (ops/lrn.py) vs the single-pass pallas kernels
(ops/lrn_pallas.py), forward and forward+backward, at AlexNet's two
LRN shapes.

Measured verdict (v5e, 2026-07-30, recorded in docs/perf.md): XLA wins
at these shapes — the pallas path stays opt-in
(VELES_TPU_LRN_PALLAS=1).

Timing method: chained calls (each consumes the previous output) ended
by a small data-FETCH of the result — a device->host fetch of bytes
that depend on the computation cannot return early (same barrier as
bench.py's honesty contract; ``block_until_ready`` was re-tested on
the directly attached chip in PR 21, tests_tpu TestHonestBarrier).
"""

import sys
import time

import numpy as np

sys.path.insert(0, ".")


def sync(a):
    return np.asarray(a[(0,) * (a.ndim - 1)])  # data-dependent fetch


def timeit_chain(fn, x, reps=20):
    out = fn(x)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(out)
    sync(out)
    return (time.perf_counter() - t0) / reps


def main():
    import jax
    import jax.numpy as jnp
    from veles_tpu.ops import lrn as lrn_mod
    from veles_tpu.ops import lrn_pallas

    mb = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    u = lrn_mod.LRNormalizer(alpha=1e-4, beta=0.75, n=5, k=2.0)
    gd = lrn_mod.GDLRNormalizer(forward=u)
    rng = np.random.default_rng(0)
    for (h, w, c) in ((55, 55, 96), (27, 27, 256)):
        shape = (mb, h, w, c)
        x = jnp.asarray(rng.standard_normal(shape, np.float32),
                        jnp.bfloat16)

        fwd_xla = jax.jit(
            lambda v: u.apply_fwd({}, v)[0].astype(v.dtype))
        fwd_pl = jax.jit(
            lambda v: lrn_pallas.lrn_fwd(v, u.n, u.k, u.alpha))

        @jax.jit
        def fb_xla(v):
            y, res = u.apply_fwd({}, v)
            ei, _ = gd.backward_from_saved({}, res, y)
            return ei.astype(v.dtype)

        @jax.jit
        def fb_pl(v):
            # feed the forward's OUTPUT to the backward as the error
            # signal: a data dependency, so jit cannot dead-code-
            # eliminate the side-effect-free forward pallas_call (an
            # earlier version discarded y and timed the backward only)
            y = lrn_pallas.lrn_fwd(v, u.n, u.k, u.alpha)
            ei = lrn_pallas.lrn_bwd(v, y, u.n, u.k, u.alpha)
            return ei.astype(v.dtype)

        # numerics check at bf16 tolerance before timing
        d = jnp.max(jnp.abs(fwd_xla(x).astype(jnp.float32)
                            - fwd_pl(x).astype(jnp.float32)))
        assert float(d) < 0.05, float(d)

        for name, f in (("xla fwd", fwd_xla), ("pallas fwd", fwd_pl),
                        ("xla f+b", fb_xla), ("pallas f+b", fb_pl)):
            t = timeit_chain(f, x)
            print(f"{shape} {name:12s}: {t * 1e3:7.3f}ms")


if __name__ == "__main__":
    main()
