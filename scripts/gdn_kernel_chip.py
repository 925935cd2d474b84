"""Time the fused chunk-product kernels of the gated delta rule on the
chip, alone, at Qwen3-Next's published widths (16 key heads serving 32
value heads of 128, chunks of 64, bf16), against the XLA form — the
products' forward and forward + backward, and the whole chunked rule:

    chiprun -- python scripts/gdn_kernel_chip.py [T]

Prints one JSON line a variant: milliseconds a call (median of 5 after
a warm call), and for the fused form its worst gap from the XLA form
over the four results and the five cotangents.  A tool for tuning the
kernels; the benchmark's own metrics are ``gdn_roofline`` and
``gdn.busy_pct``.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from veles_tpu.ops import deltanet  # noqa: E402

HK, HV, D, CHUNK = 16, 32, 128, 64
CD = jnp.bfloat16


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(ms), out


def gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def main(argv):
    t = int(argv[0]) if argv else 32768
    assert jax.devices()[0].platform == "tpu", jax.devices()
    r = HV // HK
    ks = jax.random.split(jax.random.key(33), 9)
    unit = lambda a: a / jnp.linalg.norm(  # noqa: E731
        a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (1, t, HK, D))) / D ** 0.5
    k = unit(jax.random.normal(ks[1], (1, t, HK, D)))
    v = jax.random.normal(ks[2], (1, t, HV, D))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (1, t, HV))) * 0.2
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (1, t, HV)))
    do = jax.random.normal(ks[5], (1, t, HV, D), CD)
    parts = deltanet.chunk_parts(q, k, v, g, beta, CHUNK, CD)
    shapes = jax.eval_shape(lambda *a: deltanet.products_of(a), *parts)
    cots = tuple(jax.random.normal(kk, s.shape, s.dtype)
                 for kk, s in zip(ks[5:], shapes))
    path = deltanet.products_path("tpu", CHUNK, D, D, r)
    assert path["products"] == "fused", path
    seen = {}
    for name, tiles in (("xla", None), ("fused", path["tiles"])):
        def products(*a, tiles=tiles):
            return deltanet.products_of(a, tiles)

        def products_both(*a, products=products):
            out, back = jax.vjp(products, *a[:5])
            return out, back(a[5:])

        def rule(*a, tiles=tiles):
            return deltanet.rule_chunked(*a, CHUNK, CD, tiles)

        def rule_both(*a, rule=rule):
            out, back = jax.vjp(rule, *a[:5])
            return out, back(a[5])

        p_ms, _ = timed(jax.jit(products), *parts)
        pb_ms, made = timed(jax.jit(products_both), *parts, *cots)
        r_ms, _ = timed(jax.jit(rule), q, k, v, g, beta)
        rb_ms, ruled = timed(jax.jit(rule_both), q, k, v, g, beta, do)
        seen[name] = (made, ruled)
        line = {"variant": name, "T": t, "tiles": tiles and tuple(tiles),
                "products_forward_ms": round(p_ms, 3),
                "products_forward_backward_ms": round(pb_ms, 3),
                "rule_forward_ms": round(r_ms, 3),
                "rule_forward_backward_ms": round(rb_ms, 3),
                "device": jax.devices()[0].device_kind}
        if name == "fused":
            for what, mine, theirs in (
                    ("products", made, seen["xla"][0]),
                    ("rule", ruled, seen["xla"][1])):
                line[what + "_gaps"] = [
                    float("%.3g" % gap(a, b)) for a, b in zip(
                        jax.tree.leaves(mine), jax.tree.leaves(theirs))]
        print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
