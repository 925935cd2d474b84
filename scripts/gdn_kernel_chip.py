"""Time the Pallas kernels of the gated delta rule on the chip, alone,
at Qwen3-Next's published widths (16 key heads serving 32 value heads
of 128, chunks of 64, bf16), against the XLA forms:

    chiprun -- python scripts/gdn_kernel_chip.py [T] [HEADS:UNROLL ...]

Prints one JSON line a variant: milliseconds a call (median of 5 after
a warm call), and for a kernel form its worst gap from the XLA form.
``xla``: products and scan as XLA ops; ``fused``: the chunk-product
kernels (PR 33), the scan a ``lax.scan``; ``scan<H>u<U>``: both
kernel pairs, the scan kernels at H key heads a grid step, U a trip of
their loop over heads (default: the ``scan_tiles_for`` choice) — the
scan forward alone, the
scan forward + backward, and the whole rule both ways.  A tool for
tuning the kernels; the benchmark's own metrics are ``gdn_roofline``
and ``gdn.busy_pct``.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from veles_tpu.ops import deltanet, deltanet_pallas  # noqa: E402

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


def gaps(mine, theirs):
    return [float("%.3g" % gap(a, b)) for a, b in zip(
        jax.tree.leaves(mine), jax.tree.leaves(theirs))]


def main(argv):
    t = int(argv[0]) if argv else 32768
    assert jax.devices()[0].platform == "tpu", jax.devices()
    r = HV // HK
    ks = jax.random.split(jax.random.key(33), 10)
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
    scan = deltanet.scan_path("tpu", CHUNK, D, D, HK, r)
    assert scan["scan"] == "fused", scan
    choices = [deltanet_pallas.ScanTiles(*map(int, a.split(":")))
               for a in argv[1:]] or [scan["scan_tiles"]]
    made = jax.jit(lambda *a: deltanet.products_of(a))(*parts)
    scan_in = tuple(made) + parts[:2]
    do_c = jax.random.normal(ks[9], shapes[0].shape, CD)
    variants = [("xla", None, None), ("fused", path["tiles"], None)] + [
        (f"scan{c.heads}u{c.unroll}", path["tiles"], c) for c in choices]
    seen = {}
    for name, tiles, scan_tiles in variants:
        def rule(*a, tiles=tiles, scan_tiles=scan_tiles):
            return deltanet.rule_chunked(*a, CHUNK, CD, tiles,
                                         scan=scan_tiles)

        def rule_both(*a, rule=rule):
            out, back = jax.vjp(rule, *a[:5])
            return out, back(a[5])

        def scan_fwd(*a, scan_tiles=scan_tiles):
            return deltanet.scan_of(a[:4], a[4], a[5], scan_tiles)

        def scan_both(*a, scan_fwd=scan_fwd):
            out, back = jax.vjp(scan_fwd, *a[:6])
            return out, back(a[6])

        line = {"variant": name, "T": t, "tiles": tiles and tuple(tiles),
                "scan_tiles": scan_tiles and tuple(scan_tiles),
                "device": jax.devices()[0].device_kind}
        if name in ("xla", "fused"):
            def products(*a, tiles=tiles):
                return deltanet.products_of(a, tiles)

            def products_both(*a, products=products):
                out, back = jax.vjp(products, *a[:5])
                return out, back(a[5:])

            line["products_forward_ms"], _ = timed(jax.jit(products),
                                                   *parts)
            line["products_forward_backward_ms"], made_both = timed(
                jax.jit(products_both), *parts, *cots)
            if name == "xla":
                seen["products"] = made_both
            else:
                line["products_gaps"] = gaps(made_both, seen.pop(
                    "products"))
            del made_both
        if name != "xla":
            line["scan_forward_ms"], _ = timed(jax.jit(scan_fwd), *scan_in)
            line["scan_forward_backward_ms"], scanned = timed(
                jax.jit(scan_both), *scan_in, do_c)
            if name == "fused":
                # the kernels' da_qk is masked as the decayed scores are
                o, (du, dw, da, *rest) = scanned
                seen["scan"] = (o, (du, dw, jnp.tril(da), *rest))
            else:
                line["scan_gaps"] = gaps(scanned, seen["scan"])
            del scanned
        line["rule_forward_ms"], _ = timed(jax.jit(rule), q, k, v, g, beta)
        line["rule_forward_backward_ms"], ruled = timed(
            jax.jit(rule_both), q, k, v, g, beta, do)
        if name == "xla":
            seen["rule"] = ruled
        else:
            line["rule_gaps"] = gaps(ruled, seen["rule"])
        del ruled
        print(json.dumps({k: round(v, 3) if isinstance(v, float) else v
                          for k, v in line.items()}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
