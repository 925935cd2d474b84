"""Time the fused EVA attention kernels on the chip, alone, at the
published widths (32 heads x 128, window 2048, chunk 16), against the
XLA form — one layer's forward, and forward + backward:

    chiprun -- python scripts/eva_kernel_chip.py [T] [q,k,r ...]

Each ``q,k,r`` is a tiling to try beside the one ``tiles_for`` picks
(queries a grid step, local keys a tile, summaries a tile).  Prints one
JSON line a variant: milliseconds a call (median of 5 after a warm
call) and the share of the v5e's bf16 peak that the forward's useful
matmul FLOPs make (the XLA form timed is the one off the chip's
fused path: ``eva_rows``, ``jax.checkpoint`` and tie included).  A tool for choosing tiles; the benchmark's own
metric is ``eva_attention_roofline``.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, ".")
from benchmarks.lib import peaks  # noqa: E402
from veles_tpu.ops import eva_pallas, sequence  # noqa: E402

NH, D, WIN, CHUNK = 32, 128, 2048, 16


def xla_rows(q, k, v, ks, vs):
    """The form off the fused path, ``jax.checkpoint`` and tie included."""
    return sequence.eva_rows(q, k, v, ks, vs, WIN, CHUNK)


def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ms.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(ms)


def main(argv):
    t = int(argv[0]) if argv else 32768
    assert jax.devices()[0].platform == "tpu", jax.devices()
    peak = peaks.peaks_for(jax.devices()[0].device_kind)["flops_bf16"]
    keys = jax.random.split(jax.random.key(29), 6)
    q, k, v, do = (jax.random.normal(keys[i], (1, t, NH, D),
                                     jnp.bfloat16) for i in range(4))
    ks, vs = (jax.random.normal(keys[i], (1, t // CHUNK, NH, D),
                                jnp.bfloat16) for i in (4, 5))
    n_keys = t * ((WIN + 1) / 2 + (WIN // CHUNK) * (t // WIN - 1) / 2)
    flops = 4.0 * NH * D * n_keys
    auto = eva_pallas.tiles_for(D, WIN, CHUNK, t)
    variants = {"xla": xla_rows, f"fused{tuple(auto)}": auto}
    for arg in argv[1:]:
        bq, bk, rb = (int(n) for n in arg.split(","))
        tiles = eva_pallas.Tiles(bq, bk, rb, eva_pallas.LANES)
        variants[f"fused{tuple(tiles)}"] = tiles
    for name, how in variants.items():
        fwd = how if callable(how) else (
            lambda q, k, v, ks, vs, tiles=how: eva_pallas.eva_fused(
                q, k, v, ks, vs, WIN, CHUNK, tiles))

        def both(q, k, v, ks, vs, do, fwd=fwd):
            return jax.vjp(fwd, q, k, v, ks, vs)[1](do)

        try:
            f_ms = timed(jax.jit(fwd), q, k, v, ks, vs)
            fb_ms = timed(jax.jit(both), q, k, v, ks, vs, do)
        except Exception as e:  # noqa: BLE001 — a tiling the compiler refuses
            print(json.dumps({"variant": name,
                              "error": repr(e)[:300]}), flush=True)
            continue
        print(json.dumps({
            "variant": name, "T": t, "forward_ms": round(f_ms, 3),
            "forward_backward_ms": round(fb_ms, 3),
            "forward_share_of_peak_pct":
                round(100 * flops / peak / (f_ms / 1e3), 2),
            "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
