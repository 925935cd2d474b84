"""Time the sliding-window attention core on the chip, alone, at the
benchmark's two window shapes (four rows of 8 192, head size 128,
bf16): ``laguna`` (a window of 512 keys, 72 query heads over 8 key
heads) and ``mellum2`` (1 024 keys, 32 over 4):

    python scripts/attn_window_chip.py [laguna|mellum2 ...]   # on a TPU

Prints one JSON line a variant: milliseconds a layer (median of 5
after a warm call) of the forward and of forward + backward — the
arrays in the projections' layout ``[rows, T, heads * d]``, as the
attention layer hands them over, so splash's transposes are in its
time and the band kernels have none — and the worst gap of o, dq, dk,
dv from the shipped splash kernel's at 512 x 512 tiles.  Variants: ``splash512`` (the splash kernel under a
``LocalMask``, the parent of the band kernels), ``splash256``, and
``band128`` / ``band256`` (``ops/attention_pallas.py`` at that query
block).  Then, a shape, the fit of the forward's times at the two
blocks to ``grid steps x (fixed cost + a step's FLOPs / rate)``: the
fixed cost of a grid step that ``attention_pallas.band_tiles`` weighs
(``STEP_SECONDS``), and the block it chooses.  A tool for tuning the
kernels; the benchmark's own metrics are ``window_attention_roofline``
and ``window_attention.busy_pct``.
"""

import json
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from veles_tpu.ops import attention, attention_pallas  # noqa: E402

ROWS, T, D = 4, 8192, 128
#: (key heads, query heads a key head, window)
SHAPES = {"laguna": (8, 9, 512), "mellum2": (4, 8, 1024)}


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


def core_of(variant, nkv, group, window):
    """The core function of a variant over the projections' layout, as
    the attention layer calls it: q ``[rows, T, heads * d]``, k, v
    ``[rows, T, key heads * d]`` -> o like q."""
    kind, block = variant[:-3], int(variant[-3:])
    tiles = attention_pallas.BandTiles(block, window + block)

    def core(q, k, v):
        q5 = q.reshape(ROWS, T, nkv, group, D)
        k4, v4 = (a.reshape(ROWS, T, nkv, D) for a in (k, v))
        o = attention.core_splash(q5, k4, v4, block, window) \
            if kind == "splash" else attention_pallas.band_attention(
                q5, k4, v4, window, tiles)
        return o.reshape(q.shape)
    return core


def fit(times, nkv, group, window):
    """(fixed cost of a grid step in seconds, MXU rate in FLOP/s) from
    the band forward's milliseconds at the two blocks."""
    rows = []
    for bq, ms in times.items():
        steps = ROWS * nkv * (T // bq)
        work = 4.0 * group * bq * (window + bq) * D
        rows.append(([steps, steps * work], ms / 1e3))
    a = np.array([r[0] for r in rows])
    b = np.array([r[1] for r in rows])
    fixed, per_flop = np.linalg.solve(a, b)
    return float(fixed), float(1.0 / per_flop)


def main(argv):
    assert jax.devices()[0].platform == "tpu", jax.devices()
    kind = jax.devices()[0].device_kind
    for name in argv or list(SHAPES):
        nkv, group, window = SHAPES[name]
        ks = jax.random.split(jax.random.key(43), 4)
        q = (jax.random.normal(ks[0], (ROWS, T, nkv * group * D))
             / D ** 0.5).astype(jnp.bfloat16)
        k, v = (jax.random.normal(kk, (ROWS, T, nkv * D)).astype(
            jnp.bfloat16) for kk in ks[1:3])
        do = jax.random.normal(ks[3], q.shape).astype(jnp.bfloat16)
        seen, band_fwd = None, {}
        for variant in ("splash512", "splash256", "band128", "band256"):
            core = core_of(variant, nkv, group, window)

            def both(q, k, v, do, core=core):
                out, back = jax.vjp(core, q, k, v)
                return (out,) + back(do)

            line = {"shape": name, "variant": variant, "window": window,
                    "group": group, "device": kind}
            line["forward_ms"], _ = timed(jax.jit(core), q, k, v)
            line["forward_backward_ms"], out = timed(jax.jit(both), q, k,
                                                     v, do)
            if seen is None:
                seen = out
            else:
                line["gaps"] = [float("%.3g" % gap(a, b))
                                for a, b in zip(out, seen)]
            if variant.startswith("band"):
                band_fwd[int(variant[-3:])] = line["forward_ms"]
            del out
            print(json.dumps(line), flush=True)
        fixed, rate = fit(band_fwd, nkv, group, window)
        print(json.dumps({
            "shape": name, "step_fixed_us": round(1e6 * fixed, 3),
            "mxu_tflops": round(rate / 1e12, 1),
            "chosen": attention_pallas.band_tiles(T, window, group, D)
            ._asdict(), "device": kind}), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
