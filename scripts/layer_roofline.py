"""Per-layer analytic roofline for the AlexNet fused step (round-4
VERDICT next #3: pin the MFU ceiling or find the next lever).

For every forward layer this prints analytic training FLOPs, a
minimum-HBM-traffic estimate, the implied MXU-time and HBM-time floors
(v5e: 197 TFLOP/s bf16, 819 GB/s), which of the two binds, and the
layer's floor share of the whole step.  The sum of per-layer floors is
the step's analytic lower bound; analytic-train-FLOPs over that bound
is the model's MFU CEILING on this chip — what a perfect scheduler
could reach, independent of XLA.

Traffic model (bf16 activations, f32 master params + momentum),
per sample, assuming perfect elementwise fusion (optimistic — real
XLA materializes more, so the printed ceiling is an upper bound):

- weighted layers (conv/dense): fwd reads in + weights, writes out;
  bwd reads err_out + residual(in) + weights (dgrad) + residual(in)
  again (wgrad), writes err_in; optimizer traffic is
  16 B/param / minibatch (f32 read+write of weights and velocity).
- LRN: fwd reads in, writes out + den residual; bwd reads err_out +
  in + den, writes err_in.
- pooling: fwd read in / write out; bwd read err_out + in, write
  err_in (select-and-scatter needs the argmax source).
- activation/dropout: fused into their producers — zero extra traffic
  (dropout's bf16 mask residual counted: one write + one read).

Usage: python scripts/layer_roofline.py [mb] [--measure] [--iters K]

``--measure`` (round-5 VERDICT next #3 — finish the ceiling proof):
runs each AlexNet conv's fwd+bwd ALONE on the TPU (-b tpu rules) at
the same shapes/dtypes the fused step uses (bf16 compute on TPU, f32
master params, per-iteration param carry inside a lax.scan so XLA
cannot hoist the loop-invariant work) and prints measured us/sample
next to the analytic floor — per-layer MEASURED MXU efficiency
replacing the previously inferred ~62% residual in docs/perf.md.
"""

from __future__ import annotations

import sys

import numpy as np

sys.path.insert(0, ".")

PEAK_FLOPS = 197e12     # v5e bf16
HBM_BPS = 819e9         # v5e HBM bandwidth
ACT = 2                 # bf16 activation bytes
P32 = 4                 # f32 param bytes


def build_workflow(mb: int):
    from veles_tpu import prng
    from veles_tpu.backends import NumpyDevice
    from veles_tpu.loader.synthetic import SyntheticClassificationLoader
    from veles_tpu.models.alexnet import alexnet_layers
    from veles_tpu.ops.standard_workflow import StandardWorkflow

    prng.seed_all(1234)
    w = StandardWorkflow(
        loader_factory=lambda wf: SyntheticClassificationLoader(
            wf, name="loader", minibatch_size=mb, n_train=mb,
            n_valid=0, shape=(227, 227, 3), n_classes=1000,
            seed=227227),
        layers=alexnet_layers(1000),
        loss_function="softmax",
        decision_config={"max_epochs": 1},
        name="RooflineShapes")
    w.initialize(device=NumpyDevice())   # shape resolution only
    return w


def build_forwards(mb: int):
    return build_workflow(mb).forwards


def layer_rows(forwards, mb: int):
    from veles_tpu import profiling

    rows = []
    for i, u in enumerate(forwards):
        kind = type(u).__name__
        fwd_flops = profiling.forward_flops_per_sample(u)
        weighted = profiling.unit_has_weights(u)
        train_flops = fwd_flops * (3.0 if weighted else 2.0)
        in_b = int(np.prod(u.input.shape[1:])) * ACT
        out_b = int(np.prod(u.output.shape[1:])) * ACT
        params = (int(np.prod(u.weights.shape)) if weighted else 0) + \
            (int(np.prod(u.bias.shape))
             if weighted and u.bias else 0)
        w_b = params * ACT              # bf16 cast the step computes in
        first = i == 0                  # chain head skips err_input
        if weighted:
            # fwd: in + weights(bf16) + out; bwd: err_out + in (dgrad
            # src) + weights + in again (wgrad) + err_in write.  ALL
            # weight traffic amortizes over the minibatch: one batched
            # matmul reads the weights once for mb samples.  Optimizer
            # traffic is f32 read+write of weights and velocity
            # (16 B/param), also once per minibatch.
            wpm = w_b / mb
            bytes_s = (in_b + wpm + out_b
                       + out_b + in_b + wpm + in_b
                       + (0 if first else in_b)
                       + 16.0 * params / mb)
        elif "LRN" in kind:
            bytes_s = (in_b + out_b + out_b * 2            # fwd + den
                       + out_b + in_b + out_b * 2 + in_b)  # bwd
        elif "Pooling" in kind:
            bytes_s = in_b + out_b + out_b + in_b + in_b
        elif "Dropout" in kind:
            bytes_s = out_b * 2                            # mask w+r
        else:                                              # activation
            bytes_s = 0.0
        # MXU time only for matmul-family work; VPU elementwise is
        # bandwidth-modelled, not FLOPs-modelled
        mxu_flops = train_flops if weighted else 0.0
        if "LRN" in kind:   # banded matmul rides the MXU
            mxu_flops = train_flops
        t_mxu = mxu_flops / PEAK_FLOPS
        t_hbm = bytes_s / HBM_BPS
        rows.append({
            "name": u.name, "kind": kind,
            "out": tuple(int(s) for s in u.output.shape[1:]),
            "params": params,
            "train_gflops": train_flops / 1e9,
            "mb_bytes": bytes_s / 2 ** 20,
            "t_mxu_us": t_mxu * 1e6,
            "t_hbm_us": t_hbm * 1e6,
            "bound": "mxu" if t_mxu >= t_hbm else "hbm",
            "floor_us": max(t_mxu, t_hbm) * 1e6,
        })
    return rows


def measure_conv_layers(w, rows, mb: int, iters: int = 8,
                        repeats: int = 3):
    """Each conv's fwd+bwd ALONE on the device, scanned.

    The scan carries the PARAMS (a tiny SGD step per iteration, like
    the fused trace) so the per-iteration work has a genuine data
    dependency — a loop-invariant fwd+bwd would be hoisted out of the
    scan and the timing would measure one iteration no matter what
    ``iters`` says.  The timing barrier is a host fetch of the updated
    bias (bytes-tiny, data-dependent on every iteration).  Chain-head
    convs skip err_input exactly like the production step
    (need_err_input=False), so conv1's number excludes the dgrad the
    real step never computes.
    """
    import time

    import jax
    import jax.numpy as jnp
    from jax import lax

    from veles_tpu.backends import make_device
    from veles_tpu.engine import core as engine_core

    from veles_tpu import profiling

    device = make_device("tpu")   # a chip timing or none
    # the floors this is printed against are v5e's: a chip with other
    # peaks (or one profiling.py does not know) has no roofline here
    peak = profiling.device_peak_flops(device.jax_device)
    if peak != PEAK_FLOPS:
        raise RuntimeError(
            f"--measure: floors are computed for {PEAK_FLOPS:.3g} "
            f"FLOP/s (v5e); {device.jax_device.device_kind!r} peaks "
            f"at {peak} in profiling.PEAK_FLOPS")
    cd = jnp.dtype(device.compute_dtype)
    mixed = cd != jnp.float32
    floor_by_name = {r["name"]: r for r in rows}
    out = []
    for i, (u, gd) in enumerate(zip(w.forwards, w.gds)):
        if "Conv" not in type(u).__name__ or gd is None:
            continue
        first = i == 0 and gd.can_skip_err_input

        def cast(tree):
            if not mixed:
                return tree
            return jax.tree_util.tree_map(
                lambda a: a.astype(cd) if a.dtype == jnp.float32
                else a, tree)

        def step(params, x, _u=u, _gd=gd, _first=first, _cast=cast):
            def body(p, _):
                cp = _cast(p)
                y, res = _u.apply_fwd(cp, x, rng=None, train=True)
                err = (y * jnp.asarray(1e-3, y.dtype))  # dep chain
                if _first:
                    _, grads = _gd.backward_from_saved(
                        cp, res, err, need_err_input=False)
                else:
                    _, grads = _gd.backward_from_saved(cp, res, err)
                p = {k: p[k] - 1e-6 * grads[k].astype(jnp.float32)
                     for k in p}
                return p, None
            params, _ = lax.scan(body, params, None, length=iters)
            return params

        fn = engine_core.donating_jit(step, donate=(0,))
        params = {k: device.put(np.asarray(v, np.float32))
                  for k, v in u.gather_params().items()}
        x_host = np.random.default_rng(5).standard_normal(
            (mb,) + tuple(u.input.shape[1:])).astype(np.float32)
        x = device.put(x_host.astype(cd) if mixed else x_host)
        params = fn(params, x)               # compile + warmup
        np.asarray(params["bias"])           # drain
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            params = fn(params, x)
            np.asarray(params["bias"])       # the honest barrier
            times.append(time.perf_counter() - t0)
        us = float(np.median(times)) / (iters * mb) * 1e6
        floor = floor_by_name[u.name]
        out.append({
            "name": u.name,
            "floor_us": floor["floor_us"],
            "t_mxu_us": floor["t_mxu_us"],
            "measured_us": us,
            "efficiency": floor["floor_us"] / us if us > 0 else 0.0,
        })
    return out


def print_measured(measured, device_kind: str):
    print(f"\n# measured per-conv fwd+bwd, isolated, scanned "
          f"({device_kind}); efficiency = analytic floor / measured")
    print(f"{'layer':<22}{'floor_us':>10}{'measured_us':>13}"
          f"{'efficiency':>12}")
    for r in measured:
        print(f"{r['name']:<22}{r['floor_us']:>10.2f}"
              f"{r['measured_us']:>13.2f}"
              f"{100 * r['efficiency']:>11.1f}%")
    tot_floor = sum(r["floor_us"] for r in measured)
    tot_meas = sum(r["measured_us"] for r in measured)
    print(f"{'all convs':<22}{tot_floor:>10.2f}{tot_meas:>13.2f}"
          f"{100 * tot_floor / tot_meas:>11.1f}%")


def main():
    measure, iters, positional = False, 8, []
    argv = iter(sys.argv[1:])
    for a in argv:
        if a == "--measure":
            measure = True
        elif a == "--iters":
            iters = int(next(argv))
        else:
            positional.append(a)
    mb = int(positional[0]) if positional else 512
    w = build_workflow(mb)
    forwards = w.forwards
    rows = layer_rows(forwards, mb)
    total_floor = sum(r["floor_us"] for r in rows)
    total_flops = sum(r["train_gflops"] for r in rows)
    print(f"# per-sample, mb={mb}; floors vs v5e peaks "
          f"(197 TF bf16, 819 GB/s)")
    hdr = (f"{'layer':<22}{'out':<16}{'tGFLOP':>8}{'MB':>7}"
           f"{'t_mxu':>8}{'t_hbm':>8}{'bound':>6}{'floor':>8}"
           f"{'share':>7}")
    print(hdr)
    for r in rows:
        print(f"{r['name']:<22}{str(r['out']):<16}"
              f"{r['train_gflops']:>8.3f}{r['mb_bytes']:>7.2f}"
              f"{r['t_mxu_us']:>8.2f}{r['t_hbm_us']:>8.2f}"
              f"{r['bound']:>6}{r['floor_us']:>8.2f}"
              f"{100 * r['floor_us'] / total_floor:>6.1f}%")
    ceiling = total_flops * 1e9 / PEAK_FLOPS / (total_floor * 1e-6)
    print(f"\ntotal: {total_flops:.3f} train GFLOP/sample, "
          f"floor {total_floor:.1f} us/sample "
          f"-> analytic MFU ceiling {100 * ceiling:.1f}%")
    if mb == 512:
        # the round-5 measured reference point at this exact config
        # (bench.py mb=512 ss=8, real chip) — only meaningful against
        # mb=512 floors
        print(f"measured at mb=512 (round-5 bench): ~14100 img/s = "
              f"~70.9 us/sample -> ~48.9% MFU; gap to floor = "
              f"{70.9 / total_floor:.2f}x")
    if measure:
        from veles_tpu.backends import make_device
        measured = measure_conv_layers(w, rows, mb, iters=iters)
        kind = make_device("tpu").jax_device.device_kind
        print_measured(measured, kind)


if __name__ == "__main__":
    main()
