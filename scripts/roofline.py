"""Roofline check for the fused AlexNet train step.

Compares the measured steady-state superstep time against compute- and
HBM-bound floors derived from TWO flop/byte sources:

- the analytic per-layer count (veles_tpu/profiling.py) — trusted;
- XLA's own ``compiled.cost_analysis()`` — reported for reference but
  NOT trusted on TPU: it undercounts convolution FLOPs after fusion
  (measured ~0.8 GFLOP/image where the analytic count is ~2.3 fwd /
  6.8 train — docs/perf.md), so floors derived from it are labeled.

Distinguishes "the kernels are inefficient" (measured >> both floors)
from "we are at a roof" (measured ~= floor) — the decision input for
docs/perf.md.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

V5E_HBM_BW = 819e9           # bytes/sec (xla-floor reference only)


def main():
    mb = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    ss = int(sys.argv[2]) if len(sys.argv) > 2 else 8

    from veles_tpu import profiling, prng
    from veles_tpu.backends import make_device
    from veles_tpu.loader.synthetic import SyntheticClassificationLoader
    from veles_tpu.models.alexnet import alexnet_layers
    from veles_tpu.ops.standard_workflow import StandardWorkflow

    prng.seed_all(1234)
    w = StandardWorkflow(
        loader_factory=lambda wf: SyntheticClassificationLoader(
            wf, name="loader", minibatch_size=mb, n_train=mb * ss,
            n_valid=0, shape=(227, 227, 3), n_classes=1000,
            seed=227227),
        layers=alexnet_layers(1000),
        loss_function="softmax",
        decision_config={"max_epochs": 10 ** 9},
        superstep=ss, name="Roofline")
    w.evaluator.compute_confusion = False
    device = make_device("tpu")   # a chip timing or none
    w.initialize(device=device)
    loader, fused = w.loader, w.fused

    def fire():
        loader.run()
        fused.run()

    for _ in range(3):
        fire()
    np.asarray(fused._acc)     # the honest barrier (bench.py contract)

    # steady-state superstep time: median of repeats, amortized firings
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(8):
            fire()
        np.asarray(fused._acc)
        times.append((time.perf_counter() - t0) / 8)
    dt = float(np.median(times))

    n_img = mb * ss
    analytic = profiling.model_flops_per_sample(w.forwards)["train"]
    a_flops = analytic * n_img
    # peak resolved from the ACTUAL device (None on CPU/unknown —
    # same helper bench.py trusts), not a hardcoded v5e constant
    peak = profiling.device_peak_flops(device.jax_device)
    u = profiling.mfu(n_img / dt, analytic, device.jax_device)
    out = {"mb": mb, "superstep": ss,
           "measured_superstep_sec": round(dt, 4),
           "images_per_sec": round(n_img / dt, 1),
           "analytic_train_gflops_per_image": round(analytic / 1e9, 3),
           "analytic_compute_floor_sec":
               round(a_flops / peak, 4) if peak else None,
           "mfu": round(u, 4) if u is not None else None}

    try:
        ld = loader
        args = (fused._params, fused._opt, fused._acc, fused._conf,
                ld.original_data.unmap(), fused._target_store(),
                ld.superstep_indices, ld.superstep_mask,
                fused._lr_rates_array(ld.superstep_indices.shape[0]),
                fused._rng_counter)
        ca = fused._train_step.lower(*args).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        if "flops" not in ca:
            # no FLOP count at all on this backend: emit the raw dict,
            # derive nothing (a zero would fire the undercount note)
            out["cost_analysis"] = {k: ca[k] for k in sorted(ca)[:12]}
        else:
            flops = float(ca["flops"])
            nbytes = float(ca.get("bytes accessed", 0))
            out.update({
                "xla_tflops_per_superstep": round(flops / 1e12, 3),
                "xla_gbytes_per_superstep": round(nbytes / 1e9, 3),
                "xla_hbm_floor_sec": round(nbytes / V5E_HBM_BW, 4),
                "xla_transcendentals": ca.get("transcendentals"),
                "xla_flops_vs_analytic": round(flops / a_flops, 3),
            })
            if flops < 0.5 * a_flops:
                out["note"] = ("xla cost_analysis undercounts fused "
                               "conv FLOPs on TPU; trust the analytic "
                               "floor")
    except Exception as e:  # noqa: BLE001 — reference data only
        out["cost_analysis_error"] = str(e)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
