"""Cost attribution for the AlexNet fused step: measure images/sec with
one component ablated at a time (docs/perf.md records the findings).

Not a benchmark — a profiling instrument: the deltas tell us which op
family to optimize (pooling backward's select-and-scatter, LRN, first
-layer dgrad, dropout, f32 gather).  A jax.profiler trace on the
directly attached chip does carry a TPU device plane (PR 21,
tests_tpu TestProfilerTrace), so this is the cross-check for a
trace-based per-op reduction (ROADMAP S1), not its replacement.

Usage: python scripts/ablate_alexnet.py [mb] [firings] [variant ...]
Variants default to all.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

sys.path.insert(0, ".")

SUPERSTEP = 8


def variant_layers(name: str, n_classes: int = 1000):
    from veles_tpu.models.alexnet import alexnet_layers
    layers = alexnet_layers(n_classes)
    if name == "base":
        return layers
    if name == "no_lrn":
        return [l for l in layers if l["type"] != "norm"]
    if name == "avg_pool":
        return [dict(l, type="avg_pooling") if l["type"] == "max_pooling"
                else l for l in layers]
    if name == "no_dropout":
        return [l for l in layers if l["type"] != "dropout"]
    if name == "fc_only":
        # drop everything conv-side except one cheap pool to shrink:
        # isolates the FC tail's share
        return [
            {"type": "max_pooling", "->": {"kx": 8, "ky": 8,
                                           "sliding": 8}, "<-": {}},
        ] + [l for l in layers if l["type"].startswith("all2all")
             or l["type"] in ("softmax", "dropout")]
    if name == "conv_only":
        out = [l for l in layers if not (
            l["type"].startswith("all2all") or
            l["type"] in ("softmax", "dropout"))]
        out.append({"type": "softmax", "->": {"output_sample_shape":
                                              n_classes}, "<-": {}})
        return out
    raise ValueError(name)


def measure(name: str, mb: int, firings: int) -> dict:
    from veles_tpu import prng
    from veles_tpu.backends import make_device
    from veles_tpu.loader.synthetic import SyntheticClassificationLoader
    from veles_tpu.ops.standard_workflow import StandardWorkflow
    from veles_tpu import profiling

    prng.seed_all(1234)
    w = StandardWorkflow(
        loader_factory=lambda wf: SyntheticClassificationLoader(
            wf, name="loader", minibatch_size=mb,
            n_train=mb * SUPERSTEP, n_valid=0,
            shape=(227, 227, 3), n_classes=1000, seed=227227),
        layers=variant_layers(name),
        loss_function="softmax",
        decision_config={"max_epochs": 10 ** 9},
        superstep=SUPERSTEP,
        name=f"ablate_{name}")
    w.evaluator.compute_confusion = False
    device = make_device("tpu")   # a chip timing or none
    w.initialize(device=device)
    loader, fused = w.loader, w.fused

    def fire():
        loader.run()
        fused.run()

    for _ in range(3):
        fire()
    np.asarray(fused._acc)
    img0 = float(fused.processed_images)
    t0 = time.perf_counter()
    for _ in range(firings):
        fire()
    np.asarray(fused._acc)
    dt = time.perf_counter() - t0
    img = float(fused.processed_images) - img0
    flops = profiling.model_flops_per_sample(w.forwards)
    rate = img / dt
    u = profiling.mfu(rate, flops["train"], device.jax_device)
    w.stop()
    # release this variant's HBM (dataset + params + carries) before
    # the next one builds, or variants accumulate and the chip OOMs
    # (same lesson as bench.py's resident->streaming handoff)
    w.fused.release_device_state()
    w.loader.original_data.reset()
    w.loader.original_labels.reset()
    w.loader.original_targets.reset()
    import gc
    del w, loader, fused
    gc.collect()
    return {"variant": name, "images_per_sec": round(rate, 1),
            "train_gflops_per_image": round(flops["train"] / 1e9, 3),
            "mfu": round(u, 4) if u else None,
            "ms_per_image": round(1000.0 / rate, 4)}


def main():
    import os
    # every variant loads the identical synthetic dataset — memoize it
    os.environ.setdefault("VELES_TPU_SYNTH_CACHE", "1")
    mb = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    firings = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    names = sys.argv[3:] or ["base", "no_lrn", "avg_pool", "no_dropout",
                             "conv_only", "fc_only"]
    out = []
    for name in names:
        r = measure(name, mb, firings)
        out.append(r)
        print(json.dumps(r), flush=True)
    base = next((r for r in out if r["variant"] == "base"), None)
    if base:
        for r in out:
            if r is not base:
                print(f"# {r['variant']}: saves "
                      f"{base['ms_per_image'] - r['ms_per_image']:+.4f}"
                      f" ms/image vs base", flush=True)


if __name__ == "__main__":
    main()
