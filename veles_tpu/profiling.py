"""Profiling: analytic FLOPs accounting, MFU, and jax.profiler traces.

Reference parity: SURVEY.md §5.1 — the reference accumulates per-unit
wall time around ``run()`` and prints a summary (that part lives in
veles_tpu/workflow.py); OpenCL event timing and block-size autotuning
have no TPU meaning (XLA autotunes).  The TPU-era replacement specified
by the survey is "``jax.profiler`` traces + per-unit host timers" plus
the accounting this module adds: analytic per-layer FLOPs for the
models built through StandardWorkflow, so throughput can be reported as
**MFU** (model FLOPs utilization = achieved FLOP/s over the chip's peak)
and physically impossible numbers are caught at the source.

FLOPs conventions (standard practice, e.g. the public scaling-book
accounting):

- one multiply-accumulate = 2 FLOPs;
- training step = forward + backward, where the backward of a weighted
  layer costs ~2x its forward (grad wrt input + grad wrt weights), so a
  weighted layer contributes 3x forward FLOPs and a weightless layer
  2x;
- elementwise/pooling/normalization ops are counted by output elements
  — they are HBM-bound, not MXU work, but keeping them in the total
  makes the estimate conservative (MFU is *under*-reported).
"""

from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

#: peak dense-matmul FLOP/s by device_kind substring, first match
#: wins.  bf16 numbers (the MXU's native format and what the fused
#: path computes in on TPU).  Public spec-sheet values.
PEAK_FLOPS = (
    ("v5 lite", 197e12),      # TPU v5e
    ("v5e", 197e12),
    ("v5p", 459e12),
    ("v4", 275e12),
    ("v6", 918e12),           # Trillium
    ("v3", 123e12),
    ("v2", 45e12),
)


def device_peak_flops(device) -> Optional[float]:
    """Peak bf16 FLOP/s for a jax device, or None if unknown (CPU)."""
    kind = getattr(device, "device_kind", "").lower()
    if not kind or "cpu" in kind:
        return None
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def _numel(shape: Iterable[int]) -> int:
    return int(np.prod([int(s) for s in shape])) if shape else 0


def forward_flops_per_sample(unit) -> float:
    """Analytic forward-pass FLOPs for ONE sample through a forward
    unit.  Shapes must be resolved (call after workflow.initialize)."""
    if hasattr(unit, "mxu_flops_per_sample"):
        # the sequence family counts its own matmuls (ops/sequence.py)
        return float(unit.mxu_flops_per_sample())
    out_shape = tuple(unit.output.shape)
    out_elems = _numel(out_shape[1:])
    kind = type(unit).__name__

    if hasattr(unit, "n_kernels") and hasattr(unit, "kx"):
        # conv family: 2 * ky*kx*c_in * n_kernels per output pixel.
        # deconv runs the same MACs laid out over its INPUT pixels.
        c_in = int(unit.input.shape[-1])
        macs_per_px = unit.ky * unit.kx * c_in * unit.n_kernels
        if "Deconv" in kind:
            spatial = _numel(unit.input.shape[1:3])
        else:
            spatial = _numel(out_shape[1:3])
        return 2.0 * macs_per_px * spatial
    if hasattr(unit, "output_sample_shape"):
        # all2all (dense): 2 * in_features * out_features
        in_feat = _numel(unit.input.shape[1:])
        return 2.0 * in_feat * _numel(unit.output_sample_shape)
    if hasattr(unit, "kx"):        # pooling: window reduce per output
        return float(unit.ky * unit.kx * out_elems)
    if "LRN" in kind:
        return 10.0 * out_elems
    return float(out_elems)        # activation / dropout / etc.


def unit_has_weights(unit) -> bool:
    if getattr(unit, "matrix_names", ()):
        return True               # the sequence family's projections
    w = getattr(unit, "weights", None)
    return w is not None and getattr(w, "mem", None) is not None


def _is_mxu(unit) -> bool:
    """Conv and dense layers: the MACs the MXU runs."""
    return (hasattr(unit, "n_kernels") and hasattr(unit, "kx")) \
        or hasattr(unit, "output_sample_shape") \
        or hasattr(unit, "mxu_flops_per_sample")


def model_flops_per_sample(forwards: List[Any]) -> Dict[str, float]:
    """{"forward": F, "train": T, "mxu_train": M} FLOPs for one
    sample.  ``train`` uses the 3x/2x weighted/weightless multipliers
    over EVERY layer (a conservative work estimate); ``mxu_train`` is
    conv + dense MACs x 2 x 3 alone — the count a utilisation of the
    MXU's peak divides by (the convention of benchmarks/lib/flops.py,
    whose independent count a tier-1 test pins this one to)."""
    fwd = 0.0
    train = 0.0
    mxu = 0.0
    for u in forwards:
        f = forward_flops_per_sample(u)
        fwd += f
        train += f * (3.0 if unit_has_weights(u) else 2.0)
        if _is_mxu(u):
            mxu += 3.0 * f
    return {"forward": fwd, "train": train, "mxu_train": mxu}


def layer_flops_table(forwards: List[Any]) -> List[Dict[str, Any]]:
    """Per-layer rows for the timing/profile report."""
    rows = []
    for u in forwards:
        f = forward_flops_per_sample(u)
        rows.append({
            "name": u.name,
            "type": type(u).__name__,
            "output_shape": tuple(int(s) for s in u.output.shape),
            "fwd_flops_per_sample": f,
            "train_flops_per_sample":
                f * (3.0 if unit_has_weights(u) else 2.0),
            "params": (_numel(u.weights.shape)
                       if unit_has_weights(u) else 0) +
                      (_numel(u.bias.shape)
                       if getattr(u, "bias", None) and
                       getattr(u.bias, "mem", None) is not None else 0),
        })
    return rows


def mfu(images_per_sec: float, train_flops_per_sample: float,
        device) -> Optional[float]:
    """Model FLOPs utilization in [0, 1]; None when peak is unknown."""
    peak = device_peak_flops(device)
    if not peak:
        return None
    return images_per_sec * train_flops_per_sample / peak


@contextlib.contextmanager
def trace(log_dir: Optional[str]):
    """``jax.profiler`` trace context; no-op when log_dir is falsy.

    The captured trace is a TensorBoard/perfetto-compatible directory —
    the survey's §5.1 "jax.profiler traces" deliverable."""
    if not log_dir:
        yield
        return
    import os

    import jax
    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir):
        yield
