"""Local response normalization (across channels).

Reference parity: veles/znicz/normalization.py — AlexNet's LRN:
``y_i = x_i / (k + alpha * sum_{j in window(i)} x_j^2) ^ beta`` with a
channel window of size n centered on i, plus its analytic backward.

TPU-first implementation notes (this op was HALF the AlexNet step time
when written naively — docs/perf.md):

- ``den^-0.75`` as a pow lowers to exp(log(x)) — two VPU
  transcendentals per element over the largest activations in the
  net, forward AND backward.  For the standard beta=3/4 it is instead
  computed as ``r*sqrt(r)`` with ``r = rsqrt(den)`` (hardware rsqrt +
  one sqrt); the backward's ``den^-1.75`` is ``d34 * r * r`` — zero
  transcendentals anywhere on the hot path.
- the windowed channel sum is a BANDED MATMUL on the jax path:
  ``sum_window(v) = v @ B`` with ``B[c, d] = 1 iff |c - d| <= n//2``
  (a C x C constant).  The extra FLOPs are negligible (2*C^2 per
  pixel, <1% of the conv FLOPs around it) and they run on the MXU,
  while the elementwise alternative (pad + n shifted adds) cost ~n
  materialized passes over the largest activations in the net.  The
  numpy oracle keeps the explicit shifted-adds form — an independent
  implementation the tests compare against.
- residual policy: by default the forward saves ``den`` so the
  backward skips the windowed reduction; the opt-in variants (pallas
  kernels via VELES_TPU_LRN_PALLAS, x-only residual via
  VELES_TPU_LRN_RECOMPUTE) save just ``x`` and re-derive ``den`` in
  the backward.  Measured on a v5e the two policies tie — fwd and bwd
  share one scan body, so XLA schedules the residual freely either
  way (docs/perf.md).
"""

from __future__ import annotations

import os
from typing import Any, Dict

import numpy as np

from veles_tpu.ops.nn_units import ForwardUnit, GradientUnit


def _xp(x):
    if isinstance(x, np.ndarray):
        return np
    import jax.numpy as jnp
    return jnp


def band_matrix(c: int, n: int, transpose: bool = False) -> np.ndarray:
    """The n-tap window as a C x C 0/1 matrix:
    ``(v @ band)[d] = sum_{j=-half}^{n-1-half} v[d+j]`` — eye-offset
    ``off`` contributes v[d-off], hence the negated range.  EXACTLY n
    taps for both parities of n (a symmetric -half..+half band would
    sum n+1 taps for even n).  ``transpose=True`` gives the adjoint
    window (taps j in [-(n-1-half), half]) — equal to the forward
    window only for ODD n; the backward pass needs the adjoint.
    Single source of truth for lrn.py and lrn_pallas.py."""
    half = n // 2
    band = np.zeros((c, c), np.float32)
    for off in range(half - n + 1, half + 1):
        band += np.eye(c, c, off, dtype=np.float32)
    return np.ascontiguousarray(band.T) if transpose else band


def _window_sum(xp, v, n: int, transpose: bool = False):
    """Sum of v over the n-wide channel window (same shape).
    jax: one banded matmul over the channel axis (MXU); numpy: explicit
    shifted adds (the independent oracle).  ``transpose`` selects the
    adjoint window — required in the backward pass; for even n the two
    differ (the window is centered only for odd n)."""
    half = n // 2
    c = v.shape[-1]
    if xp is not np:
        band = band_matrix(c, n, transpose)
        return v @ xp.asarray(band, dtype=v.dtype)
    # taps j in [-half, n-1-half] (forward) or the negated set
    # (adjoint): left-pad by -min_tap, right-pad by max_tap
    lo = (n - 1 - half) if transpose else half
    pad = [(0, 0)] * (v.ndim - 1) + [(lo, n - 1 - lo)]
    vp = np.pad(v, pad)
    out = vp[..., 0:c]
    for i in range(1, n):
        out = out + vp[..., i:i + c]
    return out


def _neg_beta_pow(xp, den, beta: float):
    """den**(-beta) without transcendentals for the quarter-multiples
    every real config uses (0.75 is AlexNet's; 0.5/1.0 appear in
    variants).  Falls back to pow otherwise."""
    if beta == 0.75:
        r = den ** -0.5 if xp is np else _rsqrt(xp, den)
        return r * xp.sqrt(r), r
    if beta == 0.5:
        r = den ** -0.5 if xp is np else _rsqrt(xp, den)
        return r, r
    if beta == 1.0:
        inv = 1.0 / den
        return inv, None
    return den ** (-beta), None


def _rsqrt(xp, v):
    from jax import lax
    return lax.rsqrt(v)


class LRNormalizer(ForwardUnit):
    has_params = False

    def __init__(self, workflow=None, alpha: float = 1e-4,
                 beta: float = 0.75, n: int = 5, k: float = 2.0,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.alpha, self.beta, self.n, self.k = alpha, beta, n, k

    def output_shape_for(self, input_shape):
        return tuple(input_shape)

    def param_shapes(self, input_shape):
        return {}

    def _den(self, xp, x):
        return self.k + self.alpha * _window_sum(xp, x * x, self.n)

    def apply(self, params, inputs, rng=None) -> Dict[str, Any]:
        x = inputs["input"]
        xp = _xp(x)
        d, _ = _neg_beta_pow(xp, self._den(xp, x), self.beta)
        return {"output": x * d}

    def _use_pallas(self, x) -> bool:
        """Whether the hand kernels (ops/lrn_pallas.py) take the hot
        fused path.  OPT-IN via VELES_TPU_LRN_PALLAS=1: measured on a
        v5e chip with a data-fetch barrier, XLA's banded-matmul form
        BEATS the hand kernels at AlexNet's shapes (docs/perf.md
        records the shootout), so the default stays XLA.  The kernels
        remain for other shapes/platforms and as tuning
        infrastructure.  Further requirements: a real TPU (not the
        XLA:CPU test platform), no sharded mesh (XLA partitions
        poorly around custom calls — ``force_xla`` is set by the
        fused runner), beta=3/4, and a tileable shape."""
        if not os.environ.get("VELES_TPU_LRN_PALLAS"):
            return False
        if getattr(self, "force_xla", False):
            return False
        dev = getattr(self, "device", None)
        if dev is None or not getattr(dev, "is_jax", False) or \
                getattr(dev, "platform", "cpu") == "cpu":
            return False
        if getattr(dev, "mesh", None) is not None:
            # a MeshJaxDevice reaches here on the eager path too, where
            # the fused runner's force_xla loop never runs
            return False
        from veles_tpu.ops import lrn_pallas
        return lrn_pallas.usable(x.shape, self.n, self.beta)

    def apply_fwd(self, params, x, rng=None, train=True):
        """Residual policy: pallas path and the recompute variant save
        only ``x`` — the backward re-derives ``den`` (a cheap banded
        MXU matmul) instead of storing/loading an f32 array the size
        of the largest activations in the net.  Default XLA path
        carries ``den``; VELES_TPU_LRN_RECOMPUTE=1 switches (both
        measured in docs/perf.md — fwd+bwd live in ONE scan body, so
        XLA schedules the residual freely either way)."""
        xp = _xp(x)
        if xp is not np and self._use_pallas(x):
            from veles_tpu.ops import lrn_pallas
            return lrn_pallas.lrn_fwd(x, self.n, self.k,
                                      self.alpha), (x, None)
        den = self._den(xp, x)
        d, _ = _neg_beta_pow(xp, den, self.beta)
        if xp is not np and os.environ.get("VELES_TPU_LRN_RECOMPUTE"):
            return x * d, (x, None)
        return x * d, (x, den)


class GDLRNormalizer(GradientUnit):
    def backward_from_saved(self, params, saved, err_output):
        f = self.forward
        x, den = saved
        xp = _xp(err_output)
        if den is None:  # x-only residual: recompute den here
            if f._use_pallas(x):
                from veles_tpu.ops import lrn_pallas
                return lrn_pallas.lrn_bwd(x, err_output, f.n, f.k,
                                          f.alpha), {}
            den = f._den(xp, x)
        d_nb, r = _neg_beta_pow(xp, den, f.beta)      # den^-beta
        if f.beta == 0.75 and r is not None:
            d_nb1 = d_nb * (r * r)                    # den^-(beta+1)
        elif f.beta == 0.5 and r is not None:
            d_nb1 = d_nb * (r * r)
        elif f.beta == 1.0:
            d_nb1 = d_nb * d_nb
        else:
            d_nb1 = den ** (-f.beta - 1.0)
        t = err_output * x * d_nb1
        # the backward needs the ADJOINT window (transpose=True): it
        # equals the forward window for odd n, but differs for even n
        # (fd-checked in tests/test_ops.py — an earlier "the window is
        # symmetric" shortcut was wrong for even n)
        err_input = (err_output * d_nb
                     - 2.0 * f.alpha * f.beta * x
                     * _window_sum(xp, t, f.n, transpose=True))
        return err_input, {}
