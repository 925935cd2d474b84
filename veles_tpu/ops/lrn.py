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
- residual policy: the forward saves ``den`` so the backward skips
  the windowed reduction.  Saving ``x`` alone and re-deriving ``den``
  tied on a v5e, and hand Pallas kernels lost to this form (fwd and
  bwd share one scan body, so XLA schedules the residual freely and
  fuses into the neighbours — docs/perf.md); both are gone.
"""

from __future__ import annotations

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
    window only for ODD n; the backward pass needs the adjoint."""
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

    def apply_fwd(self, params, x, rng=None, train=True):
        xp = _xp(x)
        den = self._den(xp, x)
        d, _ = _neg_beta_pow(xp, den, self.beta)
        return x * d, (x, den)


class GDLRNormalizer(GradientUnit):
    def backward_from_saved(self, params, saved, err_output):
        f = self.forward
        x, den = saved
        xp = _xp(err_output)
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
