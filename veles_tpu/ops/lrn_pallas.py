"""Pallas TPU kernels for LRN (AlexNet's local response normalization).

Why a hand kernel when the banded-matmul XLA form (ops/lrn.py) already
rides the MXU: LRN is pure memory traffic — the op reads/writes the
largest activations in the network — and XLA still materializes the
windowed sum, the saved ``den`` residual, and the backward's regathered
intermediates as separate HBM round trips.  These kernels do the whole
op in ONE VMEM pass each way:

- forward: read x -> x^2 -> banded matmul (MXU) -> k + alpha*s ->
  rsqrt chain (zero transcendentals for beta=3/4) -> write y.  The
  ONLY residual is x itself (which the scan already has): ``den`` is
  never stored.
- backward: read x and err -> recompute den with the same tiny matmul
  (MXU FLOPs are free here; HBM bytes are not) -> err_input in one
  write.

HBM traffic drops from ~8 array passes (fwd materialize + den
store/load + bwd regather) to 5 (x, y | x, err, err_input).

The channel window always lives entirely inside a tile: tiles span the
full channel axis (C <= 256 in every real config) and rows are
independent, so the grid only splits rows.  Rows per tile are chosen as
a divisor of the row count — no padding pass, no masked tail.

Reference parity: veles/znicz/normalization.py semantics, same formula
as ops/lrn.py (whose numpy shifted-adds path remains the independent
oracle; tests/test_ops.py compares the three implementations).
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import numpy as np


def _band(c: int, n: int, transpose: bool = False) -> np.ndarray:
    """The window matrix — shared single source with the XLA form
    (ops/lrn.py band_matrix; the parity-sensitive tap convention must
    never live in two places)."""
    from veles_tpu.ops.lrn import band_matrix
    return band_matrix(c, n, transpose)


#: VMEM bytes one f32 (rows, C) working buffer may occupy; the
#: kernels keep ~5 live plus pallas's own block double-buffers
_TILE_BUDGET = 512 * 1024


def _tile_rows(n_rows: int, c: int) -> Optional[int]:
    """Rows per VMEM tile: a divisor of n_rows, multiple of 8 (f32
    sublane; Mosaic also takes such tiles for the bf16 the hot path
    feeds — PR 21 compiled 968 = 8 x 121 rows at 55x55x96), sized so
    the kernel's ~6 live f32 (rows, C) buffers stay well under VMEM.
    None = no usable divisor; caller falls back."""
    budget = max(8, _TILE_BUDGET // (4 * c) // 8 * 8)
    t = min(n_rows, budget)
    t -= t % 8
    while t >= 8:
        if n_rows % t == 0:
            return t
        t -= 8
    return None


def usable(shape, n: int, beta: float) -> bool:
    """True when these kernels implement this config: beta=3/4 (the
    rsqrt chain; every real config), channels last and small enough
    that a full-channel tile fits VMEM, and the row count tiles."""
    if beta != 0.75 or len(shape) < 2:
        return False
    c = shape[-1]
    n_rows = int(np.prod(shape[:-1]))
    return 0 < n <= c <= 1024 and _tile_rows(n_rows, c) is not None


def _fwd_kernel(x_ref, band_ref, y_ref, *, k, alpha):
    import jax
    import jax.numpy as jnp
    x = x_ref[:]
    # the dot stays in the INPUT dtype (bf16 on TPU) with f32
    # accumulation — the MXU's native mode and exactly what the XLA
    # banded form computes; an f32 x f32 matmul is several times
    # slower and was the whole kernel's bottleneck
    s = jnp.dot(x * x, band_ref[:],
                preferred_element_type=jnp.float32)
    r = jax.lax.rsqrt(k + alpha * s)
    y_ref[:] = (x.astype(jnp.float32)
                * (r * jnp.sqrt(r))).astype(y_ref.dtype)


def _bwd_kernel(x_ref, err_ref, band_ref, bandt_ref, out_ref,
                *, k, alpha):
    import jax
    import jax.numpy as jnp
    x = x_ref[:]
    e = err_ref[:]
    s = jnp.dot(x * x, band_ref[:],
                preferred_element_type=jnp.float32)
    xf = x.astype(jnp.float32)
    ef = e.astype(jnp.float32)
    r = jax.lax.rsqrt(k + alpha * s)       # den^-0.5
    d = r * jnp.sqrt(r)                    # den^-0.75
    t = ef * xf * (d * r * r)              # err * x * den^-1.75
    wt = jnp.dot(t.astype(x.dtype), bandt_ref[:],
                 preferred_element_type=jnp.float32)
    out = ef * d - (2.0 * alpha * 0.75) * xf * wt
    out_ref[:] = out.astype(out_ref.dtype)


@functools.lru_cache(maxsize=None)
def _specs(n_rows: int, c: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    tile = _tile_rows(n_rows, c)
    row_spec = pl.BlockSpec((tile, c), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    band_spec = pl.BlockSpec((c, c), lambda i: (0, 0),
                             memory_space=pltpu.VMEM)
    return n_rows // tile, row_spec, band_spec


def lrn_fwd(x: Any, n: int, k: float, alpha: float,
            interpret: bool = False) -> Any:
    """y = x * (k + alpha * window_sum(x^2)) ** -0.75, one VMEM pass."""
    import jax
    from jax.experimental import pallas as pl
    c = x.shape[-1]
    xr = x.reshape(-1, c)
    grid, row_spec, band_spec = _specs(xr.shape[0], c)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, k=float(k), alpha=float(alpha)),
        out_shape=jax.ShapeDtypeStruct(xr.shape, x.dtype),
        grid=(grid,),
        in_specs=[row_spec, band_spec],
        out_specs=row_spec,
        interpret=interpret,
    )(xr, _band(c, n).astype(x.dtype))  # 0/1 taps: exact in bf16
    return y.reshape(x.shape)


def lrn_bwd(x: Any, err_output: Any, n: int, k: float, alpha: float,
            interpret: bool = False) -> Any:
    """err_input for the forward above, recomputing den in-kernel
    instead of loading a stored residual."""
    import jax
    from jax.experimental import pallas as pl
    c = x.shape[-1]
    xr = x.reshape(-1, c)
    er = err_output.reshape(-1, c)
    grid, row_spec, band_spec = _specs(xr.shape[0], c)
    band = _band(c, n)
    out = pl.pallas_call(
        functools.partial(_bwd_kernel, k=float(k), alpha=float(alpha)),
        out_shape=jax.ShapeDtypeStruct(xr.shape, err_output.dtype),
        grid=(grid,),
        in_specs=[row_spec, row_spec, band_spec, band_spec],
        out_specs=row_spec,
        interpret=interpret,
    )(xr, er, band.astype(x.dtype),
      _band(c, n, transpose=True).astype(x.dtype))
    return out.reshape(err_output.shape)
