"""Convolution units.

Reference parity: veles/znicz/conv.py (``Conv``, ``ConvTanh``,
``ConvRELU``) and veles/znicz/gd_conv.py (``GradientDescentConv`` +
variants).  The reference runs hand-written im2col-style OpenCL/CUDA
kernels; here the TPU path is a single ``lax.conv_general_dilated`` —
XLA tiles it onto the MXU directly — and the backward pass is derived
with ``jax.vjp`` of the pre-activation (XLA emits the transposed-conv
and filter-gradient convs; inside the fused trace CSE merges the
recomputed forward with the outer one).  The numpy golden path is an
explicit im2col / col2im implementation, giving the tests an
independent oracle for gradient checks.

Layout: NHWC activations, HWIO weights — the TPU-native choice.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from veles_tpu.ops.nn_units import ForwardUnit, GradientUnit


def _pair(v) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else tuple(v)


def conv_out_size(n: int, k: int, pad: int, stride: int) -> int:
    return (n + 2 * pad - k) // stride + 1


# -- numpy im2col helpers (golden path) --------------------------------

def im2col(x: np.ndarray, ky: int, kx: int, pad: Tuple[int, int],
           stride: Tuple[int, int]) -> np.ndarray:
    """(B,H,W,C) -> (B,OH,OW,ky,kx,C) patch view (zero-padded copy)."""
    b, h, w, c = x.shape
    py, px = pad
    sy, sx = stride
    xp = np.pad(x, ((0, 0), (py, py), (px, px), (0, 0)))
    oh = conv_out_size(h, ky, py, sy)
    ow = conv_out_size(w, kx, px, sx)
    sb, sh, sw, sc = xp.strides
    shape = (b, oh, ow, ky, kx, c)
    strides = (sb, sh * sy, sw * sx, sh, sw, sc)
    return np.lib.stride_tricks.as_strided(xp, shape, strides,
                                           writeable=False)


def col2im(cols: np.ndarray, in_shape: Tuple[int, ...],
           pad: Tuple[int, int], stride: Tuple[int, int]) -> np.ndarray:
    """Scatter-add (B,OH,OW,ky,kx,C) patches back to (B,H,W,C)."""
    b, h, w, c = in_shape
    py, px = pad
    sy, sx = stride
    _, oh, ow, ky, kx, _ = cols.shape
    out = np.zeros((b, h + 2 * py, w + 2 * px, c), cols.dtype)
    for iy in range(ky):
        for ix in range(kx):
            out[:, iy:iy + oh * sy:sy, ix:ix + ow * sx:sx, :] += \
                cols[:, :, :, iy, ix, :]
    return out[:, py:py + h, px:px + w, :]


class Conv(ForwardUnit):
    """2-D convolution, NHWC x HWIO -> NHWC."""

    activation_mode = "linear"

    def __init__(self, workflow=None, n_kernels: int = None,  # type: ignore
                 kx: int = 3, ky: int = 3,
                 padding: Any = 0, sliding: Any = 1,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        if n_kernels is None:
            raise ValueError(f"{self.name}: n_kernels required")
        self.n_kernels = n_kernels
        self.kx, self.ky = kx, ky
        self.padding = _pair(padding)   # (pad_y, pad_x)
        self.sliding = _pair(sliding)   # (stride_y, stride_x)

    def output_shape_for(self, input_shape):
        b, h, w, c = input_shape
        py, px = self.padding
        sy, sx = self.sliding
        return (b, conv_out_size(h, self.ky, py, sy),
                conv_out_size(w, self.kx, px, sx), self.n_kernels)

    def param_shapes(self, input_shape):
        c = input_shape[-1]
        shapes = {"weights": (self.ky, self.kx, c, self.n_kernels)}
        if self.include_bias:
            shapes["bias"] = (self.n_kernels,)
        return shapes

    # -- compute -------------------------------------------------------

    def pre_activation(self, params, x):
        if isinstance(x, np.ndarray):
            patches = im2col(x, self.ky, self.kx, self.padding,
                             self.sliding)
            b, oh, ow = patches.shape[:3]
            w2 = params["weights"].reshape(-1, self.n_kernels)
            v = patches.reshape(b, oh, ow, -1) @ w2
        else:
            from jax import lax
            py, px = self.padding
            v = lax.conv_general_dilated(
                x, params["weights"],
                window_strides=self.sliding,
                padding=((py, py), (px, px)),
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if "bias" in params:
            v = v + params["bias"]
        return v

    def activation(self, v):
        return v

    def apply(self, params, inputs, rng=None) -> Dict[str, Any]:
        return {"output": self.activation(
            self.pre_activation(params, inputs["input"]))}


class ConvTanh(Conv):
    activation_mode = "tanh"

    def activation(self, v):
        if isinstance(v, np.ndarray):
            return np.tanh(v)
        import jax.numpy as jnp
        return jnp.tanh(v)


class ConvRELU(Conv):
    activation_mode = "relu"

    def activation(self, v):
        if isinstance(v, np.ndarray):
            return np.maximum(v, 0)
        import jax.numpy as jnp
        return jnp.maximum(v, 0)


class GradientDescentConv(GradientUnit):
    """Backward for Conv* (reference: veles/znicz/gd_conv.py)."""

    can_skip_err_input = True

    def backward_from_saved(self, params, saved, err_output,
                            need_err_input=True):
        x, out = saved
        err_pre = self.act_deriv(out, err_output)
        f = self.forward
        if isinstance(err_output, np.ndarray):
            patches = im2col(x, f.ky, f.kx, f.padding, f.sliding)
            b, oh, ow = patches.shape[:3]
            pf = patches.reshape(b * oh * ow, -1)
            ef = err_pre.reshape(b * oh * ow, f.n_kernels)
            grads = {"weights": (pf.T @ ef).reshape(
                f.ky, f.kx, x.shape[-1], f.n_kernels)}
            if "bias" in params:
                grads["bias"] = err_pre.sum(axis=(0, 1, 2))
            if not need_err_input:
                return None, grads
            # err_input: scatter err_pre @ W^T back through the windows
            cols = (ef @ params["weights"].reshape(-1, f.n_kernels).T) \
                .reshape(b, oh, ow, f.ky, f.kx, x.shape[-1])
            err_input = col2im(cols, x.shape, f.padding, f.sliding)
            return err_input, grads
        import jax

        if not need_err_input:
            _, vjp = jax.vjp(lambda p: f.pre_activation(p, x), params)
            (grads,) = vjp(err_pre)
            return None, grads

        def pre(p, xx):
            return f.pre_activation(p, xx)

        _, vjp = jax.vjp(pre, params, x)
        grads, err_input = vjp(err_pre)
        return err_input, grads


GDConvTanh = GradientDescentConv
GDConvRELU = GradientDescentConv
