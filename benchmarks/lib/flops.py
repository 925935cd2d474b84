"""Shapes, operations and minimum HBM bytes of a ``layers`` list, from
the list and the input shape alone.

MACs count convolutions and dense layers only: pooling, LRN, ReLU and
dropout element work is not model FLOPs (adding it would raise a
utilisation).  A trained image costs 3x its forward MACs (forward,
input gradient, weight gradient) — the usual convention; the first
layer's unused input gradient is NOT subtracted there, but it is in
the roofline floor, which must stay a true lower bound.

The byte model is the arithmetic of ``scripts/layer_roofline.py``
(``layer_rows``): bf16 activations and weight casts (2 B), every
tensor read or written once per use, weight and optimiser traffic
(f32 weights + momentum read and written: 16 B a parameter) amortised
over the minibatch.
"""

from __future__ import annotations

from math import prod
from typing import Any, Dict, List, Sequence, Tuple

ACT_BYTES = 2          # bf16 activations and the bf16 weight cast
OPT_BYTES = 16         # f32 weight + momentum, read and written

WEIGHTED = ("conv", "conv_relu", "conv_tanh", "all2all", "all2all_relu",
            "all2all_tanh", "softmax")


def pair(v) -> Tuple[int, int]:
    return (v, v) if isinstance(v, int) else (int(v[0]), int(v[1]))


def layer_shapes(layers: Sequence[Dict[str, Any]],
                 input_shape: Sequence[int]) -> List[Dict[str, Any]]:
    """One row per layer: kind, input/output sample shape, parameter
    shapes (HWIO conv kernels, (n_in, n_out) dense) and forward MACs
    per image."""
    rows = []
    shape = tuple(int(s) for s in input_shape)
    for i, cfg in enumerate(layers):
        kind = cfg["type"]
        fw = cfg.get("->", {})
        params: Dict[str, Tuple[int, ...]] = {}
        macs = 0
        if kind.startswith("conv"):
            h, w, c = shape
            ky, kx = int(fw.get("ky", 3)), int(fw.get("kx", 3))
            py, px = pair(fw.get("padding", 0))
            sy, sx = pair(fw.get("sliding", 1))
            n = int(fw["n_kernels"])
            out = ((h + 2 * py - ky) // sy + 1,
                   (w + 2 * px - kx) // sx + 1, n)
            params = {"weights": (ky, kx, c, n), "bias": (n,)}
            macs = out[0] * out[1] * ky * kx * c * n
        elif kind in ("max_pooling", "avg_pooling"):
            h, w, c = shape
            ky, kx = int(fw.get("ky", 2)), int(fw.get("kx", 2))
            sy, sx = pair(fw["sliding"]) if fw.get("sliding") \
                is not None else (ky, kx)
            out = ((h - ky) // sy + 1, (w - kx) // sx + 1, c)
        elif kind in ("norm", "dropout"):
            out = shape
        elif kind.startswith("all2all") or kind == "softmax":
            n_in = prod(shape)
            oss = fw["output_sample_shape"]
            n_out = int(oss) if isinstance(oss, int) else prod(oss)
            out = (n_out,)
            params = {"weights": (n_in, n_out), "bias": (n_out,)}
            macs = n_in * n_out
        else:
            raise ValueError(f"layer {i}: unknown type {kind!r}")
        rows.append({"index": i, "kind": kind, "in": shape, "out": out,
                     "params": params, "macs": macs})
        shape = out
    return rows


def forward_macs(layers, input_shape) -> int:
    return sum(r["macs"] for r in layer_shapes(layers, input_shape))


def train_flops_per_image(layers, input_shape) -> float:
    """2 FLOPs a MAC, x3 for forward + both backward products."""
    return 6.0 * forward_macs(layers, input_shape)


def param_count(layers, input_shape) -> int:
    return sum(prod(s) for r in layer_shapes(layers, input_shape)
               for s in r["params"].values())


def step_floor_seconds(layers, input_shape, minibatch: int,
                       peak_flops: float, hbm_bytes_per_s: float
                       ) -> Tuple[float, List[Dict[str, Any]]]:
    """Least seconds one train step (one minibatch, forward + backward
    + update) can take: the sum over layers of the larger of its MXU
    time and its HBM time.  Returns (seconds, per-layer rows)."""
    out_rows = []
    total = 0.0
    first_weighted = True
    for r in layer_shapes(layers, input_shape):
        in_b = prod(r["in"]) * ACT_BYTES
        out_b = prod(r["out"]) * ACT_BYTES
        kind = r["kind"]
        flops = 0.0
        if r["params"]:
            n_par = sum(prod(s) for s in r["params"].values())
            wpm = n_par * ACT_BYTES / minibatch
            first = first_weighted
            first_weighted = False
            # the chain head needs no input gradient: 2 products, not 3
            flops = 2.0 * r["macs"] * (2.0 if first else 3.0)
            bytes_s = (in_b + wpm + out_b            # forward
                       + out_b + in_b + wpm + in_b   # backward reads
                       + (0 if first else in_b)      # err_input write
                       + OPT_BYTES * n_par / minibatch)
        elif kind == "norm":
            bytes_s = (in_b + out_b + out_b * 2
                       + out_b + in_b + out_b * 2 + in_b)
        elif kind.endswith("pooling"):
            bytes_s = in_b + out_b + out_b + in_b + in_b
        elif kind == "dropout":
            bytes_s = out_b * 2
        else:
            bytes_s = 0.0
        t_mxu = flops * minibatch / peak_flops
        t_hbm = bytes_s * minibatch / hbm_bytes_per_s
        t = max(t_mxu, t_hbm)
        total += t
        out_rows.append({"index": r["index"], "kind": kind,
                         "t_mxu_s": t_mxu, "t_hbm_s": t_hbm,
                         "bound": "mxu" if t_mxu >= t_hbm else "hbm",
                         "floor_s": t})
    return total, out_rows
