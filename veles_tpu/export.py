"""Export a trained workflow to the VTPN binary format for the native
C++ inference runtime (native/src/libveles.cc — the libVeles/libZnicz
equivalent, SURVEY.md §3.3).

The format carries only what inference needs: the forward op chain with
shapes, hyperparameters, and float32 weights.  Training-only units
(dropout keeps its slot as identity so layer indices match the source
workflow) are preserved structurally.
"""

from __future__ import annotations

import struct
from typing import Any, BinaryIO, Dict, List, Tuple

import numpy as np

# op / activation / attr enums — must match native/src/libveles.cc
OP_DENSE, OP_CONV, OP_MAXPOOL, OP_AVGPOOL, OP_LRN, OP_DROPOUT, \
    OP_DECONV, OP_ACTIVATION, OP_STOCHPOOL_EVAL, OP_BINARIZE \
    = range(1, 11)
ACT = {"linear": 0, "tanh": 1, "relu": 2, "sigmoid": 3, "softmax": 4,
       "log": 5}
A_KX, A_KY, A_SX, A_SY, A_PX, A_PY, A_NKERN, A_LRN_N, A_ALPHA, \
    A_BETA, A_K = range(11)

MAGIC = b"VTPN"
VERSION = 1


def _op_record(unit) -> Tuple[int, int, Dict[int, float],
                              Dict[int, np.ndarray]]:
    """(op_type, act, attrs, tensors) for one forward unit."""
    from veles_tpu.ops.activation import ActivationBase
    from veles_tpu.ops.all2all import All2All
    from veles_tpu.ops.conv import Conv
    from veles_tpu.ops.deconv import Deconv
    from veles_tpu.ops.dropout import Dropout
    from veles_tpu.ops.lrn import LRNormalizer
    from veles_tpu.ops.pooling import (AvgPooling, MaxPooling,
                                       StochasticPooling)
    from veles_tpu.ops.rbm import Binarization

    act = ACT.get(unit.activation_mode, 0)
    tensors: Dict[int, np.ndarray] = {}
    if getattr(unit, "weights", None) and unit.weights:
        tensors[0] = np.asarray(unit.weights.map_read(), np.float32)
    if getattr(unit, "bias", None) and unit.bias and unit.include_bias:
        tensors[1] = np.asarray(unit.bias.map_read(), np.float32)

    if isinstance(unit, Deconv):
        py, px = unit.padding
        sy, sx = unit.sliding
        return OP_DECONV, act, {A_KX: unit.kx, A_KY: unit.ky,
                                A_SX: sx, A_SY: sy, A_PX: px, A_PY: py,
                                A_NKERN: unit.n_kernels}, tensors
    if isinstance(unit, Conv):
        py, px = unit.padding
        sy, sx = unit.sliding
        return OP_CONV, act, {A_KX: unit.kx, A_KY: unit.ky,
                              A_SX: sx, A_SY: sy, A_PX: px, A_PY: py,
                              A_NKERN: unit.n_kernels}, tensors
    if isinstance(unit, All2All):
        return OP_DENSE, act, {}, tensors
    if isinstance(unit, StochasticPooling):
        sy, sx = unit.sliding
        return OP_STOCHPOOL_EVAL, 0, {A_KX: unit.kx, A_KY: unit.ky,
                                      A_SX: sx, A_SY: sy}, {}
    if isinstance(unit, MaxPooling):
        sy, sx = unit.sliding
        return OP_MAXPOOL, 0, {A_KX: unit.kx, A_KY: unit.ky,
                               A_SX: sx, A_SY: sy}, {}
    if isinstance(unit, AvgPooling):
        sy, sx = unit.sliding
        return OP_AVGPOOL, 0, {A_KX: unit.kx, A_KY: unit.ky,
                               A_SX: sx, A_SY: sy}, {}
    if isinstance(unit, LRNormalizer):
        return OP_LRN, 0, {A_LRN_N: unit.n, A_ALPHA: unit.alpha,
                           A_BETA: unit.beta, A_K: unit.k}, {}
    if isinstance(unit, Dropout):
        return OP_DROPOUT, 0, {}, {}
    if isinstance(unit, Binarization):
        # inference semantics = the unit's eval mode: x > 0.5
        return OP_BINARIZE, 0, {}, {}
    if isinstance(unit, ActivationBase):
        return OP_ACTIVATION, act, {}, {}
    raise ValueError(
        f"unit {unit.name} ({type(unit).__name__}) has no native "
        f"inference equivalent")


def _write_op(f: BinaryIO, op_type: int, act: int,
              attrs: Dict[int, float],
              tensors: Dict[int, np.ndarray]) -> None:
    f.write(struct.pack("<III", op_type, act, len(attrs)))
    for key in sorted(attrs):
        f.write(struct.pack("<Id", key, float(attrs[key])))
    f.write(struct.pack("<I", len(tensors)))
    for tid in sorted(tensors):
        arr = np.ascontiguousarray(tensors[tid], np.float32)
        f.write(struct.pack("<II", tid, arr.ndim))
        f.write(struct.pack(f"<{arr.ndim}q", *arr.shape))
        f.write(arr.tobytes())


def export_model(workflow, path: str) -> str:
    """Serialize an initialized workflow's forward chain to ``path``."""
    forwards: List[Any] = list(workflow.forwards)
    if not forwards:
        raise ValueError("workflow has no forward units")
    if any(getattr(u, "residual_of", None) is not None
           for u in forwards):
        raise ValueError("a residual entry has no native inference "
                         "equivalent (the format is a plain line)")
    fused = getattr(workflow, "fused", None)
    if fused is not None and fused._params is not None:
        fused.sync_params_to_vectors()  # pull trained HBM state to host
    in_shape = tuple(forwards[0].input.shape[1:])
    records = [_op_record(u) for u in forwards]
    from veles_tpu.snapshotter import atomic_write
    with atomic_write(path, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<II", VERSION, len(records)))
        f.write(struct.pack("<q", len(in_shape)))
        f.write(struct.pack(f"<{len(in_shape)}q", *in_shape))
        for rec in records:
            _write_op(f, *rec)
    return path
