"""Operations and minimum HBM bytes of a sequence configuration's
``layers`` list (``residual`` entries and the sequence layer types),
from the list and the row length alone — kept with the benchmark and
read the same whatever implements a layer.

Model FLOPs count matmuls only (projections, attention scores and
weighted sums, the head): the embedding is a gather, norms, RoPE, the
softmax and SwiGLU's product are element work (adding them would raise
a utilisation).  A trained token costs 3x its forward FLOPs;
**recomputed forwards are not counted**.
"""

from __future__ import annotations

from typing import Any, Dict

from .reference_evabyte import flatten

ACT_BYTES = 2          # bf16 activations


def eva_keys_per_query(fw: Dict[str, Any], seq_len: int) -> float:
    """Mean number of keys a query scores: its place in its window
    (exact local keys) plus one summary for every chunk of every
    earlier window."""
    win = min(int(fw["window_size"]), seq_len)
    per_window = win // int(fw["chunk_size"])
    n_windows = seq_len // win
    local = (win + 1) / 2.0
    remote = per_window * (n_windows - 1) / 2.0
    return local + remote


def eva_forward_flops(fw: Dict[str, Any], seq_len: int,
                      summaries: bool = False) -> float:
    """Forward FLOPs of one EVA attention layer over one row, without
    its projections: 2 (score) + 2 (weighted sum) per key, head and
    head element; with ``summaries`` also the chunk pooling (a score
    and two weighted sums per position)."""
    width = int(fw["n_heads"]) * int(fw["head_size"])
    flops = 4.0 * width * eva_keys_per_query(fw, seq_len) * seq_len
    if summaries:
        flops += 6.0 * width * seq_len
    return flops


def forward_flops_per_row(layers, seq_len: int) -> float:
    total, width = 0.0, None
    for cfg in flatten(layers):
        kind, fw = cfg["type"], cfg.get("->", {})
        if kind == "embedding":
            width = int(fw["hidden_size"])
        elif kind == "eva_attention":
            out = int(fw["n_heads"]) * int(fw["head_size"])
            total += 2.0 * seq_len * width * 3 * out \
                + eva_forward_flops(fw, seq_len)
            width = out
        elif kind == "dense":
            total += 2.0 * seq_len * width * int(fw["output_size"])
            width = int(fw["output_size"])
        elif kind == "swiglu":
            total += 2.0 * seq_len * width * 2 * int(
                fw["intermediate_size"])
            width = int(fw["intermediate_size"])
        elif kind == "lm_head":
            total += 2.0 * seq_len * width * int(fw["n_pred_heads"]) \
                * int(fw["vocab_size"])
        elif kind != "rmsnorm":
            raise ValueError(f"flops_seq: unknown layer type {kind!r}")
    return total


def train_flops_per_row(layers, seq_len: int) -> float:
    return 3.0 * forward_flops_per_row(layers, seq_len)


def eva_floor_seconds(layers, seq_len: int, rows: int,
                      peak_flops: float, peak_bytes_per_s: float
                      ) -> float:
    """The least time one train step can spend in EVA attention
    (summaries + local + remote, forward + backward) over ``rows``
    rows: per layer the larger of FLOPs / peak and minimum bytes / peak
    — q, k, v, o and their gradients read or written once each."""
    floor = 0.0
    for cfg in flatten(layers):
        if cfg["type"] != "eva_attention":
            continue
        fw = cfg["->"]
        width = int(fw["n_heads"]) * int(fw["head_size"])
        flops = 3.0 * eva_forward_flops(fw, seq_len, summaries=True)
        nbytes = 8.0 * seq_len * width * ACT_BYTES
        floor += rows * max(flops / peak_flops,
                            nbytes / peak_bytes_per_s)
    return floor
