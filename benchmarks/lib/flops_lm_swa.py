"""Operations and minimum HBM bytes of a language-model configuration's
``layers`` list whose attention layers are plain grouped-query
``attention`` — causal, under a window of keys where the layer has one —
beside ``moe`` and the older sequence types: from the list and the row
length alone, kept with the benchmark and read the same whatever
implements a layer.

Model FLOPs count matmuls only.  The attention core by the keys a query
reads: those up to itself, ``window`` of them at most — ``min(n + 1,
window)`` at position n — whatever blocks a kernel visits; the routed
experts by the EXPECTED number of held experts a token (``flops_lm``'s
count: ``top_k * experts_held / experts_total``, 2 at 16 of 64, top 8).
A trained token costs 3x its forward FLOPs; **recomputed forwards are
not counted**.

A core's floor is, per layer, the larger of its FLOPs over the chip's
peak and its minimum bytes over the chip's bandwidth, forward +
backward: the least time a step can spend under its scope.
"""

from __future__ import annotations

from typing import Any, Dict

from .flops_lm import ACT_BYTES, moe_flops
from .reference_qwen3next import flatten


def pairs(seq_len: int, window) -> float:
    """(query, key) pairs of one row: a query reads the keys up to
    itself, ``window`` of them at most."""
    w = seq_len if window is None else min(int(window), seq_len)
    return w * (w + 1) / 2.0 + (seq_len - w) * float(w)


def attention_core_flops(fw: Dict[str, Any], seq_len: int) -> float:
    """Forward FLOPs of scores and weighted sums over one row: 2 + 2 a
    (query, key) pair, head and head element."""
    return 4.0 * int(fw["n_heads"]) * int(fw["head_size"]) \
        * pairs(seq_len, fw.get("window"))


def forward_flops_per_row(layers, seq_len: int) -> float:
    total, width = 0.0, None
    for cfg in flatten(layers):
        kind, fw = cfg["type"], cfg.get("->", {})
        if kind == "embedding":
            width = int(fw["hidden_size"])
        elif kind == "attention":
            nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
            d = int(fw["head_size"])
            total += 2.0 * seq_len * width * (nh + 2 * nkv) * d \
                + attention_core_flops(fw, seq_len)
            width = nh * d
        elif kind == "moe":
            total += moe_flops(fw, width, seq_len)
        elif kind == "dense":
            total += 2.0 * seq_len * width * int(fw["output_size"])
            width = int(fw["output_size"])
        elif kind == "lm_head":
            total += 2.0 * seq_len * width * int(fw["n_pred_heads"]) \
                * int(fw["vocab_size"])
        elif kind != "rmsnorm":
            raise ValueError(f"flops_lm_swa: unknown layer type {kind!r}")
    return total


def train_flops_per_row(layers, seq_len: int) -> float:
    return 3.0 * forward_flops_per_row(layers, seq_len)


def attention_floor_seconds(layers, seq_len: int, rows: int,
                            peak_flops: float, peak_bytes_per_s: float,
                            windowed: bool) -> float:
    """Scores, softmax and weighted sums (not the projections) of the
    layers that have a window (``windowed``) or of those that have none,
    forward + backward: their FLOPs, or q, k, v, o and their gradients
    read or written once."""
    floor = 0.0
    for cfg in flatten(layers):
        fw = cfg.get("->", {})
        if cfg["type"] != "attention" \
                or (fw.get("window") is not None) != windowed:
            continue
        wide = (2 * int(fw["n_heads"]) + 2 * int(fw["n_kv_heads"])) \
            * int(fw["head_size"])
        floor += rows * max(
            3.0 * attention_core_flops(fw, seq_len) / peak_flops,
            2.0 * seq_len * wide * ACT_BYTES / peak_bytes_per_s)
    return floor
