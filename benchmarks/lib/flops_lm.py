"""Operations and minimum HBM bytes of a language-model configuration's
``layers`` list whose layers are ``gated_delta_net``,
``gated_attention`` and ``moe`` beside the older sequence types — from
the list and the row length alone, kept with the benchmark and read the
same whatever implements a layer (the older types by ``flops_seq``'s
own counts).

Model FLOPs count matmuls only.  The gated delta rule is counted as the
token-by-token recurrence counts it — ``S^T k``, ``k delta^T``, ``S^T
q``: 6 x dk x dv a token and value head — whatever chunked form runs;
the routed experts by the EXPECTED number of held experts a token
(``top_k * experts_held / experts_total``: 0.625 at 32 of 512, top 10),
not by what a seed's routing sends here; causal attention by the keys
up to the query.  A trained token costs 3x its forward FLOPs;
**recomputed forwards are not counted**.

A mechanism's floor is, per layer, the larger of its FLOPs over the
chip's peak and its minimum bytes over the chip's bandwidth, forward +
backward: the least time a step can spend under its scopes.
"""

from __future__ import annotations

from typing import Any, Dict

from .reference_qwen3next import flatten

ACT_BYTES = 2          # bf16 activations and the step's weight copy


def _held_per_token(fw: Dict[str, Any]) -> float:
    return float(fw["top_k"]) * int(fw["experts_held"]) \
        / int(fw["experts_total"])


def gdn_rule_flops(fw: Dict[str, Any], seq_len: int) -> float:
    """Forward FLOPs of the rule alone over one row."""
    return 6.0 * seq_len * int(fw["n_value_heads"]) \
        * int(fw["key_head_size"]) * int(fw["value_head_size"])


def gdn_widths(fw: Dict[str, Any]):
    """(convolved channels, value width) of a Gated DeltaNet layer."""
    kw = int(fw["n_key_heads"]) * int(fw["key_head_size"])
    vw = int(fw["n_value_heads"]) * int(fw["value_head_size"])
    return 2 * kw + vw, vw


def attention_core_flops(fw: Dict[str, Any], seq_len: int) -> float:
    """Forward FLOPs of causal scores and weighted sums over one row:
    2 + 2 per key up to the query, head and head element."""
    width = int(fw["n_heads"]) * int(fw["head_size"])
    return 4.0 * width * seq_len * (seq_len + 1) / 2.0


def moe_flops(fw: Dict[str, Any], width: int, seq_len: int) -> float:
    """Forward FLOPs of router + shared expert + the held experts'
    expected share, over one row."""
    return seq_len * width * (
        2.0 * int(fw["experts_total"]) + 6.0 * int(fw["shared_size"])
        + 6.0 * int(fw["expert_size"]) * _held_per_token(fw))


def moe_weights(fw: Dict[str, Any], width: int) -> int:
    """Parameters a ``moe`` layer holds here."""
    return width * int(fw["experts_total"]) \
        + 3 * int(fw["experts_held"]) * width * int(fw["expert_size"]) \
        + 3 * width * int(fw["shared_size"]) + width


def forward_flops_per_row(layers, seq_len: int) -> float:
    total, width = 0.0, None
    for cfg in flatten(layers):
        kind, fw = cfg["type"], cfg.get("->", {})
        if kind == "embedding":
            width = int(fw["hidden_size"])
        elif kind == "gated_delta_net":
            conv, out = gdn_widths(fw)
            total += 2.0 * seq_len * width * (
                conv + out + 2 * int(fw["n_value_heads"])) \
                + gdn_rule_flops(fw, seq_len)
            width = out
        elif kind == "gated_attention":
            nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
            d = int(fw["head_size"])
            total += 2.0 * seq_len * width * (2 * nh + 2 * nkv) * d \
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
            raise ValueError(f"flops_lm: unknown layer type {kind!r}")
    return total


def train_flops_per_row(layers, seq_len: int) -> float:
    return 3.0 * forward_flops_per_row(layers, seq_len)


def _floor(layers, kind, rows, per_layer, peak_flops, peak_bytes_per_s):
    floor, width = 0.0, None
    for cfg in flatten(layers):
        fw = cfg.get("->", {})
        if cfg["type"] == kind:
            flops, nbytes = per_layer(fw, width)
            floor += rows * max(flops / peak_flops,
                                nbytes / peak_bytes_per_s)
        if cfg["type"] == "embedding":
            width = int(fw["hidden_size"])
        elif cfg["type"] == "dense":
            width = int(fw["output_size"])
        elif cfg["type"] == "gated_delta_net":
            width = gdn_widths(fw)[1]
        elif cfg["type"] == "gated_attention":
            width = int(fw["n_heads"]) * int(fw["head_size"])
    return floor


def gdn_floor_seconds(layers, seq_len: int, rows: int, peak_flops: float,
                      peak_bytes_per_s: float) -> float:
    """Convolution + rule + gated norm (not the projections), forward +
    backward: the rule's FLOPs, or the pre-convolution q, k, v, the
    gate z and the output, each and its gradient read or written once."""
    def per_layer(fw, width):
        conv, out = gdn_widths(fw)
        return (3.0 * gdn_rule_flops(fw, seq_len),
                2.0 * seq_len * (conv + 2 * out) * ACT_BYTES)
    return _floor(layers, "gated_delta_net", rows, per_layer, peak_flops,
                  peak_bytes_per_s)


def attention_floor_seconds(layers, seq_len: int, rows: int,
                            peak_flops: float, peak_bytes_per_s: float
                            ) -> float:
    """Scores, softmax and weighted sums (not the projections), forward
    + backward: their FLOPs, or q, k, v, o and their gradients read or
    written once."""
    def per_layer(fw, width):
        d = int(fw["head_size"])
        wide = (2 * int(fw["n_heads"]) + 2 * int(fw["n_kv_heads"])) * d
        return (3.0 * attention_core_flops(fw, seq_len),
                2.0 * seq_len * wide * ACT_BYTES)
    return _floor(layers, "gated_attention", rows, per_layer, peak_flops,
                  peak_bytes_per_s)


def moe_floor_seconds(layers, seq_len: int, rows: int, peak_flops: float,
                      peak_bytes_per_s: float) -> float:
    """Router + dispatch + held experts + shared expert, forward +
    backward: their FLOPs, or the layer's weights read twice and their
    gradients written once, the layer's input and output and their
    gradients once."""
    def per_layer(fw, width):
        return (3.0 * moe_flops(fw, width, seq_len),
                (3.0 * moe_weights(fw, width)
                 + 4.0 * seq_len * width) * ACT_BYTES)
    return _floor(layers, "moe", rows, per_layer, peak_flops,
                  peak_bytes_per_s)
