"""Operations of a language-model configuration's ``layers`` list whose
attention layers are ``attention`` with a sigmoid gate a head, beside
a dense ``swiglu`` MLP, ``moe``, ``dense`` and ``lm_head``: from the
list and the row length alone, kept with the benchmark and read the
same whatever implements a layer.

Model FLOPs count matmuls only: an attention layer's projections (its
gate's ``[hidden, heads]`` product among them) and its core by the keys
a query reads (``flops_lm_swa``'s count: ``min(n + 1, window)`` at
position n); a SwiGLU's gate and up products (its down projection is
the ``dense`` that follows); the routed experts by the EXPECTED number
of held experts a token (``flops_lm``'s count: ``top_k *
experts_held / experts_total``, 0.3125 at 8 of 256, top 10).  A
trained token costs 3x its forward FLOPs; **recomputed forwards are
not counted**.
"""

from __future__ import annotations

from .flops_lm import moe_flops
from .flops_lm_swa import attention_core_flops
from .reference_qwen3next import flatten


def forward_flops_per_row(layers, seq_len: int) -> float:
    total, width = 0.0, None
    for cfg in flatten(layers):
        kind, fw = cfg["type"], cfg.get("->", {})
        if kind == "embedding":
            width = int(fw["hidden_size"])
        elif kind == "attention":
            nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
            d = int(fw["head_size"])
            gate = nh if fw.get("gate") else 0
            total += 2.0 * seq_len * width * ((nh + 2 * nkv) * d + gate) \
                + attention_core_flops(fw, seq_len)
            width = nh * d
        elif kind == "swiglu":
            n = int(fw["intermediate_size"])
            total += 4.0 * seq_len * width * n
            width = n
        elif kind == "moe":
            total += moe_flops(fw, width, seq_len)
        elif kind == "dense":
            total += 2.0 * seq_len * width * int(fw["output_size"])
            width = int(fw["output_size"])
        elif kind == "lm_head":
            total += 2.0 * seq_len * width * int(fw["n_pred_heads"]) \
                * int(fw["vocab_size"])
        elif kind != "rmsnorm":
            raise ValueError(f"flops_lm_gate: unknown layer type {kind!r}")
    return total


def train_flops_per_row(layers, seq_len: int) -> float:
    return 3.0 * forward_flops_per_row(layers, seq_len)
