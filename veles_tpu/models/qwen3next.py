"""Qwen3-Next: a decoder of Gated DeltaNet layers with one gated
softmax-attention layer a period and a mixture of experts behind every
layer, trained through ``StandardWorkflow`` like every other model file:

    python -m veles_tpu -b tpu veles_tpu/models/qwen3next.py

Source: https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/
main/config.json (``model_type`` ``qwen3_next``); Gated DeltaNet is
Yang, Kautz, Hatamizadeh, arXiv:2412.06464.

**Published sizes** (``PUBLISHED``): 48 layers, hidden 2048, vocabulary
151 936, context 262 144; ``full_attention_interval`` 4 — layer i is
attention where (i + 1) mod 4 = 0, DeltaNet otherwise; DeltaNet: 16 key
heads serving 32 value heads, both head sizes 128, convolution kernel
4; attention: 16 query heads x 256 over 2 key/value heads,
``partial_rotary_factor`` 0.25 (RoPE on 64 of 256), ``rope_theta`` 1e7;
``rms_norm_eps`` 1e-6; every layer's MLP 512 experts of width 512, 10 a
token, ``norm_topk_prob``, beside one shared expert of width 512;
untied embedding and head.  No width is a parameter here.  What is:
``n_layers`` (48 published), ``experts_held`` / ``first_held`` (which of
the 512 experts this device holds) and ``vocab_held`` (how many
vocabulary rows).  **The cut the default and the benchmark's cell
train**: whole, one layer's experts are 1.61 G parameters — 16.1 GB at
this repo's 10 B a parameter — so no chip holds a layer; 16 chips share
each layer (expert-parallel, the vocabulary in eighths) and this chip
is one of them, holding the first pipeline stage of one period: 4 of 48
layers, experts 0-31 of 512 a layer, 18 992 of 151 936 vocabulary rows:
625 667 136 parameters.  The router scores all 512 experts and a token
takes its 10 as published; only held experts contribute, and that
partial sum goes on — no code stands in for the absent chips.  ``TINY``
is for the CPU tests only.

**What is computed** (``ops/deltanet.py``, ``ops/attention.py``,
``ops/moe.py``, ``ops/sequence.py``; the plain reference is
``benchmarks/lib/reference_qwen3next.py``).  ``N(x) = x / sqrt(mean(x^2)
+ 1e-6) * (1 + g)``, statistics in f32.  A layer: ``h = x +
Mix(N1(x))``, ``x' = h + MoE(N2(h))`` — two ``residual`` entries of the
``layers`` list.  After the last layer ``N``, then logits ``W_head
N(x)`` in f32 over the held ids; the loss (``next_byte`` with one head)
is the mean next-token cross-entropy over the valid positions.

Gated DeltaNet: from ``u = N1(x)``, ``q, k [T, 16, 128]``, ``v, z [T,
32, 128]``, ``b, a [T, 32]`` by bias-free projections; ``(q, k, v) <-
silu(causal depthwise conv4(q, k, v))`` (8192 channels, zeros left of
the row); ``beta = sigmoid(b)``; ``g = -exp(A_log) softplus(a +
dt_bias)``; q, k L2-normalised over the head (eps 1e-6), ``q <- q /
sqrt(128)``; value head h uses key head h // 2.  A head, in f32, ``S0
= 0 [128, 128]``: ``S <- exp(g_t) S``; ``delta_t = beta_t (v_t - S^T
k_t)``; ``S <- S + k_t delta_t^T``; ``o_t = S^T q_t``.  Out: ``y_t = w
* o_t / sqrt(mean(o_t^2) + 1e-6) * silu(z_t)`` a head, then the 4096 ->
2048 out-projection (the entry's ``dense``).  The program runs the
rule in chunks of 64 (the triangular system inside a chunk solved once
for all chunks, the state carried chunk to chunk) where the row is
whole chunks, the recurrence itself otherwise; on a TPU a chunk's
``[64, 64]`` matrices are made, inverted and used inside the fused
kernels of ``ops/deltanet_pallas.py``, forward and backward, elsewhere
as XLA ops (``gdn.path``: ``form``, ``products``).

Gated attention: ``W_q u [T, 16, 512]`` splits a head into query (256)
and gate (256); ``k, v [T, 2, 256]``; ``q <- N(q)``, ``k <- N(k)`` a
head; RoPE rotate-half on elements 0-63 of each head at theta 1e7, 64-
255 untouched; ``o = softmax(q k^T / 16 + causal) v``, scores and
softmax in f32, 8 query heads a key head; ``y = o * sigmoid(gate)``;
then the 4096 -> 2048 out-projection.  On a TPU the core is the flash
kernel that ships with jax, elsewhere a block of queries at a time
(``attn.path``); the ``[16, T, T]`` scores never exist.

MoE: ``p = softmax(W_r u)`` over all 512 in f32; the 10 largest, ``w_e
= p_e / sum_10 p``; ``y = sum_{e in top10 and held} w_e W_d,e(silu(W_g,e
u) * W_u,e u) + sigmoid(w_s . u) Shared(u)``.  Static dispatch buffers
sized for the worst routing, nothing dropped (``moe.share``,
``moe.load``).

**Assumed** — the published config is silent on each; one line each:
what, why, what in the numbers would change if it were wrong:

1. the optimiser: the repo's momentum SGD (0.01 / 0.9 / 5e-4; no decay
   on gains, ``A_log``, ``dt_bias``, the convolution) — the repo has no
   other; Adam would add 8 B a parameter of state (the cut would hold
   fewer experts) and an elementwise pass a step.
2. bf16 compute, f32 parameters; the rule's state and decays, the
   router's softmax and its product's accumulator in f32 — the
   config's ``torch_dtype`` and the published code's upcasts; f32
   compute would halve the MXU's rate.
3. matrices N(0, 0.02^2) (``initializer_range``), ``A_log`` = log of a
   uniform on [1, 16], ``dt_bias`` 1, the gated norm's gain 1, ``1 +
   g`` gains 0 (the published modeling code's initialisation), the
   convolution uniform on +-1/2 (torch's ``Conv1d`` default) — affects
   the loss's value, not the work.
4. no auxiliary load-balancing loss (the config carries no
   coefficient) — one would add a reduction over the router's
   probabilities a layer, nothing to the matmuls.
5. rows are packed documents with no mask and no reset of the
   convolution or the rule's state at document boundaries — a reset
   changes values, not the work.
6. the in-projections are held as separate matrices (the published
   code fuses and interleaves q, k, v, z and b, a) — the same products.

**Departures**: the multi-token prediction module the model card
mentions is not in the config's keys and is not built.

Recomputation and the blocked loss are not settings:
``FusedStepRunner`` keeps a residual entry's input alone and re-runs
its forward inside the backward walk when the residuals would not fit
(``fused.recompute``), and makes the head's logits, the loss and the
head's backward a block of positions at a time when the whole logits
would not (``loss.blocked``).
"""

from __future__ import annotations

from veles_tpu.loader.synthetic import PackedTokensLoader
from veles_tpu.models import model_config
from veles_tpu.ops.standard_workflow import StandardWorkflow

GD = {"learning_rate": 0.01, "weight_decay": 0.0005,
      "gradient_moment": 0.9}

PUBLISHED = {
    "hidden_size": 2048, "vocab_size": 151936, "n_layers": 48,
    "full_attention_interval": 4,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4,
    "num_attention_heads": 16, "num_key_value_heads": 2,
    "head_dim": 256, "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "rms_norm_eps": 1e-6,
    "num_experts": 512, "num_experts_per_tok": 10,
    "moe_intermediate_size": 512,
    "shared_expert_intermediate_size": 512,
    "initializer_range": 0.02, "max_position_embeddings": 262144,
    # how the program runs the rule, not a published size
    "chunk_size": 64}

#: the share of the published model one chip holds (the docstring's
#: cut), and the row it trains on
CUT = {"n_layers": 4, "experts_held": 32, "first_held": 0,
       "vocab_held": 18992, "seq_len": 32768}

TINY = {
    "hidden_size": 64, "vocab_size": 512, "n_layers": 4,
    "full_attention_interval": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "partial_rotary_factor": 0.25, "rope_theta": 1e7,
    "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32,
    "initializer_range": 0.05, "chunk_size": 16,
    "experts_held": 4, "first_held": 0, "vocab_held": 64,
    "seq_len": 128}


def qwen3next_layers(n_layers: int = CUT["n_layers"],
                     experts_held: int = CUT["experts_held"],
                     first_held: int = CUT["first_held"],
                     vocab_held: int = CUT["vocab_held"], **sizes):
    """The ``layers`` list of ``n_layers`` layers at ``PUBLISHED``
    sizes (``sizes`` overrides: the tests' ``TINY``), this device
    holding ``experts_held`` experts from ``first_held`` and
    ``vocab_held`` vocabulary rows."""
    s = dict(PUBLISHED, **sizes)
    std = {"weights_stddev": s["initializer_range"]}
    eps = s["rms_norm_eps"]
    hidden = s["hidden_size"]
    norm = {"type": "rmsnorm", "->": {"eps": eps}, "<-": GD}
    out = {"type": "dense", "->": {"output_size": hidden, **std},
           "<-": GD}
    delta = {"type": "gated_delta_net",
             "->": {"n_key_heads": s["linear_num_key_heads"],
                    "n_value_heads": s["linear_num_value_heads"],
                    "key_head_size": s["linear_key_head_dim"],
                    "value_head_size": s["linear_value_head_dim"],
                    "conv_kernel": s["linear_conv_kernel_dim"],
                    "chunk_size": s["chunk_size"], "eps": eps, **std},
             "<-": GD}
    attention = {"type": "gated_attention",
                 "->": {"n_heads": s["num_attention_heads"],
                        "n_kv_heads": s["num_key_value_heads"],
                        "head_size": s["head_dim"],
                        "rotary_size": int(
                            s["head_dim"] * s["partial_rotary_factor"]),
                        "rope_theta": s["rope_theta"], "eps": eps,
                        **std},
                 "<-": GD}
    moe = {"type": "moe",
           "->": {"experts_total": s["num_experts"],
                  "experts_held": experts_held,
                  "first_held": first_held,
                  "top_k": s["num_experts_per_tok"],
                  "expert_size": s["moe_intermediate_size"],
                  "shared_size": s["shared_expert_intermediate_size"],
                  **std},
           "<-": GD}
    layers = [{"type": "embedding",
               "->": {"vocab_size": vocab_held, "hidden_size": hidden,
                      **std},
               "<-": GD}]
    for i in range(n_layers):
        mixer = attention \
            if (i + 1) % s["full_attention_interval"] == 0 else delta
        layers.append({"type": "residual", "layers": [norm, mixer, out]})
        layers.append({"type": "residual", "layers": [norm, moe]})
    layers.append(norm)
    layers.append({"type": "lm_head",
                   "->": {"vocab_size": vocab_held, "n_pred_heads": 1,
                          **std},
                   "<-": GD})
    return layers


DEFAULTS = {
    # one packed row a step; the store is short — the model, its
    # momentum and the row's activations are what fill a chip
    "loader": {"minibatch_size": 1, "n_train": 4, "n_valid": 0,
               "seq_len": CUT["seq_len"], "median_len": 1024,
               "seed": 151936},
    "n_layers": CUT["n_layers"],
    "experts_held": CUT["experts_held"],
    "first_held": CUT["first_held"],
    "vocab_held": CUT["vocab_held"],
    "sizes": {},
    "superstep": 2,
    "decision": {"max_epochs": 8, "fail_iterations": 1000},
    "snapshotter": None,
}


def create_workflow(launcher, **overrides):
    cfg = model_config("qwen3next", DEFAULTS).todict()
    cfg.update(overrides)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedTokensLoader(
            wf, name="loader", vocab_size=cfg["vocab_held"],
            **cfg["loader"]),
        layers=cfg.get("layers") or qwen3next_layers(
            cfg["n_layers"], cfg["experts_held"], cfg["first_held"],
            cfg["vocab_held"], **(cfg.get("sizes") or {})),
        loss_function="next_byte",
        decision_config=cfg["decision"],
        snapshotter_config=cfg.get("snapshotter"),
        superstep=cfg["superstep"],
        name="Qwen3NextWorkflow")
    launcher.workflow = w
    return w


def run(launcher):
    launcher.create_workflow(create_workflow)
    launcher.initialize()
    launcher.run()
