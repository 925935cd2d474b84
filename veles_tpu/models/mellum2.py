"""Mellum2: a decoder whose attention layers are sliding-window and full
causal attention mixed 3 : 1, each kind with a RoPE law of its own, and
a mixture of experts with no shared expert behind every layer, trained
through ``StandardWorkflow`` like every other model file:

    python -m veles_tpu -b tpu veles_tpu/models/mellum2.py

Source: https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/
blob/main/config.json (``model_type`` ``mellum``).

**Published sizes** (``PUBLISHED``): 28 layers, hidden 2304, vocabulary
98 304, context 131 072; ``layer_types[i]`` is ``sliding_attention``
except where (i + 1) mod 4 = 0, ``full_attention``; 32 query heads x
128 over 4 key/value heads, no bias; ``sliding_window`` 1024;
``rope_parameters``: the window layers plain RoPE at theta 5e5, the
full layers YaRN (theta 5e5, factor 16, original 8192, beta 32 / 1,
``attention_factor`` 1.2772588722239782); ``rms_norm_eps`` 1e-6; every
``mlp_layer_types[i]`` ``sparse``: 64 experts of width 896, 8 a token,
``norm_topk_prob``, no shared expert (``intermediate_size`` 7168 is
carried and unused); untied embedding and head.  No width is a
parameter here.  What is: ``n_layers`` (28 published), ``experts_held``
/ ``first_held`` (which of the 64 experts this device holds) and
``vocab_held`` (how many vocabulary rows).  **The cut the default and
the benchmark's cell train**: whole, one layer is 417 747 456
parameters — 4.18 GB at this repo's 10 B a parameter — so a period of
four (16.7 GB) fits no chip; 4 chips share each layer (experts
expert-parallel in quarters, the vocabulary in quarters, attention
whole on each) and this chip is one of them, holding the first of 7
pipeline stages of one period: 4 of 28 layers, experts 0-15 of 64 a
layer, 24 576 of 98 304 vocabulary rows: 595 153 152 parameters.  The
router scores all 64 experts and a token takes its 8 as published; only
held experts contribute, and that partial sum goes on — no code stands
in for the absent chips.  A step is four rows of 8 192 tokens (the
length the model was pre-trained at): a held expert sees 32 768 x 8 /
64 = 4 096 rows a step.  ``TINY`` is for the CPU tests only.

**What is computed** (``ops/attention.py``, ``ops/moe.py``,
``ops/sequence.py``; the plain reference is ``benchmarks/lib/
reference_mellum2.py``).  ``N(x) = x / sqrt(mean(x^2) + 1e-6) * g``,
statistics in f32 (the ``rmsnorm`` unit holds ``g - 1``, from 0, under
no decay: the same function and the same gradient).  A layer: ``h = x
+ Attn_i(N1(x)) W_o``, ``x' = h + MoE(N2(h))`` — two ``residual``
entries of the ``layers`` list.  After the last layer ``N``, then
logits ``W_head N(x)`` in f32 over the held ids; the loss
(``next_byte`` with one head) is the mean next-token cross-entropy over
the valid positions.

Attention: ``q = u W_q [T, 32, 128]``, ``k, v = u W_k, u W_v [T, 4,
128]``; rotate-half RoPE over the whole head with inverse frequencies
``f`` and scale ``a``: ``q, k <- a (x cos(n f) + rot(x) sin(n f))``.
Window layers: ``f_j = 5e5^(-2j/128)``, ``a = 1``.  Full layers (YaRN
as ``transformers`` computes it): ``e_j = 5e5^(-2j/128)``, ``p_j = e_j
/ 16``, ``dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 5e5)``, ``low =
max(floor(dim(32)), 0)``, ``high = min(ceil(dim(1)), 127)``, ``ramp_j =
clip((j - low) / (high - low), 0, 1)``, ``f_j = p_j ramp_j + e_j (1 -
ramp_j)``, ``a = 1.2772588722239782`` (on q and k alike, so on the
scores squared).  Query head h reads key head h // 8; ``o_n =
softmax_m(q_n . k_m / sqrt(128)) v_m`` over ``m <= n`` on a full layer
and over ``n - 1024 < m <= n`` on a window layer, scores and softmax in
f32; then the 4096 -> 2304 out-projection (the entry's ``dense``).  On
a TPU the core is the flash kernel that ships with jax — it visits no
block above the diagonal and none left of the window — elsewhere a
block of queries at a time (``attn.path``: ``form``, ``window``,
``rope``, ``kv_blocks``).

MoE: ``p = softmax(u W_r)`` over all 64 in f32; the 8 largest, ``w_e =
p_e / sum_8 p``; ``y = sum_{e in top 8 and held} w_e W_d,e(silu(W_g,e u)
* W_u,e u)``, expert width 896; no shared expert.  Static dispatch
buffers sized for the worst routing, nothing dropped (``moe.share``,
``moe.load``).

**Assumed** — the published config is silent on each; one line each:
what, why, what in the numbers would change if it were wrong:

1. no per-head q / k norm (the config has no key for one) — one would
   add two elementwise passes over q and k a layer.
2. softmax over all 64 experts, then the top 8, renormalised (no
   ``scoring_func`` key; ``norm_topk_prob`` true) — a sigmoid score
   would change values, not the work.
3. a window of 1024 keys counting the query's own (``n - 1024 < m <=
   n``, as ``transformers`` masks) — one key more or fewer a query.
4. no auxiliary load-balancing loss (the config carries no
   coefficient) — one would add a reduction over the router's
   probabilities a layer, nothing to the matmuls.
5. rows are packed documents with no mask at document boundaries — a
   document mask would skip blocks, fewer the longer the documents.
6. the optimiser: the repo's momentum SGD (0.01 / 0.9 / 5e-4; no decay
   on gains) — the repo has no other; Adam would add 8 B a parameter
   of state (the cut would hold fewer experts).
7. bf16 compute, f32 parameters; scores, softmax, the router's softmax
   and the logits in f32 — the published code's upcasts; f32 compute
   would halve the MXU's rate.
8. matrices N(0, 0.02^2), gains 1 — affects the loss's value, not the
   work.

**Departures**: the multi-token-prediction module the model card
mentions is in no key of the config and is not built.

Recomputation and the blocked loss are not settings:
``FusedStepRunner`` decides both from shapes against the device's free
memory (``fused.recompute``, ``loss.blocked``).
"""

from __future__ import annotations

from veles_tpu.loader.synthetic import PackedTokensLoader
from veles_tpu.models import model_config
from veles_tpu.ops.standard_workflow import StandardWorkflow

GD = {"learning_rate": 0.01, "weight_decay": 0.0005,
      "gradient_moment": 0.9}

PUBLISHED = {
    "hidden_size": 2304, "vocab_size": 98304, "n_layers": 28,
    "full_attention_interval": 4,
    "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
    "sliding_window": 1024,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "rms_norm_eps": 1e-6,
    "num_experts": 64, "num_experts_per_tok": 8,
    "moe_intermediate_size": 896, "intermediate_size": 7168,
    "initializer_range": 0.02, "max_position_embeddings": 131072}

#: the share of the published model one chip holds (the docstring's
#: cut), and the rows it trains on
CUT = {"n_layers": 4, "experts_held": 16, "first_held": 0,
       "vocab_held": 24576, "seq_len": 8192, "minibatch": 4}

TINY = {
    "hidden_size": 64, "vocab_size": 256, "n_layers": 4,
    "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 32,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 64, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "rms_norm_eps": 1e-6,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32,
    "initializer_range": 0.05,
    "experts_held": 2, "first_held": 0, "vocab_held": 64,
    "seq_len": 128}


def layer_types(n_layers: int, interval: int):
    """``sliding_attention`` except where (i + 1) mod ``interval`` = 0."""
    return ["full_attention" if (i + 1) % interval == 0
            else "sliding_attention" for i in range(n_layers)]


def mellum2_layers(n_layers: int = CUT["n_layers"],
                   experts_held: int = CUT["experts_held"],
                   first_held: int = CUT["first_held"],
                   vocab_held: int = CUT["vocab_held"], **sizes):
    """The ``layers`` list of ``n_layers`` layers at ``PUBLISHED``
    sizes (``sizes`` overrides: the tests' ``TINY``), this device
    holding ``experts_held`` experts from ``first_held`` and
    ``vocab_held`` vocabulary rows."""
    s = dict(PUBLISHED, **sizes)
    std = {"weights_stddev": s["initializer_range"]}
    hidden = s["hidden_size"]
    norm = {"type": "rmsnorm", "->": {"eps": s["rms_norm_eps"]},
            "<-": GD}
    out = {"type": "dense", "->": {"output_size": hidden, **std},
           "<-": GD}

    def attention(kind):
        return {"type": "attention",
                "->": {"n_heads": s["num_attention_heads"],
                       "n_kv_heads": s["num_key_value_heads"],
                       "head_size": s["head_dim"],
                       "window": s["sliding_window"]
                       if kind == "sliding_attention" else None,
                       "rope": dict(s["rope_parameters"][kind]), **std},
                "<-": GD}

    moe = {"type": "moe",
           "->": {"experts_total": s["num_experts"],
                  "experts_held": experts_held,
                  "first_held": first_held,
                  "top_k": s["num_experts_per_tok"],
                  "expert_size": s["moe_intermediate_size"],
                  "shared_size": 0, **std},
           "<-": GD}
    layers = [{"type": "embedding",
               "->": {"vocab_size": vocab_held, "hidden_size": hidden,
                      **std},
               "<-": GD}]
    for kind in layer_types(n_layers, s["full_attention_interval"]):
        layers.append({"type": "residual",
                       "layers": [norm, attention(kind), out]})
        layers.append({"type": "residual", "layers": [norm, moe]})
    layers.append(norm)
    layers.append({"type": "lm_head",
                   "->": {"vocab_size": vocab_held, "n_pred_heads": 1,
                          **std},
                   "<-": GD})
    return layers


DEFAULTS = {
    # four packed rows a step; the store is short — the model, its
    # momentum and the step's activations are what fill a chip
    "loader": {"minibatch_size": CUT["minibatch"], "n_train": 16,
               "n_valid": 0, "seq_len": CUT["seq_len"],
               "median_len": 1024, "seed": 98304},
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
    cfg = model_config("mellum2", DEFAULTS).todict()
    cfg.update(overrides)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedTokensLoader(
            wf, name="loader", vocab_size=cfg["vocab_held"],
            **cfg["loader"]),
        layers=cfg.get("layers") or mellum2_layers(
            cfg["n_layers"], cfg["experts_held"], cfg["first_held"],
            cfg["vocab_held"], **(cfg.get("sizes") or {})),
        loss_function="next_byte",
        decision_config=cfg["decision"],
        snapshotter_config=cfg.get("snapshotter"),
        superstep=cfg["superstep"],
        name="Mellum2Workflow")
    launcher.workflow = w
    return w


def run(launcher):
    launcher.create_workflow(create_workflow)
    launcher.initialize()
    launcher.run()
