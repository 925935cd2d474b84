"""Laguna-S-2.1: a decoder whose attention layers are full causal and
sliding-window attention mixed 1 : 3 with head counts of their own, a
sigmoid gate a head on every one, each kind with a RoPE law of its own
(YaRN on half of each head of the full layers), one leading dense
SwiGLU layer and then a mixture of 256 routed experts beside one shared
expert behind every other layer, trained through ``StandardWorkflow``
like every other model file:

    python -m veles_tpu -b tpu veles_tpu/models/laguna.py

Source: https://huggingface.co/poolside/Laguna-S-2.1/blob/main/
config.json (``model_type`` ``laguna``).

**Published sizes** (``PUBLISHED``): 48 layers, hidden 3072, vocabulary
100 352, untied embedding and head, ``rms_norm_eps`` 1e-6, no bias;
``layer_types[i]`` is ``full_attention`` where i mod 4 = 0, else
``sliding_attention`` with ``sliding_window`` 512;
``num_attention_heads_per_layer``: 48 query heads on a full layer, 72
on a window layer, both over 8 key / value heads of 128 (groups of 6
and of 9); ``gating`` ``per-head`` on every layer; ``rope_parameters``:
the full layers YaRN (theta 5e5, factor 128, original 8192, beta 32 /
1, ``attention_factor`` 1.4852030263919618, ``partial_rotary_factor``
0.5), the window layers plain RoPE at theta 1e4 over the whole head;
``mlp_only_layers`` [0]: a dense SwiGLU of width 12 288; every other
layer 256 routed experts of width 1024, 10 a token,
``norm_topk_prob``, ``moe_routed_scaling_factor`` 2.5, one shared
expert of width 1024; no softcapping.  No width is a parameter here.
What is: ``n_layers`` (48 published), ``experts_held`` / ``first_held``
(which of the 256 experts this device holds) and ``vocab_held``.

**The cut the default and the benchmark's cell train**: 32 chips share
each layer — the experts expert-parallel in 32nds, the vocabulary in
eighths, attention, the router, the shared expert and the dense layer
whole on each — and this chip holds the first pipeline stage, layers
0-4: the leading dense layer and four MoE layers (three window layers
and one full: one period of the pattern in its published 3 : 1),
experts 0-7 of 256 a layer and 12 544 of 100 352 vocabulary rows:
157 440 000 + 3 x 148 866 048 + 129 917 952 + 2 x 3072 x 12 544 + 3072
= 811 029 504 parameters, 8.11 GB at this repo's 10 B a parameter (f32
weights + momentum + the step's bf16 copy).  16 experts a layer would
be 11.1 GB and leave under 6 GB for four 8 k rows; 32 would be 17.2 GB.
The router scores all 256 experts and a token takes its 10 as
published; only held experts contribute, and that partial sum goes on
— no code stands in for the absent chips.  A step is four rows of 8 192
tokens: a held expert sees 32 768 x 10 / 256 = 1 280 rows a step.
``TINY`` is for the CPU tests only.

**What is computed** (``ops/attention.py``, ``ops/moe.py``,
``ops/sequence.py``; the plain reference is ``benchmarks/lib/
reference_laguna.py``).  ``N(x) = x / sqrt(mean(x^2) + 1e-6) * g``,
statistics in f32 (the ``rmsnorm`` unit holds ``g - 1``, from 0, under
no decay: the same function and the same gradient).  Layer i: ``h = x
+ Attn_i(N1(x))``, ``x' = h + FFN_i(N2(h))`` — two ``residual`` entries
of the ``layers`` list.  After the last layer ``N``, then logits
``W_head N(x)`` in f32 over the held ids; the loss (``next_byte`` with
one head) is the mean next-token cross-entropy over the valid
positions.

Attention, with ``u = N1(x)``: ``q = u W_q [T, nh, 128]`` (nh 48 on a
full layer, 72 on a window layer), ``k, v = u W_k, u W_v [T, 8, 128]``;
query head a reads key head ``a // (nh / 8)``.  Rotate-half RoPE ``q, k
<- a (x cos(n f) + rot(x) sin(n f))`` on the first r elements of each
head, the other ``128 - r`` untouched and unscaled.  Full layers (YaRN
as ``transformers`` computes it, over the r = 64 rotated elements):
``e_j = 5e5^(-2j/64)``, ``p_j = e_j / 128`` for j < 32, ``dim(r') = 64
ln(8192 / (2 pi r')) / (2 ln 5e5)``, ``low = max(floor(dim(32)), 0)``
= 9, ``high = min(ceil(dim(1)), 63)`` = 18, ``ramp_j = clip((j - low)
/ (high - low), 0, 1)``, ``f_j = p_j ramp_j + e_j (1 - ramp_j)``, ``a
= 1.4852030263919618``.  Window layers: r = 128, ``f_j =
1e4^(-2j/128)``, ``a = 1``.  ``o_n = softmax_m(q_n . k_m / sqrt(128))
v_m`` over ``m <= n`` on a full layer and over ``n - 512 < m <= n`` on
a window layer, scores and softmax in f32.  The gate: ``y_a =
sigmoid(u W_g)_a o_a``, ``W_g [3072, nh]``; then the ``nh 128 -> 3072``
out-projection (the entry's ``dense``).  On a TPU the window layers'
core is the band kernels of ``ops/attention_pallas.py`` at group 9, the
full layers' the flash kernel that ships with jax at group 6
(``attn.path``: ``form``, ``window``, ``rope``, ``rotary``, ``gate``,
``visited`` / ``kv_blocks``).

FFN of layer 0: ``W_down(silu(u W_gate) * u W_up)``, 12 288 wide
(``swiglu`` then ``dense``).  FFN of the MoE layers: ``p = softmax(u
W_r)`` over all 256 in f32; S the 10 largest; ``w_e = 2.5 p_e /
sum_S p``; ``y = sum_{e in S and held} w_e SwiGLU_e(u) + sigmoid(u .
w_s) SwiGLU_shared(u)``, all 1024 wide.  Static dispatch buffers sized
for the worst routing, nothing dropped (``moe.share``, ``moe.load``).

**Assumed** — the published config is silent on each; one line each:
what, why, what in the numbers would change if it were wrong:

1. no per-head q / k norm (no key gives one) — one would add two
   elementwise passes over q and k a layer.
2. the gate's input is the attention's normed input ``u``: one sigmoid
   a head, no bias, applied before ``W_o`` (the head-wise gate of Qiu
   et al. 2025, arXiv:2505.06708) — a gate on another input would
   change values, not the work (one ``[3072, nh]`` product a layer).
3. softmax over all 256 experts, then the top 10, renormalised, then
   x 2.5 on the routed experts alone (no ``scoring_func`` key) — a
   sigmoid score would change values, not the work.
4. the shared expert gated by ``sigmoid(u . w_s)``: the Qwen-MoE
   convention whose keys the config uses (``decoder_sparse_step``,
   ``mlp_only_layers``, ``shared_expert_intermediate_size``,
   ``norm_topk_prob``) — without the gate, 3072 parameters fewer a
   layer and one product less.
5. YaRN's ``attention_factor`` multiplies the rotated part only, as
   ``transformers`` applies it through cos and sin — over the whole
   head it would scale the scores' unrotated half too.
6. a window of 512 keys counting the query's own (``n - 512 < m <=
   n``) — one key more or fewer a query.
7. no auxiliary load-balancing loss (the config carries no
   coefficient) — one would add a reduction over the router's
   probabilities a layer, nothing to the matmuls.
8. rows are packed documents with no mask at document boundaries — a
   document mask would skip blocks, fewer the longer the documents.
9. the optimiser: the repo's momentum SGD (0.01 / 0.9 / 5e-4; no decay
   on gains) — the repo has no other; Adam would add 8 B a parameter
   of state (the cut would hold fewer experts).
10. bf16 compute, f32 parameters; scores, softmax, the router's
    softmax and the logits in f32 — f32 compute would halve the MXU's
    rate.
11. matrices N(0, 0.02^2), gains 1 — affects the loss's value, not the
    work.

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
    "hidden_size": 3072, "vocab_size": 100352, "n_layers": 48,
    "full_attention_interval": 4,
    "num_attention_heads_per_layer": [48 if i % 4 == 0 else 72
                                      for i in range(48)],
    "num_key_value_heads": 8, "head_dim": 128,
    "sliding_window": 512,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 128,
            "original_max_position_embeddings": 8192, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.4852030263919618,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "rms_norm_eps": 1e-6, "mlp_only_layers": [0],
    "intermediate_size": 12288,
    "num_experts": 256, "num_experts_per_tok": 10,
    "moe_intermediate_size": 1024,
    "shared_expert_intermediate_size": 1024,
    "moe_routed_scaling_factor": 2.5,
    "initializer_range": 0.02}

#: the share of the published model one chip holds (the docstring's
#: cut), and the rows it trains on
CUT = {"n_layers": 5, "experts_held": 8, "first_held": 0,
       "vocab_held": 12544, "seq_len": 8192, "minibatch": 4}

TINY = {
    "hidden_size": 64, "vocab_size": 256, "n_layers": 5,
    "full_attention_interval": 4,
    "num_attention_heads_per_layer": [4, 6, 6, 6, 4],
    "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 32,
    "rope_parameters": {
        "full_attention": dict(
            PUBLISHED["rope_parameters"]["full_attention"],
            original_max_position_embeddings=64),
        "sliding_attention": PUBLISHED["rope_parameters"][
            "sliding_attention"]},
    "intermediate_size": 96,
    "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "initializer_range": 0.05,
    "experts_held": 2, "first_held": 0, "vocab_held": 64,
    "seq_len": 128}


def layer_types(n_layers: int, interval: int):
    """``full_attention`` where i mod ``interval`` = 0, else
    ``sliding_attention``."""
    return ["full_attention" if i % interval == 0
            else "sliding_attention" for i in range(n_layers)]


def laguna_layers(n_layers: int = CUT["n_layers"],
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

    def attention(i, kind):
        return {"type": "attention",
                "->": {"n_heads": s["num_attention_heads_per_layer"][i],
                       "n_kv_heads": s["num_key_value_heads"],
                       "head_size": s["head_dim"],
                       "window": s["sliding_window"]
                       if kind == "sliding_attention" else None,
                       "rope": dict(s["rope_parameters"][kind]),
                       "gate": True, **std},
                "<-": GD}

    dense = [{"type": "swiglu",
              "->": {"intermediate_size": s["intermediate_size"], **std},
              "<-": GD}, out]
    moe = [{"type": "moe",
            "->": {"experts_total": s["num_experts"],
                   "experts_held": experts_held,
                   "first_held": first_held,
                   "top_k": s["num_experts_per_tok"],
                   "expert_size": s["moe_intermediate_size"],
                   "shared_size": s["shared_expert_intermediate_size"],
                   "routed_scale": s["moe_routed_scaling_factor"], **std},
            "<-": GD}]
    layers = [{"type": "embedding",
               "->": {"vocab_size": vocab_held, "hidden_size": hidden,
                      **std},
               "<-": GD}]
    for i, kind in enumerate(layer_types(n_layers,
                                         s["full_attention_interval"])):
        layers.append({"type": "residual",
                       "layers": [norm, attention(i, kind), out]})
        layers.append({"type": "residual", "layers": [norm] + (
            dense if i in s["mlp_only_layers"] else moe)})
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
               "median_len": 1024, "seed": 100352},
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
    cfg = model_config("laguna", DEFAULTS).todict()
    cfg.update(overrides)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedTokensLoader(
            wf, name="loader", vocab_size=cfg["vocab_held"],
            **cfg["loader"]),
        layers=cfg.get("layers") or laguna_layers(
            cfg["n_layers"], cfg["experts_held"], cfg["first_held"],
            cfg["vocab_held"], **(cfg.get("sizes") or {})),
        loss_function="next_byte",
        decision_config=cfg["decision"],
        snapshotter_config=cfg.get("snapshotter"),
        superstep=cfg["superstep"],
        name="LagunaWorkflow")
    launcher.workflow = w
    return w


def run(launcher):
    launcher.create_workflow(create_workflow)
    launcher.initialize()
    launcher.run()
