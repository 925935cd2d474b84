"""EvaByte: a byte-level decoder with EVA chunked linearized attention,
trained through ``StandardWorkflow`` like every other model file:

    python -m veles_tpu -b tpu veles_tpu/models/evabyte.py

Source: https://huggingface.co/EvaByte/EvaByte/blob/main/config.json
(``model_type`` ``evabyte``); EVA is Zheng, Yuan, Wang, Kong: Efficient
Attention via Control Variates, ICLR 2023, arXiv:2302.04542.

**Published sizes** (``PUBLISHED``): 32 layers, hidden 4096, 32 heads x
128 (``num_key_value_heads`` 32: plain MHA), ``intermediate_size``
11008, ``hidden_act`` silu, vocabulary 320, ``rope_theta`` 1e5 (no
scaling), ``rms_norm_eps`` 1e-5, ``norm_add_unit_offset``, no attention
bias, ``window_size`` 2048, ``chunk_size`` 16, ``num_pred_heads`` 8,
untied embeddings, ``fp32_logits``, ``fp32_skip_add``, ``mixedp_attn``,
``max_seq_length`` 32768.  No width is a parameter here; ``n_layers``
is: 32 published; the default and the benchmark's cell train 4 — one
202 391 552-parameter layer is 1.6 GB of f32 weights and momentum, and
a chip holds the first of 8 pipeline stages of 4 layers each with the
embedding and the head (821 366 784 parameters).  ``TINY`` is for the
CPU tests only.

**What is computed** (``ops/sequence.py``; the plain reference is
``benchmarks/lib/reference_evabyte.py``).  ``x0 = E[id]``.  A block:
``h = x + W_o Attn(N(x))``, ``x' = h + W_d(silu(W_g N(h)) * W_u N(h))``
— two ``residual`` entries of the ``layers`` list, the adds in f32;
``N(x) = x / sqrt(mean(x^2) + 1e-5) * (1 + g)``.  After the last block
``N``, then logits ``l[n, j] = W_head,j N(x_n)`` for j = 0..7 in f32;
head j at position n predicts byte n + 1 + j; the loss
(``loss_function="next_byte"``) is the mean cross-entropy over the
valid (n, j).

Attention, per head, d = 128, s = d^-1/2, window W = 2048, chunk c =
16: ``q_n, k_n`` = RoPE(theta 1e5, rotate-half over the whole head) of
``W_q N(x)_n``, ``W_k N(x)_n``; ``v_n = W_v N(x)_n``.  Chunk j =
positions [jc, jc + c) has the summary ``a_jm = softmax_m(s k_m . phi)``,
``vs_j = sum_m a_jm v_m``, ``ks_j = sum_m a_jm k_m + mu`` (phi, mu one
learned vector a head).  Query n of window w = n // W scores the local
set {m : wW <= m <= n} exactly and the remote set {j : (j + 1) c <= wW}
(every chunk of every earlier window, 128 w of them) by its summary:
``Z_n = sum_local exp(s q_n.k_m) + sum_remote exp(s q_n.ks_j)``,
``o_n = (sum_local exp(.) v_m + sum_remote exp(.) vs_j) / Z_n``.
Scores and softmax in f32, products in bf16 (``mixedp_attn``).  A
query scores at most 2048 + 1920 keys, never 32 768; with T <= W this
is exactly causal softmax attention.  What runs it: on a TPU at these
sizes the fused Pallas kernels of ``ops/eva_pallas.py`` — a row's local
and remote scores tile by tile in VMEM under one running softmax,
forward and backward, nothing of them in HBM; on XLA:CPU, at ``TINY``
or under ``vmap`` the same sums as plain XLA ops a window at a time
(``ops/sequence.py`` ``eva_window``).  The unit chooses from its
platform and shapes and journals which (``eva.path``).

**Assumed** — the published config is silent on each; the form is
arXiv:2302.04542 section 4 (EVA with the exact set E = the local
window).  One line each: what, why, what in the numbers would change
if it were wrong:

1. the summary's parameterisation: the paper's random omega_c made one
   learned phi and mu a head, ``ks_j`` pooled with the weights of
   ``vs_j``, built from rotated keys — the released code is not here
   to read; the work (FLOPs, bytes, shapes) is the same for any
   pooling of this form, so nothing in the timings would change.
2. the head: one 4096 -> 8 * 320 linear, equal loss weight a head —
   the config gives only ``num_pred_heads``; same work either way.
3. rows are packed documents with no mask at document boundaries — a
   mask would change which scores are kept, not how many are computed.
4. the optimiser: the repo's momentum SGD (0.01 / 0.9 / 5e-4, as the
   other configurations; no decay on gains, phi, mu) — the repo has no
   other and the recipe is not in the config; Adam would add 8 B a
   parameter of state and an elementwise pass per step.
5. bf16 compute, f32 parameters — the config's ``torch_dtype``; f32
   compute would halve the MXU's rate.
6. initialisation N(0, 0.01275^2) (``init_std``) for every matrix, phi
   and mu alike (zero mean, same sigma, so the summaries are not
   uniform pooling), gains zero — affects the loss's value, not the
   work.

Recomputation is not a setting: ``FusedStepRunner`` keeps a residual
entry's input alone and re-runs its forward inside the backward walk
when the residuals the chain would keep do not fit beside the state
(journal event ``fused.recompute``).
"""

from __future__ import annotations

from veles_tpu.loader.synthetic import PackedBytesLoader
from veles_tpu.models import model_config
from veles_tpu.ops.standard_workflow import StandardWorkflow

GD = {"learning_rate": 0.01, "weight_decay": 0.0005,
      "gradient_moment": 0.9}

PUBLISHED = {"hidden_size": 4096, "n_heads": 32, "head_size": 128,
             "intermediate_size": 11008, "vocab_size": 320,
             "window_size": 2048, "chunk_size": 16, "n_pred_heads": 8,
             "rope_theta": 1e5, "rms_norm_eps": 1e-5,
             "init_std": 0.01275, "n_layers": 32, "seq_len": 32768}

TINY = {"hidden_size": 64, "n_heads": 4, "head_size": 16,
        "intermediate_size": 96, "vocab_size": 320, "window_size": 32,
        "chunk_size": 4, "n_pred_heads": 8, "rope_theta": 1e5,
        "rms_norm_eps": 1e-5, "init_std": 0.05, "n_layers": 2,
        "seq_len": 128}


def evabyte_layers(n_layers: int = 4, **sizes):
    """The ``layers`` list of ``n_layers`` blocks at ``PUBLISHED``
    sizes (``sizes`` overrides: the tests' ``TINY``)."""
    s = dict(PUBLISHED, **sizes)
    std = {"weights_stddev": s["init_std"]}
    norm = {"type": "rmsnorm", "->": {"eps": s["rms_norm_eps"]},
            "<-": GD}
    layers = [{"type": "embedding",
               "->": {"vocab_size": s["vocab_size"],
                      "hidden_size": s["hidden_size"], **std},
               "<-": GD}]
    for _ in range(n_layers):
        layers.append({"type": "residual", "layers": [
            norm,
            {"type": "eva_attention",
             "->": {"n_heads": s["n_heads"],
                    "head_size": s["head_size"],
                    "window_size": s["window_size"],
                    "chunk_size": s["chunk_size"],
                    "rope_theta": s["rope_theta"], **std},
             "<-": GD},
            {"type": "dense",
             "->": {"output_size": s["hidden_size"], **std},
             "<-": GD}]})
        layers.append({"type": "residual", "layers": [
            norm,
            {"type": "swiglu",
             "->": {"intermediate_size": s["intermediate_size"],
                    **std},
             "<-": GD},
            {"type": "dense",
             "->": {"output_size": s["hidden_size"], **std},
             "<-": GD}]})
    layers.append(norm)
    layers.append({"type": "lm_head",
                   "->": {"vocab_size": s["vocab_size"],
                          "n_pred_heads": s["n_pred_heads"], **std},
                   "<-": GD})
    return layers


DEFAULTS = {
    # one packed sequence a step; the store is short — the model, its
    # momentum and its activations are what fill a chip
    "loader": {"minibatch_size": 1, "n_train": 4, "n_valid": 0,
               "seq_len": PUBLISHED["seq_len"], "seed": 320320},
    # one chip holds 4 of the 32 published layers (a pipeline stage)
    "n_layers": 4,
    "sizes": {},
    "superstep": 2,
    "decision": {"max_epochs": 8, "fail_iterations": 1000},
    "snapshotter": None,
}


def create_workflow(launcher, **overrides):
    cfg = model_config("evabyte", DEFAULTS).todict()
    cfg.update(overrides)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedBytesLoader(
            wf, name="loader", **cfg["loader"]),
        layers=cfg.get("layers") or evabyte_layers(
            cfg["n_layers"], **(cfg.get("sizes") or {})),
        loss_function="next_byte",
        decision_config=cfg["decision"],
        snapshotter_config=cfg.get("snapshotter"),
        superstep=cfg["superstep"],
        name="EvaByteWorkflow")
    launcher.workflow = w
    return w


def run(launcher):
    launcher.create_workflow(create_workflow)
    launcher.initialize()
    launcher.run()
