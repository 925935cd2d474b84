"""A tiny language-model configuration and mix for rehearsals on the
CPU: Laguna's layer types at toy widths (the model file's ``TINY``),
two rows a step.  Numbers from such a run are counts and correctness
checks, never speeds."""

import json

from veles_tpu.models.laguna import TINY, laguna_layers

CFG = {
    "name": "tiny_laguna", "input_shape": [TINY["seq_len"]],
    "loss": "next_byte", "init_std": TINY["initializer_range"],
    "dataset": {"kind": "packed_token_documents",
                "->": {"n_values": TINY["vocab_held"] - 1,
                       "separator": TINY["vocab_held"] - 1,
                       "median_len": 48, "sigma": 1.2}},
    "layers": json.loads(json.dumps(laguna_layers(**TINY))),
}

MIX = {
    "name": "tiny_laguna.train_packed", "config": "tiny_laguna",
    "traffic": "train_resident_lm_gate", "chips": 1,
    "seq_len": TINY["seq_len"], "minibatch": 2, "superstep": 2,
    "n_train": 8, "trace_seconds": 0.2, "trace_firings": 2,
    "reference_seq_block": 32,
    "end_to_end": ["setup_s", "train_images_per_s"],
    "per_layer": ["workflow.initialize_ms", "fused.first_submit_ms",
                  "fused.compile_ms", "fused.submit_ms",
                  "fused.fetch_wait_ms", "loop.turnaround_ms",
                  "fused.compiles_in_window",
                  "device.idle_pct", "fused.recomputed_pct",
                  "moe_roofline", "moe.busy_pct",
                  "full_attention.busy_pct", "lm_gate.step_mfu_pct",
                  "window_attention_roofline",
                  "window_attention.busy_pct",
                  "full_attention_swa_roofline", "swiglu.busy_pct"],
    # f32 program against the f32 reference on XLA:CPU
    "limits": {"loss_gap": 1e-4, "momentum_gap": 2e-3,
               "update_gap": 2e-3},
}
