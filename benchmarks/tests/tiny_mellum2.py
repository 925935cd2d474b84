"""A tiny language-model configuration and mix for rehearsals on the
CPU: Mellum2's layer types at toy widths (the model file's ``TINY``),
two rows a step.  Numbers from such a run are counts and correctness
checks, never speeds."""

import json

from veles_tpu.models.mellum2 import TINY, mellum2_layers

CFG = {
    "name": "tiny_mellum2", "input_shape": [TINY["seq_len"]],
    "loss": "next_byte", "init_std": TINY["initializer_range"],
    "dataset": {"kind": "packed_token_documents",
                "->": {"n_values": TINY["vocab_held"] - 1,
                       "separator": TINY["vocab_held"] - 1,
                       "median_len": 48, "sigma": 1.2}},
    "layers": json.loads(json.dumps(mellum2_layers(**TINY))),
}

MIX = {
    "name": "tiny_mellum2.train_packed", "config": "tiny_mellum2",
    "traffic": "train_resident_lm_swa", "chips": 1,
    "seq_len": TINY["seq_len"], "minibatch": 2, "superstep": 2,
    "n_train": 8, "trace_seconds": 0.2, "trace_firings": 2,
    "reference_seq_block": 32,
    "end_to_end": ["setup_s", "train_images_per_s"],
    "per_layer": ["loader.run_ms", "fused.dispatch_ms",
                  "fused.compiles_in_window", "decision.epoch_end_ms",
                  "device.idle_pct", "fused.recomputed_pct",
                  "moe_roofline", "moe.busy_pct",
                  "full_attention.busy_pct", "lm_swa.step_mfu_pct",
                  "window_attention_roofline",
                  "window_attention.busy_pct",
                  "full_attention_swa_roofline"],
    # f32 program against the f32 reference on XLA:CPU
    "limits": {"loss_gap": 1e-4, "momentum_gap": 2e-3,
               "update_gap": 2e-3},
}
