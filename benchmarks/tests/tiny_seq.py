"""A tiny sequence configuration and mix for rehearsals on the CPU:
EvaByte's layer types at toy widths (the model file's ``TINY``).
Numbers from such a run are counts and correctness checks, never
speeds."""

import json

from veles_tpu.models.evabyte import TINY, evabyte_layers

CFG = {
    "name": "tiny_seq", "input_shape": [TINY["seq_len"]],
    "loss": "next_byte", "init_std": TINY["init_std"],
    "dataset": {"kind": "packed_byte_documents",
                "->": {"median_len": 48, "sigma": 1.2,
                       "separator": 256}},
    "layers": json.loads(json.dumps(evabyte_layers(**TINY))),
}

MIX = {
    "name": "tiny_seq.train_packed", "config": "tiny_seq",
    "traffic": "train_resident_seq", "chips": 1,
    "seq_len": TINY["seq_len"], "minibatch": 1, "superstep": 2,
    "n_train": 4, "trace_seconds": 0.2, "trace_firings": 2,
    "reference_seq_block": 32, "reference_head_block": 2,
    "end_to_end": ["setup_s", "train_images_per_s"],
    "per_layer": ["loader.run_ms", "fused.dispatch_ms",
                  "fused.compiles_in_window", "decision.epoch_end_ms",
                  "device.idle_pct", "seq.step_mfu_pct",
                  "eva_attention_roofline", "eva_attention.busy_pct",
                  "fused.recomputed_pct"],
    # f32 program against the f32 reference on XLA:CPU
    "limits": {"loss_gap": 1e-4, "momentum_gap": 1e-3,
               "update_gap": 1e-3},
}
