"""A tiny configuration and mix for rehearsals on the CPU: every layer
type of the real configurations at toy widths.  Numbers from such a
run are counts and correctness checks, never speeds."""

GD = {"learning_rate": 0.01, "weight_decay": 0.0005,
      "gradient_moment": 0.9}

CFG = {
    "name": "tiny", "input_shape": [24, 24, 3], "n_classes": 10,
    "loss": "softmax",
    "dataset": {"kind": "synthetic_templates", "noise": 0.5,
                "max_shift": 2},
    "layers": [
        {"type": "conv_relu", "->": {"n_kernels": 8, "kx": 5, "ky": 5,
                                     "sliding": 2}, "<-": GD},
        {"type": "norm", "->": {"alpha": 1e-4, "beta": 0.75, "n": 5,
                                "k": 2.0}, "<-": {}},
        {"type": "max_pooling", "->": {"kx": 3, "ky": 3, "sliding": 2},
         "<-": {}},
        {"type": "conv_relu", "->": {"n_kernels": 16, "kx": 3, "ky": 3,
                                     "padding": 1}, "<-": GD},
        {"type": "max_pooling", "->": {"kx": 2, "ky": 2, "sliding": 2},
         "<-": {}},
        {"type": "all2all_relu", "->": {"output_sample_shape": 32},
         "<-": GD},
        {"type": "dropout", "->": {"dropout_ratio": 0.5}, "<-": {}},
        {"type": "softmax", "->": {"output_sample_shape": 10},
         "<-": GD},
    ],
}

MIX = {
    "name": "tiny.train_resident", "config": "tiny",
    "traffic": "train_resident", "chips": 1, "minibatch": 8,
    "superstep": 4, "n_train": 64, "trace_seconds": 0.2,
    "trace_firings": 2, "reference_block_rows": 0,
    "end_to_end": ["setup_s", "train_images_per_s"],
    "per_layer": ["loader.run_ms", "fused.dispatch_ms",
                  "fused.compiles_in_window", "decision.epoch_end_ms",
                  "step.mfu_pct", "kernels_roofline",
                  "device.idle_pct"],
    # f32 program against the f32 reference on XLA:CPU
    "limits": {"loss_gap": 1e-4, "momentum_gap": 1e-3,
               "update_gap": 1e-3},
}
