"""Milliseconds under the program's ``fused.plan`` span: what
``_build_steps`` decides from shapes alone before anything is jitted —
what the chain keeps or re-runs (two ``eval_shape`` walks of every
layer), the loss's blocks, on a mesh the gradient exchange."""

from benchmarks.lib import inside


def read(ctx):
    return inside.sum_ms("fused.plan")
