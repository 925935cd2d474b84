"""Clean twin: a placement setting through the knob registry, a
path chosen from what the code observes (veleslint fixture)."""
import os

from veles_tpu import knobs


def placement():
    return knobs.get(knobs.MESH_SHARD_DATA)


def pick_kernel(x, platform):
    environ = {"not": "the process environment"}
    if platform == "tpu" and x.shape[-1] % 128 == 0:
        return os.path.basename(environ["not"])
    return x
