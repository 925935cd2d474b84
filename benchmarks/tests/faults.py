"""The timed path, broken underneath: each function takes a built
``Cell`` (``run_cell(..., sabotage=...)``) and plants one fault or
switches the program's own lower-precision path on.  Every one has to
come out as not correct."""

#: the nearest precision below the one the program computes in
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def lower_precision(cell):
    """The program with its ``compute_dtype`` one step down — the
    switch a later PR would be tempted to flip.  It runs for float32
    -> bfloat16 (the tiny cell of the tests); bfloat16 -> float8
    raises in the program's own update, so the cells' control is the
    reference in float8 (``control_chip.py``)."""
    import jax.numpy as jnp
    fused = cell.w.fused
    fused.compute_dtype = BELOW[jnp.dtype(fused._resolved_dtype()).name]
    fused._train_step = fused._eval_step = None     # re-trace


def state_unchanged(cell):
    """A step that returns its state as given."""
    import jax
    import jax.numpy as jnp
    fused = cell.w.fused
    step = fused._train_step

    def broken(params, opt, *rest):
        keep = jax.tree.map(jnp.copy, (params, opt))
        _, _, acc, conf = step(params, opt, *rest)
        return keep[0], keep[1], acc, conf
    fused._train_step = broken


def half_batch(cell):
    """Half of every minibatch left out, the mean taken over the
    rest."""
    loader = cell.w.loader
    orig = loader.run

    def broken():
        orig()
        loader.superstep_mask[:, loader.superstep_mask.shape[1] // 2:] = 0
    loader.run = broken
