"""Backend compile events (jax.monitoring) inside the window.  Expected
0; a count, so 0 is a reading."""


def read(ctx):
    return float(ctx["compiles_in_window"])
