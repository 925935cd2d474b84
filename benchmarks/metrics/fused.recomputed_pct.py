"""Share of the device's busy time spent on forwards that run a second
time: a residual entry re-run inside the backward walk
(``bwd/<layer>/recompute``) or a ``jax.checkpoint`` region re-made for
its backward.  0 where the program keeps every residual; None where
the trace names no scopes at all."""


def read(ctx):
    sc = ctx.get("scopes") or {}
    if not sc.get("busy_s") or "recomputed_s" not in sc:
        return None
    return 100.0 * sc["recomputed_s"] / sc["busy_s"]
