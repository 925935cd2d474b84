"""Time the grouped products of the mixture-of-experts layer on the chip,
alone, at the two LM cells' shapes (one dispatch block through its
compact buffers, bf16), under three tilings:

    python scripts/moe_kernel_chip.py [mellum2|qwen3next ...]  # one chip

``by_product``: one tuple a product for its three calls — 512 x
``min(width, 1024)`` x ``min(inner, 512)`` for gate and up, its mirror
for down, the segment sum taking down's columns; ``tuple``: ONE tuple,
512 x 1 152 x 896, for every call; ``chooser``: ``ops/moe.py``
``GroupedTiles``, each call its own tiles from its own ``(m, k, n)``.

Prints the scoped VMEM limit the compiler holds megablox's kernels to
(they set none: read from its refusal of one that asks more), then
one JSON line a (cell, tiling): milliseconds a call of each call kind
(``in`` = gate or up, ``out`` = down; ``forward``, ``input_grad``,
``weight_grad``; ``fold`` = the segment sum), median of 5 runs of 20
queued calls after a warm one, each call's tiles and padding, the
block's experts + fold forward + backward, the sum of its kernel
calls as the cell's step runs them, and the worst gap of the block's
results from ``ragged_dot``'s.  A tool for splitting a gain by
call kind; the benchmark's own metrics are ``moe_roofline`` and
``moe.busy_pct``.
"""

import json
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from veles_tpu.ops import moe  # noqa: E402

#: (tokens a step, hidden, expert width, experts held, of, top k)
CELLS = {"mellum2": (4 * 8192, 2304, 896, 16, 64, 8),
         "qwen3next": (32768, 2048, 512, 32, 512, 10)}
CD = jnp.bfloat16


def scoped_vmem_limit(sharding=None) -> str:
    """The limit in the compiler's refusal of megablox's ``tgmm`` at
    512 x 2 304 x 896 tiles in bf16 (a step of ≈ 18 MiB, which the
    chooser never takes), or "none" where it compiles."""
    from jax.experimental.pallas.ops.tpu.megablox import ops

    def spec(shape, dtype=CD):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    try:
        jax.jit(lambda a, b, s: ops.backend.tgmm(
            a, b, s, CD, (512, 2304, 896))).lower(
            spec((2304, 4096)), spec((4096, 896)),
            spec((16,), jnp.int32)).compile()
        return "none"
    except Exception as e:          # the compiler's refusal is the reading
        found = re.search(r"size ([\d.]+[KMG]?) and limit ([\d.]+[KMG]?)",
                          str(e))
        return "asked %s, limit %s" % found.groups() if found \
            else str(e)[:300]


def timed(fn, *args):
    out = jax.block_until_ready(fn(*args))
    ms = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(20):
            got = fn(*args)
        jax.block_until_ready(got)
        ms.append(1e3 * (time.perf_counter() - t0) / 20)
    return statistics.median(ms), out


def gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def tilings(width, inner):
    """name -> (gate/up tiling, down tiling, the fold's column tile)."""
    by_in = (moe.GMM_ROWS, min(width, 1024), min(inner, 512))
    by_out = (moe.GMM_ROWS, min(inner, 512), min(width, 1024))
    one = (moe.GMM_ROWS, 1152, 896)
    chooser = moe.GroupedTiles(jnp.dtype(CD).itemsize)
    return {"by_product": (by_in, by_out, by_out[2]),
            "tuple": (one, one, one[2]),
            "chooser": (chooser, chooser, None)}


def inputs(cell, seed=41):
    tokens, width, inner, held, total, top_k = CELLS[cell]
    block = moe.block_tokens(tokens, top_k, width)
    rows = block * top_k
    capacity = moe.dispatch_capacity(rows, held, total, moe.GMM_ROWS)
    rng = np.random.default_rng(seed)
    live = min(capacity, rows * held // total)
    sizes = jnp.asarray(rng.multinomial(live, [1.0 / held] * held),
                        jnp.int32)
    pairs = jnp.asarray(np.sort(rng.choice(rows, capacity, replace=False)),
                        jnp.int32)
    keys = jax.random.split(jax.random.key(seed), 6)
    tokens_x = jax.random.normal(keys[0], (block, width), CD)
    x = jnp.take(tokens_x, pairs // top_k, axis=0)
    w = {name: (0.02 * jax.random.normal(key, shape)).astype(CD)
         for name, key, shape in (
             ("w_gate", keys[1], (held, width, inner)),
             ("w_up", keys[2], (held, width, inner)),
             ("w_down", keys[3], (held, inner, width)))}
    weight = jax.random.uniform(keys[4], (capacity,), jnp.float32)
    err = jax.random.normal(keys[5], (block, width), jnp.float32)
    return dict(block=block, top_k=top_k, width=width, inner=inner,
                capacity=capacity, sizes=sizes, pairs=pairs,
                by_token=jnp.argsort(pairs).astype(jnp.int32), x=x,
                tokens_x=tokens_x, w=w, weight=weight, err=err)


def block_fn(a, t_in, t_out, fold_cols):
    """The block's compact gather, experts and combine, forward +
    backward, as ``MoE._compact_block`` runs them under these tiles (the
    fold is the combine and the gather's backward)."""
    fold = None if t_in is None else (
        t_in if fold_cols is None else lambda m, k, n: (0, 0, fold_cols))
    gather, combine = moe._compact_dispatch(a["top_k"], a["block"], fold)

    def forward(w, tokens_x):
        x = gather(tokens_x, a["pairs"], a["by_token"])
        gate = moe.grouped_matmul(x, w["w_gate"], a["sizes"], t_in)
        up = moe.grouped_matmul(x, w["w_up"], a["sizes"], t_in)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)
               * a["weight"][:, None]).astype(x.dtype)
        y = moe.grouped_matmul(act, w["w_down"], a["sizes"], t_out)
        return combine(y, a["pairs"], a["by_token"], y.dtype)

    def both(w, x):
        out, back = jax.vjp(forward, w, x)
        return (out,) + back(a["err"])

    return jax.jit(both)


def calls(a, t_in, t_out, fold_cols):
    """ms a call of each kernel call kind, and each call's tiles."""
    from jax.experimental.pallas.ops.tpu.megablox import ops
    b = ops.backend
    x, sizes, width, inner = a["x"], a["sizes"], a["width"], a["inner"]
    act = x[:, :inner]
    grad = {"in": jax.random.normal(jax.random.key(1), (len(x), inner), CD),
            "out": jax.random.normal(jax.random.key(2), (len(x), width), CD)}
    line = {}
    for product, lhs, rhs, tiles in (("in", x, a["w"]["w_gate"], t_in),
                                     ("out", act, a["w"]["w_down"], t_out)):
        g = grad[product]
        fns = {"forward": (lambda l, r, t=tiles: b.gmm(
                   l, r, sizes, CD, t), lhs, rhs),
               "input_grad": (lambda gg, r, t=tiles: b.gmm(
                   gg, r, sizes, CD, t, transpose_rhs=True), g, rhs),
               "weight_grad": (lambda l, gg, t=tiles: b.tgmm(
                   l.swapaxes(0, 1), gg, sizes, CD, t), lhs, g)}
        for call, (fn, *args) in fns.items():
            m, k = args[0].shape
            n = rhs.shape[1] if call == "input_grad" else rhs.shape[2]
            if call == "weight_grad":
                m, k, n = lhs.shape[0], lhs.shape[1], g.shape[1]
            used = tuple(tiles(m, k, n)) if callable(tiles) else tiles
            line[f"{product}.{call}_ms"], _ = timed(jax.jit(fn), *args)
            line[f"{product}.{call}_tiles"] = used
            line[f"{product}.{call}_padding"] = round(moe.padding(
                used, k, n), 4)
    cols = fold_cols or t_in(len(x), moe.fold_tile(a["block"]), width)[2]
    tile = moe.fold_tile(a["block"])
    onehot = (jnp.arange(tile)[:, None]
              == jnp.arange(len(x))[None, :] % tile).astype(CD)
    fold_sizes = jnp.full((a["block"] // tile,), len(x) // (
        a["block"] // tile), jnp.int32)
    line["fold_ms"], _ = timed(jax.jit(lambda o, y: b.tgmm(
        o, y, fold_sizes, jnp.float32, (moe.GMM_ROWS, tile, cols))),
        onehot, x)
    line["fold_tiles"] = (moe.GMM_ROWS, tile, cols)
    line["fold_padding"] = round(moe.padding(line["fold_tiles"], tile,
                                             width), 4)
    return line


def main(argv):
    assert jax.devices()[0].platform == "tpu", jax.devices()
    print(json.dumps({"scoped_vmem_limit": scoped_vmem_limit(),
                      "device": jax.devices()[0].device_kind}), flush=True)
    for cell in argv or list(CELLS):
        a = inputs(cell)
        want = block_fn(a, None, None, None)(a["w"], a["tokens_x"])
        for name, (t_in, t_out, cols) in tilings(
                a["width"], a["inner"]).items():
            line = {"cell": cell, "tiling": name, "rows": len(a["x"]),
                    "live_rows": int(a["sizes"].sum())}
            line["block_ms"], got = timed(block_fn(a, t_in, t_out, cols),
                                          a["w"], a["tokens_x"])
            line["block_gap"] = max(gap(g, w) for g, w in zip(
                jax.tree.leaves(got), jax.tree.leaves(want)))
            line.update(calls(a, t_in, t_out, cols))
            # a block's kernels as the cell's step runs them: gate and
            # up each an ``in`` product, the forward twice (the block's
            # backward re-makes it), the fold in both combines and in
            # the gather's backward
            line["step_block_kernels_ms"] = sum(
                (2 if product == "in" else 1) * (
                    2 * line[f"{product}.forward_ms"]
                    + line[f"{product}.input_grad_ms"]
                    + line[f"{product}.weight_grad_ms"])
                for product in ("in", "out")) + 3 * line["fold_ms"]
            print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                              for k, v in line.items()}), flush=True)
            del got


if __name__ == "__main__":
    main(sys.argv[1:])
