"""Shared fixed-shape batching machinery of the fused engines.

FusedStepRunner, EnsembleEvalEngine, and PopulationTrainEngine (and
now the Hive serving tier) all rely on the same three mechanical
ideas, which used to live as near-identical private helpers inside the
ever-growing ops/fused.py:

- **member stacking**: N param pytrees stacked along a leading MEMBER
  axis and uploaded once, so ``jax.vmap`` turns an N-member sweep into
  one dispatch;
- **fixed-shape chunk + validity mask**: every dispatch sees the SAME
  array shape (ragged tails are zero-padded and masked out of the
  math), so a jitted step compiles exactly once per step kind — the
  property the serving tier's zero-recompile steady state rests on;
- **compute-dtype resolution + pytree casting**: matmuls/convs run in
  the device's compute dtype (bf16 on TPU) against f32 master params;
  each engine resolves the dtype the same way and casts the same way.

This module is the single home for all three (a concrete down payment
on the ROADMAP's "unify the fused engines" item): the engines import
from here, behavior unchanged — pinned by their existing parity tests.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

import numpy as np


def resolve_compute_dtype(compute_dtype: Any, device: Any):
    """The jnp dtype an engine computes in: an explicit
    ``compute_dtype`` wins, else the device's policy (bf16 on TPU, f32
    elsewhere), else float32."""
    import jax.numpy as jnp
    cd = compute_dtype
    if cd is None and device is not None:
        cd = device.compute_dtype
    return jnp.dtype(cd) if cd is not None else jnp.float32


def make_caster(cd):
    """``cast(tree)`` mapping every f32 leaf to ``cd`` (identity when
    ``cd`` IS f32) — the mixed-precision entry every engine applies to
    its param pytree before the forward chain."""
    import jax
    import jax.numpy as jnp
    if cd == jnp.float32:
        return lambda tree: tree

    def cast(tree):
        return jax.tree_util.tree_map(
            lambda a: a.astype(cd) if a.dtype == jnp.float32 else a,
            tree)
    return cast


def stack_member_params(forwards: List[Any],
                        member_params: List[Dict[str, Dict[str, Any]]],
                        device: Any, put: Any = None
                        ) -> Dict[str, Dict[str, Any]]:
    """{fwd_name: {pname: (n_members, ...)}} — every member's f32
    params stacked along a leading MEMBER axis and uploaded once.
    Shared by the vmapped engines: EnsembleEvalEngine stacks N distinct
    trained members; PopulationTrainEngine stacks P copies of one init
    (same-signature genomes share the weight-init draw by seed); the
    Hive residency manager re-uploads a spilled model through it.
    ``put`` overrides the placement (default ``device.put``,
    replicated on a mesh) — the member-sharded cohort path passes a
    member-sharded placement so each device uploads P/N members."""
    putf = put if put is not None else device.put
    return {
        f.name: {
            pn: putf(np.stack(
                [np.asarray(m[f.name][pn], np.float32)
                 for m in member_params]))
            for pn in member_params[0][f.name]}
        for f in forwards}


def stacked_param_bytes(member_params:
                        List[Dict[str, Dict[str, Any]]]) -> int:
    """HBM bytes :func:`stack_member_params` will occupy for these
    members (f32) — the residency-budget accounting the serving tier's
    LRU spill decisions read, computed host-side BEFORE any upload."""
    total = 0
    for m in member_params:
        for p in m.values():
            for arr in p.values():
                total += int(np.prod(np.shape(arr))) * 4
    return total


def pad_chunk(xb: np.ndarray, lb: np.ndarray,
              chunk: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Fixed-shape (rows, labels) chunk + validity mask: the consuming
    jit compiles exactly once; padded rows carry mask 0 and cannot
    score."""
    mask = np.ones(chunk, np.float32)
    if len(xb) < chunk:
        pad = chunk - len(xb)
        mask[len(xb):] = 0.0
        xb = np.concatenate(
            [xb, np.zeros((pad,) + xb.shape[1:], xb.dtype)])
        lb = np.concatenate([lb, np.zeros(pad, lb.dtype)])
    return xb, lb, mask


def pad_rows(x: np.ndarray,
             chunk: int) -> Tuple[np.ndarray, np.ndarray]:
    """Label-less variant of :func:`pad_chunk` — the serving tier's
    micro-batch assembly: rows zero-padded to the fixed ``chunk``
    shape plus the validity mask (padded rows are discarded host-side
    after the dispatch)."""
    mask = np.ones(chunk, np.float32)
    if len(x) < chunk:
        pad = chunk - len(x)
        mask[len(x):] = 0.0
        x = np.concatenate(
            [x, np.zeros((pad,) + x.shape[1:], x.dtype)])
    return x, mask


def make_sharded_row_gather(mesh):
    """Traced ``gather(indices, *stores) -> rows per store`` over
    ROW-SHARDED resident stores (each device holds 1/N of the rows;
    ``parallel.mesh.put_row_sharded`` placement).  One store returns
    its gathered rows bare — the SOM epoch builders
    (``engine_core.build_som_epoch`` / ``build_som_eval``) consume
    that form directly, target-less as the SOM is; several (dataset +
    labels/targets) return a tuple, gathered with ONE shard_map.

    The gather is a ``shard_map`` local gather + psum assembly: every
    device looks the full (replicated) index vector up in its OWN
    shard, zeroes the rows it does not own, and the psum across the
    data axis assembles the full minibatch on every device.  Exactly
    one device contributes each row, so the reduction sums one real
    value with N-1 zeros — f32-EXACT by IEEE-754 (x + 0.0 == x),
    which is what lets sharded residency pin bitwise parity against
    the replicated-residency oracle.  Integer stores (uint8 quantized
    datasets, int32 labels) ride the psum as int32 — narrow-int
    collectives are not universally lowered — and cast back, which is
    exact for any byte/label value.

    Indices must reference REAL rows only (< R); the padded tile tail
    exists purely as placement filler, and the loaders' index
    machinery (np.resize padding + validity masks) never points at
    it."""
    import jax.numpy as jnp
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec

    axis = mesh.axis_names[0]
    n = int(mesh.devices.size)

    def _assemble(local_store, loc, hit):
        x = jnp.take(local_store, loc, axis=0)
        x = jnp.where(
            hit.reshape(hit.shape + (1,) * (x.ndim - hit.ndim)),
            x, jnp.zeros((), x.dtype))
        if jnp.issubdtype(x.dtype, jnp.floating):
            return lax.psum(x, axis)
        return lax.psum(x.astype(jnp.int32), axis).astype(x.dtype)

    def gather(indices, *stores):
        rows_local = stores[0].shape[0] // n   # static at trace time

        def local(idx, *local_stores):
            lo = lax.axis_index(axis) * rows_local
            loc = jnp.clip(idx - lo, 0, rows_local - 1)
            hit = (idx >= lo) & (idx < lo + rows_local)
            return tuple(_assemble(s, loc, hit) for s in local_stores)

        spec = PartitionSpec(axis)
        out = shard_map(
            local, mesh=mesh,
            in_specs=(PartitionSpec(),) + (spec,) * len(stores),
            out_specs=(PartitionSpec(),) * len(stores),
            check_vma=False)(indices, *stores)
        return out[0] if len(stores) == 1 else out

    return gather


def pad_members(arrays: List[np.ndarray],
                multiple: int) -> Tuple[List[np.ndarray], int]:
    """Pad each array's leading MEMBER axis to a whole multiple of
    ``multiple`` by repeating the first member's row — the
    member-sharded cohort convention: padded members train harmlessly
    (identical math to member 0) and their fitness rows are sliced
    off before anything reads them.  Returns the padded arrays and
    the padded member count."""
    p = len(arrays[0])
    p_pad = -(-p // multiple) * multiple
    if p_pad == p:
        return list(arrays), p
    out = []
    for a in arrays:
        filler = np.repeat(a[:1], p_pad - p, axis=0)
        out.append(np.concatenate([a, filler], axis=0))
    return out, p_pad


def padded_index_chunk(start: int, stop: int, chunk: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Fixed-shape index window [start, stop) + validity mask for the
    resident gather paths (indices are padded with 0 — a valid row
    index — and masked out of the scoring math)."""
    idx = np.arange(start, stop, dtype=np.int32)
    mask = np.ones(chunk, np.float32)
    if len(idx) < chunk:
        mask[len(idx):] = 0.0
        idx = np.pad(idx, (0, chunk - len(idx)))
    return idx, mask
