"""The comparison that decides ``correct`` for a training cell: what
the timed path's first call left, against the plain reference driven
over the same rows from the same seed-made weights.

Numbers compared (each with a limit of its own, from the cell's file):

- ``loss_gap``     |program - reference| / reference of the summed loss
                   of the first call's steps;
- ``momentum_gap`` worst leaf of | ||v_prog|| - ||v_ref|| | over
                   max(||v_ref|| of the leaf, of the median leaf) —
                   the optimiser's state: the gradients as it got them;
- ``update_gap``   the same of the parameters' change over the call.

Leaves whose first reference gradient is under a thousandth of the
median leaf's are left out (they move by round-off alone).
"""

from __future__ import annotations

import statistics
from typing import Any, Dict, List, Optional, Tuple

NAMES = ("loss_gap", "momentum_gap", "update_gap")


def counted_leaves(ref: Dict[str, Any]) -> List[str]:
    g0 = ref["grad0"]
    floor = statistics.median(g0.values()) * 1e-3
    return [k for k, v in g0.items() if v >= floor]


def worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
               leaves: List[str]) -> Tuple[float, str]:
    med = statistics.median(ref[k] for k in leaves)
    worst, at = 0.0, ""
    for k in leaves:
        if k not in prog:
            return float("inf"), k
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if not gap <= worst:          # NaN counts as the worst
            worst, at = (gap, k) if gap == gap else (float("inf"), k)
    return worst, at


def gaps(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, Any]:
    """The numbers compared, of any two sides that carry ``loss_sum``,
    ``momentum`` and ``update`` (the program, or the reference in a
    lower precision or with a fault put in the program's place)."""
    leaves = counted_leaves(ref)
    mom, mom_at = worst_leaf(prog["momentum"], ref["momentum"], leaves)
    upd, upd_at = worst_leaf(prog["update"], ref["update"], leaves)
    loss = abs(prog["loss_sum"] - ref["loss_sum"]) \
        / max(abs(ref["loss_sum"]), 1e-30)
    if loss != loss:
        loss = float("inf")
    return {"loss_gap": loss, "momentum_gap": mom, "update_gap": upd,
            "at": {"momentum_gap": mom_at, "update_gap": upd_at},
            "leaves": len(leaves),
            # [program, reference] norms of the two worst leaves, for
            # whoever reads a run that came out not correct
            "norms": {mom_at: [prog["momentum"].get(mom_at),
                               ref["momentum"][mom_at]],
                      "update " + upd_at: [prog["update"].get(upd_at),
                                           ref["update"][upd_at]]}
            if mom_at and upd_at else {}}


def judge(numbers: Dict[str, Any], limits: Dict[str, float]
          ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {name: {"value", "limit"}}) — every number has to be
    under its limit; a number without a limit is an error."""
    out, ok = {}, True
    for name in NAMES:
        v, lim = float(numbers[name]), float(limits[name])
        out[name] = {"value": v, "limit": lim}
        ok = ok and v <= lim
    return ok, out


def reference_rows(cfg: Dict[str, Any], seed: int, indices
                   ) -> Tuple[Any, Any]:
    """(xs, ys) — the rows of each step of the first call, regenerated
    from the seed: (k, mb, H, W, C) f32 and (k, mb) int32."""
    import numpy as np

    from . import seeded
    ds = cfg["dataset"]
    idx = np.asarray(indices)
    x, y = seeded.dataset_rows(seed, idx.reshape(-1), cfg["input_shape"],
                               cfg["n_classes"], ds["noise"],
                               ds["max_shift"])
    return (x.reshape(idx.shape + x.shape[1:]), y.reshape(idx.shape))


def follow_reference(cfg: Dict[str, Any], seed: int, indices,
                     precision: str = "f32", fault: Optional[str] = None,
                     block_rows: int = 0) -> Dict[str, Any]:
    from . import flops, reference, seeded
    rows = flops.layer_shapes(cfg["layers"], cfg["input_shape"])
    xs, ys = reference_rows(cfg, seed, indices)
    return reference.follow(cfg["layers"], seed,
                            seeded.init_params(seed, rows), xs, ys,
                            precision, fault, block_rows)


def feed_faults(first: Dict[str, Any], n_train: int) -> List[str]:
    """What is wrong with the first call's feed, if anything: every row
    real (mask 1), in range and distinct."""
    import numpy as np
    idx, mask = first["indices"], first["mask"]
    bad = []
    if not np.all(mask == 1.0):
        bad.append("masked rows in the first call")
    if idx.min() < 0 or idx.max() >= n_train:
        bad.append("row number out of range")
    if len(np.unique(idx)) != idx.size:
        bad.append("rows repeat within the first call")
    if first["count"] != idx.size:
        bad.append(f"the step counted {first['count']} rows of "
                   f"{idx.size}")
    return bad
