"""Traffic kind ``train_resident_seq``: train a sequence configuration
(rows of token ids, ``loss`` ``next_byte``) on a store that lives in
device memory, through the program's normal loop.  The window, the
barriers, the traced sub-window and the host timers are
``train_resident``'s own (its ``Cell``, subclassed); what differs is
what a sequence configuration needs made and compared:

- rows of packed byte documents from ``--seed``, made on the device
  (``lib/seeded_seq.py``), an INTEGER store with no labels and no
  targets — the loss reads its targets off the rows;
- weights from ``--seed`` (``lib/reference_evabyte.py``
  ``init_params``), bound to the units BEFORE the program's
  ``initialize`` (so its host-side fill finds them and is skipped);
- ``judge()``: the timed object's own first firing against
  ``lib/reference_evabyte.py`` at the timed sizes;
- ``context()`` also reads the trace's device time by ``named_scope``
  for the readers (``run.py`` removes the trace before one runs): the
  ``tf_op`` stat of a device event's metadata, through the wire reader
  of ``tests/scopes_chip.py``.
"""

from __future__ import annotations

import gc
import re
import sys
from typing import Any, Dict, Optional

import numpy as np

from ..lib import check, xplane
from ..lib import reference_evabyte as reference
from ..lib import seeded_seq
from . import train_resident
from .train_resident import _placement

EVA = re.compile(r"eva/(summaries|local|remote)")
LAYER = re.compile(r"(?:^|/)((?:fwd|bwd|update)/[^/:()]+)")


def is_recomputed_forward(path: str) -> bool:
    """A forward op that runs a second time: an entry's re-run inside
    the backward walk (``bwd/<layer>/recompute/...``; the backward ops
    that its closure later yields carry ``transpose(`` too), or a
    ``jax.checkpoint`` region's re-made forward."""
    return "rematted_computation" in path or (
        "/recompute/" in path and "transpose(" not in path)


def scope_times(path: str) -> Optional[Dict[str, Any]]:
    """Device SELF seconds of the traced window by what the ops'
    metadata says they are, of the first device plane: busy, under an
    ``eva/`` scope (and by part), recomputed forwards, by layer scope;
    None where the trace holds no device ops with metadata."""
    from ..tests import scopes_chip
    planes = scopes_chip.planes(path)
    win = next(((s, e) for evs in scopes_chip.host_spans(planes).values()
                for n, s, e in evs if n == xplane.WINDOW_SPAN), None)
    for p in sorted(planes, key=lambda p: p["name"]):
        if not p["name"].startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        ops = p["lines"].get(xplane.OPS_LINE, [])
        if not ops:
            continue
        lo, hi = win or (min(e[1] for e in ops),
                         max(e[1] + e[2] for e in ops))
        ops = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
        paths = {n: (st.get("tf_op") or "") for n, _, _, st in ops}
        selfs = xplane.self_times([(n, s, d) for n, s, d, _ in ops])
        out = {"busy_s": 0.0, "eva_s": 0.0, "recomputed_s": 0.0,
               "eva_recomputed_s": 0.0, "eva_parts": {}, "layers": {}}
        for name, ns in selfs.items():
            sec, path_ = ns / 1e9, paths.get(name, "")
            out["busy_s"] += sec
            again = is_recomputed_forward(path_)
            if again:
                out["recomputed_s"] += sec
            m = EVA.search(path_)
            if m:
                out["eva_s"] += sec
                if again:
                    out["eva_recomputed_s"] += sec
                part = out["eva_parts"]
                part[m.group(1)] = part.get(m.group(1), 0.0) + sec
            m = LAYER.search(path_)
            key = m.group(1) if m else "(none)"
            out["layers"][key] = out["layers"].get(key, 0.0) + sec
        return out
    return None


def _seeded_loader_class():
    from veles_tpu.loader.base import TEST, TRAIN, VALID
    from veles_tpu.loader.fullbatch import FullBatchLoader

    class SeededRowsLoader(FullBatchLoader):
        """The benchmark's rows of ids, born in device memory."""

        def __init__(self, workflow=None, make_data=None, **kw):
            super().__init__(workflow, **kw)
            self._make_data = make_data

        def load_data(self) -> None:
            data = self._make_data(self.device)
            self.class_lengths[TEST] = self.class_lengths[VALID] = 0
            self.class_lengths[TRAIN] = int(data.shape[0])
            self.original_data.devmem = data

    return SeededRowsLoader


class Cell(train_resident.Cell):
    """One run of a ``train_resident_seq`` cell."""

    def __init__(self, mix, cfg, seed, seconds, trace, device=None,
                 t_start=None, chip_start_s: float = 0.0) -> None:
        # the parent's shapes come from ``lib/flops.py``, which knows
        # image layers only: it is given no layers, and ``rows`` is
        # this kind's own
        super().__init__(mix, dict(cfg, layers=[]), seed, seconds, trace,
                         device=device, t_start=t_start,
                         chip_start_s=chip_start_s)
        self.cfg = cfg
        self.seq_len = int(mix["seq_len"])
        self.scopes: Optional[Dict[str, Any]] = None
        if trace:
            # the harness starts its trace two firings into the window
            # and wants whole firings between barriers; where a firing
            # takes seconds, the traced run (which reports no end-to-end
            # metric) keeps its window open for as long as that takes
            self.seconds = max(self.seconds, float(
                mix.get("traced_run_seconds", 0.0)))

    # -- set-up ----------------------------------------------------------

    def build(self) -> None:
        import jax

        from veles_tpu import prng
        from veles_tpu.backends import make_device
        from veles_tpu.ops.registry import forward_registry
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        mix, cfg = self.mix, self.cfg
        flat = reference.flatten(cfg["layers"])
        missing = sorted({c["type"] for c in flat} - set(forward_registry))
        if missing:
            # a program from before the sequence op family: fail at
            # once, before any data is made
            raise RuntimeError(
                f"the program has no layer types {missing}: it cannot "
                f"run configuration {cfg['name']!r}")
        self.mark("imports")
        if self.device is None:
            self.device = make_device("tpu")
        self.mark("device")
        prng.seed_all(self.seed % (2 ** 63))

        def make_data(device):
            data = jax.device_put(
                seeded_seq.dataset(self.seed, int(mix["n_train"]),
                                   self.seq_len, **cfg["dataset"]["->"]),
                _placement(device))
            jax.block_until_ready(data)
            self.mark("dataset")
            return data

        loader_cls = _seeded_loader_class()
        w = StandardWorkflow(
            loader_factory=lambda wf: loader_cls(
                wf, name="loader", make_data=make_data,
                minibatch_size=int(mix["minibatch"])),
            layers=cfg["layers"], loss_function=cfg["loss"],
            decision_config={"max_epochs": 10 ** 9},
            superstep=int(mix["superstep"]), name="Bench")
        # the benchmark's weights, in place before the program's own
        # fill would run (a unit fills only what it finds empty)
        std = float(cfg["init_std"])
        shapes = reference.param_shapes(cfg["layers"])
        for i, (f, names) in enumerate(zip(w.forwards, shapes)):
            for name, shape in names.items():
                getattr(f, name).devmem = jax.device_put(
                    reference.init_leaf(self.seed, i, name, shape, std),
                    _placement(self.device))
        self.mark("weights")
        w.initialize(device=self.device)
        for f, names in zip(w.forwards, shapes):
            vecs = f.param_vectors()
            assert set(vecs) == set(names), (f.name, set(vecs), names)
            for name, vec in vecs.items():
                assert tuple(vec.shape) == tuple(names[name]), \
                    (f.name, name, vec.shape, names[name])
        self.mark("initialize")
        self.w = w
        self._wrap()

    def _capture_first(self) -> None:
        """After the first dispatch: which rows it was fed, and the
        per-leaf norms of the state it left — a leaf at a time, the
        initial leaf made again from the seed (no second copy of the
        weights is ever held)."""
        import jax
        import jax.numpy as jnp
        w, fused = self.w, self.w.fused
        std = float(self.cfg["init_std"])

        @jax.jit
        def norms(p, v, z):
            n = lambda a: jnp.sqrt(jnp.sum(jnp.square(  # noqa: E731
                a.astype(jnp.float32))))
            return n(p - z), n(v)

        upd, mom = {}, {}
        for i, (f, g) in enumerate(zip(w.forwards, w.gds)):
            for name, p in fused._params[f.name].items():
                z = reference.init_leaf(self.seed, i, name, p.shape, std)
                upd[f"{i}.{name}"], mom[f"{i}.{name}"] = norms(
                    p, fused._opt[g.name][name], z)
        upd, mom = jax.device_get((upd, mom))
        acc = np.asarray(fused._acc, dtype=np.float64)
        ld = w.loader
        self.first = {
            "indices": np.array(ld.superstep_indices, copy=True),
            "mask": np.array(ld.superstep_mask, copy=True),
            "loss_sum": float(acc[1]), "count": float(acc[2]),
            "n_err": float(acc[0]),
            "update": {k: float(v) for k, v in upd.items()},
            "momentum": {k: float(v) for k, v in mom.items()}}

    def release(self) -> None:
        w = self.w
        w.fused.release_device_state()
        w.loader.original_data.drop_devmem()
        w.stop()
        self.w = None
        gc.collect()

    # -- what the run reports ---------------------------------------------

    def summary(self) -> Dict[str, Any]:
        out = super().summary()
        out["train_bytes_per_s"] = out["train_images_per_s"] \
            * self.seq_len
        out["traced_firings"] = int(self.traced.get("firings", 0))
        if self.scopes:
            out["scopes"] = {
                **{k: v for k, v in self.scopes.items()
                   if k != "layers"},
                "layers": sorted(self.scopes["layers"].items(),
                                 key=lambda kv: -kv[1])[:48]}
        return out

    def follow_reference(self, precision: str = "f32",
                         fault: Optional[str] = None) -> Dict[str, Any]:
        """The reference over the first call's rows, from the seed."""
        mix, cfg = self.mix, self.cfg
        rows = seeded_seq.dataset(self.seed, int(mix["n_train"]),
                                  self.seq_len, **cfg["dataset"]["->"])
        rows = np.asarray(rows)[np.asarray(self.first["indices"])]
        std = float(cfg["init_std"])
        shapes = reference.param_shapes(cfg["layers"])

        def make_w0(i, name):
            return reference.init_leaf(self.seed, i, name,
                                       shapes[i][name], std)

        return reference.follow(
            cfg["layers"], reference.init_params(self.seed,
                                                 cfg["layers"], std),
            rows, precision=precision, fault=fault,
            seq_block=int(mix.get("reference_seq_block", 0)),
            head_block=int(mix.get("reference_head_block", 0)),
            make_w0=make_w0)

    def run(self, sabotage=None) -> None:
        super().run(sabotage)
        e2e = self.end_to_end()
        # the reference runs for a minute after this: what the window
        # measured is on the log before it starts
        print("benchmark: window closed: " + ", ".join(
            f"{k} {v:.6g}" for k, v in e2e.items())
            + f", memory_peak_bytes {self.memory_peak_bytes()}",
            file=sys.stderr, flush=True)

    def judge(self):
        mix, first = self.mix, self.first
        bad = check.feed_faults(
            dict(first, count=float(first["indices"].size)),
            int(mix["n_train"]))
        k, mb = first["indices"].shape
        want = k * reference.valid_count(
            mb, self.seq_len,
            int(reference.flatten(self.cfg["layers"])[-1]["->"][
                "n_pred_heads"]))
        if first["count"] != want:
            bad.append(f"the step counted {first['count']} predictions "
                       f"of {want}")
        ref = self.follow_reference()
        numbers = check.gaps(first, ref)
        ok, compared = check.judge(numbers, mix["limits"])
        losses = self.losses
        finite = all(abs(x) < float("inf") for x in losses)  # no NaN
        compared["feed_faults"] = {"value": float(len(bad)),
                                   "limit": 0.0}
        compared["nonfinite_epoch_losses"] = {
            "value": float(0 if finite else 1), "limit": 0.0}
        detail = {"reference_losses": ref["losses"],
                  "at": numbers["at"], "leaves": numbers["leaves"],
                  "norms": numbers["norms"], "feed": bad,
                  "loss_sum": [first["loss_sum"], ref["loss_sum"]],
                  "epoch_losses": losses[:3] + losses[-2:]}
        return ok and not bad and finite, compared, detail

    # -- what the metric readers see --------------------------------------

    def context(self) -> Dict[str, Any]:
        ctx = super().context()
        ctx["cfg"] = self.cfg
        ctx["seq_len"] = self.seq_len
        if self.trace and self.trace_dir:
            try:
                self.scopes = scope_times(
                    xplane.find_trace(self.trace_dir))
            except FileNotFoundError:
                self.scopes = None
        ctx["scopes"] = self.scopes
        return ctx
