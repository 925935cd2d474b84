"""Traffic kind ``train_resident_lm``: train a language-model
configuration over a vocabulary of token ids — Gated DeltaNet, gated
attention and a share of a mixture of experts among its layers — on a
store that lives in device memory, through the program's normal loop.
The window, the barriers, the traced sub-window, the host timers, the
summary and the run line are ``train_resident_seq``'s ``Cell``,
subclassed; what differs is what this configuration needs made and
compared:

- it **fails at once, before any data is made**, on a program without
  the layer types (the commit before them exits in seconds);
- rows of packed token documents over the held slice of the
  vocabulary (``lib/seeded_tokens.py``) and weights from ``--seed``
  (``lib/reference_qwen3next.py`` ``init_leaf``), bound to the units
  BEFORE the program's ``initialize``;
- before the first firing it asks the program which experts every
  token of every row chooses under the seed's weights
  (``FusedStepRunner.probe_units``: set-up's own forward-only program),
  so that ``judge()`` can report the share of (token, slot) choices on
  which the bf16 program and the f32 reference differ — reported, never
  fed from one side to the other;
- ``judge()``: the timed object's own first firing against
  ``lib/reference_qwen3next.py`` at the timed sizes;
- ``context()`` reads the trace's device time under the scopes of the
  three mechanisms and of the blocked loss for the readers.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

import numpy as np

from ..lib import check, xplane
from ..lib import reference_qwen3next as reference
from ..lib import seeded_tokens
from . import train_resident, train_resident_seq
from .train_resident import _placement
from .train_resident_seq import LAYER, is_recomputed_forward

#: device scopes of each mechanism (``veles_tpu/events.py``)
MECHANISMS = {
    "gdn": re.compile(r"gdn/(conv|rule|gate_norm)"),
    "attention": re.compile(r"attn/(core)"),
    "moe": re.compile(r"moe/(router|dispatch|experts|shared)"),
    "loss": re.compile(r"loss/(block)"),
}


def scope_times(path: str) -> Optional[Dict[str, Any]]:
    """Device SELF seconds of the traced window, of the first device
    plane: busy, recomputed forwards, under each mechanism's scopes
    (``<mechanism>_s`` and, by part, ``<mechanism>_parts``), by layer
    scope; None where the trace holds no device ops with metadata."""
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
        out: Dict[str, Any] = {"busy_s": 0.0, "recomputed_s": 0.0,
                               "layers": {}}
        for name in MECHANISMS:
            out[name + "_s"], out[name + "_parts"] = 0.0, {}
        for name, ns in selfs.items():
            sec, path_ = ns / 1e9, paths.get(name, "")
            out["busy_s"] += sec
            if is_recomputed_forward(path_):
                out["recomputed_s"] += sec
            for mech, pattern in MECHANISMS.items():
                m = pattern.search(path_)
                if m:
                    out[mech + "_s"] += sec
                    part = out[mech + "_parts"]
                    part[m.group(1)] = part.get(m.group(1), 0.0) + sec
                    break
            m = LAYER.search(path_)
            key = m.group(1) if m else "(none)"
            out["layers"][key] = out["layers"].get(key, 0.0) + sec
        return out
    return None


class Cell(train_resident_seq.Cell):
    """One run of a ``train_resident_lm`` cell."""

    def __init__(self, *args, **kwargs) -> None:
        super().__init__(*args, **kwargs)
        #: {row: {unit name: ids [T, top_k]}}: what the program's
        #: router chose under the seed's weights, before any step
        self.choices: Dict[int, Dict[str, Any]] = {}

    # -- set-up ----------------------------------------------------------

    def _leaf(self, i: int, name: str, shape):
        return reference.init_leaf(self.seed, i, name, shape,
                                   float(self.cfg["init_std"]))

    def _rows(self):
        return seeded_tokens.dataset(
            self.seed, int(self.mix["n_train"]), self.seq_len,
            **self.cfg["dataset"]["->"])

    def build(self) -> None:
        import jax

        from veles_tpu.ops.registry import forward_registry

        mix, cfg = self.mix, self.cfg
        flat = reference.flatten(cfg["layers"])
        missing = sorted({c["type"] for c in flat} - set(forward_registry))
        if missing:
            raise RuntimeError(
                f"the program has no layer types {missing}: it cannot "
                f"run configuration {cfg['name']!r}")
        from veles_tpu import prng
        from veles_tpu.backends import make_device
        from veles_tpu.ops.standard_workflow import StandardWorkflow
        self.mark("imports")
        if self.device is None:
            self.device = make_device("tpu")
        self.mark("device")
        prng.seed_all(self.seed % (2 ** 63))

        def make_data(device):
            data = jax.device_put(self._rows(), _placement(device))
            jax.block_until_ready(data)
            self.mark("dataset")
            return data

        loader_cls = train_resident_seq._seeded_loader_class()
        w = StandardWorkflow(
            loader_factory=lambda wf: loader_cls(
                wf, name="loader", make_data=make_data,
                minibatch_size=int(mix["minibatch"])),
            layers=cfg["layers"], loss_function=cfg["loss"],
            decision_config={"max_epochs": 10 ** 9},
            superstep=int(mix["superstep"]), name="Bench")
        # the benchmark's weights, in place before the program's own
        # fill would run (a unit fills only what it finds empty)
        shapes = reference.param_shapes(cfg["layers"])
        for i, (f, names) in enumerate(zip(w.forwards, shapes)):
            for name, shape in names.items():
                getattr(f, name).devmem = jax.device_put(
                    self._leaf(i, name, shape), _placement(self.device))
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
        data = w.loader.original_data.unmap()
        for r in range(int(mix["n_train"])):
            got = w.fused.probe_units(data[r:r + 1])
            self.choices[r] = {
                name: np.asarray(a["choice"][0], np.int16)
                for name, a in got.items()}
        self.mark("choices")
        self._wrap()

    def _capture_first(self) -> None:
        """After the first dispatch: which rows it was fed, and the
        per-leaf norms of the state it left — a leaf at a time, the
        initial leaf made again from the seed."""
        import jax
        import jax.numpy as jnp
        w, fused = self.w, self.w.fused

        @jax.jit
        def norms(p, v, z):
            n = lambda a: jnp.sqrt(jnp.sum(jnp.square(  # noqa: E731
                a.astype(jnp.float32))))
            return n(p - z), n(v)

        upd, mom = {}, {}
        for i, (f, g) in enumerate(zip(w.forwards, w.gds)):
            for name, p in fused._params[f.name].items():
                upd[f"{i}.{name}"], mom[f"{i}.{name}"] = norms(
                    p, fused._opt[g.name][name],
                    self._leaf(i, name, p.shape))
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

    # -- the comparison ---------------------------------------------------

    def follow_reference(self, precision: str = "f32",
                         fault: Optional[str] = None) -> Dict[str, Any]:
        """The reference over the first call's rows, from the seed."""
        cfg = self.cfg
        rows = np.asarray(self._rows())[np.asarray(self.first["indices"])]
        shapes = reference.param_shapes(cfg["layers"])
        return reference.follow(
            cfg["layers"],
            reference.init_params(self.seed, cfg["layers"],
                                  float(cfg["init_std"])),
            rows, precision=precision, fault=fault,
            seq_block=int(self.mix.get("reference_seq_block", 0)),
            make_w0=lambda i, name: self._leaf(i, name, shapes[i][name]))

    def routing_differs(self, ref: Dict[str, Any]) -> Dict[str, float]:
        """{layer: share of the first step's (token, slot) choices on
        which program and reference differ} — a token's set of experts
        against the other side's, whatever the order."""
        flat = reference.flatten(self.cfg["layers"])
        row = int(np.asarray(self.first["indices"])[0, 0])
        out = {}
        for i, theirs in ref.get("choices0", {}).items():
            name = f"fwd{i}_{flat[i]['type']}"
            mine = self.choices.get(row, {}).get(name)
            if mine is None:
                continue
            theirs = np.asarray(theirs)[0]
            same = (mine[:, :, None] == theirs[:, None, :]).any(-1)
            out[name] = float(1.0 - same.mean())
        return out

    def judge(self):
        mix, first = self.mix, self.first
        bad = check.feed_faults(
            dict(first, count=float(first["indices"].size)),
            int(mix["n_train"]))
        k, mb = first["indices"].shape
        want = k * reference.valid_count(mb, self.seq_len)
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
                  "routing_differs_share": self.routing_differs(ref),
                  "epoch_losses": losses[:3] + losses[-2:]}
        return ok and not bad and finite, compared, detail

    def summary(self) -> Dict[str, Any]:
        """The run line's own numbers, and what the program journaled
        about the three mechanisms and the loss."""
        from veles_tpu import events, telemetry
        out = super().summary()
        out["journal"] = {
            name: [{k: v for k, v in e.items()
                    if k not in ("ts", "mono", "event")}
                   for e in telemetry.recent_events(name)]
            for name in (events.EV_GDN_PATH, events.EV_ATTN_PATH,
                         events.EV_MOE_SHARE, events.EV_MOE_LOAD,
                         events.EV_LOSS_BLOCKED,
                         events.EV_FUSED_RECOMPUTE)}
        return out

    # -- what the metric readers see --------------------------------------

    def context(self) -> Dict[str, Any]:
        ctx = train_resident.Cell.context(self)
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
