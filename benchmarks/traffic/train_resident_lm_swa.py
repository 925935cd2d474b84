"""Traffic kind ``train_resident_lm_swa``: train a language-model
configuration whose attention layers are sliding-window and full causal
attention — plain grouped-query, each kind with a RoPE law of its own —
with a share of a mixture of experts that has no shared expert behind
every layer, several rows a step, on a store that lives in device
memory, through the program's normal loop.  The window, the barriers,
the traced sub-window, the host timers, the first firing's capture, the
comparison, the summary and the run line are ``train_resident_lm``'s
``Cell``, subclassed; that ``Cell`` reads a module-level ``reference``
that knows neither layer type, so what reads it is overridden here:

- ``build``: fails at once, before any data is made, on a program
  without the layer types; binds the seed's weights by
  ``lib/reference_mellum2.py``'s shapes; asks the program which experts
  every token chooses a MINIBATCH of rows at a time, so that the probe
  sees the step's own shapes (one ``moe.share`` a layer in the journal);
- ``follow_reference``: ``lib/reference_mellum2.py`` at the timed sizes;
- ``context()``: the trace's device time under ``attn/window`` (the
  layers that have a window) apart from ``attn/core`` (the full layers,
  which the accepted ``full_attention.busy_pct`` reads as
  ``attention_s``), the experts' and the blocked loss's.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Optional

import numpy as np

from ..lib import reference_mellum2 as reference
from ..lib import xplane
from . import train_resident, train_resident_lm, train_resident_seq
from .train_resident import _placement
from .train_resident_seq import LAYER, is_recomputed_forward

#: device scopes of each mechanism (``veles_tpu/events.py``)
MECHANISMS = {
    "window_attention": re.compile(r"attn/(window)"),
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


class Cell(train_resident_lm.Cell):
    """One run of a ``train_resident_lm_swa`` cell."""

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
        mb = int(mix["minibatch"])
        w = StandardWorkflow(
            loader_factory=lambda wf: loader_cls(
                wf, name="loader", make_data=make_data,
                minibatch_size=mb),
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
        # a minibatch of rows at a time: the probe's shapes are the
        # step's, and so is the share each layer journals
        data = w.loader.original_data.unmap()
        for r in range(0, int(mix["n_train"]), mb):
            got = w.fused.probe_units(data[r:r + mb])
            for j in range(mb):
                self.choices[r + j] = {
                    name: np.asarray(a["choice"][j], np.int16)
                    for name, a in got.items()}
        self.mark("choices")
        self._wrap()

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

    def summary(self) -> Dict[str, Any]:
        from veles_tpu import telemetry
        out = super().summary()
        # (by name: a program from before the gauge has none to read)
        out["journal"]["attn.window_layers"] = telemetry.gauge(
            "attn.window_layers").value
        return out

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
