"""StandardWorkflow: build a full training workflow from a declarative
``layers`` config.

Reference parity: veles/znicz/standard_workflow.py — the API all five
BASELINE.json configs go through: a list of layer dicts
``{"type": "conv", "->": {forward params}, "<-": {gd params}}`` becomes
loader -> forwards -> evaluator -> gd chain (reversed) -> decision ->
loop, plus snapshotter and plotters (SURVEY.md §4.5).

TPU-first: on a jax device the forwards/evaluator/gds are NOT linked
into the control graph — a single FusedStepRunner node executes the
whole iteration as one jitted call (ops/fused.py).  On the numpy
backend the classic unit-by-unit graph runs, serving as the golden
path.  The same StandardWorkflow instance can be re-wired for either
mode at initialize() time (snapshot on TPU, resume on numpy, etc.).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

from veles_tpu.backends import Device
from veles_tpu.loader.base import TRAIN, Loader
from veles_tpu.mutable import Bool
from veles_tpu.ops.decision import DecisionGD
from veles_tpu.ops.evaluator import (EvaluatorMSE, EvaluatorNextByte,
                                      EvaluatorSoftmax)
from veles_tpu.ops.fused import FusedStepRunner
from veles_tpu.ops.nn_units import NNWorkflow
from veles_tpu.ops.registry import forward_registry
from veles_tpu.workflow import Repeater


class StandardWorkflow(NNWorkflow):
    def __init__(self, workflow=None,
                 loader: Optional[Loader] = None,
                 loader_factory: Optional[Callable[..., Loader]] = None,
                 layers: Optional[List[Dict[str, Any]]] = None,
                 loss_function: str = "softmax",
                 decision_config: Optional[Dict[str, Any]] = None,
                 snapshotter_config: Optional[Dict[str, Any]] = None,
                 lr_adjust_config: Optional[Dict[str, Any]] = None,
                 superstep: int = 8,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.loss_function = loss_function
        self.layers_config = layers or []
        #: fused mode runs up to this many same-class minibatches per
        #: device dispatch (lax.scan) — amortizes dispatch latency
        self.superstep = max(1, superstep)

        self.repeater = Repeater(self, name="repeater")
        if loader is None:
            if loader_factory is None:
                raise ValueError("need loader or loader_factory")
            loader = loader_factory(self)
        elif loader.workflow is not self:
            self.add_unit(loader)
        self.loader = loader

        self._create_forwards()
        self._create_evaluator()
        self._create_gds()
        self._create_decision(decision_config or {})
        self._create_snapshotter(snapshotter_config)
        self.fused = FusedStepRunner(
            self, loader=self.loader, forwards=self.forwards,
            evaluator=self.evaluator, gds=self.gds,
            name="fused")
        self.lr_adjust = None
        if lr_adjust_config:
            from veles_tpu.ops.lr_adjust import LearningRateAdjust
            self.lr_adjust = LearningRateAdjust(
                self, name="lr_adjust", **lr_adjust_config)
            self.lr_adjust.loader = self.loader
            self.lr_adjust.gds = self.gds
            self.lr_adjust.fused = self.fused
        self._extra_after_decision: list = []
        self.plotters: list = []

    # -- plotters ------------------------------------------------------

    def link_plotters(self) -> None:
        """Attach the reference's standard plotters (error curves,
        confusion matrix, first-layer weight images); they fire once per
        epoch after Decision and render through the graphics bus
        (reference: StandardWorkflow.link_plotters)."""
        if self.plotters:
            return
        from veles_tpu.plotting_units import (AccumulatingPlotter,
                                              MatrixPlotter, Weights2D)
        ps = [AccumulatingPlotter(self, name="plt_error"),
              AccumulatingPlotter(self, field="loss", name="plt_loss")]
        # the confusion Vector is allocated at initialize(), after this
        # runs — gate on the evaluator's intent, not the buffer
        if getattr(self.evaluator, "compute_confusion", False):
            ps.append(MatrixPlotter(self, evaluator=self.evaluator,
                                    name="plt_confusion"))
        ps.append(Weights2D(self, unit=self.forwards[0],
                            name="plt_weights"))
        for p in ps:
            p.link_decision(self.decision)
        self._extra_after_decision.extend(ps)
        self.plotters = ps

    def link_status_reporter(self, url: str,
                             mode: str = "standalone") -> None:
        """Attach a per-epoch POST to a web-status dashboard
        (reference: veles/web_status.py client side)."""
        from veles_tpu.web_status import StatusReporter
        if any(type(u) is StatusReporter
               for u in self._extra_after_decision):
            return  # snapshot resume: the pickled reporter stays
        rep = StatusReporter(self, url=url, mode=mode,
                             name="status_reporter")
        rep.link_decision(self.decision)
        self._extra_after_decision.append(rep)

    # -- unit creation -------------------------------------------------

    def _flatten_layers(self) -> List[Optional[int]]:
        """``layers`` may hold **residual** entries ``{"type":
        "residual", "layers": [...]}``: ``x + f_k(...f_1(x))`` round
        the inner layers.  Every inner layer is a unit of its own, in
        order, so ``flat_layers`` / ``forwards`` / ``gds`` stay lists
        over ONE index; each inner layer's unit carries the index of
        its entry's first layer (``ForwardUnit.residual_of``), which
        is how every walk of the forwards finds the skip path
        (engine/core.py ``chain_of``); returned here, a mark a layer."""
        self.flat_layers: List[Dict[str, Any]] = []
        residual_of: List[Optional[int]] = []
        for cfg in self.layers_config:
            if cfg["type"] != "residual":
                self.flat_layers.append(cfg)
                residual_of.append(None)
                continue
            inner = cfg["layers"]
            if not inner or any(c["type"] == "residual" for c in inner):
                raise ValueError("a residual entry wraps a non-empty "
                                 "list of plain layers")
            residual_of += [len(self.flat_layers)] * len(inner)
            self.flat_layers.extend(inner)
        return residual_of

    def _create_forwards(self) -> None:
        residual_of = self._flatten_layers()
        self.forwards = []
        prev = None
        for i, cfg in enumerate(self.flat_layers):
            kind = cfg["type"]
            if kind not in forward_registry:
                raise ValueError(f"unknown layer type {kind!r}; have "
                                 f"{sorted(forward_registry)}")
            fwd_cls, _ = forward_registry[kind]
            fwd_kwargs = dict(cfg.get("->", {}))
            unit = fwd_cls(self, name=f"fwd{i}_{kind}", **fwd_kwargs)
            unit.residual_of = residual_of[i]
            if prev is None:
                unit.link_attrs(self.loader, ("input", "minibatch_data"))
            else:
                unit.link_attrs(prev, ("input", "output"))
            self.forwards.append(unit)
            prev = unit

    def _create_evaluator(self) -> None:
        last = self.forwards[-1]
        if self.loss_function == "softmax":
            ev = EvaluatorSoftmax(self, name="evaluator")
            ev.link_attrs(last, ("input", "output"))
            ev.link_attrs(self.loader, ("labels", "minibatch_labels"),
                          ("mask", "minibatch_mask"))
        elif self.loss_function == "next_byte":
            # the targets are the rows themselves: the fused step
            # hands the data store in as the target store
            ev = EvaluatorNextByte(self, name="evaluator")
            ev.link_attrs(last, ("input", "output"))
            ev.link_attrs(self.loader, ("target", "minibatch_data"),
                          ("mask", "minibatch_mask"))
        elif self.loss_function == "mse":
            ev = EvaluatorMSE(self, name="evaluator")
            ev.link_attrs(last, ("input", "output"))
            ev.link_attrs(self.loader, ("target", "minibatch_targets"),
                          ("mask", "minibatch_mask"))
        else:
            raise ValueError(f"unknown loss {self.loss_function!r}")
        self.evaluator = ev

    def _create_gds(self) -> None:
        self.gds = []
        loader = self.loader
        for i, (cfg, fwd) in enumerate(zip(self.flat_layers,
                                           self.forwards)):
            kind = cfg["type"]
            _, gd_cls = forward_registry[kind]
            gd_kwargs = dict(cfg.get("<-", {}))
            gd = gd_cls(self, forward=fwd, name=f"gd{i}_{kind}",
                        **gd_kwargs)
            self.gds.append(gd)

    def _create_decision(self, cfg: Dict[str, Any]) -> None:
        self.decision = DecisionGD(self, name="decision", **cfg)
        self.decision.loader = self.loader
        self.decision.evaluator = self.evaluator

    def _create_snapshotter(self, cfg: Optional[Dict[str, Any]]) -> None:
        self.snapshotter = None
        if cfg is None:
            return
        from veles_tpu.snapshotter import Snapshotter
        self.snapshotter = Snapshotter(self, name="snapshotter", **cfg)
        self.snapshotter.decision = self.decision

    # -- wiring --------------------------------------------------------

    def _clear_control_links(self) -> None:
        for u in self.units:
            u.links_from.clear()
            u.links_to.clear()
        # Derived Bool gates hold closures, which pickling flattens to
        # their momentary values (mutable.Bool.__getstate__) — every
        # expression gate must therefore be re-established at wiring
        # time, or a resumed run trains on validation minibatches.
        loader = self.loader
        for gd in self.gds:
            gd.gate_skip = Bool.from_expr(
                lambda ld=loader: ld.minibatch_class != TRAIN)
        # plotters / status reporters carry the same pickled-frozen-gate
        # hazard — re-derive their gates from the live decision too
        for extra in self._extra_after_decision:
            if hasattr(extra, "link_decision"):
                extra.link_decision(self.decision)

    def _wire_common_tail(self, before_decision) -> None:
        self.decision.link_from(before_decision)
        tail = self.decision
        if self.snapshotter is not None:
            # fire on the validation-improved firing (weights at that
            # moment are the end-of-previous-train-epoch weights the
            # improvement was measured with; reference: Decision
            # triggers Snapshotter on improvement)
            self.snapshotter.link_from(self.decision)
            self.snapshotter.gate_skip = Bool.from_expr(
                lambda d=self.decision: not bool(d.improved))
            tail = self.snapshotter
        for extra in self._extra_after_decision:
            extra.link_from(tail)
            tail = extra
        self.repeater.link_from(tail)          # loop back edge
        self.repeater.gate_block = self.decision.complete
        self.end_point.link_from(tail)
        self.end_point.gate_block = ~self.decision.complete

    def wire_eager(self) -> None:
        """Classic per-unit graph (numpy golden path)."""
        from veles_tpu.engine.core import has_residual
        if has_residual(self.forwards):
            raise NotImplementedError(
                "a layers list with residual entries runs fused only "
                "(the per-unit graph has no skip path)")
        self._clear_control_links()
        self.loader.host_fill_enabled = True
        self.loader.superstep = 1
        self.decision.metrics_source = None
        self.repeater.link_from(self.start_point)
        self.loader.link_from(self.repeater)
        prev = self.loader
        if self.lr_adjust is not None:
            self.lr_adjust.link_from(prev)
            prev = self.lr_adjust
        for f in self.forwards:
            f.link_from(prev)
            prev = f
        self.evaluator.link_from(prev)
        # backward chain, reversed; err chains via link_attrs
        prev = self.evaluator
        last_gd = None
        for i in range(len(self.gds) - 1, -1, -1):
            gd = self.gds[i]
            if last_gd is None:
                gd.link_attrs(self.evaluator, "err_output")
            else:
                gd.link_attrs(last_gd, ("err_output", "err_input"))
            gd.link_from(prev)
            prev = gd
            last_gd = gd
        self._wire_common_tail(prev)

    def wire_fused(self) -> None:
        """Single fused jitted scan per iteration (TPU path)."""
        self._clear_control_links()
        self.loader.host_fill_enabled = False
        self.loader.superstep = self.superstep
        self.decision.metrics_source = self.fused
        self.repeater.link_from(self.start_point)
        self.loader.link_from(self.repeater)
        prev = self.loader
        if self.lr_adjust is not None:
            self.lr_adjust.link_from(prev)
            prev = self.lr_adjust
        self.fused.link_from(prev)
        self._wire_common_tail(self.fused)

    # -- lifecycle -----------------------------------------------------

    def __setstate__(self, state: dict) -> None:
        super().__setstate__(state)
        # attrs introduced after a snapshot was written must default,
        # or pre-existing snapshots become unresumable
        self.__dict__.setdefault("_extra_after_decision", [])
        self.__dict__.setdefault("plotters", [])
        self.__dict__.setdefault("flat_layers", self.layers_config)

    def initialize(self, device: Optional[Device] = None, **kwargs) -> None:
        use_fused = device is not None and device.is_jax \
            and kwargs.pop("fused", True)
        if use_fused:
            self.wire_fused()
        else:
            self.wire_eager()
        super().initialize(device=device, **kwargs)
