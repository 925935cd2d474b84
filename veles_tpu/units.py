"""The Unit: node of the dataflow graph.

Reference parity: veles/units.py — a ``Unit`` has ``initialize()`` and
``run()``; control edges are made with ``link_from(src)`` (the unit
fires when ALL linked predecessors have fired since its last firing);
data edges with ``link_attrs(src, "a", ("mine", "theirs"))`` which alias
attributes to the source unit.  ``gate_block`` stops propagation through
the unit entirely; ``gate_skip`` skips ``run()`` but still propagates —
both are lazily-evaluated ``Bool``s so Decision's ``complete`` flag can
gate the training loop.

TPU-first note: the graph engine is pure host-side Python and carries no
tensors itself — compute lives in jitted step functions (see
veles_tpu/ops/fused.py).  The scheduler is synchronous and
deterministic; per-unit wall time is accumulated for the end-of-run
timing report (reference: workflow unit-timing table).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Set, Tuple, Union

from veles_tpu import telemetry
from veles_tpu.logger import Logger
from veles_tpu.mutable import Bool, LinkableAttribute


class Unit(Logger):
    """A schedulable node. Subclasses override ``initialize`` and ``run``."""

    def __init__(self, workflow: Optional["Unit"] = None,
                 name: Optional[str] = None, **kwargs: Any) -> None:
        self._name = name
        self.workflow = None
        self.links_from: Dict[Unit, bool] = {}
        self.links_to: Set[Unit] = set()
        self.gate_block = Bool(False)
        self.gate_skip = Bool(False)
        self._initialized = False
        self.run_count = 0
        self.run_time = 0.0
        if workflow is not None:
            workflow.add_unit(self)
        self.__dict__.setdefault("_attr_links", {})

    # -- identity ------------------------------------------------------

    @property
    def name(self) -> str:
        return self._name or type(self).__name__

    @name.setter
    def name(self, value: str) -> None:
        self._name = value

    def __repr__(self) -> str:
        return f"<{type(self).__name__} '{self.name}'>"

    # -- attribute linking (data edges) -------------------------------

    def __getattr__(self, name: str) -> Any:
        links = self.__dict__.get("_attr_links")
        if links and name in links:
            return links[name].get()
        raise AttributeError(
            f"{type(self).__name__} '{self.__dict__.get('_name') or ''}' "
            f"has no attribute {name!r}")

    def __setattr__(self, name: str, value: Any) -> None:
        links = self.__dict__.get("_attr_links")
        if links and name in links:
            links[name].set(value)
            return
        object.__setattr__(self, name, value)

    def link_attrs(self, other: "Unit",
                   *names: Union[str, Tuple[str, str]]) -> "Unit":
        """Alias attributes of ``self`` to attributes of ``other``.

        Each name is either ``"attr"`` (same name on both sides) or a
        tuple ``("mine", "theirs")``.  Reads/writes pass through to the
        source unit, so downstream units always observe the producer's
        current value (reference: Unit.link_attrs).
        """
        for n in names:
            mine, theirs = (n, n) if isinstance(n, str) else n
            LinkableAttribute(self, mine, other, theirs)
        return self

    # -- control edges -------------------------------------------------

    def link_from(self, *units: "Unit") -> "Unit":
        for u in units:
            self.links_from[u] = False
            u.links_to.add(self)
        return self

    def unlink_from(self, *units: "Unit") -> "Unit":
        for u in units:
            self.links_from.pop(u, None)
            u.links_to.discard(self)
        return self

    def unlink_all(self) -> None:
        for u in list(self.links_from):
            self.unlink_from(u)
        for u in list(self.links_to):
            u.unlink_from(self)

    @property
    def ready(self) -> bool:
        return all(self.links_from.values()) if self.links_from else True

    def _reset_trigger_state(self) -> None:
        for u in self.links_from:
            self.links_from[u] = False

    # -- lifecycle -----------------------------------------------------

    def initialize(self, **kwargs: Any) -> None:
        """Allocate state. Called by Workflow.initialize in dependency
        order, possibly more than once until it stops raising."""

    def run(self) -> None:
        """Do the unit's work for one firing."""

    def stop(self) -> None:
        """Called when the workflow is stopping (cleanup hook)."""

    # -- scheduler internals (called by Workflow) ----------------------

    # -- snapshot support (SURVEY.md §7 "whole-workflow pickling") -----

    _unpicklable = ("device", "_compiled")

    def __getstate__(self) -> dict:
        """Drop device handles and compiled executables; everything else
        (including the unit graph's cyclic refs) pickles.  Resume
        re-attaches devices and re-jits (reference: snapshot contract,
        SURVEY.md §4.4)."""
        d = dict(self.__dict__)
        for k in self._unpicklable:
            d.pop(k, None)
        return d

    def __setstate__(self, state: dict) -> None:
        self.__dict__.update(state)
        for k in self._unpicklable:
            self.__dict__.setdefault(k, None)
        self._initialized = False

    def fire(self) -> bool:
        """Execute one firing; returns True if ``run()`` actually ran."""
        if bool(self.gate_skip):
            return False
        # the ``<unit>.run`` span (events.DYNAMIC_FAMILIES): its two
        # clock reads also feed the end-of-run timing report
        with telemetry.span(self.name + ".run") as span:
            self.run()
        self.run_time += span.seconds
        self.run_count += 1
        return True


class TrivialUnit(Unit):
    """A no-op pass-through unit (reference: veles/units.py)."""


class Container(Unit):
    """A unit that owns other units (base of Workflow)."""

    def __init__(self, workflow: Optional[Unit] = None, **kwargs: Any) -> None:
        self.units: list = []
        super().__init__(workflow, **kwargs)

    def add_unit(self, unit: Unit) -> None:
        self.units.append(unit)
        unit.workflow = self

    def del_unit(self, unit: Unit) -> None:
        if unit in self.units:
            self.units.remove(unit)
            unit.unlink_all()
