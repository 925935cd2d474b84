"""Workflow: a container of linked Units with a run loop.

Reference parity: veles/workflow.py — ``Workflow`` holds the unit DAG
between a ``StartPoint`` and an ``EndPoint``; ``initialize()`` recurses
over units in dependency order; ``run()`` drives firings until the end
point fires (Decision's ``complete`` gate steers the loop-back edge vs
the exit edge); a per-unit timing table is reported at the end.

The scheduler here is synchronous and deterministic: a FIFO of ready
units.  The reference used a thread pool, but its compute graph per
iteration is sequential through the chain anyway (SURVEY.md §3.4); on
TPU all heavy work is inside jitted functions dispatched from the
firing unit, and JAX's async dispatch already overlaps host scheduling
with device compute.
"""

from __future__ import annotations

import collections
from typing import Any, Optional

from veles_tpu import events, telemetry
from veles_tpu.mutable import Bool
from veles_tpu.units import Container, Unit


class StartPoint(Unit):
    pass


class EndPoint(Unit):
    def run(self) -> None:
        if self.workflow is not None:
            self.workflow.stopped.set(True)


class Workflow(Container):
    """Container + scheduler for a unit graph."""

    def __init__(self, workflow: Optional[Unit] = None,
                 name: Optional[str] = None, **kwargs: Any) -> None:
        super().__init__(workflow, name=name, **kwargs)
        self.start_point = StartPoint(self, name="start_point")
        self.end_point = EndPoint(self, name="end_point")
        self.stopped = Bool(False)
        #: cooperative graceful-stop request (Phoenix): set from a
        #: signal handler / watchdog thread via ``request_stop()``; the
        #: run loop honors it at the next ITERATION BOUNDARY (a unit
        #: flagged ``iteration_boundary`` — the Repeater), where every
        #: unit has finished the iteration and a snapshot taken there
        #: resumes exactly like one written by the Snapshotter.  A
        #: plain bool: assignment is atomic under the GIL and safe from
        #: signal-handler context (no locks, no allocation).
        self.stop_requested = False
        self.device = None
        self._max_firings = kwargs.get("max_firings", 10_000_000)
        #: cumulative wall-clock seconds spent inside run() — unlike
        #: per-unit run_time this brackets the async device work too,
        #: because the loop's metric fetches (Decision) block on it
        self.wall_time = 0.0

    # -- lifecycle -----------------------------------------------------

    def initialize(self, device: Any = None, **kwargs: Any) -> None:
        """Initialize all units in control-dependency order.

        Like the reference, initialization is iterated: a unit whose
        ``initialize`` raises ``AttributeError`` (its linked inputs not
        yet allocated by a predecessor) is retried after the others; a
        full pass with no progress re-raises.
        """
        self.device = device
        # (spans are ``with`` blocks in place, never helper calls: an
        # extra Python frame between the entry point and a jitted call
        # moves how long jax takes to lower it — PERF.md, PR 26)
        with telemetry.span(events.SPAN_WORKFLOW_INITIALIZE):
            order = self._dependency_order()
            pending = [u for u in order if u is not self]
            while pending:
                errors = {}
                still = []
                for u in pending:
                    try:
                        # ``init.<unit>``: one span per attempt
                        with telemetry.span("init." + u.name):
                            u.initialize(device=device, **kwargs)
                        u._initialized = True
                    except AttributeError as e:
                        errors[u] = e
                        still.append(u)
                if len(still) == len(pending):
                    u, e = next(iter(errors.items()))
                    raise RuntimeError(
                        f"initialization deadlock: {len(still)} units "
                        f"cannot initialize; first: {u} -> {e}") from e
                pending = still
        self._initialized = True

    def _dependency_order(self) -> list:
        """Topological-ish order over control edges, ignoring back edges
        (edges from units later discovered — the training loop edge)."""
        seen = []
        seen_set = set()
        queue = collections.deque([self.start_point])
        while queue:
            u = queue.popleft()
            if u in seen_set:
                continue
            seen.append(u)
            seen_set.add(u)
            for succ in sorted(u.links_to, key=lambda x: x.name):
                if succ not in seen_set:
                    queue.append(succ)
        # Units never linked from the start point chain still need init.
        for u in self.units:
            if u not in seen_set:
                seen.append(u)
                seen_set.add(u)
        return seen

    # -- run loop ------------------------------------------------------

    def run(self) -> None:
        """Fire the start point and drive the graph until stopped."""
        if not self._initialized:
            raise RuntimeError("workflow.run() before initialize()")
        # self time of ``workflow.run`` (less its ``<unit>.run``
        # children) is the loop's own overhead
        with telemetry.span(events.SPAN_WORKFLOW_RUN) as span:
            self.stopped.set(False)
            # a stop requested before (or during a previous) run must
            # not leak into this one — notably a workflow snapshotted
            # by a graceful stop carries stop_requested=True on disk,
            # and the RESUMED run would otherwise stop before its
            # first firing
            self.stop_requested = False
            queue: collections.deque = collections.deque(
                [self.start_point])
            firings = 0
            while queue and not bool(self.stopped):
                unit = queue.popleft()
                if self.stop_requested and \
                        getattr(unit, "iteration_boundary", False):
                    # graceful stop lands HERE: the boundary unit
                    # (Repeater) is about to open the next iteration,
                    # so every unit has completed the current one —
                    # identical to the state a fresh run() reaches
                    # right before the same firing, which is what
                    # makes the final snapshot resume exactly
                    break
                if bool(unit.gate_block):
                    continue
                unit._reset_trigger_state()
                unit.fire()
                firings += 1
                if firings > self._max_firings:
                    raise RuntimeError("workflow exceeded max firings "
                                       "(runaway loop?)")
                if bool(self.stopped):
                    break
                for succ in sorted(unit.links_to, key=lambda x: x.name):
                    succ.links_from[unit] = True
                    if succ.ready and not bool(succ.gate_block):
                        queue.append(succ)
        self.wall_time += span.seconds
        self.on_workflow_finished()

    def stop(self) -> None:
        self.stopped.set(True)
        for u in self.units:
            u.stop()

    def request_stop(self) -> None:
        """Ask the run loop to stop at the next iteration boundary
        (see ``stop_requested``).  Unlike ``stop()`` this never fires
        unit cleanup hooks and leaves the graph in a resumable state —
        the preemption path (Launcher graceful stop) snapshots right
        after ``run()`` returns."""
        self.stop_requested = True

    def on_workflow_finished(self) -> None:
        self.report_timings()
        # end-of-run telemetry summary (images/sec + achieved MFU
        # gauges): the CLI standalone path finishes run() without ever
        # calling stop(), so the fused runner's summary fires here too
        # (idempotent — the runner gates on its first-firing timestamp)
        fused = getattr(self, "fused", None)
        if fused is not None and \
                hasattr(fused, "_record_telemetry_summary"):
            fused._record_telemetry_summary()

    def report_timings(self) -> None:
        """Per-unit wall-time table (reference: end-of-run unit timing)."""
        rows = [(u.name, u.run_count, u.run_time)
                for u in self.units if u.run_count]
        if not rows:
            return
        total = sum(r[2] for r in rows) or 1e-12
        self.info("unit timing report:")
        for name, count, t in sorted(rows, key=lambda r: -r[2]):
            self.info("  %-28s %8d runs  %9.3fs  %5.1f%%",
                      name, count, t, 100.0 * t / total)

    # -- snapshot support ---------------------------------------------

    def __setstate__(self, state: dict) -> None:
        super().__setstate__(state)
        self.__dict__.setdefault("wall_time", 0.0)
        # pre-Phoenix snapshots lack the flag; and NEVER carry a stale
        # request into a resumed run
        self.stop_requested = False

    def generate_data_for_master(self) -> Any:
        return None

    def generate_data_for_slave(self, slave: Any = None) -> Any:
        return None

    def apply_data_from_master(self, data: Any) -> None:
        pass

    def apply_data_from_slave(self, data: Any, slave: Any = None) -> None:
        pass


class Repeater(Unit):
    """Joins the loop-back edge with the initial edge so the loader can
    be triggered either by the start point or by the end of an iteration
    (reference: veles/workflow.py Repeater).

    A Repeater fires when ANY predecessor fires (OR semantics), unlike
    normal units (AND semantics).
    """

    #: the Repeater opens each training iteration, so the instant it is
    #: about to fire is the graceful-stop boundary: loader pointer
    #: advanced, params updated, decision/snapshotter done — a snapshot
    #: here resumes exactly (Workflow.run honors stop_requested on it)
    iteration_boundary = True

    @property
    def ready(self) -> bool:
        if not self.links_from:
            return True
        return any(self.links_from.values())
