"""Sightline: the unified telemetry core — metrics registry, span
tracing, and the run journal.

Until this module existed, every subsystem grew its own ad-hoc
telemetry attributes (``FusedStepRunner.stream_transfer_bytes``,
``ChipEvaluatorPool.hangs_detected``, ``GeneticOptimizer.eval_count``)
and each experiment scraped them field by field — nothing could answer the
questions the roadmap's serving/scaling items are graded on (p50/p99
latency, sustained throughput) without bespoke instrumentation per
experiment.  Sightline is the read-side twin of the Faultline
injection registry (veles_tpu/faults.py): NAMED instrumentation
points, armed through ONE inherited environment variable, near-free
when idle.

Three primitives, one process-wide registry:

- **Counter** — a monotonic number (``counter("fused.dispatches")
  .inc()``).  Float-valued counters accumulate seconds/bytes.
- **Gauge** — a last-write-wins level (``gauge("ga.last_hang_wait")
  .set(3.1)``).
- **Histogram** — fixed log-spaced buckets (32 per decade over
  [1e-7, 1e7)), so p50/p90/p99 are computed exactly from the bucket
  counts with geometric in-bucket interpolation — no sample retention,
  O(1) memory, <= ~7.5% worst-case relative quantile error (typically
  far less), bucket-exact min/max/count/sum.  ``record()`` costs one
  ``log10`` + a list increment.

**Spans** (``with span(events.SPAN_WORKFLOW_RUN):``) are nestable (a
thread-local stack) and feed the histogram of the same name; spans
opened with ``journal=True`` also append an event line.  In a process
that has imported jax a span is also a ``jax.profiler.TraceAnnotation``
named ``veles:<name>``, so a profiler trace shows the program's own
spans on the device trace's clock (see :class:`span`).

**The set-up timeline**: until it is sealed — once, by the training
loop when its first train class's metric fetch has returned — every
span's exit also appends ``(name, parent, start, end, thread)`` to
one bounded list (``setup_timeline()``), so set-up, which runs under
no profiler, keeps what a layer's self time is computed from.  After
the seal a span's exit pays one global load and a check for None.

**The journal** is an append-only JSONL file of notable run events
(``event("ga.hang_detected", kind=...)``): hang detections, restarts,
OOM degradations, snapshot fallbacks, epoch ends — the replayable
timeline a post-mortem reads next to the quantile tables.  Hot-path
metrics never journal; events are for state transitions.

**Persistence**: when ``$VELES_METRICS_DIR`` is set (or
``configure(dir)`` ran — which exports the variable so every child
process inherits it, exactly like ``VELES_FAULTS``), each process
appends its journal to ``journal-<pid>.jsonl`` and flushes a cumulative
registry snapshot to ``metrics-<pid>.json`` via the PR-6 tempfile +
``os.replace`` discipline (a reader NEVER sees a torn file).  Flushes
happen on journal events (throttled), at exit, and wherever a
subsystem calls ``flush()`` explicitly (the serve-mode GA evaluator
flushes after every job so a kill -9 loses at most one genome's
numbers).  ``ChipEvaluatorPool`` merges a dead/closed evaluator
child's snapshot back into the parent registry
(``adopt_child_snapshot``), so a GA run yields ONE aggregate view
across its process tree; ``scripts/obs_report.py`` renders a metrics
dir into the human-readable summary.

Telemetry must never take down a run: file errors drop the sink and
keep counting in memory; ``set_enabled(False)`` reduces every call to
one module-attribute load + falsy check (a span keeps its two clock
reads for ``seconds``).
"""

from __future__ import annotations

import atexit
import json
import math
import os
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from veles_tpu.analysis import witness

ENV_DIR = "VELES_METRICS_DIR"

#: what a span is called in a ``jax.profiler`` trace's host plane
ANNOTATION_PREFIX = "veles:"

#: histogram bucket layout: log-spaced, 32 per decade over
#: [10^LOG_LO, 10^LOG_HI); bucket 0 is the underflow bin (x < lo,
#: including zero/negative), the last is overflow.  32/decade bounds
#: the relative quantile error at 10^(1/32)-1 ~ 7.5% worst case
#: (geometric interpolation typically lands within ~2%).
LOG_LO = -7
LOG_HI = 7
PER_DECADE = 32
NBUCKETS = (LOG_HI - LOG_LO) * PER_DECADE
_STEP = 10.0 ** (1.0 / PER_DECADE)

#: global kill switch — when False every record/inc/event is one
#: attribute load + falsy check (the bench's overhead probe)
_enabled = True


def set_enabled(on: bool) -> None:
    global _enabled
    _enabled = bool(on)


def enabled() -> bool:
    return _enabled


class Counter:
    """Monotonic counter (int or float increments)."""

    __slots__ = ("name", "value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value = 0
        self._lock = witness.lock("telemetry.counter")

    def inc(self, n: float = 1) -> None:
        if not _enabled:
            return
        with self._lock:
            self.value += n

    def _reset(self) -> None:
        with self._lock:
            self.value = 0


class Gauge:
    """Last-write-wins level; ``value`` is None until first set."""

    __slots__ = ("name", "value")

    def __init__(self, name: str) -> None:
        self.name = name
        self.value: Optional[float] = None

    def set(self, v: float) -> None:
        if not _enabled:
            return
        self.value = v

    def _reset(self) -> None:
        self.value = None


class Histogram:
    """Fixed log-spaced buckets; exact-from-buckets quantiles.

    No samples are retained: ``record`` increments one bucket and the
    exact count/sum/min/max scalars.  ``quantile(q)`` walks the
    cumulative bucket counts to the target rank and interpolates
    geometrically inside the selected bucket, clamped to the exact
    observed [min, max] — deterministic, O(buckets), and mergeable
    across processes by bucket-wise addition.
    """

    __slots__ = ("name", "count", "sum", "min", "max", "buckets",
                 "exemplars", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.buckets: List[int] = [0] * (NBUCKETS + 2)
        #: bucket index -> last trace_id that landed there (tail
        #: exemplars: "why is p99 high" jumps straight to a trace)
        self.exemplars: Dict[int, str] = {}
        self._lock = witness.lock("telemetry.histogram")

    @staticmethod
    def _index(x: float) -> int:
        if x < 10.0 ** LOG_LO:
            return 0
        if x >= 10.0 ** LOG_HI:
            return NBUCKETS + 1
        i = int((math.log10(x) - LOG_LO) * PER_DECADE)
        return 1 + min(NBUCKETS - 1, max(0, i))

    def record(self, x: float,
               exemplar: Optional[str] = None) -> None:
        """Record one sample; ``exemplar`` (a trace_id) is retained
        per landing bucket, last-wins, so the tail buckets always
        name a renderable trace (Flightline's p99 exemplars)."""
        if not _enabled:
            return
        x = float(x)
        with self._lock:
            self.count += 1
            self.sum += x
            if x < self.min:
                self.min = x
            if x > self.max:
                self.max = x
            i = self._index(x)
            self.buckets[i] += 1
            if exemplar is not None:
                self.exemplars[i] = exemplar

    @property
    def mean(self) -> Optional[float]:
        return self.sum / self.count if self.count else None

    def quantile(self, q: float) -> Optional[float]:
        """The q-quantile (q in [0, 1]) from the bucket counts."""
        if self.count == 0:
            return None
        target = max(1, math.ceil(q * self.count))
        cum = 0
        for i, c in enumerate(self.buckets):
            if not c:
                continue
            if cum + c >= target:
                if i == 0:
                    return self.min
                if i == NBUCKETS + 1:
                    return self.max
                e0 = 10.0 ** (LOG_LO + (i - 1) / PER_DECADE)
                frac = (target - cum) / c
                v = e0 * (_STEP ** frac)
                return min(max(v, self.min), self.max)
            cum += c
        return self.max

    def snapshot_buckets(self) -> Any:
        """An opaque (bucket counts, count) base for
        :meth:`delta_quantile` — take one per window edge."""
        with self._lock:
            return list(self.buckets), self.count

    def delta_quantile(self, base: Any, q: float,
                       min_count: int = 1) -> Optional[float]:
        """The q-quantile of ONLY the samples recorded since ``base``
        (a :meth:`snapshot_buckets` result) — the windowed read the
        fleet sentinel's adaptive hedge threshold uses: a cumulative
        quantile lags the live distribution badly when load shifts,
        so hedging against it fires on far more than the intended
        tail.  None when fewer than ``min_count`` samples landed in
        the window."""
        base_buckets, base_count = base
        with self._lock:
            delta = [b - p for b, p in zip(self.buckets,
                                           base_buckets)]
            n = self.count - base_count
        if n < max(1, min_count):
            return None
        target = max(1, math.ceil(q * n))
        cum = 0
        for i, c in enumerate(delta):
            if c <= 0:
                continue
            if cum + c >= target:
                if i == 0:
                    return 10.0 ** LOG_LO
                if i == NBUCKETS + 1:
                    return 10.0 ** LOG_HI
                e0 = 10.0 ** (LOG_LO + (i - 1) / PER_DECADE)
                frac = (target - cum) / c
                return e0 * (_STEP ** frac)
            cum += c
        return None

    def to_dict(self) -> Dict[str, Any]:
        with self._lock:
            d: Dict[str, Any] = {
                "count": self.count,
                "sum": self.sum,
                "min": self.min if self.count else None,
                "max": self.max if self.count else None,
                "buckets": {str(i): c
                            for i, c in enumerate(self.buckets) if c},
            }
            if self.exemplars:
                d["exemplars"] = {str(i): t
                                  for i, t in self.exemplars.items()}
        for q, key in ((0.5, "p50"), (0.9, "p90"), (0.99, "p99")):
            d[key] = self.quantile(q)
        return d

    def merge_dict(self, d: Dict[str, Any]) -> None:
        """Bucket-wise merge of a snapshot dict (quantile keys in the
        dict are ignored — they are recomputed from the buckets)."""
        with self._lock:
            self.count += int(d.get("count", 0))
            self.sum += float(d.get("sum", 0.0))
            if d.get("min") is not None:
                self.min = min(self.min, float(d["min"]))
            if d.get("max") is not None:
                self.max = max(self.max, float(d["max"]))
            for i, c in (d.get("buckets") or {}).items():
                self.buckets[int(i)] += int(c)
            for i, t in (d.get("exemplars") or {}).items():
                self.exemplars.setdefault(int(i), str(t))

    def _reset(self) -> None:
        with self._lock:
            self.count = 0
            self.sum = 0.0
            self.min = math.inf
            self.max = -math.inf
            self.buckets = [0] * (NBUCKETS + 2)
            self.exemplars = {}


class Registry:
    """A namespace of metrics.  The module-level singleton is the
    process registry; standalone instances serve offline merging
    (scripts/obs_report.py)."""

    def __init__(self) -> None:
        self._lock = witness.lock("telemetry.registry")
        self.counters: Dict[str, Counter] = {}
        self.gauges: Dict[str, Gauge] = {}
        self.histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        c = self.counters.get(name)
        if c is None:
            with self._lock:
                c = self.counters.setdefault(name, Counter(name))
        return c

    def gauge(self, name: str) -> Gauge:
        g = self.gauges.get(name)
        if g is None:
            with self._lock:
                g = self.gauges.setdefault(name, Gauge(name))
        return g

    def histogram(self, name: str) -> Histogram:
        h = self.histograms.get(name)
        if h is None:
            with self._lock:
                h = self.histograms.setdefault(name, Histogram(name))
        return h

    def snapshot(self) -> Dict[str, Any]:
        """The registry as one JSON-ready dict (cumulative totals)."""
        return {
            "counters": {n: c.value for n, c in
                         sorted(self.counters.items()) if c.value},
            "gauges": {n: g.value for n, g in
                       sorted(self.gauges.items())
                       if g.value is not None},
            "histograms": {n: h.to_dict() for n, h in
                           sorted(self.histograms.items()) if h.count},
        }

    def merge_snapshot(self, snap: Dict[str, Any]) -> None:
        """Fold another process's cumulative snapshot in: counters and
        histogram buckets ADD; gauges fill only where this registry has
        no value (a level from another process must not clobber a live
        local one)."""
        for n, v in (snap.get("counters") or {}).items():
            self.counter(n).inc(v)
        for n, v in (snap.get("gauges") or {}).items():
            if self.gauge(n).value is None:
                self.gauge(n).value = v
        for n, d in (snap.get("histograms") or {}).items():
            self.histogram(n).merge_dict(d)

    def reset(self) -> None:
        """Zero every metric IN PLACE — object identity is preserved,
        so call sites holding a Counter/Histogram reference stay wired
        to the registry (tests reset between cases)."""
        for c in self.counters.values():
            c._reset()
        for g in self.gauges.values():
            g._reset()
        for h in self.histograms.values():
            h._reset()


#: the process registry
_registry = Registry()

#: in-memory ring of recent journal events (tests and drills read this
#: even with no metrics dir configured)
_recent: "deque[Dict[str, Any]]" = deque(maxlen=4096)

_dir: Optional[str] = os.environ.get(ENV_DIR) or None
_journal_file = None
_journal_lock = witness.lock("telemetry.journal")
_last_flush = 0.0
FLUSH_EVERY = 5.0

_tls = threading.local()

#: the set-up timeline: ``(name, parent, start, end, thread)`` of every
#: span that exited while it was open, on ``time.perf_counter`` (the
#: histograms' clock).  Open from import, sealed once (``seal_setup``);
#: past ``TIMELINE_CAP`` records are counted, not kept.
TIMELINE_CAP = 4096
_timeline: List[tuple] = []
_timeline_dropped = 0
#: (perf_counter, thread) of the seal; None while the timeline is open
_timeline_sealed: Optional[tuple] = None
_timeline_lock = witness.lock("telemetry.timeline")


def counter(name: str) -> Counter:
    return _registry.counter(name)


def gauge(name: str) -> Gauge:
    return _registry.gauge(name)


def histogram(name: str) -> Histogram:
    return _registry.histogram(name)


def metrics_dir() -> Optional[str]:
    return _dir


def configure(metrics_dir: Optional[str]) -> None:
    """Point the persistence layer at ``metrics_dir`` (None tears it
    down).  Exports ``$VELES_METRICS_DIR`` so child processes (GA
    evaluators, multihost peers) inherit the arming — one flag covers
    the whole process tree, the Faultline convention."""
    global _dir, _journal_file
    with _journal_lock:
        if _journal_file is not None:
            try:
                _journal_file.close()
            except OSError:
                pass
            _journal_file = None
    _dir = metrics_dir or None
    if _dir:
        os.makedirs(_dir, exist_ok=True)
        os.environ[ENV_DIR] = _dir
    else:
        os.environ.pop(ENV_DIR, None)


def _journal(rec: Dict[str, Any]) -> None:
    global _journal_file
    if not _dir:
        return
    with _journal_lock:
        if _journal_file is None:
            try:
                os.makedirs(_dir, exist_ok=True)
                _journal_file = open(
                    os.path.join(_dir,
                                 f"journal-{os.getpid()}.jsonl"),
                    "a", buffering=1 << 16)
            except OSError:
                return
        try:
            _journal_file.write(json.dumps(rec) + "\n")
            # notable state transitions hit the disk IMMEDIATELY (the
            # pre-Flightline per-line behavior: a concurrent reader —
            # chaos drills poll live journals — sees them at once).
            # Only the per-request ``trace.*`` family rides the file
            # buffer: a write() syscall per hop is exactly the serving
            # overhead the tracing gate bounds, its readers are
            # offline assemblers, and the crash tail lives in the
            # flight-recorder ring anyway.  The buffer drains on the
            # next notable event, the periodic background flush, or
            # shutdown.
            if not str(rec.get("event", "")).startswith("trace."):
                _journal_file.flush()
        except (OSError, ValueError):
            # full/vanished disk or closed handle: observability must
            # never take down the run — drop the sink, keep the ring
            try:
                _journal_file.close()
            except OSError:
                pass
            _journal_file = None


#: the Flightline seam: a zero-arg callable returning (trace_id,
#: span_id) when a sampled trace context is parked on this thread,
#: else None — veles_tpu/trace.py registers it at import so every
#: journal event inside ``trace.use(ctx)`` auto-carries its trace
_trace_provider: Optional[Any] = None


def set_trace_provider(fn: Optional[Any]) -> None:
    global _trace_provider
    _trace_provider = fn


def event(name: str, **fields: Any) -> None:
    """Append one journal event (and keep it in the in-memory ring).
    Events are for notable state transitions, not per-dispatch data —
    histograms carry the hot-path distributions.  Every event carries
    a ``mono`` monotonic stamp next to ``ts`` so cross-process merges
    can skew-correct the interleaving (obs.py), plus ``trace``/
    ``span`` when the thread runs under a sampled trace context
    (explicit caller fields win over the provider's)."""
    if not _enabled:
        return
    rec: Dict[str, Any] = {"ts": round(time.time(), 3),
                           "mono": round(time.monotonic(), 6),
                           "event": name}
    rec.update(fields)
    if _trace_provider is not None:
        try:
            t = _trace_provider()
        except Exception:  # noqa: BLE001 — tracing must never break
            t = None       # the journal
        if t is not None:
            rec.setdefault("trace", t[0])
            rec.setdefault("span", t[1])
    _recent.append(rec)
    _journal(rec)
    _maybe_flush()


def recent_events(name: Optional[str] = None) -> List[Dict[str, Any]]:
    evs = list(_recent)
    if name is not None:
        evs = [e for e in evs if e.get("event") == name]
    return evs


class span:
    """Time a block into ``histogram(name)``.  Spans nest through a
    thread-local stack (``span_stack()``); ``journal=True`` also emits
    an event at exit carrying the duration, the parent span, and the
    caller's fields.

    Where ``jax`` is ALREADY imported in this process the block also
    runs under ``jax.profiler.TraceAnnotation("veles:" + name)``: with
    no profiler session that is well under a microsecond; with one,
    the span sits in the trace's host plane beside the device's ops,
    on the profiler's clock, nested as the stack nests.  This module
    never imports jax itself (supervisor, launcher and GA pool parents
    must stay off it).

    ``seconds`` holds the block's duration after exit — also with
    telemetry disabled, when the two clock reads are all a span costs
    — so a caller that keeps its own total (``Unit.run_time``) reads
    the same clock pair."""

    __slots__ = ("name", "journal", "fields", "seconds", "_t0",
                 "_stack", "_parent", "_annotation")

    def __init__(self, name: str, journal: bool = False,
                 **fields: Any) -> None:
        self.name = name
        self.journal = journal
        self.fields = fields
        self.seconds = 0.0
        self._stack: Optional[List["span"]] = None
        self._annotation = None

    def __enter__(self) -> "span":
        if _enabled:
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            self._parent = stack[-1].name if stack else None
            stack.append(self)
            self._stack = stack
            # getattr: another thread may be half way through
            # ``import jax``
            annotate = getattr(sys.modules.get("jax.profiler"),
                               "TraceAnnotation", None)
            if annotate is not None:
                self._annotation = annotate(ANNOTATION_PREFIX
                                            + self.name)
                self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = self.seconds = time.perf_counter() - self._t0
        stack = self._stack
        if stack is None:
            return
        self._stack = None
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
            self._annotation = None
        if stack and stack[-1] is self:
            stack.pop()
        histogram(self.name).record(dt)
        if _timeline_sealed is None:
            _timeline_add(self.name, self._parent, self._t0,
                          self._t0 + dt)
        if self.journal:
            event(self.name, seconds=round(dt, 6),
                  parent=self._parent, depth=len(stack),
                  **self.fields)


def span_stack() -> List[str]:
    """The current thread's open spans, outermost first."""
    return [s.name for s in getattr(_tls, "stack", None) or ()]


def _timeline_add(name: str, parent: Optional[str], start: float,
                  end: float) -> None:
    global _timeline_dropped
    with _timeline_lock:
        if len(_timeline) < TIMELINE_CAP:
            _timeline.append((name, parent, start, end,
                              threading.get_ident()))
        else:
            _timeline_dropped += 1


def seal_setup() -> None:
    """End of set-up, as the program itself sees it: stop keeping
    spans.  The spans still open on the calling thread (the loop's:
    ``workflow.run``, ``decision.run``) are kept as ending now, so the
    thread's records cover it up to the seal.  Only the first call
    seals."""
    global _timeline_sealed
    with _timeline_lock:
        if _timeline_sealed is not None:
            return
        now = time.perf_counter()
        me = threading.get_ident()
        for s in reversed(getattr(_tls, "stack", None) or ()):
            _timeline.append((s.name, s._parent, s._t0, now, me))
        _timeline_sealed = (now, me)


def setup_timeline() -> Dict[str, Any]:
    """The set-up timeline as it stands: ``records`` — ``[name, parent,
    start, end, thread]`` in order of exit, seconds on
    ``time.perf_counter`` — ``sealed_at`` and ``sealed_thread`` (None
    while it is open), and ``dropped``, the spans past the cap."""
    with _timeline_lock:
        at, thread = _timeline_sealed or (None, None)
        return {"records": [list(r) for r in _timeline],
                "sealed_at": at, "sealed_thread": thread,
                "dropped": _timeline_dropped}


def snapshot() -> Dict[str, Any]:
    snap = _registry.snapshot()
    snap["pid"] = os.getpid()
    snap["ts"] = round(time.time(), 3)
    if _timeline:
        snap["setup_timeline"] = setup_timeline()
    return snap


def merge_snapshot(snap: Dict[str, Any]) -> None:
    _registry.merge_snapshot(snap)


def flush() -> Optional[str]:
    """Write this process's cumulative snapshot atomically to
    ``metrics-<pid>.json`` (tempfile + ``os.replace`` — a concurrent
    reader always parses a complete file).  No-op (None) when no
    metrics dir is configured; never raises."""
    global _last_flush
    d = _dir
    if not d:
        return None
    try:
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"metrics-{os.getpid()}.json")
        fd, tmp = tempfile.mkstemp(
            dir=d, prefix=os.path.basename(path) + ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(snapshot(), f)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        with _journal_lock:
            if _journal_file is not None:
                _journal_file.flush()
        witness.write_snapshot(d)
        _last_flush = time.monotonic()
        return path
    except OSError:
        return None


def _maybe_flush() -> None:
    # the throttled flush runs OFF the emitting thread: a full
    # snapshot write costs 1-3ms, and paying it synchronously inside
    # event() put a once-per-FLUSH_EVERY stall squarely into the
    # serving p99 whenever tracing (or any per-request journaling)
    # was on — the Flightline overhead gate caught it
    global _last_flush
    if _dir and time.monotonic() - _last_flush > FLUSH_EVERY:
        _last_flush = time.monotonic()   # even on failure: no storms

        def _bg() -> None:
            try:
                flush()
            except Exception:  # noqa: BLE001 — interpreter teardown
                pass
        threading.Thread(target=_bg, daemon=True,
                         name="telemetry-flush").start()


def maybe_flush() -> None:
    """Throttled flush (at most once per FLUSH_EVERY seconds): the
    periodic pulse long-lived serving processes call from their
    heartbeat loop, so the on-disk snapshot (and the lock-witness
    table riding on it) stays fresh even when no journal event fires
    — a SIGKILLed replica then leaves observations at most one
    heartbeat window stale."""
    _maybe_flush()


def adopt_child_snapshot(pid: int) -> bool:
    """Merge a child process's ``metrics-<pid>.json`` into this
    registry and rename it ``*.merged`` so offline aggregation
    (obs_report) cannot double-count it.  Returns True when a file was
    merged.  The parent's next flush then carries the aggregate."""
    d = _dir
    if not d:
        return False
    path = os.path.join(d, f"metrics-{pid}.json")
    if not os.path.isfile(path):
        return False
    try:
        with open(path) as f:
            snap = json.load(f)
    except (OSError, ValueError):
        return False
    merge_snapshot(snap)
    try:
        os.replace(path, path + ".merged")
    except OSError:
        pass
    return True


def reset() -> None:
    """Zero every metric in place, clear the event ring, drop the
    journal handle, reopen the set-up timeline empty, and re-read the
    environment arming — the test fixture's clean-slate hook.  Live
    Counter/Histogram references
    held by long-lived objects stay valid (they are zeroed, not
    replaced)."""
    global _dir, _journal_file, _last_flush
    global _timeline_dropped, _timeline_sealed
    _registry.reset()
    _recent.clear()
    with _timeline_lock:
        del _timeline[:]
        _timeline_dropped, _timeline_sealed = 0, None
    with _journal_lock:
        if _journal_file is not None:
            try:
                _journal_file.close()
            except OSError:
                pass
            _journal_file = None
    _dir = os.environ.get(ENV_DIR) or None
    _last_flush = 0.0
    if getattr(_tls, "stack", None):
        _tls.stack = []


atexit.register(flush)
