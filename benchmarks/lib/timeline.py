"""Arithmetic on the program's set-up timeline
(``veles_tpu.telemetry.setup_timeline()``): the spans that exited from
import until the program sealed set-up — its first train class's
metric fetch returned — each ``[name, parent, start, end, thread]`` on
``time.perf_counter``.  Set-up runs under no profiler, so this is the
only place a span's start and end are kept, and with them what the
``choosing-metrics`` guide calls a layer's self time: its span's
duration less the part its child spans cover.

Everything is reckoned on ONE thread, the one that sealed (the
training loop's): a span on another thread runs beside the loop and
covers none of its time.  Every instant between the thread's first
start and the seal goes to the span that started last among those
open at it — so spans that nest, touch or overlap are each counted
once, and the self times and the gaps add up to the interval."""

from __future__ import annotations

from typing import Any, Dict, List, Optional

from veles_tpu import telemetry

#: spans that only say "the program is setting up" / "the loop is
#: running": time under them and no other span has no name yet
UNNAMED = ("workflow.initialize", "workflow.run")


def get() -> Optional[Dict[str, Any]]:
    """The timeline of this process, or None on a program that keeps
    none (a parent commit) or kept nothing."""
    fn = getattr(telemetry, "setup_timeline", None)
    tl = fn() if fn is not None else None
    return tl if tl and tl["records"] else None


def self_times(records: List[Any], until: float) -> Dict[str, float]:
    """{span name: seconds of self time before ``until``}, over
    records of one thread; ``""`` holds the time under no span between
    the first start and ``until``."""
    spans = sorted(((s, min(e, until), name)
                    for name, _parent, s, e, _thread in records
                    if s < until), key=lambda x: (x[0], -x[1]))
    if not spans:
        return {}
    edges = sorted({t for s, e, _ in spans for t in (s, e)} | {until})
    out: Dict[str, float] = {}
    live: List[Any] = []
    i = 0
    for a, b in zip(edges, edges[1:]):
        while i < len(spans) and spans[i][0] <= a:
            live.append(spans[i])
            i += 1
        live = [sp for sp in live if sp[1] > a]
        name = live[-1][2] if live else ""
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def breakdown(tl: Dict[str, Any]) -> Dict[str, Any]:
    """Of a timeline: ``interval_s`` from the sealing thread's first
    start to the seal (to its last end while unsealed), ``self_s`` by
    span name, of them ``unspanned_s`` — under no span, or under
    ``UNNAMED`` alone — and the same as ``rows`` for a table: ``(name,
    calls, self seconds, first start since the interval's)`` in order
    of first start, so outermost first."""
    records = tl["records"]
    thread = tl["sealed_thread"]
    if thread is None:
        thread = records[0][4]
    mine = [r for r in records if r[4] == thread]
    until = tl["sealed_at"]
    if until is None:
        until = max(r[3] for r in mine)
    selfs = self_times(mine, until)
    t0 = min(r[2] for r in mine)
    first: Dict[str, float] = {"": t0}
    calls: Dict[str, int] = {}
    for name, _parent, start, _end, _thread in mine:
        first[name] = min(start, first.get(name, start))
        calls[name] = calls.get(name, 0) + 1
    return {"interval_s": until - t0,
            "self_s": selfs,
            "rows": sorted(((n, calls.get(n, 0), s, first[n] - t0)
                            for n, s in selfs.items()),
                           key=lambda row: (row[3], row[0] != "")),
            "unspanned_s": sum(selfs.get(n, 0.0)
                               for n in ("",) + UNNAMED),
            "others": len(records) - len(mine),
            "dropped": tl["dropped"]}
