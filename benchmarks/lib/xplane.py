"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to what the
per-layer metrics read: the union of the intervals in which an
operation ran on each device, the operations that took most time, and
the longest idle gaps labelled by what the host was doing.

Works on plain ``(name, start_ns, duration_ns)`` tuples so it can be
checked on a small recorded sample without a profiler
(``benchmarks/tests/``); ``load`` turns a trace file into them.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # name, start_ns, duration_ns

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:traced_window"


def short_name(name: str, limit: int = 160) -> str:
    """An XLA op as the trace prints it, without its layouts and cut to
    ``limit`` characters: the result line and the ledger keep it."""
    return re.sub(r"\{[^{}]*\}", "", name).lstrip("%")[:limit]


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Dict[str, Any]:
    """{"devices": {plane name: [Event]}, "spans": [Event]} — device op
    events of every TPU plane's "XLA Ops" line, and the harness's own
    ``bench:`` host spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def union(events: Iterable[Event], lo: Optional[int] = None,
          hi: Optional[int] = None) -> List[Tuple[int, int]]:
    """Merged [start, end) intervals of the events, clipped to
    [lo, hi)."""
    iv = []
    for _, s, d in events:
        e = s + d
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e > s:
            iv.append((s, e))
    iv.sort()
    out: List[Tuple[int, int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events: Sequence[Event]) -> Dict[str, int]:
    """Total SELF nanoseconds by op name: an event's duration minus
    what the events nested inside it cover (a ``while`` spans its
    body's ops on the same line)."""
    out: Dict[str, int] = {}
    stack: List[List[Any]] = []      # [name, end, self_ns]
    for name, s, d in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][1]:
            n, _, self_ns = stack.pop()
            out[n] = out.get(n, 0) + self_ns
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    while stack:
        n, _, self_ns = stack.pop()
        out[n] = out.get(n, 0) + self_ns
    return out


def window_of(spans: Sequence[Event]) -> Optional[Tuple[int, int]]:
    for name, s, d in spans:
        if name == WINDOW_SPAN:
            return s, s + d
    return None


def label_gap(gap: Tuple[int, int], spans: Sequence[Event]) -> str:
    """The harness span that covers most of the gap, else "workflow
    loop"."""
    best, best_ns = "workflow loop", 0
    for name, s, d in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best_ns:
            best, best_ns = name[len(SPAN_PREFIX):], ov
    return best


def reduce(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """busy_s (mean over device planes), window_s, the top device ops
    by self time and the longest idle gaps of the fullest-traced
    plane, inside the harness's traced-window span (or the extent of
    the device events when the span is missing)."""
    spans = trace["spans"]
    devices = trace["devices"]
    if not devices:
        return {}
    win = window_of(spans)
    if win is None:
        lo = min(s for ev in devices.values() for _, s, _ in ev)
        hi = max(s + d for ev in devices.values() for _, s, d in ev)
        win = (lo, hi)
    lo, hi = win
    busy = []
    for ev in devices.values():
        busy.append(sum(e - s for s, e in union(ev, lo, hi)))
    name0 = sorted(devices)[0]
    ev0 = [e for e in devices[name0] if e[1] + e[2] > lo and e[1] < hi]
    ops = sorted(self_times(ev0).items(), key=lambda kv: -kv[1])[:top]
    iv = union(ev0, lo, hi)
    edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "n_device_events": len(ev0),
        "device_ops": [[short_name(n), ns / 1e9] for n, ns in ops],
        "idle_gaps": [[label_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in gaps[:top]],
    }
