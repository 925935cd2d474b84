"""The program's own spans and scopes, read by hand (the harness does
not run this; like ``readings_chip.py``):

    python3 benchmarks/tests/scopes_chip.py run <workload> <seed> <dir|->
    python3 benchmarks/tests/scopes_chip.py read <file.xplane.pb>

``run`` makes one ``--trace 1`` run of a cell on the chip in this
process, keeps its trace in ``<dir>`` (``-``: keeps none and reads
none), and prints two JSON lines: the harness's result, then
``{"inside": ...}`` — the six metrics the program records about itself
(``benchmarks/metrics/``: not wired into the cells yet, PERF.md
section 7) beside the outside ones they should agree with, and the
whole registry.  It compiles with the op metadata IN the cache key:
the key leaves metadata out by default, so a step program cached
before the scopes existed would be loaded as is, without them (a
second ``run`` finds the first one's programs and is warm).

``read`` reduces a kept trace, on any machine:

- device self time by ``jax.named_scope`` (``fwd/<layer>``,
  ``bwd/<layer>``, ``update/<layer>``, ``gather``, ``ingest``,
  ``cast_params``, ``loss``).  The scope path is the ``tf_op`` stat of
  a device event's METADATA (jax 0.9.0 / libtpu 0.0.34) — which
  ``jax.profiler.ProfileData`` does not show, hence the small reader
  of the xplane wire format below.  A fusion carries one op's path,
  so a pass XLA fused across two layers counts under one of them;
- that every ``veles:fused.train_submit`` lies inside a
  ``bench:fused.run`` on the same thread;
- each long device gap beside the host's ``veles:fused.fetch_metrics``
  before it and the next ``veles:fused.*_submit`` after it.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import struct
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib import xplane  # noqa: E402

INSIDE = ("workflow.initialize_ms", "fused.first_submit_ms",
          "fused.compile_ms", "fused.submit_ms", "fused.fetch_wait_ms",
          "loop.turnaround_ms")

SCOPE = re.compile(r"(?:^|/)((?:fwd|bwd|update)/[^/:]+|gather|ingest|"
                   r"cast_params|loss)(?=[/:]|$)")
LONG_GAP_NS = 500_000


# -- the xplane wire format (tsl/profiler/protobuf/xplane.proto) --------

def _varint(buf, i):
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        if b < 0x80:
            return v, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: ints for varints, bytes
    views for length-delimited fields, raw bytes for fixed ones."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = bytes(buf[i:i + 8]), i + 8
        elif wire == 5:
            v, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wire}")
        yield key >> 3, v


def _text(v):
    return bytes(v).decode("utf-8", "replace")


def _stat(buf, stat_names):
    """(name, value) of one XStat."""
    name = value = None
    for f, v in _fields(buf):
        if f == 1:
            name = stat_names.get(v, str(v))
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f in (3, 4):
            value = v
        elif f == 5:
            value = _text(v)
        elif f == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _map_entry(buf):
    key = value = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def planes(path):
    """[{"name", "lines": {line name: [(event name, start_ns, dur_ns,
    metadata stats)]}}] of a ``*.xplane.pb``.  ``metadata stats`` is
    the dict the event's XEventMetadata carries (``tf_op``, ``flops``,
    ``bytes_accessed``, ``hlo_category``, ...), shared by the events
    of one op."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = []
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, raw_lines, raw_events, stat_names = "", [], {}, {}
        for g, v in _fields(plane):
            if g == 2:
                name = _text(v)
            elif g == 3:
                raw_lines.append(v)
            elif g == 4:
                k, m = _map_entry(v)
                raw_events[k] = m
            elif g == 5:
                k, m = _map_entry(v)
                stat_names[k] = next(
                    (_text(x) for h, x in _fields(m) if h == 2), "")
        events = {}
        for k, m in raw_events.items():
            ev_name, stats = "", {}
            for h, x in _fields(m):
                if h == 2:
                    ev_name = _text(x)
                elif h == 5:
                    s, val = _stat(x, stat_names)
                    stats[s] = val
            events[k] = (ev_name, stats)
        lines = {}
        for raw in raw_lines:
            line_name, t0_ns, evs = "", 0, []
            for h, x in _fields(raw):
                if h == 2:
                    line_name = _text(x)
                elif h == 3:
                    t0_ns = x
                elif h == 4:
                    mid = off_ps = dur_ps = 0
                    for j, y in _fields(x):
                        if j == 1:
                            mid = y
                        elif j == 2:
                            off_ps = y
                        elif j == 3:
                            dur_ps = y
                    evs.append((mid, off_ps, dur_ps))
            out_evs = lines.setdefault(line_name, [])
            for mid, off_ps, dur_ps in evs:
                ev_name, stats = events.get(mid, ("?", {}))
                out_evs.append((ev_name, t0_ns + off_ps // 1000,
                                dur_ps // 1000, stats))
        out.append({"name": name, "lines": lines})
    return out


# -- the reduction -------------------------------------------------------

def scope_of(tf_op):
    m = SCOPE.search(tf_op or "")
    return m.group(1) if m else None


def by_scope(ops, lo, hi):
    """[(scope, self seconds, flops, bytes)] of the device's ops inside
    [lo, hi), largest first; ops outside every scope are listed by
    their own name under ``(none) <op>``."""
    ops = [e for e in ops if e[1] + e[2] > lo and e[1] < hi]
    label = {}
    for name, _, _, st in ops:
        if name not in label:
            s = scope_of(st.get("tf_op"))
            label[name] = s or "(none) " + xplane.short_name(name, 60)
    selfs = xplane.self_times([(label[n], s, d) for n, s, d, _ in ops])
    work = {}
    for n, _, _, st in ops:
        if st.get("hlo_category") == "while":
            continue              # its body's ops carry the work
        w = work.setdefault(label[n], [0, 0])
        w[0] += int(st.get("flops") or 0)
        w[1] += int(st.get("bytes_accessed") or 0)
    return sorted(((k, ns / 1e9, *work.get(k, (0, 0)))
                   for k, ns in selfs.items()), key=lambda r: -r[1])


def host_spans(all_planes, prefixes=("veles:", "bench:")):
    """{thread: [(name, start_ns, end_ns)]} of the host plane."""
    out = {}
    for p in all_planes:
        if not p["name"].startswith(xplane.HOST_PLANE_PREFIX):
            continue
        for line, evs in p["lines"].items():
            keep = [(n, s, s + d) for n, s, d, _ in evs
                    if n.startswith(prefixes)]
            if keep:
                out.setdefault(line, []).extend(keep)
    return out


def nested(spans, inner, outer):
    """(inner spans that lie inside an outer span of their thread,
    inner spans in all)."""
    ok = total = 0
    for evs in spans.values():
        outs = [(s, e) for n, s, e in evs if n == outer]
        for n, s, e in evs:
            if n == inner:
                total += 1
                ok += any(a <= s and e <= b for a, b in outs)
    return ok, total


def gaps_beside_the_host(ops, spans, lo, hi):
    """Each long device gap of the window with the host's last
    ``veles:fused.fetch_metrics`` that ended before the gap closed and
    the first ``veles:fused.*_submit`` that started after that fetch:
    the gap should lie between the fetch's start and the submit's
    end, and its length is the host's turnaround plus the launch."""
    busy = xplane.union([(n, s, d) for n, s, d, _ in ops], lo, hi)
    edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] - edges[i] >= LONG_GAP_NS]
    flat = [x for evs in spans.values() for x in evs]
    fetches = sorted((s, e) for n, s, e in flat
                     if n == "veles:fused.fetch_metrics")
    submits = sorted((s, e) for n, s, e in flat
                     if re.fullmatch(r"veles:fused\.\w+_submit", n))
    starts = [s for s, _ in submits]
    rows = []
    for g0, g1 in gaps:
        row = {"gap_ms": (g1 - g0) / 1e6, "inside": False}
        before = [f for f in fetches if f[1] <= g1]
        if before:
            f0, f1 = before[-1]
            i = bisect.bisect_left(starts, f1)
            if i < len(submits):
                s0, s1 = submits[i]
                row.update(
                    inside=bool(f0 <= g0 and g1 <= s1 + LONG_GAP_NS),
                    fetch_returned_after_gap_opened_ms=(f1 - g0) / 1e6,
                    turnaround_ms=(s0 - f1) / 1e6,
                    gap_closed_after_submit_began_ms=(g1 - s0) / 1e6)
        rows.append(row)
    return rows


def read(path):
    all_planes = planes(path)
    spans = host_spans(all_planes)
    flat = [x for evs in spans.values() for x in evs]
    win = next(((s, e) for n, s, e in flat
                if n == xplane.WINDOW_SPAN), None)
    out = {"trace": os.path.basename(path), "devices": {}}
    for p in all_planes:
        if not p["name"].startswith(xplane.DEVICE_PLANE_PREFIX):
            continue
        ops = p["lines"].get(xplane.OPS_LINE, [])
        if not ops:
            continue
        lo, hi = win or (min(e[1] for e in ops),
                         max(e[1] + e[2] for e in ops))
        rows = by_scope(ops, lo, hi)
        busy = sum(r[1] for r in rows)
        scoped = sum(r[1] for r in rows
                     if not r[0].startswith("(none)"))
        out["devices"][p["name"]] = {
            "window_s": (hi - lo) / 1e9, "busy_s": busy,
            "under_a_scope_pct": 100.0 * scoped / busy if busy else 0,
            "by_scope": [[k, s, 100.0 * s / busy, fl, by]
                         for k, s, fl, by in rows],
            "gaps": gaps_beside_the_host(ops, spans, lo, hi)}
    out["nesting"] = {
        f"{inner} in {outer}": nested(spans, inner, outer)
        for inner, outer in (
            ("veles:fused.train_submit", "bench:fused.run"),
            ("veles:fused.train_submit", "veles:fused.run"),
            ("veles:fused.fetch_metrics", "veles:decision.run"),
            ("bench:fused.run", "veles:fused.run"),
            ("bench:decision.run", "veles:decision.run"))}
    out["host_span_counts"] = {}
    for n, _, _ in flat:
        out["host_span_counts"][n] = out["host_span_counts"].get(n, 0) + 1
    return out


def show(red):
    for dev, d in red["devices"].items():
        print(f"{dev}: window {d['window_s']:.3f} s, busy "
              f"{d['busy_s']:.3f} s, {d['under_a_scope_pct']:.2f} % of "
              f"it under a scope")
        print(f"  {'scope':<44} {'self s':>9} {'%busy':>7} "
              f"{'TFLOP/s':>8} {'GB/s':>7}")
        for k, s, pct, fl, by in d["by_scope"][:32]:
            print(f"  {k:<44} {s:9.4f} {pct:7.2f} "
                  f"{fl / s / 1e12 if s else 0:8.1f} "
                  f"{by / s / 1e9 if s else 0:7.0f}")
        for g in d["gaps"]:
            print("  gap " + ", ".join(
                f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}"
                for k, v in g.items()))
    for k, (ok, total) in red["nesting"].items():
        print(f"nesting: {k}: {ok} of {total}")
    print("host spans: " + ", ".join(
        f"{n} x{c}" for n, c in sorted(red["host_span_counts"].items())))


# -- the traced run ------------------------------------------------------

def inside_metrics():
    from benchmarks import run
    return {name: run.metric_reader(name)({}) for name in INSIDE}


def registry():
    """Every span of the process as [count, sum ms, median ms], and
    its counters and gauges: where set-up went (``init.<unit>``), what
    compiled, the loop's own overhead."""
    from veles_tpu import telemetry
    snap = telemetry.snapshot()
    return {"spans": {n: [h["count"], 1e3 * h["sum"], 1e3 * h["p50"]]
                      for n, h in snap["histograms"].items()},
            "counters": snap["counters"], "gauges": snap["gauges"]}


def run_traced(workload, seed, out_dir, seconds=10.0):
    from benchmarks import run
    jax = run.setup_jax()
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    mix, cfg = run.load_cell(workload)
    chips = int(mix.get("chips", 1))
    info = run.device_info(run.require_chips(jax, chips), chips)
    keep = None if out_dir == "-" else out_dir
    result = run.run_cell(mix, cfg, seed, seconds, 1, device_info=info,
                          keep_trace=keep)
    print(json.dumps(result), flush=True)
    outside = {k: v["value"] for k, v in result["metrics"].items()}
    tr = result["run"]
    print(json.dumps({"workload": workload, "seed": seed,
                      "inside": inside_metrics(), "outside": outside,
                      "window_s": tr.get("window_s"),
                      "registry": registry()}), flush=True)
    if keep is None:
        return None
    return sorted(os.path.join(keep, f) for f in os.listdir(keep)
                  if f.endswith(".xplane.pb"))[-1]


def main(argv):
    if argv[0] == "run":
        path = run_traced(argv[1], int(argv[2]), argv[3])
    elif argv[0] == "read":
        path = argv[1]
    else:
        sys.exit(__doc__)
    if path is None:
        return
    red = read(path)
    with open(os.path.splitext(path)[0] + ".scopes.json", "w") as f:
        json.dump(red, f, indent=1)
    show(red)


if __name__ == "__main__":
    main(sys.argv[1:])
