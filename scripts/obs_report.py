"""Sightline report: render a run's metrics dir into the human-readable
observability summary.

Usage::

    python scripts/obs_report.py METRICS_DIR [--json] [--events N]

The loading/merging/rendering internals live in ``veles_tpu/obs.py``
(shared with the ``web_status.py --metrics-dir`` live dashboard); this
script is the CLI: counter/gauge tables, a quantile table per
histogram (count, mean, p50, p90, p99, max) — the per-dispatch /
per-genome / per-request latency distributions the serving and
multi-chip SLOs hang on — derived per-engine throughput, the
interleaved multi-process event timeline, and a "set-up" section a
training process: the self time of every span between its first
span's start and the end of its first train epoch, with the first
call of each step kind split into trace / lowering / compile or cache
load / rest — the arithmetic the benchmark's ``setup.unspanned_ms``
reader uses (``benchmarks/lib/timeline.py``).  ``--json`` emits the merged
snapshot (plus the event count) as one JSON object for machines.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.lib import timeline  # noqa: E402
from veles_tpu.obs import (assemble_traces,  # noqa: E402
                           fleet_model_rows, fleet_rows, learner_rows,
                           load_dir, load_tree, render, render_fleet,
                           render_trace)
from veles_tpu.telemetry import Histogram  # noqa: E402


def render_setup(snaps, events) -> str:
    """The "set-up" section: one block a process whose flushed
    snapshot holds a set-up timeline (``telemetry.setup_timeline``) —
    self time by span, in order of first start (outermost first), then
    the journal's ``fused.first_dispatch`` events of that process, each
    first call's seconds split by what jax reported inside it."""
    out = []
    for path in snaps:
        with open(path) as f:
            snap = json.load(f)
        tl = snap.get("setup_timeline")
        if not tl or not tl["records"]:
            continue
        pid = str(snap.get("pid"))
        b = timeline.breakdown(tl)
        state = "sealed at the first train class end" \
            if tl["sealed_at"] is not None else "still open"
        out.append(
            f"-- set-up [{pid}]: {b['interval_s']:.3f} s from the "
            f"first span's start, {state}; "
            f"{1e3 * b['unspanned_s']:.1f} ms under no span or under "
            f"{' / '.join(timeline.UNNAMED)} alone; dropped "
            f"{b['dropped']}, on other threads {b['others']} --")
        w = max(len(n) for n in b["self_s"])
        out.append(f"  {'span':<{w}}  {'calls':>6} {'self ms':>11}")
        for name, calls, self_s, _at in b["rows"]:
            out.append(f"  {name or '(no span)':<{w}}  {calls:>6} "
                       f"{1e3 * self_s:>11.2f}")
        for ev in events:
            if ev.get("event") != "fused.first_dispatch" \
                    or ev.get("_pid") != pid \
                    or "trace_seconds" not in ev:
                continue
            parts = [ev["trace_seconds"], ev["lower_seconds"],
                     ev["compile_seconds"]]
            out.append(
                f"  first {ev.get('kind')} submit "
                f"{1e3 * ev['seconds']:.1f} ms = trace "
                f"{1e3 * parts[0]:.1f} + lowering {1e3 * parts[1]:.1f}"
                f" + {'compile' if ev.get('cold') else 'cache load'} "
                f"{1e3 * parts[2]:.1f} + rest "
                f"{1e3 * (ev['seconds'] - sum(parts)):.1f}")
        out.append("")
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="obs_report")
    p.add_argument("metrics_dir")
    p.add_argument("--json", action="store_true",
                   help="emit the merged snapshot as one JSON object")
    p.add_argument("--fleet", action="store_true",
                   help="render the fleet view: per-replica rows "
                        "(pid, resident models, queue depth, qps, "
                        "p99) from the replica-* child dirs plus the "
                        "per-model canary traffic split")
    p.add_argument("--trace", metavar="TRACE_ID", default=None,
                   help="render ONE assembled Flightline trace (hop "
                        "timeline + critical path) by trace id; "
                        "merges the replica-* child journals")
    p.add_argument("--traces", action="store_true",
                   help="list every assembled trace id with its "
                        "outcome and total latency, slowest first")
    p.add_argument("--events", type=int, default=40,
                   help="timeline length (default 40)")
    args = p.parse_args(argv)

    if not os.path.isdir(args.metrics_dir):
        print(f"obs_report: {args.metrics_dir!r} is not a directory",
              file=sys.stderr)
        return 2
    if args.trace or args.traces:
        from veles_tpu.obs import critical_path
        _reg, merged = load_tree(args.metrics_dir)
        traces = assemble_traces(merged)
        if args.trace:
            evs = traces.get(args.trace)
            if not evs:
                print(f"obs_report: no events for trace "
                      f"{args.trace!r} (have {len(traces)} traces)",
                      file=sys.stderr)
                return 1
            print(render_trace(evs))
            return 0
        rows = sorted((critical_path(evs) for evs in traces.values()),
                      key=lambda c: c.get("total_s") or 0.0,
                      reverse=True)
        for cp in rows:
            total = cp.get("total_s")
            print(f"{cp.get('trace')}  {cp.get('model') or '-':<12} "
                  f"{cp.get('outcome') or '-':<8} "
                  f"{1000.0 * total:9.2f}ms  legs={cp['legs']}"
                  f"{' hedged' if cp['hedged'] else ''}"
                  f"{' retried' if cp['retried'] else ''}"
                  if total is not None else
                  f"{cp.get('trace')}  (no root event)")
        return 0
    reg, snaps, journals, events = load_dir(args.metrics_dir)
    if not snaps and not events \
            and not fleet_rows(args.metrics_dir):
        print(f"obs_report: no metrics-*.json or journal-*.jsonl in "
              f"{args.metrics_dir} (run with --metrics-dir DIR or "
              f"$VELES_METRICS_DIR)", file=sys.stderr)
        return 1
    if args.json:
        merged = reg.snapshot()
        merged["snapshots"] = len(snaps)
        merged["journal_events"] = len(events)
        if args.fleet:
            merged["fleet"] = {
                "replicas": fleet_rows(args.metrics_dir),
                "models": fleet_model_rows(reg, events)}
        learners = learner_rows(reg, events)
        if learners:
            merged["learner"] = learners
        print(json.dumps(merged))
        return 0
    if args.fleet:
        fleet = render_fleet(args.metrics_dir)
        if not fleet:
            print(f"obs_report: no replica-* child dirs in "
                  f"{args.metrics_dir} — not a fleet metrics dir "
                  f"(spawn with --serve-fleet N --metrics-dir DIR)",
                  file=sys.stderr)
            return 1
        print(fleet)
        print()
    print(render(args.metrics_dir, reg, snaps, journals, events,
                 max_events=args.events))
    setup = render_setup(snaps, events)
    if setup:
        print("\n" + setup.rstrip())
    return 0


if __name__ == "__main__":
    sys.exit(main())


# re-exported for tests (quantile sanity against a raw histogram)
__all__ = ["load_dir", "render", "render_setup", "main", "Histogram"]
