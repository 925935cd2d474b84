"""Set-up of one cell seen from inside, on the chip (a hand tool; PERF.md
§5's set-up tables come from it):

    python benchmarks/tests/setup_chip.py run <workload> <seed> \
        [--trace 1] [--cold] [--off] [--no-judge] [--tag NAME]
    python benchmarks/tests/setup_chip.py micro

``run`` drives the cell as ``benchmarks/run.py`` does and prints, after
the result line, what the program recorded about its own set-up: the
set-up readers' values, the timeline's self times in order of first
start (``lib/timeline.py``), the harness's marks, the first calls'
split, and every trace / lowering / compile of 0.2 s or more with the
spans it fell in.  ``--cold`` gives the run an empty compile cache of
its own; ``--off`` runs it with ``telemetry.set_enabled(False)`` (what
tracing costs when it is on: compare ``setup_s``); ``--no-judge`` skips
the reference (a cold reference compiles for minutes).  The JSON goes
to ``chiprun_out/setup_chip/``.

``micro`` times a span's enter + exit on this host, with the timeline
sealed and with it open (needs no chip; a tree without a timeline
prints its one number).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SETUP_READERS = [
    "workflow.initialize_ms", "fused.first_submit_ms",
    "fused.compile_ms", "fused.trace_ms", "fused.lower_ms",
    "fused.cold_compiles", "fused.plan_ms", "fused.probe_ms",
    "setup.unspanned_ms"]
SLOW = 0.2


def inside_view():
    """What the program recorded about its own set-up, JSON-ready."""
    from benchmarks import run
    from benchmarks.lib import timeline
    from veles_tpu import telemetry
    out = {"readers": {n: run.metric_reader(n)({})
                       for n in SETUP_READERS}}
    tl = timeline.get()
    if tl is not None:
        b = timeline.breakdown(tl)
        out["timeline"] = {
            "interval_s": b["interval_s"], "dropped": b["dropped"],
            "others": b["others"], "sealed": tl["sealed_at"] is not None,
            "unspanned_s": b["unspanned_s"],
            "self_s": [list(row) for row in b["rows"]]}
    out["first_dispatch"] = [
        {k: e.get(k) for k in ("kind", "seconds", "trace_seconds",
                               "lower_seconds", "compile_seconds",
                               "cold")}
        for e in telemetry.recent_events("fused.first_dispatch")]
    out["slow"] = [
        [e["event"], e["seconds"], e.get("fun"), e.get("cached"),
         e["during"][-2:]]
        for e in telemetry.recent_events()
        if e["event"] in ("xla.trace", "xla.lower", "xla.compile")
        and e["seconds"] >= SLOW]
    snap = telemetry.snapshot()
    out["counters"] = {k: v for k, v in snap["counters"].items()
                       if k.startswith(("xla.", "fused."))}
    return out


def show(view, result):
    run_ = result["run"]
    print(f"setup_s {run_.get('setup_s')}  marks {run_['setup_marks']}")
    print("readers: " + ", ".join(
        f"{n} {v:.1f}" if v is not None else f"{n} -"
        for n, v in view["readers"].items()))
    tl = view.get("timeline")
    if tl:
        print(f"timeline: {tl['interval_s']:.3f} s to the seal, "
              f"unspanned {tl['unspanned_s']:.3f} s, dropped "
              f"{tl['dropped']}, other threads {tl['others']}")
        for name, calls, self_s, at in tl["self_s"]:
            if self_s >= 0.02:
                print(f"  +{at:8.3f} {name or '(no span)':<34} "
                      f"x{calls:<4} {1e3 * self_s:10.1f} ms")
    for e in view["first_dispatch"]:
        print("first dispatch:", e)
    for e in view["slow"]:
        print("slow:", e)


def run_cell(args):
    if args.cold:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
            prefix="cold_cache_")
    from benchmarks import run
    from veles_tpu import telemetry
    jax = run.setup_jax()
    mix, cfg = run.load_cell(args.workload)
    chips = int(mix.get("chips", 1))
    t0 = time.time()
    info = run.device_info(run.require_chips(jax, chips), chips)
    chip_start_s = time.time() - t0
    if args.off:
        telemetry.set_enabled(False)
    if args.no_judge:
        kind = importlib.import_module(
            f"benchmarks.traffic.{mix['traffic']}")
        kind.Cell.judge = lambda self: (True, {}, {})
    result = run.run_cell(mix, cfg, args.seed, 10.0, args.trace,
                          chip_start_s=chip_start_s, device_info=info)
    telemetry.set_enabled(True)
    view = inside_view()
    view.update(workload=args.workload, seed=args.seed,
                flags={"trace": args.trace, "cold": args.cold,
                       "off": args.off, "judged": not args.no_judge},
                correct=result["correct"],
                metrics={k: v["value"]
                         for k, v in result["metrics"].items()},
                setup_marks=result["run"]["setup_marks"],
                chip_start_s=chip_start_s,
                memory_peak_bytes=result["device"]["memory_peak_bytes"])
    print(json.dumps(result), flush=True)
    show(view, result)
    out = os.path.join(ROOT, "chiprun_out", "setup_chip")
    os.makedirs(out, exist_ok=True)
    name = f"{args.workload}.{args.seed}.{args.tag}.json"
    with open(os.path.join(out, name), "w") as f:
        json.dump(view, f, indent=1)


def micro(n=200000):
    import jax  # noqa: F401 — a span is an annotation too, as in a run
    from veles_tpu import telemetry

    def per_span(count):
        t0 = time.perf_counter()
        for _ in range(count):
            with telemetry.span("t.micro"):
                pass
        return 1e9 * (time.perf_counter() - t0) / count

    per_span(20000)                       # warm
    out = {}
    if hasattr(telemetry, "seal_setup"):
        cap = telemetry.TIMELINE_CAP
        telemetry.reset()
        out["open_ns"] = per_span(cap - 8)
        telemetry.seal_setup()
        out["sealed_ns"] = sorted(per_span(n) for _ in range(5))
    else:
        out["no_timeline_ns"] = sorted(per_span(n) for _ in range(5))
    telemetry.set_enabled(False)
    out["disabled_ns"] = sorted(per_span(n) for _ in range(5))
    print(json.dumps({"span_enter_exit": out}), flush=True)


def main(argv):
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    r = sub.add_parser("run")
    r.add_argument("workload")
    r.add_argument("seed", type=int)
    r.add_argument("--trace", type=int, choices=(0, 1), default=0)
    r.add_argument("--cold", action="store_true")
    r.add_argument("--off", action="store_true")
    r.add_argument("--no-judge", action="store_true")
    r.add_argument("--tag", default="run")
    sub.add_parser("micro")
    args = ap.parse_args(argv)
    if args.mode == "micro":
        micro()
    else:
        run_cell(args)


if __name__ == "__main__":
    main(sys.argv[1:])
