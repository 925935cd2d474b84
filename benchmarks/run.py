"""The benchmark's one command:

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name:
``workloads/<name>.json`` (the traffic mix: its kind and parameters),
``configs/<config>.json`` (the model as run), ``traffic/<kind>.py``
(the adapter to the program's entry point), ``metrics/<metric>.py``
(one reader per per-layer metric).  Adding a cell, a configuration or
a per-layer metric adds files and ``BENCHMARK.json`` entries and edits
none.

The last line of standard output is the result object.  There is no
CPU mode: without the chips the cell asks for the command exits
non-zero before any data is made.
"""

from __future__ import annotations

import time

T_START = time.time()          # set-up counts from process start

import argparse                # noqa: E402
import importlib               # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_cell(name):
    mix = load_json("workloads", f"{name}.json")
    cfg = load_json("configs", f"{mix['config']}.json")
    return mix, cfg


def metric_reader(name):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics._" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def units_of():
    """name -> unit of every metric, from BENCHMARK.json (the file a
    later PR extends), so a line's units cannot drift from it."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return {m["name"]: m["unit"]
            for m in bench["end_to_end"] + bench["per_layer"]}


def setup_jax():
    """The compile cache lives at a fixed path inside the checkout
    unless the operator placed it; every program is cached, however
    fast it compiled, so a second run compiles nothing."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def require_chips(jax, chips):
    """The TPU devices, or exit: there is no CPU mode."""
    try:
        devs = jax.devices("tpu")
    except RuntimeError as e:
        sys.exit(f"benchmark: no TPU here ({e}); the benchmark "
                 f"measures only the chip")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell needs {chips} TPU chips, JAX "
                 f"finds {len(devs)}")
    return devs


def device_info(devs, chips):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def run_cell(mix, cfg, seed, seconds, trace, device=None,
             t_start=None, chip_start_s=0.0, device_info=None,
             keep_trace=None, sabotage=None):
    """Drive one run of a cell and return the result object.  The
    command's ``main`` calls this after its look for a chip; the tests
    call it with a CPU device and a tiny mix of their own, and may
    break the timed path through ``sabotage(cell)``, called once the
    program is built."""
    import jax

    from benchmarks.lib import peaks, xplane

    kind = importlib.import_module(f"benchmarks.traffic.{mix['traffic']}")
    cell = kind.Cell(mix, cfg, seed, seconds, bool(trace), device=device,
                     t_start=T_START if t_start is None else t_start,
                     chip_start_s=chip_start_s)
    cell.run(sabotage)
    t_closed = time.time()
    mem_peak = cell.memory_peak_bytes()
    ctx = cell.context()
    e2e = cell.end_to_end()
    attempted, failed = cell.attempted()
    summary = cell.summary()
    cell.release()

    info = device_info or {}
    reduced = {}
    if trace and cell.trace_dir:
        try:
            path = xplane.find_trace(cell.trace_dir)
            if keep_trace:
                os.makedirs(keep_trace, exist_ok=True)
                shutil.copy(path, keep_trace)
            reduced = xplane.reduce(xplane.load(path))
        finally:
            shutil.rmtree(cell.trace_dir, ignore_errors=True)
    ctx["reduced"] = reduced
    if info.get("kind") in peaks.PEAKS:
        ctx["peaks"] = peaks.peaks_for(info["kind"])
    units = units_of()
    metrics = {}
    if trace:
        for name in mix["per_layer"]:
            v = metric_reader(name)(ctx)
            if v is not None:
                metrics[name] = {"value": v, "unit": units[name]}
    else:
        for name in mix["end_to_end"]:
            metrics[name] = {"value": e2e[name], "unit": units[name]}

    # the reference runs last: the window has closed, the peak is
    # read and the program's state is freed
    t_reduced = time.time()
    correct, compared, detail = cell.judge()
    t_judged = time.time()

    dev = {"platform": info.get("platform"), "kind": info.get("kind"),
           "count": info.get("count"), "memory_peak_bytes": mem_peak}
    if trace and reduced:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
    result = {"correct": bool(correct),
              "attempted": int(attempted), "failed": int(failed),
              "metrics": metrics, "device": dev}
    if trace and reduced:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    summary.update(
        workload=mix["name"], seed=seed, chip_start_s=chip_start_s,
        jax=jax.__version__, setup_marks=cell.marks,
        after_window_s={"release_and_trace": t_reduced - t_closed,
                        "reference": t_judged - t_reduced},
        detail=detail)
    result["run"] = summary
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="copy the traced run's xplane file here "
                         "(to look at one by hand)")
    args = ap.parse_args(argv)
    mix, cfg = load_cell(args.workload)
    units_of()                       # fails early without BENCHMARK.json
    jax = setup_jax()
    chips = int(mix.get("chips", 1))
    t0 = time.time()
    devs = require_chips(jax, chips)
    # the runtime's own start (libtpu, 8-14 s, in streaks of 8.5 or of
    # 12.5 s on one machine) is no work of the repo's and is left out
    # of setup_s; the run line has it
    chip_start_s = time.time() - t0
    import jaxlib
    info = device_info(devs, chips)
    from benchmarks.lib import peaks
    peaks.peaks_for(info["kind"])    # an unknown chip is an error
    print(f"benchmark: {args.workload} seed {args.seed} on "
          f"{len(devs)} x {info['kind']} ({info['platform']}), using "
          f"{chips}; jax {jax.__version__} jaxlib {jaxlib.__version__}; "
          f"compile cache {os.environ['JAX_COMPILATION_CACHE_DIR']}",
          file=sys.stderr, flush=True)
    result = run_cell(mix, cfg, args.seed, args.seconds, args.trace,
                      chip_start_s=chip_start_s, device_info=info,
                      keep_trace=args.keep_trace)
    r = result["run"]
    print("benchmark: " + ", ".join(
        f"{k} {v:.6g}" for k, v in r.items()
        if isinstance(v, (int, float)) and k != "seed")
        + f", attempted {result['attempted']}, total "
        f"{time.time() - T_START:.1f} s", file=sys.stderr)
    print("benchmark: set-up marks (s since start): " + ", ".join(
        f"{k} {v}" for k, v in r["setup_marks"]) + "; after the window: "
        + ", ".join(f"{k} {v:.2f}" for k, v in r["after_window_s"].items()),
        file=sys.stderr)
    print(f"correct: {result['correct']}; each number compared, beside "
          f"its limit:", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:g})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
