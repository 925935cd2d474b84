"""CLI entry point.

Reference parity: veles/__main__.py —
``python -m veles_tpu [flags] workflow.py [config.py ...] [root.k=v ...]``

The workflow file must expose ``run(launcher)`` (builds, initializes
and runs its workflow) or ``create_workflow(launcher) -> Workflow``
(the launcher then drives initialize/run).  Config files are python
executed against the global ``root``; trailing ``root.path=value``
arguments override both.
"""

from __future__ import annotations

import argparse
import os
import sys

from veles_tpu.config import parse_overrides
from veles_tpu.launcher import (Launcher, apply_config_file,
                                drive_workflow)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="veles_tpu",
        description="TPU-native dataflow ML framework "
                    "(VELES-capability rebuild)")
    p.add_argument("files", nargs="+",
                   help="workflow.py [config.py ...]")
    p.add_argument("-b", "--backend", default="auto",
                   choices=["auto", "tpu", "cpu", "numpy",
                            "tpu-evaluator"],
                   help="execution backend (default: auto = the TPU "
                        "when JAX has one, else XLA:CPU, announced in "
                        "the launcher line); 'tpu' is the TPU or an "
                        "error, never another platform; "
                        "'tpu-evaluator' is --optimize-only: one "
                        "chip-owning evaluator process + host prep "
                        "workers, and fails when that evaluator is "
                        "not on a TPU")
    p.add_argument("-s", "--seed", type=int, default=1234)
    p.add_argument("--snapshot", default=None,
                   help="resume from a snapshot file")
    p.add_argument("--supervise", action="store_true",
                   help="Phoenix run supervisor: spawn the run as a "
                        "child and auto-resume it from the newest "
                        "intact snapshot / GA state on crash (exit "
                        "codes 13/14 always resume; crash-loops give "
                        "up after $VELES_SUPERVISE_MAX_CRASHES "
                        "failures inside $VELES_SUPERVISE_CRASH_"
                        "WINDOW seconds).  See docs/guide.md "
                        "'Operating long runs'")
    p.add_argument("--dp", type=int, default=None,
                   help="data-parallel ways over the device mesh")
    p.add_argument("--multihost", action="store_true",
                   help="call jax.distributed.initialize() "
                        "(multi-host SPMD over DCN+ICI)")
    p.add_argument("--master-address", default=None,
                   help="run as zmq slave of this master (DCN compat)")
    p.add_argument("--listen-address", default=None,
                   help="run as zmq master listening here (DCN compat)")
    p.add_argument("-p", "--plotters", action="store_true",
                   help="render per-epoch plots (error/loss curves, "
                        "confusion, weight tiles) to $VELES_PLOTS_DIR")
    p.add_argument("--plots-endpoint", default=None,
                   help="also publish plot events on this zmq PUB "
                        "endpoint for live graphics_client viewers")
    p.add_argument("--optimize", default=None, metavar="POP:GEN",
                   help="GA-tune config values wrapped in Tune(...): "
                        "population size : generations (e.g. 8:5)")
    p.add_argument("--ga-workers", type=int, default=0,
                   help="parallel GA workers (0 = auto: up to 4). "
                        "With -b cpu/numpy these are genome-evaluation "
                        "subprocesses; with -b auto/tpu-evaluator they "
                        "are host-side prep threads feeding ONE "
                        "chip-owning evaluator process — the chip is "
                        "exclusive and is never probed from the GA "
                        "parent")
    p.add_argument("--ga-eval-timeout", "--eval-timeout",
                   type=float, default=3600, dest="ga_eval_timeout",
                   help="hard cap in seconds before a genome's "
                        "training run is killed and scored inf "
                        "(default 3600).  The chip-owning evaluator "
                        "additionally enforces an ADAPTIVE per-genome "
                        "deadline (4x the EMA of measured genome "
                        "durations, floored at 60s), so a hung "
                        "evaluator is replaced long before this cap")
    p.add_argument("--heartbeat-deadline", type=float, default=60,
                   help="tpu-evaluator mode: seconds of evaluator "
                        "stdout silence (no heartbeat, no result) "
                        "before it is declared hung and replaced "
                        "(default 60; 0 disables heartbeat "
                        "supervision)")
    p.add_argument("--ga-cohort", type=int, default=0,
                   help="tpu-evaluator mode: genomes sharing a shape "
                        "signature (identical integer tunes) train as "
                        "ONE population-batched vmapped dispatch of up "
                        "to this many members (0 = auto, capped by the "
                        "HBM budget; 1 = disable cohort batching and "
                        "evaluate per genome)")
    p.add_argument("--ga-state", default=None, metavar="FILE",
                   help="per-generation GA checkpoint; an existing "
                        "file resumes the run")
    p.add_argument("--ensemble-train", type=int, default=None,
                   metavar="N",
                   help="train N ensemble members of the workflow "
                        "(per-member seeds; the workflow file must "
                        "expose create_workflow) and save them to "
                        "--ensemble-file")
    p.add_argument("--ensemble-test", action="store_true",
                   help="load --ensemble-file and report the "
                        "aggregated (mean-probability) validation "
                        "error")
    p.add_argument("--ensemble-file", default="ensemble.npz",
                   metavar="FILE",
                   help="member store for --ensemble-train/test "
                        "(default: ensemble.npz)")
    p.add_argument("--ensemble-device", default="auto",
                   choices=["auto", "host"],
                   help="--ensemble-test prediction engine: 'auto' = "
                        "one vmapped member-stacked dispatch on the "
                        "chip when the backend is jax; 'host' = the "
                        "numpy member-loop oracle")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the run plus "
                        "a per-layer FLOPs table into DIR")
    p.add_argument("--status-server", default=None,
                   help="POST per-epoch status to this web_status "
                        "dashboard (http://host:port)")
    p.add_argument("--log-events", default=None, metavar="FILE",
                   help="append every log record to FILE as JSON "
                        "lines (the reference's run-event DB sink, "
                        "file-shaped)")
    p.add_argument("--metrics-dir", default=None, metavar="DIR",
                   help="Sightline telemetry: write per-process "
                        "metrics snapshots (metrics-<pid>.json) and "
                        "the run journal (journal-<pid>.jsonl) into "
                        "DIR; exported as $VELES_METRICS_DIR so GA "
                        "evaluators and multihost peers inherit it.  "
                        "Render with scripts/obs_report.py DIR")
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("--dump-config", action="store_true",
                   help="print the effective config tree and exit")
    return p


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--supervise" in argv:
        # intercepted BEFORE argparse/config side effects: the
        # supervisor process must stay light (no device, no jax) —
        # the child re-parses the identical argv minus the flag
        from veles_tpu import supervisor
        return supervisor.run([a for a in argv if a != "--supervise"])
    if "--serve-models" in argv:
        # the Hive serving process (docs/guide.md "Online serving"):
        # its model specs are NAME=PKG pairs, not workflow files, so it
        # owns its own parser — intercepted like --supervise (and
        # composable with it: a supervised hive exits 14 on SIGTERM
        # and is resumed with warm caches)
        from veles_tpu.serve import hive
        return hive.main([a for a in argv if a != "--serve-models"])
    if "--serve-fleet" in argv:
        # Swarm (docs/guide.md "Fleet serving"): N hive replicas
        # behind one SLO-aware router, speaking the same JSONL
        # protocol — intercepted like --serve-models; the replica
        # count rides as the first positional
        from veles_tpu.serve import router
        return router.main([a for a in argv if a != "--serve-fleet"])
    # root.* overrides can appear anywhere; apply AFTER config files,
    # so collect them first but apply later.
    overrides = [a for a in argv if a.startswith("root.") and "=" in a]
    rest = [a for a in argv if a not in overrides]
    args = build_parser().parse_args(rest)

    workflow_file, *config_files = args.files
    for cf in config_files:
        apply_config_file(cf)
    parse_overrides(overrides)

    if args.plots_endpoint:
        from veles_tpu import graphics_server
        server = graphics_server.get_server()
        server.endpoint = args.plots_endpoint
        server.bind()
        args.plotters = True  # an endpoint without plotters is silence

    if args.dump_config:
        from veles_tpu.config import root
        root.print_()
        return 0

    if args.log_events:
        import atexit

        from veles_tpu.logger import add_jsonl_sink
        atexit.register(add_jsonl_sink(args.log_events))

    if args.metrics_dir:
        from veles_tpu import telemetry
        telemetry.configure(args.metrics_dir)

    if args.backend == "tpu-evaluator" and not args.optimize:
        print("-b tpu-evaluator is a GA execution mode — it needs "
              "--optimize POP:GEN", file=sys.stderr)
        return 2

    if args.optimize:
        # NO Launcher here: constructing one acquires the device, and
        # an exclusive TPU grabbed by the GA parent would lock every
        # worker subprocess out of the chip
        return run_optimizer(args, workflow_file, config_files,
                             overrides)

    if args.ensemble_train is not None or args.ensemble_test:
        if args.ensemble_train is not None and args.ensemble_train < 1:
            print(f"--ensemble-train needs N >= 1 "
                  f"(got {args.ensemble_train})", file=sys.stderr)
            return 2
        return run_ensemble(args, workflow_file)

    launcher = Launcher(
        backend=args.backend, seed=args.seed, snapshot=args.snapshot,
        dp=args.dp, master_address=args.master_address,
        listen_address=args.listen_address, multihost=args.multihost,
        plotters=args.plotters, status_server=args.status_server,
        profile=args.profile, verbose=args.verbose)
    try:
        drive_workflow(launcher, workflow_file)
    except RuntimeError as e:
        if "defines neither" in str(e):
            print(str(e), file=sys.stderr)
            return 2
        raise
    return 0


def _ga_worker_count(args) -> int:
    if args.ga_workers:
        return max(1, args.ga_workers)
    # cpu/numpy workers are evaluation subprocesses; auto/tpu-evaluator
    # workers are prep threads for the single chip-owning evaluator —
    # both parallelize across host cores.  Explicit tpu serializes
    # (the chip admits one client and the user asked for direct mode).
    if args.backend in ("numpy", "cpu", "auto", "tpu-evaluator"):
        import os
        return min(4, max(1, (os.cpu_count() or 2) // 2))
    return 1


def _resolve_ga_execution(backend: str, workers: int):
    """(workers, worker_backend) such that parallel genome workers can
    never race to initialize an exclusive TPU chip:

    - ``auto`` -> ``tpu-evaluator`` mode: ONE evaluator subprocess owns
      the device (TPU when present) and executes every genome on it;
      the N workers become host-side prep threads that never construct
      a device, so there is no race by construction.  Runs routed here
      are also eligible for POPULATION-BATCHED evaluation: genomes
      sharing a shape signature train as one vmapped cohort dispatch
      (``--ga-cohort``; run_optimizer wires evaluate_cohort).  When
      the evaluator's hello reports no accelerator, run_optimizer
      falls back to the classic ``cpu`` subprocess fan-out;
    - explicit ``tpu-evaluator`` -> the same, on a TPU or not at all:
      the evaluator asks for ``-b tpu`` and run_optimizer fails the
      run when its hello is not on one (no CPU stand-in under the
      chip's name);
    - explicit ``tpu`` + parallel workers -> serialized to 1 direct
      worker (honors the per-genome-subprocess choice; the chip admits
      one client);
    - ``cpu``/``numpy`` parallelize freely.
    """
    if backend in ("auto", "tpu-evaluator"):
        return max(1, workers), "tpu-evaluator"
    if workers <= 1 or backend in ("numpy", "cpu"):
        return workers, backend
    return 1, backend


def run_ensemble(args, workflow_file: str) -> int:
    """Ensemble mode (reference parity: the upstream CLI's ensemble
    train/test surface — SURVEY.md §3.1 Ensemble row): ``--ensemble-
    train N`` trains N members with per-member seeds and persists them
    (veles_tpu/ensemble/packaging.py npz — the same container Forge
    ensemble packages carry); ``--ensemble-test`` aggregates member
    probabilities over the validation split."""
    import json

    from veles_tpu.backends import make_device
    from veles_tpu.ensemble import (EnsemblePredictor, EnsembleTrainer,
                                    load_members, normalize_npz_path,
                                    save_members)
    from veles_tpu.launcher import load_workflow_module
    from veles_tpu.loader.base import VALID
    from veles_tpu.logger import setup_logging

    setup_logging(10 if args.verbose else 20)
    mod = load_workflow_module(workflow_file)
    create = getattr(mod, "create_workflow", None)
    if create is None:
        print(f"--ensemble-train/test need {workflow_file} to expose "
              f"create_workflow(launcher)", file=sys.stderr)
        return 2

    class _FL:
        workflow = None

    def factory():
        return create(_FL())

    def device_factory():
        return make_device(args.backend)

    members = None
    if args.ensemble_train is not None:
        trainer = EnsembleTrainer(factory, device_factory,
                                  n_members=args.ensemble_train,
                                  base_seed=args.seed)
        members = trainer.train()
        # save_members returns the REAL path (npz suffix appended by
        # numpy when missing) — report and reuse that, not the arg
        path = save_members(args.ensemble_file, members)
        print(json.dumps({
            "members": len(members),
            "member_valid_errors_pct": [round(m["valid_error"], 4)
                                        for m in members],
            "file": path}))
        if not args.ensemble_test:
            return 0

    import numpy as np
    if members is None:   # test-only invocation: load from disk
        # numpy appends .npz on save — normalize_npz_path applies the
        # SAME rule save_members used, so a suffix-less
        # --ensemble-file that trained fine also loads
        fname = normalize_npz_path(args.ensemble_file)
        try:
            members = load_members(fname)
        except FileNotFoundError:
            print(f"--ensemble-test: {fname!r} does not "
                  f"exist (train one first with --ensemble-train N)",
                  file=sys.stderr)
            return 2
    pred = EnsemblePredictor(factory, device_factory, members,
                             device=args.ensemble_device)
    ld = pred.workflow.loader
    n = ld.class_lengths[VALID]
    if not n:
        print("--ensemble-test: the workflow's loader has no "
              "validation split", file=sys.stderr)
        return 2
    off = ld.class_offset(VALID)
    try:
        # normalized_host_rows, not raw original_data: a quantized
        # loader keeps uint8 bytes there and the members were trained
        # on the dequantized float view
        if hasattr(ld, "normalized_host_rows"):
            x = np.asarray(
                ld.normalized_host_rows(slice(off, off + n)))
        else:
            x = np.asarray(ld.original_data.map_read()[off:off + n])
        y = np.asarray(ld.original_labels.map_read()[off:off + n])
    except RuntimeError:
        print("--ensemble-test needs a loader with host-resident "
              "original_data/labels (full-batch); streaming loaders "
              "are not supported here", file=sys.stderr)
        return 2
    # minibatch-sized chunks in both engines: one giant batch would
    # materialize every member's full-split activations at once (the
    # device engine additionally keeps ONE compiled shape this way)
    err = pred.error_pct(x, y, chunk=max(1, ld.max_minibatch_size))
    print(json.dumps({
        "members": len(members),
        "ensemble_valid_error_pct": round(err, 4),
        "ensemble_eval_engine": "device" if pred.engine is not None
        else "host",
        "member_valid_errors_pct": [round(m["valid_error"], 4)
                                    for m in members]}))
    return 0


def run_optimizer(args, workflow_file: str, config_files, overrides) \
        -> int:
    """GA mode (reference: veles --optimize): genes are Tune(...)
    markers in the config tree; fitness is the best validation error
    of a full (short) training run.  Two execution modes, resolved by
    _resolve_ga_execution:

    - subprocess fan-out (cpu/numpy, or explicit tpu/jax serialized):
      each genome runs in its OWN worker subprocess
      (veles_tpu/genetics/worker.py), isolating the global ``root``
      mutation and any crash, fanned out over --ga-workers;
    - ``tpu-evaluator`` (the ``auto`` default): ONE persistent
      evaluator subprocess owns the accelerator and executes every
      genome on it (genetics/pool.py), the workers become host prep
      threads — the framework's own hyperparameter search finally
      trains on the chip with N>1 workers and no device race.

    --ga-state checkpoints every generation and resumes in both."""
    import json
    import subprocess
    from concurrent.futures import ThreadPoolExecutor

    from veles_tpu.config import root
    from veles_tpu.genetics import GeneticOptimizer, find_tunes
    from veles_tpu.logger import setup_logging

    # no Launcher in this process (the device must stay unclaimed for
    # the evaluator/workers), so logging is configured directly
    setup_logging(10 if args.verbose else 20)

    tunes = find_tunes(root)
    if not tunes:
        print("--optimize: no Tune(...) markers in the config tree",
              file=sys.stderr)
        return 2
    pop_s, _, gen_s = args.optimize.partition(":")
    pop, gen = int(pop_s), int(gen_s or 3)
    workers, worker_backend = _resolve_ga_execution(
        args.backend, _ga_worker_count(args))

    # Phoenix graceful stop for the GA parent: SIGTERM/SIGINT stops at
    # the next generation boundary (the --ga-state checkpoint is the
    # resume point) and exits 14 so a supervisor resumes it.
    # Installed after the cheap usage validation above so every path
    # from here runs finish_preempt() (handler restoration) below.
    from veles_tpu import faults
    from veles_tpu.supervisor import install_ga_stop
    stop_check, finish_preempt = install_ga_stop()
    faults.maybe_inject_sigterm(
        attempt=os.environ.get("VELES_SUPERVISE_ATTEMPT", "0"),
        mode="ga")

    pool = None
    if worker_backend == "tpu-evaluator":
        from veles_tpu.genetics.pool import ChipEvaluatorPool

        # the evaluator child is the ONLY process that probes the
        # device: `tpu-evaluator` asks it for the chip, `auto` for
        # whatever JAX has there
        strict = args.backend == "tpu-evaluator"
        serve_cmd = [sys.executable, "-m",
                     "veles_tpu.genetics.worker", "--serve",
                     workflow_file, *config_files, *overrides,
                     "-b", "tpu" if strict else "auto",
                     "-s", str(args.seed),
                     "--cohort", str(max(0, args.ga_cohort))]
        if args.verbose:
            serve_cmd.append("-v")
        pool = ChipEvaluatorPool(
            serve_cmd, workers=workers,
            timeout=args.ga_eval_timeout,
            heartbeat_deadline=args.heartbeat_deadline,
            seed=args.seed)
        try:
            hello = pool.start()
        except (RuntimeError, OSError) as e:
            pool.close()
            pool = None
            if strict:
                # asked for the chip by name: no evaluator on a TPU
                # (none there, or another process holds it) is the
                # answer, not a CPU run under the same flag
                print(f"--optimize: -b tpu-evaluator needs its "
                      f"evaluator on a TPU and it did not come up "
                      f"({e})", file=sys.stderr)
                finish_preempt()
                return 1
            print(f"--optimize: chip evaluator failed to start ({e})",
                  file=sys.stderr)
        if pool is not None and not pool.is_accelerator:
            # no chip behind `auto` (strict mode cannot get here: its
            # evaluator raises instead of saying hello from a CPU):
            # the classic CPU fan-out parallelizes better than one
            # XLA:CPU evaluator process
            print(f"--optimize: no accelerator visible (evaluator "
                  f"landed on {pool.platform}) — falling back to "
                  f"{workers} cpu evaluation subprocesses",
                  file=sys.stderr)
            pool.close()
            pool = None
        if pool is None:
            worker_backend = "cpu"
        else:
            print(f"--optimize: tpu-evaluator mode — evaluator pid "
                  f"{hello['pid']} owns {pool.platform}; {workers} "
                  f"prep workers feed its queue", file=sys.stderr)
    if pool is None and workers == 1 and args.ga_workers > 1:
        print(f"--optimize: -b {args.backend} admits one client — "
              f"--ga-workers {args.ga_workers} serialized to 1",
              file=sys.stderr)

    base_cmd = [sys.executable, "-m", "veles_tpu.genetics.worker",
                workflow_file, *config_files, *overrides,
                "-b", worker_backend, "-s", str(args.seed)]

    def evaluate_one_subprocess(values) -> float:
        cmd = base_cmd + ["--values", json.dumps(values)]
        try:
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=args.ga_eval_timeout)
            if res.returncode != 0:
                raise RuntimeError(
                    f"worker rc={res.returncode}: "
                    f"{res.stderr.strip().splitlines()[-1:]!r}")
            return float(json.loads(
                res.stdout.strip().splitlines()[-1])["fitness"])
        except Exception as e:  # noqa: BLE001 — bad genes score inf
            print(f"--optimize: genome {values} failed: {e}",
                  file=sys.stderr)
            return float("inf")

    def evaluate_many_subprocess(values_list):
        with ThreadPoolExecutor(workers) as tp:
            return list(tp.map(evaluate_one_subprocess, values_list))

    if pool is not None:
        evaluate_one, evaluate_many = pool.evaluate_one, \
            pool.evaluate_many
    else:
        evaluate_one, evaluate_many = evaluate_one_subprocess, \
            evaluate_many_subprocess

    # population-batched cohorts ride the chip-owning evaluator: the
    # optimizer buckets each generation by shape signature and the
    # evaluator trains every bucket as one vmapped dispatch chain
    # (--ga-cohort 1 opts out; any failure falls back to the
    # per-genome oracle inside _fitness_many)
    evaluate_cohort = pool.evaluate_cohort \
        if pool is not None and args.ga_cohort != 1 else None

    try:
        opt = GeneticOptimizer(evaluate_one, tunes, population=pop,
                               generations=gen,
                               evaluate_many=evaluate_many,
                               evaluate_cohort=evaluate_cohort,
                               state_path=args.ga_state,
                               stop_check=stop_check)
        best, fitness = opt.run()
    finally:
        if pool is not None:
            pool.close()
        # restore the signal handlers on EVERY path (exceptions
        # included); the returned code matters only below
        preempt_code = finish_preempt()
    if preempt_code is not None:
        # graceful stop: best-so-far reported, checkpoint on disk is
        # the resume point — exit 14 so a supervisor resumes, never
        # "done"
        print(json.dumps({"best": best, "fitness": fitness,
                          "preempted": True}))
        return preempt_code
    import math
    if not math.isfinite(fitness):
        print("--optimize: every evaluation failed (fitness inf); "
              "check the workflow runs standalone first",
              file=sys.stderr)
        return 1
    print(json.dumps({"best": best, "fitness": fitness}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
