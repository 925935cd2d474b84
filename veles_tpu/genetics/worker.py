"""Genome evaluation workers (reference parity: veles/genetics/
spawns a process per workflow run — SURVEY.md §3.1 Genetics).

One-shot mode (the classic CPU fan-out unit):

``python -m veles_tpu.genetics.worker workflow.py [config.py ...]
--values '<json {path: value}>' [-b BACKEND] [-s SEED]``

Runs ONE full training with the Tune markers substituted and prints a
single JSON line ``{"fitness": <best validation error>}`` on stdout.
The process boundary is the isolation: the global ``root`` mutation,
jit caches, and any crash stay in this process — the GA parent only
sees the fitness (or a dead worker, scored inf).

Serve mode (the chip-owning evaluator of the ``tpu-evaluator`` GA
execution policy — see veles_tpu/genetics/pool.py):

``python -m veles_tpu.genetics.worker --serve workflow.py [...]``

ONE persistent process acquires the device at startup, announces it
with a hello line (``{"ready": true, "pid", "backend", "platform",
"is_accelerator"}``), then consumes genome jobs as JSON lines on stdin
(``{"id": n, "values": {...}, "seed": s}``) and answers each with
``{"id": n, "fitness": f, "pid": p}`` (or ``{"id", "error"}`` — bad
genes must never kill the evaluator).  Owning the device across
genomes is the point: an exclusive TPU admits exactly one client, so
this is the only process that ever touches it (parallel prep workers
stay host-side), and the jax client + persistent compile cache stay
warm between genomes instead of paying process startup + backend init
+ recompile per evaluation.  The per-process ``root`` isolation the
one-shot mode gets for free is reproduced by snapshotting the pristine
config tree once and rebuilding it (restore -> config files ->
overrides -> tunes) before every genome.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _split_files(files):
    overrides = [a for a in files if a.startswith("root.") and "=" in a]
    workflow_file, *config_files = [a for a in files
                                    if a not in overrides]
    return workflow_file, config_files, overrides


def _evaluate(workflow_file: str, backend: str, seed: int,
              verbose: bool) -> float:
    """One genome's training run -> fitness.  ``root`` must already
    hold the substituted config."""
    from veles_tpu.launcher import Launcher, drive_workflow, \
        workflow_fitness

    launcher = Launcher(backend=backend, seed=seed, verbose=verbose)
    try:
        drive_workflow(launcher, workflow_file)
        return workflow_fitness(launcher.workflow)
    finally:
        _release(launcher)


def _release(launcher) -> None:
    """Return the device buffers a finished genome run holds — HBM on
    an exclusive chip must not accumulate across the generations a
    serve-mode evaluator lives through."""
    import gc
    w = getattr(launcher, "workflow", None)
    if w is not None:
        fused = getattr(w, "fused", None)
        if fused is not None and hasattr(fused, "release_device_state"):
            fused.release_device_state()
        ld = getattr(w, "loader", None)
        for vec_name in ("original_data", "original_labels",
                         "original_targets"):
            vec = getattr(ld, vec_name, None)
            if vec is not None and hasattr(vec, "reset"):
                vec.reset()
        w.stop()
    launcher.workflow = None
    gc.collect()


def _rebuild_root(pristine, config_files, overrides, values) -> None:
    """Rebuild the global config tree for one genome: pristine state ->
    config files -> overrides -> tune substitution.  Reproduces the
    per-process ``root`` isolation the one-shot mode's process boundary
    provides, inside the persistent evaluator."""
    import copy

    from veles_tpu.config import parse_overrides, root
    from veles_tpu.genetics import substitute_tunes
    from veles_tpu.launcher import apply_config_file

    root.__dict__.clear()
    root.__dict__.update(copy.deepcopy(pristine))
    for cf in config_files:
        apply_config_file(cf)
    parse_overrides(overrides)
    substitute_tunes(root, values)


class _CohortTooBig(Exception):
    """Raised by the chunk trainer when the HBM accounting caps the
    cohort below the attempted size; carries the admissible cap."""

    def __init__(self, cap: int) -> None:
        super().__init__(f"cohort over HBM budget; cap {cap}")
        self.cap = max(1, cap)


def _hbm_cohort_cap(workflow, requested: int,
                    n_devices: int = 1) -> int:
    """Largest member count one vmapped cohort may stack, from the
    params x P accounting: per member the engine holds f32 params +
    f32 momentum + a compute-dtype cast + transient grads — ~4
    param-sized buffers.  The budget is the device's reported
    ``bytes_limit`` (TPU) or ``VELES_TPU_GA_HBM_BUDGET`` (default
    8 GiB where the backend reports none), with half held back for the
    resident dataset + the cohort's activations.

    ``n_devices`` > 1 is the member-sharded mesh (Lattice): each
    device stacks only P/N members, so the admissible cohort is N x
    one device's cap at the SAME per-device budget — unless
    $VELES_MESH_SHARD_MEMBERS says never."""
    import os

    import numpy as np

    param_bytes = 0
    for f in workflow.forwards:
        for v in f.param_vectors().values():
            if v:
                param_bytes += int(np.prod(v.shape)) * 4
    per_member = max(param_bytes * 4, 1)
    from veles_tpu.backends import device_bytes_limit

    budget = None
    jdev = getattr(workflow.fused.device, "jax_device", None)
    if jdev is not None:
        budget = device_bytes_limit(jdev)
    if budget is None:
        budget = int(os.environ.get("VELES_TPU_GA_HBM_BUDGET",
                                    8 << 30))
    if n_devices > 1:
        from veles_tpu import knobs
        from veles_tpu.parallel.mesh import shard_mode
        if shard_mode(knobs.get(knobs.MESH_SHARD_MEMBERS)) != "never":
            budget *= int(n_devices)
    cap = max(1, (budget // 2) // per_member)
    if requested:
        cap = min(cap, max(1, requested))
    return cap


def _structure_sig(workflow):
    """Cheap structural fingerprint of a built (un-initialized)
    workflow: the layer configs with the liftable per-member
    hyperparameters stripped.  Members of one cohort MUST agree on it
    — the vmapped engine trains every member at the representative's
    shapes, so a member that decoded to a different structure would
    otherwise silently train as somebody else's genome."""
    from veles_tpu.genetics.core import LIFTABLE_HYPERS
    sig = []
    for cfg in getattr(workflow, "flat_layers", None) or \
            getattr(workflow, "layers_config", []):
        back = {k: v for k, v in dict(cfg.get("<-", {})).items()
                if k not in LIFTABLE_HYPERS}
        sig.append((cfg.get("type"),
                    repr(sorted(dict(cfg.get("->", {})).items())),
                    repr(sorted(back.items()))))
    # which layers a residual entry spans is structure too
    sig.append(tuple(getattr(f, "residual_of", None)
                     for f in getattr(workflow, "forwards", [])))
    return tuple(sig)


def _train_cohort_chunk(create, pristine, config_files, overrides,
                        args, members, hypers, idxs, seed):
    """Train ONE same-signature chunk via the population-batched
    engine; returns its fitness list in ``idxs`` order.  Raises
    _CohortTooBig when the HBM accounting says to split first."""
    import numpy as np

    from veles_tpu.launcher import Launcher
    from veles_tpu.ops.fused import PopulationTrainEngine

    _rebuild_root(pristine, config_files, overrides, members[idxs[0]])
    launcher = Launcher(backend=args.backend, seed=seed,
                        verbose=args.verbose)
    engine = None
    try:
        launcher.create_workflow(create)
        launcher.initialize()
        w = launcher.workflow
        dp = int(getattr(args, "dp", 0) or 0)
        cap = _hbm_cohort_cap(w, args.cohort, n_devices=dp or 1)
        if len(idxs) > cap:
            raise _CohortTooBig(cap)
        rates = np.stack([hypers[i][0] for i in idxs])
        decays = np.stack([hypers[i][1] for i in idxs])
        mesh = None
        if dp > 1:
            # member-sharded cohort (Lattice): the engine shards its
            # stacked member axis over an N-device mesh and keeps the
            # (small, GA-scale) dataset replicated on it
            import jax

            from veles_tpu.parallel import make_mesh
            mesh = make_mesh(
                dp, devices=jax.devices(launcher.device.platform))
        engine = PopulationTrainEngine(w, rates, decays, mesh=mesh)
        return [float(f) for f in engine.run()]
    finally:
        if engine is not None:
            engine.release()
        _release(launcher)


def _evaluate_cohort(workflow_file, config_files, overrides, pristine,
                     args, members, seed):
    """One same-signature cohort -> per-member fitness list.

    Per-member harvest first (rebuild root, build the workflow host-
    side, read each genome's gd learning rates / weight decays): a
    member whose decode or build fails scores inf WITHOUT poisoning
    the cohort.  The valid members then train in population-batched
    chunks; a chunk that fails (OOM included) splits in half and
    retries — never crashes — and a failing singleton falls back to
    the per-genome oracle path."""
    import logging

    import numpy as np

    from veles_tpu import prng
    from veles_tpu.launcher import load_workflow_module

    log = logging.getLogger("veles_tpu.genetics.worker")
    mod = load_workflow_module(workflow_file)
    create = getattr(mod, "create_workflow", None)
    if create is None:
        raise RuntimeError(
            f"{workflow_file}: cohort evaluation needs "
            f"create_workflow(launcher)")

    class _FL:
        workflow = None

    n = len(members)
    fits = [float("inf")] * n
    hypers = [None] * n
    sig_ref = None
    valid = []
    for i, values in enumerate(members):
        try:
            _rebuild_root(pristine, config_files, overrides, values)
            prng.seed_all(seed)
            w = create(_FL())
            sig = (len(w.gds), _structure_sig(w))
            if sig_ref is None:
                sig_ref = sig
            elif sig != sig_ref:
                raise ValueError(
                    "model structure differs from the cohort "
                    "representative (shape-signature mismatch)")
            hypers[i] = (
                np.asarray([[gd.learning_rate, gd.learning_rate_bias]
                            if gd is not None else [0.0, 0.0]
                            for gd in w.gds], np.float32),
                np.asarray([[gd.weight_decay, gd.weight_decay_bias]
                            if gd is not None else [0.0, 0.0]
                            for gd in w.gds], np.float32))
            valid.append(i)
        except KeyboardInterrupt:
            raise
        except BaseException as e:  # noqa: BLE001 — bad gene: inf
            log.warning("cohort member %d invalid (%s: %s); scoring "
                        "inf", i, type(e).__name__, e)
    if not valid:
        return fits
    pending = [list(valid)]
    while pending:
        idxs = pending.pop(0)
        try:
            chunk_fits = _train_cohort_chunk(
                create, pristine, config_files, overrides, args,
                members, hypers, idxs, seed)
            for i, f in zip(idxs, chunk_fits):
                fits[i] = f
        except KeyboardInterrupt:
            raise
        except _CohortTooBig as e:
            log.info("cohort of %d over HBM budget; chunking at %d",
                     len(idxs), e.cap)
            pending = [idxs[j:j + e.cap]
                       for j in range(0, len(idxs), e.cap)] + pending
        except BaseException as e:  # noqa: BLE001 — split, never crash
            if len(idxs) == 1:
                log.warning("cohort singleton %d failed batched (%s: "
                            "%s); per-genome oracle fallback",
                            idxs[0], type(e).__name__, e)
                try:
                    _rebuild_root(pristine, config_files, overrides,
                                  members[idxs[0]])
                    fits[idxs[0]] = _evaluate(
                        workflow_file, args.backend, seed,
                        args.verbose)
                except KeyboardInterrupt:
                    raise
                except BaseException as e2:  # noqa: BLE001
                    log.warning("oracle fallback for member %d also "
                                "failed (%s); scoring inf", idxs[0],
                                e2)
            else:
                half = len(idxs) // 2
                log.warning("cohort chunk of %d failed (%s: %s); "
                            "splitting and retrying", len(idxs),
                            type(e).__name__, e)
                pending = [idxs[:half], idxs[half:]] + pending
    return fits


def serve(args) -> int:
    """The chip-owning evaluation loop (tpu-evaluator mode).

    Emits periodic heartbeat lines (``{"hb": n, "pid", "job"}``)
    from a daemon thread so the parent pool can tell a slow genome
    from a wedged process — see genetics/pool.py for the deadlines
    the heartbeats feed.  ``--heartbeat-every 0`` disables.
    """
    import copy
    import os
    import threading

    from veles_tpu import events, faults, telemetry, trace
    from veles_tpu.analysis import witness
    from veles_tpu.backends import make_device
    from veles_tpu.config import root
    from veles_tpu.logger import setup_logging

    setup_logging(10 if args.verbose else 20)
    workflow_file, config_files, overrides = _split_files(args.files)
    # the pristine config tree, BEFORE any config file ran: each genome
    # rebuilds root from here so substitutions can't leak across jobs
    # (the isolation the one-shot mode's process boundary provided)
    pristine = copy.deepcopy(dict(root.__dict__))

    # acquire the device ONCE — this process is the chip's only client
    # for the whole GA run (make_device is memoized, so every genome's
    # Launcher reuses this same handle)
    device = make_device(args.backend)
    platform = getattr(device, "platform", device.backend_name)
    hello = {"ready": True, "pid": os.getpid(),
             "backend": device.backend_name, "platform": platform,
             "is_accelerator": bool(device.is_jax
                                    and platform != "cpu")}

    # ALL protocol lines go through one lock so the heartbeat thread
    # can never interleave bytes into a result line
    emit_lock = witness.lock("worker.emit")

    def emit(obj) -> None:
        with emit_lock:
            print(json.dumps(obj), flush=True)

    emit(hello)
    telemetry.flush()   # even a job-less child leaves a snapshot

    hb_state = {"job": None, "silent": False}
    hb_stop = threading.Event()

    def _hb_loop() -> None:
        n = 0
        while not hb_stop.wait(args.heartbeat_every):
            if hb_state["silent"]:
                continue
            emit({"hb": n, "pid": os.getpid(),
                  "job": hb_state["job"]})
            n += 1

    if args.heartbeat_every > 0:
        threading.Thread(target=_hb_loop, daemon=True,
                         name="serve-heartbeat").start()

    seq = 0   # ordinal of the job within this evaluator's life
    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        job = json.loads(line)
        if job.get("op") == "shutdown":
            break
        result = {"id": job["id"], "pid": os.getpid()}
        hb_state["job"] = job["id"]
        fault_ctx = {"job": job["id"], "seq": seq}
        if "gen" in job:
            fault_ctx["gen"] = job["gen"]
        seq += 1
        telemetry.counter(events.CTR_EVALUATOR_JOBS).inc()
        # the pool's per-job trace root off the wire: running the job
        # under our own child span makes every journaled event inside
        # (the job span below included) auto-carry trace/span, so the
        # parent-side merge decomposes a slow generation per genome
        wctx = trace.from_wire(job)
        try:
            # the span is the child-side per-job record: its histogram
            # (evaluator.job_seconds) and journal line ride the
            # snapshot the parent pool merges after this process dies
            with trace.use(wctx.child() if wctx is not None
                           else None), \
                 telemetry.span(events.SPAN_EVALUATOR_JOB_SECONDS,
                                journal=True,
                                job=job["id"],
                                cohort=len(job.get("members", []))
                                or None):
                hang = faults.fire("evaluator.hang", **fault_ctx)
                if hang:
                    # a stall mid-genome: heartbeats keep flowing
                    # unless the drill asked for a fully wedged
                    # process (silent)
                    hb_state["silent"] = bool(hang.get("silent"))
                    faults.hang(float(hang.get("seconds", 3600.0)))
                    hb_state["silent"] = False
                if "members" in job:
                    # cohort job: same-signature genomes trained as
                    # one population-batched dispatch chain (chunked
                    # to the HBM budget; bad members score inf
                    # individually)
                    result["fitnesses"] = _evaluate_cohort(
                        workflow_file, config_files, overrides,
                        pristine, args, job["members"],
                        int(job.get("seed", args.seed)))
                else:
                    _rebuild_root(pristine, config_files, overrides,
                                  job["values"])
                    result["fitness"] = _evaluate(
                        workflow_file, args.backend,
                        int(job.get("seed", args.seed)), args.verbose)
        except KeyboardInterrupt:
            raise
        except BaseException as e:  # noqa: BLE001 — bad genes score
            # inf at the parent; the evaluator must outlive them
            result["error"] = f"{type(e).__name__}: {e}"
            telemetry.counter(events.CTR_EVALUATOR_JOB_ERRORS).inc()
        hb_state["job"] = None
        # flush BEFORE the result line: once the parent sees the
        # result it may kill/merge at any time, and the snapshot must
        # already include this job
        telemetry.flush()
        if faults.fire("evaluator.garbage_line", **fault_ctx):
            # a torn protocol line (e.g. a crashing library printing
            # over stdout) — the pool must treat it as noise + proof
            # of life, never as a result
            with emit_lock:
                print(faults.garbage_text(point="evaluator"),
                      flush=True)
        emit(result)
    hb_stop.set()
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="veles_tpu.genetics.worker")
    p.add_argument("files", nargs="+")
    p.add_argument("--values", default=None,
                   help="JSON {tune_path: value} (one-shot mode)")
    p.add_argument("--serve", action="store_true",
                   help="persistent chip-owning evaluator: genome jobs "
                        "as JSON lines on stdin, results on stdout")
    p.add_argument("--cohort", type=int, default=0,
                   help="serve mode: cap on the member count of one "
                        "population-batched training dispatch "
                        "(0 = auto, bounded by the HBM budget only)")
    p.add_argument("--dp", type=int, default=0,
                   help="serve mode: member-shard cohort dispatches "
                        "over an N-device mesh (P/N members per "
                        "device; raises the HBM cohort cap by N — "
                        "simulate on CPU with XLA_FLAGS="
                        "--xla_force_host_platform_device_count=N)")
    p.add_argument("--heartbeat-every", type=float,
                   default=float(os.environ.get(
                       "VELES_HEARTBEAT_EVERY", "5.0")),
                   help="serve mode: seconds between heartbeat lines "
                        "on stdout (default 5, or "
                        "$VELES_HEARTBEAT_EVERY; 0 disables)")
    p.add_argument("-b", "--backend", default="auto")
    p.add_argument("-s", "--seed", type=int, default=1234)
    p.add_argument("-v", "--verbose", action="store_true")
    args = p.parse_args(argv)

    # genome workers (serve-mode evaluator AND one-shot subprocesses)
    # are GA children: preemption semantics belong to the parent, so
    # their Launchers must not install graceful-stop handlers — a
    # signaled worker dies plainly and the parent's retry/inf contract
    # handles it, exactly as before Phoenix
    os.environ["VELES_PREEMPT_DISABLE"] = "1"

    if args.serve:
        return serve(args)
    if args.values is None:
        p.error("--values is required without --serve")

    from veles_tpu.config import parse_overrides, root
    from veles_tpu.genetics import substitute_tunes

    workflow_file, config_files, overrides = _split_files(args.files)
    from veles_tpu.launcher import apply_config_file
    for cf in config_files:
        apply_config_file(cf)
    parse_overrides(overrides)
    substitute_tunes(root, json.loads(args.values))

    try:
        fitness = _evaluate(workflow_file, args.backend, args.seed,
                            args.verbose)
    except RuntimeError as e:
        if "defines neither" in str(e):
            print(str(e), file=sys.stderr)
            return 2
        raise
    print(json.dumps({"fitness": fitness}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
