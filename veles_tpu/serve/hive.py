"""The Hive serving process: one chip, many models, sustained QPS.

``python -m veles_tpu --serve-models NAME=PKG.vpkg [NAME=PKG ...]``

Topology is the proven chip-owning evaluator shape
(genetics/worker.py ``--serve``): ONE persistent process acquires the
device at startup, announces itself with a hello line, emits heartbeat
lines from a daemon thread, and speaks JSON lines over stdin/stdout —
so the pool-style supervision and the ``--supervise`` resume recipe
both apply unchanged.  What is new is what the process does between
lines:

- every model is a **Forge ensemble package** (``pack_ensemble`` —
  manifest + workflow entry + members npz): install, rebuild the
  config tree, build the template workflow ONCE, strip its training
  state, and register the pure forward chain + host member params
  with the residency manager;
- requests (``{"id", "model", "rows"}``) route through the model's
  ``EnsembleEvalEngine.submit()`` — the dynamic micro-batching loop
  coalesces concurrent requests into ONE fixed-shape mask-padded
  dispatch, so warm steady state has zero recompiles;
- models stay HBM-resident under the residency budget; the LRU one
  spills to host when a colder request set needs the space;
- SIGTERM drains: in-flight and already-accepted requests finish,
  telemetry flushes, and the process exits ``EXIT_PREEMPTED`` (14) so
  ``--supervise`` restarts it with warm caches.

Protocol lines (stdout; all writes serialized under one lock):

    {"ready": true, "pid", "platform", "device_kind", "backend",
     "models": {...}, "max_batch", "max_wait_ms"}     -- hello
    {"hb": n, "pid"}                                  -- heartbeat
    {"id", "model", "pred": [...], "probs": [[...]],
     "rows_n": n, "crc": c}                           -- response
    {"id", "error": "..."}                            -- failed request
    {"id", "error": "...", "expired": true}           -- past deadline
    {"id", "stats": <telemetry snapshot>}             -- op=stats

Requests (stdin): ``{"id", "model", "rows": [[...], ...]}`` with an
optional ``"deadline_ms"`` (absolute unix-epoch milliseconds — the
batcher drops a request still queued past it instead of computing an
answer nobody is waiting for), ``{"op": "stats", "id"}``,
``{"op": "shutdown"}``.  ``rows_n``/``crc`` are the response-integrity
echo: the row count and the crc32 of the float32 probability payload,
recomputed by the fleet router — a mismatch is an integrity strike
against this replica (Sentinel, veles_tpu/serve/sentinel.py).

With ``--online`` (Evergreen, veles_tpu/online): a request may carry
``"label"`` (per-row ground truth) to feed the learning tap, truth
known only later joins by wire id via ``{"label_of": <id>,
"label": [...]}`` (no response line), and ``{"op": "learn", "id"}``
answers ``{"id", "learn": {model: {state, steps, buffer_rows,
...}}}`` — the learner's per-model introspection row.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import queue
import signal
import sys
import threading
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

from veles_tpu import events, faults, knobs, telemetry, trace
from veles_tpu.analysis import witness
from veles_tpu.serve.batcher import DeadlineExpired
from veles_tpu.supervisor import EXIT_PREEMPTED


class _FL:
    """The launcher stand-in ``create_workflow`` expects."""
    workflow = None


def _strip_training_state(w) -> None:
    """Free every device buffer the template workflow's initialize
    uploaded that serving will never touch: the resident dataset and
    the training-side param/optimizer state (the engine re-uploads
    member params stacked).  A multi-model process cannot afford one
    training run's HBM per model."""
    fused = getattr(w, "fused", None)
    if fused is not None and hasattr(fused, "release_device_state"):
        fused.release_device_state()
    ld = getattr(w, "loader", None)
    for vec_name in ("original_data", "original_labels",
                     "original_targets"):
        vec = getattr(ld, vec_name, None)
        if vec is not None and hasattr(vec, "reset"):
            vec.reset()


def load_model_package(name: str, pkg_path: str, device,
                       install_dir: str, pristine: Dict[str, Any]):
    """One Forge ensemble package -> a registered-ready HostedModel.

    Installs (checksum-verified), rebuilds the global config tree from
    ``pristine`` + the package's config files (per-model isolation, the
    worker.py idiom), builds + initializes the template workflow on the
    shared device, strips its training state, and pairs the pure
    forward chain with the npz members."""
    from veles_tpu import prng
    from veles_tpu.config import root
    from veles_tpu.ensemble.packaging import load_members
    from veles_tpu.forge import ForgePackage
    from veles_tpu.launcher import apply_config_file, \
        load_workflow_module
    from veles_tpu.serve.residency import HostedModel

    manifest = ForgePackage.install(pkg_path, install_dir)
    pkg_root = manifest["root"]
    snap = manifest.get("snapshot")
    if not snap or not snap.endswith(".npz"):
        raise ValueError(
            f"{pkg_path}: serving needs an ENSEMBLE package (members "
            f"npz snapshot via ensemble.packaging.pack_ensemble); this "
            f"one carries {snap!r}")
    members = load_members(os.path.join(pkg_root, snap))

    root.__dict__.clear()
    root.__dict__.update(copy.deepcopy(pristine))
    for cf in manifest.get("configs", []):
        apply_config_file(os.path.join(pkg_root, cf))
    prng.seed_all(int(members[0].get("seed", 1234)))
    mod = load_workflow_module(os.path.join(pkg_root,
                                            manifest["entry"]))
    create = getattr(mod, "create_workflow", None)
    if create is None:
        raise ValueError(
            f"{pkg_path}: entry {manifest['entry']!r} exposes no "
            f"create_workflow(launcher)")
    w = create(_FL())
    w.initialize(device=device)
    try:
        sample_shape = tuple(w.loader.original_data.shape[1:])
    except (AttributeError, RuntimeError):
        sample_shape = None   # streaming loaders: first request pins
    _strip_training_state(w)
    return HostedModel(
        name, w.forwards, [m["params"] for m in members],
        meta={"workflow": w, "version": manifest.get("version"),
              "package": os.path.basename(pkg_path),
              # the package seed keys the online tier's deterministic
              # sample stream (the offline-oracle replay contract)
              "seed": int(members[0].get("seed", 1234))},
        sample_shape=sample_shape)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="veles_tpu --serve-models",
        description="Hive: device-resident multi-model serving with "
                    "dynamic micro-batching")
    p.add_argument("models", nargs="+", metavar="NAME=PKG",
                   help="model name = Forge ensemble package path "
                        "(.vpkg from ensemble.packaging.pack_ensemble)")
    p.add_argument("-b", "--backend", default="auto")
    p.add_argument("--mesh", type=int,
                   default=int(knobs.get(knobs.SERVE_MESH)),
                   help="devices this replica owns ($VELES_SERVE_MESH): "
                        ">1 binds an N-device mesh — residency budgets "
                        "charge per device and an over-budget model "
                        "serves member-sharded-resident instead of "
                        "LRU-spilling ($VELES_SERVE_MESH_SHARD)")
    p.add_argument("--max-batch", type=int,
                   default=int(knobs.get(knobs.SERVE_MAX_BATCH)),
                   help="rows per micro-batch — the ONE fixed dispatch "
                        "shape ($VELES_SERVE_MAX_BATCH)")
    p.add_argument("--max-wait-ms", type=float,
                   default=float(knobs.get(knobs.SERVE_MAX_WAIT_MS)),
                   help="longest a queued request waits for "
                        "co-batchable traffic ($VELES_SERVE_MAX_WAIT_MS)")
    p.add_argument("--hbm-budget", type=int, default=0,
                   help="residency budget override in bytes (default: "
                        "device bytes_limit/2 or "
                        "$VELES_SERVE_HBM_BUDGET)")
    p.add_argument("--heartbeat-every", type=float,
                   default=float(knobs.get(knobs.HEARTBEAT_EVERY)),
                   help="seconds between heartbeat lines "
                        "($VELES_HEARTBEAT_EVERY; 0 disables)")
    p.add_argument("--online", action="store_true",
                   default=bool(knobs.get(knobs.ONLINE)),
                   help="arm the Evergreen online-learning tier: tap "
                        "labeled traffic, fine-tune in serving idle "
                        "gaps, promote HBM-to-HBM through the gate "
                        "(also $VELES_ONLINE; knobs: "
                        "$VELES_ONLINE_TAP_FRAC, "
                        "$VELES_ONLINE_PROMOTE_MARGIN, ...)")
    p.add_argument("--install-dir", default=None,
                   help="package install/staging directory (default: "
                        "a temp dir)")
    p.add_argument("--metrics-dir", default=None,
                   help="arm Sightline persistence (also "
                        "$VELES_METRICS_DIR)")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    from veles_tpu.backends import make_device
    from veles_tpu.config import root
    from veles_tpu.logger import setup_logging
    from veles_tpu.serve.residency import ResidencyManager

    args = build_parser().parse_args(argv)
    setup_logging(10 if args.verbose else 20)
    if args.metrics_dir:
        telemetry.configure(args.metrics_dir)
    install_dir = args.install_dir
    if install_dir is None:
        import tempfile
        install_dir = tempfile.mkdtemp(prefix="hive_models_")

    specs: List[tuple] = []
    for spec in args.models:
        name, _, path = spec.partition("=")
        if not name or not path:
            print(f"--serve-models: bad model spec {spec!r} "
                  f"(want NAME=PACKAGE.vpkg)", file=sys.stderr)
            return 2
        if not os.path.isfile(path):
            print(f"--serve-models: no such package {path!r}",
                  file=sys.stderr)
            return 2
        specs.append((name, path))

    device = make_device(args.backend)
    if not device.is_jax:
        print("--serve-models needs a jax device (TPU or XLA:CPU); "
              "-b numpy has no vmapped serving engine",
              file=sys.stderr)
        return 2
    if args.mesh and args.mesh > 1:
        # the Prism arm: this replica OWNS an N-device mesh over the
        # devices of the REQUESTED backend's platform (jax.devices()
        # alone is the default platform, whatever -b said) — every
        # downstream consumer (residency, engines, batcher) sees a
        # device that happens to replicate rows and shard members
        import jax

        from veles_tpu.parallel.data_parallel import MeshJaxDevice
        from veles_tpu.parallel.mesh import make_mesh
        try:
            device = MeshJaxDevice(make_mesh(
                int(args.mesh), devices=jax.devices(device.platform)))
        except ValueError as e:
            print(f"--serve-models --mesh {args.mesh}: {e}",
                  file=sys.stderr)
            return 2
    platform = device.platform
    residency = ResidencyManager(
        device, budget_bytes=args.hbm_budget or None,
        max_batch=max(1, args.max_batch),
        max_wait_s=max(0.0, args.max_wait_ms) / 1000.0)
    # this manager IS the process HBM arbiter: the online tier's
    # shadow training and any in-process cohort work charge the same
    # ledger the LRU spill reads
    from veles_tpu.serve.residency import install_process_arbiter
    install_process_arbiter(residency)

    pristine = copy.deepcopy(dict(root.__dict__))
    for name, path in specs:
        model = load_model_package(name, path, device, install_dir,
                                   pristine)
        residency.register(model)
        # admit eagerly in CLI order: the budget may spill the colder
        # ones right back — that IS the steady-state policy at work
        residency.ensure(name)

    emit_lock = witness.lock("hive.emit")

    def emit(obj: Dict[str, Any]) -> None:
        with emit_lock:
            print(json.dumps(obj), flush=True)

    learner = None
    if args.online:
        from veles_tpu.online import OnlineLearner
        learner = OnlineLearner(residency)
        armed = [name for name, _ in specs
                 if learner.arm_model(name)]
        if armed:
            learner.start()
        else:
            learner = None

    hello = {
        "ready": True, "pid": os.getpid(),
        "backend": device.backend_name, "platform": platform,
        "device_kind": device.jax_device.device_kind,
        "max_batch": residency.max_batch,
        "max_wait_ms": residency.max_wait_s * 1000.0,
        "online": learner is not None,
        # the replica advertises its REAL capacity (devices x
        # per-device budget) so a mixed fleet's placement policy can
        # stop assuming every replica is one chip
        "devices": residency.n_devices,
        "device_budget": residency.budget_bytes,
        "models": {
            m.name: {"members": len(m.member_params),
                     "param_bytes": m.param_bytes,
                     "resident": m.resident,
                     "sharded": bool(m.engine is not None
                                     and m.engine.member_sharded),
                     "version": m.meta.get("version")}
            for m in residency.models.values()},
    }
    telemetry.event(events.EV_SERVE_READY, pid=os.getpid(),
                    models=sorted(residency.models),
                    max_batch=residency.max_batch,
                    **device.describe())
    emit(hello)
    telemetry.flush()

    flap = faults.fire("fleet.replica_flap")
    if flap:
        # Faultline ``fleet.replica_flap``: this replica SIGKILLs
        # itself ``after`` seconds past hello — armed with ``times=*``
        # every respawn inherits the env var and flaps again, the
        # pathological member the respawn backoff and the scale
        # controller's cooldown must absorb without a spawn storm
        import time as _time
        flap_after = float(flap.get("after", 1.0))

        def _flap() -> None:
            _time.sleep(flap_after)
            try:
                trace.dump("flap")
            except Exception:  # noqa: BLE001 — the kill is the point
                pass
            os.kill(os.getpid(), signal.SIGKILL)

        threading.Thread(target=_flap, daemon=True,
                         name="fault-replica-flap").start()

    stop = {"signal": None}
    stop_event = threading.Event()

    def _on_term(signum, frame) -> None:
        # flag only — the main loop owns the drain; a second signal
        # exits immediately (the operator insists)
        if stop["signal"] is not None:
            os.write(2, b"hive: second signal - hard exit\n")
            os._exit(EXIT_PREEMPTED)
        stop["signal"] = signum
        stop_event.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGINT, _on_term)
    except (ValueError, OSError):   # embedded / non-main thread
        pass

    hb_stop = threading.Event()

    def _hb_loop() -> None:
        n = 0
        while not hb_stop.wait(args.heartbeat_every):
            emit({"hb": n, "pid": os.getpid()})
            telemetry.maybe_flush()
            n += 1

    if args.heartbeat_every > 0:
        threading.Thread(target=_hb_loop, daemon=True,
                         name="hive-heartbeat").start()

    jobs: "queue.Queue[Optional[str]]" = queue.Queue()

    def _read_stdin() -> None:
        for line in sys.stdin:
            jobs.put(line)
        jobs.put(None)   # EOF

    threading.Thread(target=_read_stdin, daemon=True,
                     name="hive-stdin").start()

    def handle(line: str) -> bool:
        """One request line; returns False when the loop should end."""
        line = line.strip()
        if not line:
            return True
        try:
            job = json.loads(line)
        except ValueError:
            emit({"error": f"bad request line: {line[:120]!r}"})
            return True
        op = job.get("op")
        if op == "shutdown":
            return False
        if op == "stats":
            emit({"id": job.get("id"), "stats": telemetry.snapshot()})
            return True
        if op == "learn":
            emit({"id": job.get("id"),
                  "learn": learner.status() if learner else {}})
            return True
        if op in ("learner_suspend", "learner_resume"):
            # the degradation ladder's first rung, fleet-fanned by the
            # router: a no-op ack when no learner is armed
            if learner is not None:
                if op == "learner_suspend":
                    learner.suspend()
                else:
                    learner.resume()
            emit({"id": job.get("id"), "learner_ctl": {
                "online": learner is not None,
                "suspended": bool(learner is not None
                                  and learner.suspended)}})
            return True
        if "label_of" in job:
            # late ground truth joining an earlier tapped request by
            # wire id — fire-and-forget (an orphan only counts)
            if learner is not None:
                learner.tap.label_for(job["label_of"],
                                      job.get("label"))
            return True
        jid = job.get("id")
        telemetry.counter(events.CTR_SERVE_REQUESTS).inc()
        if faults.fire("hive.wedge", model=job.get("model")):
            # gray-failure rehearsal: the request vanishes into a
            # wedged batcher while heartbeats/stats keep flowing — the
            # caller's deadline (never this process) must catch it
            return True
        # the router's trace context off the wire; this process's own
        # admission span parents under the sending leg, and the
        # batcher attributes queue wait vs dispatch to it
        wctx = trace.from_wire(job)
        sctx = wctx.child() if wctx is not None else None
        try:
            with trace.use(sctx):
                model = job["model"]
                rows = np.asarray(job["rows"], np.float32)
                engine = residency.ensure(model)
                fut = engine.submit(rows,
                                    deadline_ms=job.get("deadline_ms"),
                                    ctx=sctx)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — a bad request
            # answers with an error; the process serves on
            telemetry.counter(events.CTR_SERVE_REQUEST_ERRORS).inc()
            emit({"id": jid, "error": f"{type(e).__name__}: {e}"})
            return True
        if sctx is not None and sctx.sampled:
            trace.record("hive.request", ctx=sctx, model=model,
                         rows=int(len(rows)))
        if learner is not None:
            # tapped AFTER admission: only rows the engine accepted
            # (shape-checked, submitted) may enter the replay buffer
            learner.tap.tap(model, jid, rows, job.get("label"),
                            ctx=sctx)

        def _deliver(f, jid=jid, model=model) -> None:
            try:
                probs = f.result()
            except DeadlineExpired as e:
                emit({"id": jid, "error": str(e), "expired": True})
                return
            except BaseException as e:  # noqa: BLE001 — dispatch-side
                emit({"id": jid, "error": f"{type(e).__name__}: {e}"})
                return
            probs32 = np.asarray(probs, np.float32)
            # integrity echo: crc over the CLEAN f32 payload (float32
            # round-trips JSON exactly) — computed BEFORE any injected
            # corruption so hive.garbage_response is detectable
            crc = zlib.crc32(probs32.tobytes())
            payload = probs32
            if faults.fire("hive.garbage_response", model=model):
                payload = faults.rng("hive.garbage_response") \
                    .standard_normal(probs32.shape).astype(np.float32)
            emit({"id": jid, "model": model,
                  "pred": np.argmax(probs32, axis=-1).tolist(),
                  "probs": payload.tolist(),
                  "rows_n": int(len(probs32)), "crc": int(crc)})

        fut.add_done_callback(_deliver)
        return True

    rc = 0
    while not stop_event.is_set():
        try:
            line = jobs.get(timeout=0.2)
        except queue.Empty:
            continue
        if line is None:      # stdin closed: the parent went away
            break
        if not handle(line):
            break

    # -- drain ---------------------------------------------------------
    # accept everything already on the wire (the stdin thread keeps
    # pulling bytes the clients flushed before the signal), then let
    # every model's batcher finish its queue
    if stop_event.is_set():
        stop_event.wait(0.0)
        import time as _time
        _time.sleep(0.3)
    n_late = 0
    while True:
        try:
            line = jobs.get_nowait()
        except queue.Empty:
            break
        if line is None:
            continue
        n_late += 1
        handle(line)
    if learner is not None:
        # the scavenger stops BEFORE the drain: a fine-tune step must
        # not race the batchers' final dispatches for the chip
        learner.stop()
    drained = residency.drain_all()
    telemetry.event(events.EV_SERVE_DRAIN, late_requests=n_late,
                    complete=bool(drained))
    reason = None
    if stop["signal"] is not None:
        try:
            reason = signal.Signals(stop["signal"]).name
        except ValueError:
            reason = f"sig{stop['signal']}"
        rc = EXIT_PREEMPTED
        # the flight recorder's SIGTERM hook: the ring + journal tail
        # land on disk even if the final flush below never completes
        trace.dump("sigterm")
    telemetry.event(events.EV_SERVE_SHUTDOWN, reason=reason, code=rc)
    hb_stop.set()
    telemetry.flush()
    if rc:
        # mirror the Phoenix preemption contract: flush everything and
        # exit 14 so --supervise resumes the serving process
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
