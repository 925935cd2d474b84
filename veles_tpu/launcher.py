"""Launcher: builds the runtime around a workflow and runs it.

Reference parity: veles/launcher.py — selects mode (standalone /
master / slave), creates the device, initializes the workflow, runs,
handles graceful stop and snapshots (SURVEY.md §4.1/§4.2).

TPU adaptation: the primary distributed mode is NOT master--slave —
it is single-controller SPMD: one process per host, all chips driven
through a ``jax.sharding.Mesh`` with gradient psum over ICI
(veles_tpu/parallel/).  ``--master-address``/``--listen-address`` zmq
modes survive as a DCN compat path for heterogeneous clusters
(veles_tpu/server.py, client.py).
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import threading
import time
from typing import Any, Optional

from veles_tpu import events, faults, prng, telemetry
from veles_tpu.backends import Device, make_device
from veles_tpu.config import root
from veles_tpu.logger import Logger, setup_logging


class Launcher(Logger):
    def __init__(self, backend: str = "auto",
                 seed: int = 1234,
                 snapshot: Optional[str] = None,
                 dp: Optional[int] = None,
                 master_address: Optional[str] = None,
                 listen_address: Optional[str] = None,
                 multihost: bool = False,
                 plotters: bool = False,
                 status_server: Optional[str] = None,
                 profile: Optional[str] = None,
                 verbose: bool = False,
                 **kwargs: Any) -> None:
        setup_logging(10 if verbose else 20)
        self.backend = backend
        self.snapshot = snapshot
        self.dp = dp
        self.master_address = master_address
        self.listen_address = listen_address
        self.workflow = None
        self.plotters = plotters
        self.status_server = status_server
        self.profile_dir = profile
        self.multihost = multihost
        #: Phoenix graceful-stop state: the signal that requested
        #: preemption (None = not preempted), an event the main thread
        #: sets once the final snapshot landed (stops the grace
        #: watchdog), and the multihost watchdog stopper
        self._preempt_signum: Optional[int] = None
        self._preempt_done = threading.Event()
        self._mh_watchdog_stop = None
        prng.seed_all(seed)
        if multihost:
            init_multihost()
        self.device: Device = make_device(backend)
        # the resolved platform is announced, never assumed: `auto`
        # may be XLA:CPU, and a run record must say which
        self.info("launcher: backend=%s device=%r mode=%s %s",
                  backend, self.device, self.mode,
                  " ".join(f"{k}={v}" for k, v in
                           self.device.describe().items()))

    @property
    def mode(self) -> str:
        if self.master_address:
            return "slave"
        if self.listen_address:
            return "master"
        return "standalone"

    # -- workflow lifecycle -------------------------------------------

    def create_workflow(self, factory, **kwargs: Any):
        """factory(launcher, **kwargs) -> Workflow, or resume from
        --snapshot."""
        if self.snapshot:
            from veles_tpu.snapshotter import load_workflow
            self.info("resuming from %s", self.snapshot)
            # fallback=True: a torn/corrupt snapshot resumes from the
            # newest intact sibling instead of killing the run (and
            # raises when none is intact — never a silent fresh start)
            self.workflow = load_workflow(self.snapshot, fallback=True)
        else:
            self.workflow = factory(self, **kwargs)
        if self.plotters and hasattr(self.workflow, "link_plotters"):
            self.workflow.link_plotters()
        if self.status_server and hasattr(self.workflow,
                                          "link_status_reporter"):
            self.workflow.link_status_reporter(self.status_server,
                                               mode=self.mode)
        return self.workflow

    def initialize(self, **kwargs: Any) -> None:
        if self.dp and self.dp > 1:
            from veles_tpu.parallel import DataParallel
            if not self.device.is_jax:
                raise ValueError("--dp requires a jax backend "
                                 "(tpu/cpu), not numpy")
            # mesh over the devices of the SELECTED backend platform —
            # jax.devices() alone would pick the default platform even
            # when the user asked for -b cpu
            import jax
            devices = jax.devices(self.device.platform)
            self.workflow_dp = DataParallel(self.workflow, self.dp,
                                            devices=devices)
            # the mesh device replaces the single-chip device: Vectors
            # upload replicated, the fused step jits sharded
            self.device = self.workflow_dp.install()
        if self.mode == "master" and hasattr(self.workflow,
                                             "wire_fused"):
            # The master never computes minibatches: fused wiring would
            # point Decision at a never-run FusedStepRunner (all-zero
            # metrics) and superstep>1 would advance the loader k
            # minibatches per issued job while shipping only the last
            # one.  Master-side semantics must be eager.
            kwargs.setdefault("fused", False)
        self.workflow.initialize(device=self.device, **kwargs)

    def run(self) -> None:
        from veles_tpu import profiling
        watchdog_stop = self._start_multihost_watchdog() \
            if self.multihost else None
        self._mh_watchdog_stop = watchdog_stop
        uninstall = self._install_preempt_handlers()
        faults.maybe_inject_sigterm(
            attempt=os.environ.get("VELES_SUPERVISE_ATTEMPT", "0"),
            mode=self.mode)
        try:
            with profiling.trace(self.profile_dir):
                if self.mode == "standalone":
                    self.workflow.run()
                elif self.mode == "master":
                    from veles_tpu.server import MasterServer
                    MasterServer(self.workflow,
                                 self.listen_address).serve()
                else:
                    if not self.device.is_jax:
                        raise ValueError(
                            "slave mode computes jobs with the fused "
                            "jitted step — use a jax backend (-b "
                            "tpu/cpu), not numpy")
                    from veles_tpu.client import SlaveClient
                    SlaveClient(self.workflow,
                                self.master_address).serve()
        except (KeyboardInterrupt, SystemExit):
            # with the preempt handlers installed a Ctrl-C never gets
            # here (SIGINT routes through the graceful-stop path and
            # leaves a final snapshot); this survives for embedding
            # contexts where the handlers could not be installed
            raise
        except BaseException as e:
            if self.multihost:
                # a dying peer surfaces here as a failed collective
                # (gloo/XLA distributed error) — abort CLEANLY with a
                # final snapshot instead of hanging or losing the run
                self._abort_multihost(e)
            raise
        finally:
            uninstall()
            if watchdog_stop is not None:
                watchdog_stop()
        if self._preempt_signum is not None:
            self._finish_preempt()   # never returns: os._exit(14)
        if self.profile_dir:
            self._dump_flops_table()

    #: exit code of a clean multihost peer-failure abort (documented
    #: in docs/guide.md "Operating long runs")
    MULTIHOST_ABORT_EXIT = 13
    #: exit code of a preemption-triggered graceful stop (SIGTERM /
    #: SIGINT / a peer's ``veles_preempt`` broadcast): the run stopped
    #: at a dispatch boundary and wrote a final resumable snapshot —
    #: the supervisor always resumes 13/14 without charging the crash
    #: budget
    PREEMPT_EXIT = 14
    #: seconds the graceful stop may take before the watchdog thread
    #: hard-snapshots and exits (the main thread may be wedged inside
    #: a long dispatch or a dead collective)
    PREEMPT_GRACE_ENV = "VELES_PREEMPT_GRACE"
    PREEMPT_GRACE_DEFAULT = 25.0

    # -- graceful stop (Phoenix) --------------------------------------

    def _install_preempt_handlers(self):
        """SIGTERM/SIGINT -> cooperative stop at the next dispatch
        boundary + final snapshot + exit 14.  Installable only from
        the main thread (tests and embedders calling ``run()`` from a
        worker thread keep the old raise-through behavior); returns an
        uninstall callable either way.  ``$VELES_PREEMPT_DISABLE=1``
        opts a process out entirely — the serve-mode GA evaluator sets
        it so a group-wide Ctrl-C can't make every genome child dump a
        'final snapshot' of its scratch workflow into the lineage
        (preemption semantics belong to the GA parent; a dying
        evaluator is the pool's retry-once path, as before)."""
        import signal
        if os.environ.get("VELES_PREEMPT_DISABLE") == "1":
            return lambda: None
        if threading.current_thread() is not threading.main_thread():
            return lambda: None
        prev = {}
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev[sig] = signal.signal(sig, self._on_preempt_signal)
            except (ValueError, OSError):  # non-main interp / platform
                pass

        def uninstall() -> None:
            for sig, handler in prev.items():
                try:
                    signal.signal(sig, handler)
                except (ValueError, OSError):
                    pass
        return uninstall

    def _on_preempt_signal(self, signum, frame) -> None:
        # handler body is minimal and allocation-light: the interrupted
        # main thread may hold arbitrary locks (telemetry journal,
        # logging), so flag + request + hand off to a watchdog THREAD
        # which does the talking
        if self._preempt_signum is not None:
            # second signal: the operator (or the platform) insists —
            # exit right now; the watchdog/final snapshot may be
            # mid-write, the newest intact candidate still resumes
            os.write(2, b"veles: second preempt signal - hard exit\n")
            os._exit(self.PREEMPT_EXIT)
        self._preempt_signum = signum
        self._begin_graceful_stop(publish=True)

    def _begin_graceful_stop(self, publish: bool) -> None:
        """Request a cooperative stop and arm the grace watchdog.
        Called from the signal handler or (multihost) from the
        ``veles_preempt`` watcher thread."""
        wf = self.workflow
        if wf is not None and hasattr(wf, "request_stop"):
            wf.request_stop()
        threading.Thread(target=self._preempt_watchdog,
                         args=(publish,), daemon=True,
                         name="preempt-watchdog").start()

    def preempt_grace(self) -> float:
        return float(os.environ.get(self.PREEMPT_GRACE_ENV,
                                    str(self.PREEMPT_GRACE_DEFAULT)))

    def _preempt_signal_name(self) -> str:
        import signal
        try:
            return signal.Signals(self._preempt_signum).name
        except (ValueError, TypeError):
            return f"sig{self._preempt_signum}"

    def _preempt_watchdog(self, publish: bool) -> None:
        grace = self.preempt_grace()
        name = self._preempt_signal_name()
        telemetry.event(events.EV_PREEMPT_REQUESTED, signal=name,
                        grace=grace, multihost=self.multihost)
        self.warning(
            "preemption requested (%s): stopping at the next dispatch "
            "boundary; final snapshot due within %.0fs "
            "($%s)", name, grace, self.PREEMPT_GRACE_ENV)
        if publish and self.multihost:
            # coordinated preemption: ALL peers must snapshot and exit
            # 14 together (a lone exit would read as peer death and
            # trigger the abort path on the survivors)
            client = self._kv_client()
            if client is not None:
                try:
                    client.key_value_set("veles_preempt", name)
                except Exception:  # noqa: BLE001 — best-effort
                    pass
        if self._preempt_done.wait(grace):
            return   # the main thread finished the graceful stop
        # wedged (long dispatch, dead collective, serve loop): write
        # the final snapshot from THIS thread, bounded, then exit —
        # preemption must never outlive the platform's kill deadline
        self.error("graceful stop missed the %.0fs grace deadline — "
                   "hard final snapshot from the watchdog", grace)
        telemetry.event(events.EV_PREEMPT_DEADLINE_EXCEEDED, grace=grace)
        result: dict = {}

        def snap() -> None:
            result["path"] = self.final_snapshot(f"preempt-{name}")

        t = threading.Thread(target=snap, daemon=True,
                             name="preempt-final-snapshot")
        t.start()
        t.join(timeout=max(10.0, grace))
        telemetry.flush()
        import logging
        logging.shutdown()
        sys.stderr.flush()
        os._exit(self.PREEMPT_EXIT)

    def _finish_preempt(self) -> None:
        """Main-thread completion of a graceful stop: the run loop
        stopped at a dispatch boundary, so write the final snapshot,
        journal, flush, and exit 14 (``os._exit`` — under multihost a
        normal interpreter exit would hang in jax's distributed
        shutdown barrier against peers that already left)."""
        name = self._preempt_signal_name()
        t0 = time.perf_counter()
        path = self.final_snapshot(f"preempt-{name}")
        dt = time.perf_counter() - t0
        telemetry.gauge(events.GAUGE_PREEMPT_SNAPSHOT_SECONDS).set(
            round(dt, 3))
        self._preempt_done.set()
        self.warning(
            "preempted (%s): final snapshot %s (%.2fs); exiting %d",
            name, path or "FAILED", dt, self.PREEMPT_EXIT)
        telemetry.flush()
        import logging
        logging.shutdown()
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(self.PREEMPT_EXIT)

    def _kv_client(self):
        """The jax distributed KV client, or None outside a real
        multi-process run."""
        from jax._src.distributed import global_state
        return global_state.client

    def final_snapshot(self, reason: str) -> Optional[str]:
        """Best-effort final snapshot for a stop/abort path; None when
        it could not be written (the exit must land regardless).

        Generalizes PR 6's ``_emergency_snapshot``: the file is named
        INTO the Snapshotter lineage
        (``<prefix>_final_<reason>_pid<pid>.pickle.gz`` in the
        snapshotter's directory), so ``snapshot_candidates()`` resume
        discovery finds it, and the resume manifest is pointed at it —
        ``--supervise`` restarts need no flags."""
        try:
            if self.workflow is None:
                return None
            from veles_tpu.snapshotter import (save_workflow,
                                               write_resume_manifest)
            snap = getattr(self.workflow, "snapshotter", None)
            directory = snap.directory if snap is not None else \
                os.path.join(os.path.expanduser("~"),
                             ".veles_tpu", "snapshots")
            prefix = getattr(snap, "prefix", None) or "snapshot"
            os.makedirs(directory, exist_ok=True)
            safe = re.sub(r"[^A-Za-z0-9._-]+", "-", reason)
            path = os.path.join(
                directory,
                f"{prefix}_final_{safe}_pid{os.getpid()}.pickle.gz")
            t0 = time.perf_counter()
            out = save_workflow(self.workflow, path)
            dt = round(time.perf_counter() - t0, 3)
            if reason.startswith("multihost"):
                telemetry.counter(
                    events.CTR_MULTIHOST_EMERGENCY_SNAPSHOTS).inc()
                telemetry.event(events.EV_MULTIHOST_EMERGENCY_SNAPSHOT,
                                path=out, seconds=dt)
            else:
                telemetry.counter(events.CTR_PREEMPT_FINAL_SNAPSHOTS).inc()
                telemetry.event(events.EV_PREEMPT_FINAL_SNAPSHOT,
                                path=out, reason=reason, seconds=dt)
            write_resume_manifest(snapshot=out, reason=reason)
            telemetry.flush()   # os._exit follows — atexit never runs
            return out
        except Exception as e:  # noqa: BLE001 — the exit must land
            self.warning("final snapshot (%s) failed: %s", reason, e)
            return None

    def _emergency_snapshot(self) -> Optional[str]:
        """PR-6 name kept for callers/tests; now writes into the
        snapshot lineage via ``final_snapshot``."""
        return self.final_snapshot("multihost-abort")

    def _abort_multihost(self, exc: BaseException) -> None:
        """A collective failed under --multihost (peer death, network
        partition): write a final emergency snapshot of the local
        workflow state and exit with a distinctive code — the
        supervisor's restart-from-snapshot path, not a hang and not a
        lost run."""
        telemetry.event(events.EV_MULTIHOST_COLLECTIVE_FAILED,
                        error=f"{type(exc).__name__}: {exc}")
        path = self._emergency_snapshot()
        # flush UNCONDITIONALLY: when the snapshot failed, the flush
        # inside final_snapshot never ran and the journal events above
        # (collective_failed, peer_death) would die with os._exit
        telemetry.flush()
        self.error(
            "multihost collective failed (%s: %s) — peer death or "
            "partition; aborting cleanly%s",
            type(exc).__name__, exc,
            f"; final snapshot: {path}" if path else
            " (no snapshot written)")
        # os._exit, not SystemExit: a normal interpreter exit runs
        # jax's atexit distributed.shutdown(), whose Shutdown barrier
        # waits on the DEAD peer until the coordination service
        # SIGABRTs this process (~100 s) — the clean abort must skip
        # that barrier entirely
        import logging
        import sys as _sys
        logging.shutdown()
        _sys.stderr.flush()
        os._exit(self.MULTIHOST_ABORT_EXIT)

    def _start_multihost_watchdog(self):
        """Cross-process liveness over the distributed KV store.

        A dying peer does NOT reliably surface as a catchable error:
        the XLA coordination service only declares a silent task
        unhealthy after ~100 s and then hard-ABORTS every remaining
        process from a C++ thread (SIGABRT — no Python except path,
        no snapshot), while a collective against the dead peer can
        block the main thread indefinitely.  So each process
        publishes a heartbeat key every ``$VELES_MULTIHOST_HEARTBEAT``
        (default 2 s) seconds, and a watchdog thread per peer blocks
        on the peer's next key with a ``$VELES_MULTIHOST_DEADLINE``
        (default 15 s) timeout.  A missed deadline (and no clean
        ``done`` marker) means the peer is gone: write the emergency
        snapshot (bounded wait — the main thread may be wedged inside
        the dead collective) and ``os._exit(13)``, well before the
        coordination service's own fatal abort.

        Returns a stop() callable (publishes this process's clean
        ``done`` marker), or None when not in a real multi-process
        run."""
        import threading

        import jax
        client = self._kv_client()
        if client is None or jax.process_count() <= 1:
            return None
        me = jax.process_index()
        peers = [p for p in range(jax.process_count()) if p != me]
        interval = float(os.environ.get("VELES_MULTIHOST_HEARTBEAT",
                                        "2.0"))
        deadline = float(os.environ.get("VELES_MULTIHOST_DEADLINE",
                                        "15.0"))
        stop = threading.Event()

        def beat() -> None:
            seq = 0
            while not stop.wait(interval):
                try:
                    client.key_value_set(f"veles_hb/{me}/{seq}", "1")
                except Exception:  # noqa: BLE001 — coordination gone;
                    return         # its own abort path is in flight
                seq += 1

        def watch(peer: int) -> None:
            import time as _time
            seq = 0
            last = _time.monotonic()
            while not stop.is_set():
                try:
                    client.blocking_key_value_get(
                        f"veles_hb/{peer}/{seq}",
                        int(deadline * 1000))
                    now = _time.monotonic()
                    # the freshest peer-liveness age the run observed
                    # — obs_report's first read on a wedged slice
                    telemetry.gauge(
                        events.GAUGE_MULTIHOST_PEER_HEARTBEAT_AGE
                    ).set(round(now - last, 3))
                    last = now
                    seq += 1
                    continue
                except Exception:  # noqa: BLE001 — timeout or error
                    pass
                if stop.is_set():
                    return
                try:   # did the peer just finish cleanly?
                    client.blocking_key_value_get(
                        f"veles_done/{peer}", 2000)
                    return
                except Exception:  # noqa: BLE001
                    pass
                if stop.is_set():
                    return
                if self._preempt_signum is not None:
                    # coordinated preemption in flight: a silent peer
                    # is exiting 14 like us, not dying — never convert
                    # a preemption into a peer-death abort
                    return
                self._peer_death_abort(peer, deadline)

        def watch_preempt() -> None:
            # a peer that catches SIGTERM broadcasts ``veles_preempt``
            # so the WHOLE slice snapshots and exits 14 together —
            # coordinated resume, not a peer-death abort
            import signal as _signal
            while not stop.is_set():
                try:
                    client.blocking_key_value_get("veles_preempt",
                                                  5000)
                except Exception:  # noqa: BLE001 — timeout: re-poll
                    continue
                if stop.is_set():
                    return
                if self._preempt_signum is None:
                    self._preempt_signum = int(_signal.SIGTERM)
                    telemetry.event(events.EV_PREEMPT_PEER_BROADCAST)
                    self.warning("peer broadcast veles_preempt — "
                                 "joining the coordinated graceful "
                                 "stop")
                    self._begin_graceful_stop(publish=False)
                return

        threading.Thread(target=beat, daemon=True,
                         name="mh-heartbeat").start()
        threading.Thread(target=watch_preempt, daemon=True,
                         name="mh-watch-preempt").start()
        for p in peers:
            threading.Thread(target=watch, args=(p,), daemon=True,
                             name=f"mh-watch-{p}").start()
        self.info("multihost watchdog up: %d peer(s), heartbeat "
                  "%.1fs, deadline %.1fs", len(peers), interval,
                  deadline)

        def stopper() -> None:
            stop.set()
            try:
                client.key_value_set(f"veles_done/{me}", "1")
            except Exception:  # noqa: BLE001 — shutdown race
                pass

        return stopper

    def _peer_death_abort(self, peer: int, deadline: float) -> None:
        """Watchdog-thread abort: the main thread may be blocked in a
        collective against the dead peer, so the snapshot is written
        from here with a bounded grace period, then the process exits
        with the clean abort code (never hangs, never waits for the
        coordination service's SIGABRT)."""
        telemetry.event(events.EV_MULTIHOST_PEER_DEATH, peer=peer,
                        deadline=deadline)
        self.error(
            "multihost peer %d missed its liveness deadline (%.1fs) — "
            "peer death/partition; writing a final snapshot and "
            "aborting cleanly", peer, deadline)
        result: dict = {}

        def snap() -> None:
            result["path"] = self._emergency_snapshot()

        t = threading.Thread(target=snap, daemon=True,
                             name="mh-final-snapshot")
        t.start()
        t.join(timeout=30.0)
        # flush UNCONDITIONALLY: a failed/hung snapshot skipped the
        # flush inside final_snapshot, and the peer_death event above
        # must survive os._exit
        telemetry.flush()
        path = result.get("path")
        self.error("multihost peer failure: aborting cleanly%s",
                   f"; final snapshot: {path}" if path
                   else " (snapshot did not complete)")
        # stderr flush then hard exit: the main thread cannot be
        # unblocked from a dead collective
        import logging
        logging.shutdown()
        os._exit(self.MULTIHOST_ABORT_EXIT)

    def stop(self) -> None:
        if self.workflow is not None:
            self.workflow.stop()

    def _dump_flops_table(self) -> None:
        """Write the analytic per-layer FLOPs/params table next to the
        jax.profiler trace so the two can be read together."""
        forwards = getattr(self.workflow, "forwards", None)
        if not forwards:
            return
        from veles_tpu import profiling
        from veles_tpu.snapshotter import write_json_atomic
        path = os.path.join(self.profile_dir, "flops_table.json")
        write_json_atomic(path, {
            "layers": profiling.layer_flops_table(forwards),
            "total": profiling.model_flops_per_sample(forwards)})
        self.info("profile: trace + flops_table.json in %s",
                  self.profile_dir)


_multihost_initialized = False


def init_multihost() -> None:
    """``jax.distributed.initialize()`` exactly once per process.

    Multi-host SPMD launch recipe (SURVEY.md §5.8): start the SAME
    ``python -m veles_tpu --multihost ...`` command on every host of
    the slice; on TPU pods coordinator address/process id/count are
    discovered from the TPU metadata automatically, elsewhere set
    JAX_COORDINATOR_ADDRESS / JAX_PROCESS_ID / JAX_NUM_PROCESSES.
    After initialization ``jax.devices()`` spans the whole slice, so a
    ``--dp N_total`` mesh shards over every chip; DCN carries control,
    ICI the collectives."""
    global _multihost_initialized
    if _multihost_initialized:
        return
    import jax
    # Detect prior initialization WITHOUT touching the backend:
    # jax.process_count() would itself initialize XLA, after which
    # distributed.initialize() unconditionally raises.  The distributed
    # client handle is the only side-effect-free signal.
    from jax._src.distributed import global_state
    if global_state.client is None:
        # The env-var contract this docstring promises is honored HERE:
        # this jax's bare initialize() only auto-detects known cluster
        # environments (SLURM, TPU pods) and raises "Number of
        # processes must be defined" on a plain JAX_COORDINATOR_ADDRESS
        # / JAX_PROCESS_ID / JAX_NUM_PROCESSES launch — so read them
        # explicitly and pass them through (None = keep auto-detect).
        coord = os.environ.get("JAX_COORDINATOR_ADDRESS") or None
        nproc = os.environ.get("JAX_NUM_PROCESSES")
        pid = os.environ.get("JAX_PROCESS_ID")
        # (cross-process collectives on the CPU backend ride gloo,
        # jax 0.9.0's default jax_cpu_collectives_implementation)
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(nproc) if nproc else None,
                process_id=int(pid) if pid else None)
        except RuntimeError as e:
            # Backend already up (e.g. the embedding process made a JAX
            # call first).  A --multihost launch that silently runs
            # single-process would train on 1/N of the data and
            # checkpoint a state no peer can join — fail LOUDLY unless
            # the operator explicitly accepts solo semantics.
            telemetry.event(events.EV_MULTIHOST_INIT_REFUSED, error=str(e))
            if os.environ.get("VELES_MULTIHOST_ALLOW_SOLO") == "1":
                import logging
                logging.getLogger("veles_tpu.launcher").warning(
                    "jax.distributed.initialize() refused (%s); "
                    "continuing single-process "
                    "($VELES_MULTIHOST_ALLOW_SOLO=1)", e)
            else:
                raise RuntimeError(
                    "--multihost launch refused by "
                    f"jax.distributed.initialize() ({e}); refusing to "
                    "continue single-process — set "
                    "VELES_MULTIHOST_ALLOW_SOLO=1 to accept solo "
                    "semantics") from e
    _multihost_initialized = True
    _maybe_inject_peer_exit()


def _maybe_inject_peer_exit() -> None:
    """Faultline ``multihost.peer_exit``: hard-exit THIS process (now,
    or ``after`` seconds on a timer thread) so the drill can rehearse
    a dying peer — the surviving processes must abort cleanly with a
    final snapshot (Launcher._abort_multihost), never hang."""
    from veles_tpu import faults
    if not faults.active():
        return
    proc = os.environ.get("JAX_PROCESS_ID")
    if proc is None:
        try:
            import jax
            proc = str(jax.process_index())
        except Exception:  # noqa: BLE001 — no distributed context
            proc = "0"
    f = faults.fire("multihost.peer_exit", process=proc)
    if not f:
        return
    delay = float(f.get("after", 0.0))
    if delay <= 0:
        os._exit(17)
    import threading
    import time as _time

    def _die():
        _time.sleep(delay)
        os._exit(17)

    threading.Thread(target=_die, daemon=True,
                     name="fault-peer-exit").start()


def load_workflow_module(path: str):
    """Import a workflow file the reference way (a plain python file,
    not necessarily on sys.path)."""
    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def apply_config_file(path: str) -> None:
    """Execute a config file for its side effect of mutating ``root``
    (reference: config files are python)."""
    glb = {"root": root, "__file__": path, "__name__": "__veles_config__"}
    with open(path) as f:
        code = compile(f.read(), path, "exec")
    exec(code, glb)


def drive_workflow(launcher, workflow_file: str) -> None:
    """Load a workflow module and drive it through the launcher — the
    one place the run(launcher) / create_workflow(launcher) module
    contract is interpreted (CLI main and GA workers both call this)."""
    mod = load_workflow_module(workflow_file)
    if hasattr(mod, "run"):
        mod.run(launcher)
    elif hasattr(mod, "create_workflow"):
        launcher.create_workflow(getattr(mod, "create_workflow"))
        launcher.initialize()
        launcher.run()
    else:
        raise RuntimeError(
            f"{workflow_file}: defines neither run(launcher) nor "
            "create_workflow(launcher)")


def workflow_fitness(workflow) -> float:
    """The GA fitness of a finished workflow: best validation error,
    falling back to best train error for valid-less configs."""
    d = workflow.decision
    err = d.min_valid_error
    if err == float("inf"):
        err = d.min_train_error
    return err
