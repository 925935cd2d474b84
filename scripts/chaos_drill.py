"""Chaos drill: run the Faultline fault matrix on CPU and verify the
supervision layer recovers from every injected failure.

Usage::

    JAX_PLATFORMS=cpu python scripts/chaos_drill.py [--json] [--only F]

Each drill arms one (or a pair of) named injection point(s)
(veles_tpu/faults.py), exercises the REAL code path it lives in, and
asserts the documented recovery: a hung evaluator is replaced within
the heartbeat deadline, torn snapshots / GA checkpoints fall back to
the newest intact predecessor, corrupt stream files are skipped and
counted (and abort loudly past the tolerance), an OOMing upload
degrades instead of dying, a dying multihost peer aborts the
survivors cleanly with a final snapshot, a SIGTERM (preemption
notice) stops gracefully — final snapshot inside the grace deadline,
exit 14, supervisor auto-resume, trajectory f32-exact vs the
uninterrupted oracle — and a SIGKILLed GA run resumes from its
per-generation checkpoint bit-identically.

The last stdout line is one JSON record::

    {"fault_drill_ok": bool, "results": [
        {"fault": ..., "ok": bool, "recovery_sec": float, "detail":
         ...}, ...]}

``--only NAME`` (substring match) runs a subset; the multihost drill
is the only one that spawns a process pair and respects
``CHAOS_SKIP_MULTIHOST=1``.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

# the drill is a CPU rehearsal: pin BEFORE any jax import so it can
# run next to (not on) a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

# expected-event names and the exit-code contract come from the
# shared registries — an emitter/asserter typo is a veleslint
# finding, not a mystery drill failure
from veles_tpu import events  # noqa: E402
from veles_tpu.supervisor import EXIT_MULTIHOST_ABORT  # noqa: E402


def log(msg: str) -> None:
    print(f"[chaos] {msg}", file=sys.stderr, flush=True)


def assert_journal_event(name: str, since: int = 0) -> dict:
    """Every drill must leave its expected Sightline event in the run
    journal — a fault that is recovered from but not REPORTED would
    leave the operator blind.  Returns the newest matching event (from
    the in-process ring, which mirrors the journal file)."""
    from veles_tpu import telemetry
    evs = telemetry.recent_events(name)
    assert len(evs) > since, \
        f"no {name!r} event in the telemetry journal " \
        f"(have: {sorted({e['event'] for e in telemetry.recent_events()})})"
    return evs[-1]


def journal_events_from_dir(mdir: str, name: str = None) -> list:
    """Events from every ``journal-*.jsonl`` under ``mdir`` — the way
    to verify what SUBPROCESSES (supervisor, launcher children,
    multihost peers) reported; the in-process ring only mirrors this
    process's journal."""
    import glob
    evs = []
    for jf in glob.glob(os.path.join(mdir, "journal-*.jsonl")):
        with open(jf) as f:
            for line in f:
                try:
                    evs.append(json.loads(line))
                except ValueError:
                    pass
    if name is not None:
        evs = [e for e in evs if e.get("event") == name]
    return sorted(evs, key=lambda e: e.get("ts", 0))


def drill(fn):
    """Run one drill function -> result record (never raises)."""
    name = fn.__name__.replace("drill_", "").replace("__", ".")
    t0 = time.monotonic()
    try:
        detail = fn() or {}
        ok = True
    except KeyboardInterrupt:
        raise
    except BaseException as e:  # noqa: BLE001 — record, keep drilling
        detail = {"error": f"{type(e).__name__}: {e}"}
        ok = False
    rec = {"fault": name, "ok": ok,
           "recovery_sec": round(time.monotonic() - t0, 2)}
    rec.update(detail)
    log(f"{name}: {'OK' if ok else 'FAILED'} "
        f"({rec['recovery_sec']}s) {detail}")
    return rec


# -- persistence drills ------------------------------------------------

def drill_snapshot__torn_write():
    from veles_tpu import faults
    from veles_tpu.snapshotter import (SnapshotCorruptError,
                                       load_workflow, save_workflow)
    d = tempfile.mkdtemp(prefix="chaos_snap_")
    p1 = os.path.join(d, "snap_epoch1.pickle.gz")
    p2 = os.path.join(d, "snap_epoch2.pickle.gz")
    save_workflow({"marker": 1}, p1)
    faults.arm("snapshot.torn_write")
    save_workflow({"marker": 2}, p2)
    faults.arm("")
    try:
        load_workflow(p2)
        raise AssertionError("torn snapshot loaded verbatim")
    except SnapshotCorruptError:
        pass
    got = load_workflow(p2, fallback=True)
    assert got == {"marker": 1}, got
    ev = assert_journal_event(events.EV_SNAPSHOT_FALLBACK)
    assert ev["used"] == p1, ev
    return {"fell_back_to": os.path.basename(p1),
            "journal_event": events.EV_SNAPSHOT_FALLBACK}


def drill_checkpoint__corrupt():
    from veles_tpu import faults, prng
    from veles_tpu.genetics import GeneticOptimizer, Tune

    tunes = {"x": Tune(5.0, -10.0, 10.0), "y": Tune(-3.0, -10.0, 10.0)}

    def quad(v):
        return (v["x"] - 2.0) ** 2 + (v["y"] + 1.0) ** 2

    d = tempfile.mkdtemp(prefix="chaos_ckpt_")
    state = os.path.join(d, "ga.json")
    prng.seed_all(4242)
    _, fit_ref = GeneticOptimizer(quad, tunes, population=6,
                                  generations=4,
                                  state_path=state + ".ref").run()
    # the FINAL checkpoint write is torn by the injected fault; the
    # resume must fall back to .prev and still finish bit-identically
    prng.seed_all(4242)
    faults.arm("checkpoint.corrupt@gen=4")
    GeneticOptimizer(quad, tunes, population=6, generations=4,
                     state_path=state).run()
    faults.arm("")
    prng.seed_all(31337)   # irrelevant: resume restores the rng
    _, fit2 = GeneticOptimizer(quad, tunes, population=6,
                               generations=4, state_path=state).run()
    assert abs(fit2 - fit_ref) < 1e-12, (fit2, fit_ref)
    ev = assert_journal_event(events.EV_GA_CHECKPOINT_FALLBACK)
    assert ev["used"].endswith(".prev"), ev
    return {"bit_identical_resume": True,
            "journal_event": events.EV_GA_CHECKPOINT_FALLBACK}


# -- loader drills -----------------------------------------------------

def _make_image_tree(n=12, shape=(8, 8, 3)):
    from PIL import Image
    d = tempfile.mkdtemp(prefix="chaos_imgs_")
    rng = np.random.default_rng(7)
    paths = []
    for i in range(n):
        p = os.path.join(d, f"img_{i:02d}.png")
        Image.fromarray(rng.integers(0, 255, shape, dtype="uint8")) \
            .save(p)
        paths.append((p, i % 3))
    return paths


def drill_stream__corrupt_file():
    from veles_tpu import faults
    from veles_tpu.loader.image import FileListImageLoader

    paths = _make_image_tree()
    # 1/12 corrupt under a 10% tolerance: skipped, counted, zero row
    faults.arm("stream.corrupt_file@index=7")
    ld = FileListImageLoader(train=paths, minibatch_size=4,
                             target_shape=(8, 8, 3), streaming=False,
                             corrupt_tolerance=0.1, name="chaosldr")
    ld.load_data()
    data = ld.original_data.mem
    assert len(ld.corrupt_indices) == 1, ld.corrupt_indices
    assert not data[sorted(ld.corrupt_indices)[0]].any()
    good = [i for i in range(len(paths)) if i not in ld.corrupt_indices]
    assert all(data[i].any() for i in good)
    # 3/12 corrupt blows through the tolerance: must abort loudly
    faults.arm("stream.corrupt_file@index=3,stream.corrupt_file@index=4"
               ",stream.corrupt_file@index=5")
    ld2 = FileListImageLoader(train=paths, minibatch_size=4,
                              target_shape=(8, 8, 3), streaming=False,
                              corrupt_tolerance=0.1, name="chaosldr2")
    try:
        ld2.load_data()
        raise AssertionError("over-threshold corruption did not abort")
    except RuntimeError as e:
        assert "corrupt_tolerance" in str(e)
    finally:
        faults.arm("")
    assert_journal_event(events.EV_LOADER_CORRUPT_FILE)
    assert_journal_event(events.EV_LOADER_CORRUPT_OVER_TOLERANCE)
    return {"skipped": 1, "threshold_aborted": True,
            "journal_event": events.EV_LOADER_CORRUPT_FILE}


def _tiny_workflow(streaming: bool):
    from veles_tpu import prng
    from veles_tpu.datasets import synthetic_classification
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.ops.standard_workflow import StandardWorkflow
    prng.seed_all(1357)
    train, valid, _ = synthetic_classification(
        160, 40, (8, 8, 1), n_classes=4, seed=7)
    kw = {"max_resident_bytes": 0} if streaming else {}
    gd = {"learning_rate": 0.1}
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid, minibatch_size=20,
            name="loader", **kw),
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
             "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": gd},
        ],
        decision_config={"max_epochs": 2}, name="chaos_wf")


def drill_device__oom_on_put_stream():
    from veles_tpu import faults
    from veles_tpu.backends import JaxDevice
    w = _tiny_workflow(streaming=True)
    w.initialize(device=JaxDevice(platform="cpu"))
    assert w.fused.streaming
    faults.arm("device.oom_on_put@site=stream")
    try:
        w.run()
    finally:
        faults.arm("")
    assert w.fused.stream_oom_retries == 1, w.fused.stream_oom_retries
    hist = [h for h in w.decision.history if h["class"] == "validation"]
    assert hist and np.isfinite(hist[-1]["loss"])
    w.stop()
    ev = assert_journal_event(events.EV_DEVICE_OOM_RETRY)
    assert ev["site"] == "stream", ev
    return {"oom_retries": 1, "run_completed": True,
            "journal_event": events.EV_DEVICE_OOM_RETRY}


def drill_device__oom_on_put_resident():
    from veles_tpu import faults
    from veles_tpu.backends import JaxDevice
    w = _tiny_workflow(streaming=False)
    faults.arm("device.oom_on_put@site=resident_dataset")
    try:
        w.initialize(device=JaxDevice(platform="cpu"))
    finally:
        faults.arm("")
    # the budget said resident; the injected OOM degraded to streaming
    assert not w.loader.device_resident
    assert w.fused.streaming
    w.run()
    hist = [h for h in w.decision.history if h["class"] == "validation"]
    assert hist and np.isfinite(hist[-1]["loss"])
    w.stop()
    ev = assert_journal_event(events.EV_DEVICE_OOM_DEGRADED)
    assert ev["site"] == "resident_dataset", ev
    return {"degraded_to_streaming": True,
            "journal_event": events.EV_DEVICE_OOM_DEGRADED}


# -- evaluator drills (real serve-mode child process) ------------------

def _wine_ga_files(d):
    import textwrap
    wf = os.path.join(d, "wf.py")
    with open(wf, "w") as f:
        f.write(textwrap.dedent("""
            from veles_tpu.models import wine

            def run(launcher):
                launcher.create_workflow(wine.create_workflow)
                launcher.initialize()
                launcher.run()
        """))
    cfg = os.path.join(d, "cfg.py")
    with open(cfg, "w") as f:
        f.write(textwrap.dedent("""
            from veles_tpu.config import root
            from veles_tpu.genetics import Tune

            root.wine.decision = {"max_epochs": 3}
            root.wine.layers = [
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 8},
                 "<-": {"learning_rate": Tune(0.3, 0.01, 1.0)}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.3}},
            ]
        """))
    return wf, cfg


def drill_evaluator__hang_and_garbage():
    """The headline drill: a real serve-mode evaluator hangs SILENTLY
    mid-genome (heartbeats stop too) and also tears the protocol with
    a garbage line on another genome; the pool must detect the hang
    within the heartbeat deadline, replace the evaluator, re-dispatch
    the genome, and finish the generation with fitness parity against
    an unfaulted pass."""
    from veles_tpu.genetics.pool import ChipEvaluatorPool

    d = tempfile.mkdtemp(prefix="chaos_ga_")
    wf, cfg = _wine_ga_files(d)
    lr = "wine.layers[0]['<-']['learning_rate']"
    values = [{lr: 0.1}, {lr: 0.3}, {lr: 0.6}]
    hb_deadline = float(os.environ.get("CHAOS_HB_DEADLINE", "10"))

    def run_pool(fault_env):
        env_key = "VELES_FAULTS"
        saved = os.environ.get(env_key)
        if fault_env:
            os.environ[env_key] = fault_env
        else:
            os.environ.pop(env_key, None)
        try:
            pool = ChipEvaluatorPool(
                [sys.executable, "-m", "veles_tpu.genetics.worker",
                 "--serve", wf, cfg, "-b", "cpu", "-s", "1234",
                 "--heartbeat-every", "0.5"],
                workers=2, timeout=600,
                heartbeat_deadline=hb_deadline,
                restart_backoff=0.1)
            with pool:
                fits = pool.evaluate_many(values)
            return pool, fits
        finally:
            if saved is None:
                os.environ.pop(env_key, None)
            else:
                os.environ[env_key] = saved

    _, fits_ref = run_pool("")
    assert all(np.isfinite(f) for f in fits_ref), fits_ref
    t0 = time.monotonic()
    # job=2&seq=1: hang exactly once — on the first evaluator (job 2
    # arrives as its second job), not on the replacement (where the
    # retried job 2 comes first)
    pool, fits = run_pool(
        "evaluator.hang@job=2&seq=1&silent=1&seconds=600,"
        "evaluator.garbage_line@job=1")
    wall = time.monotonic() - t0
    assert fits == fits_ref, (fits, fits_ref)
    assert pool.hangs_detected >= 1, pool.hangs_detected
    assert pool.last_hang_kind == "heartbeat", pool.last_hang_kind
    assert pool.last_hang_wait <= hb_deadline + 5.0, pool.last_hang_wait
    ev = assert_journal_event(events.EV_GA_HANG_DETECTED)
    assert ev["kind"] == "heartbeat", ev
    assert_journal_event(events.EV_GA_EVALUATOR_RESTART)
    return {"hang_detect_sec": round(pool.last_hang_wait, 2),
            "heartbeat_deadline": hb_deadline,
            "fitness_parity": True, "wall_sec": round(wall, 1),
            "journal_event": events.EV_GA_HANG_DETECTED}


# -- multihost drill ---------------------------------------------------

def drill_multihost__peer_exit():
    """Process 1 of a 2-process CPU multihost run hard-exits shortly
    after init (injected peer death); process 0 must NOT hang in the
    collective — it aborts cleanly (exit 13) with a final snapshot."""
    if os.environ.get("CHAOS_SKIP_MULTIHOST"):
        return {"skipped": True}
    import socket
    import subprocess
    import textwrap

    d = tempfile.mkdtemp(prefix="chaos_mh_")
    wf = os.path.join(d, "mh_wf.py")
    with open(wf, "w") as f:
        f.write(textwrap.dedent("""
            from veles_tpu.workflow import Workflow


            class PsumLoop(Workflow):
                # keep running collectives until the peer dies under
                # one of them — the watchdog (launcher.run) must abort
                # this cleanly
                def run(self):
                    import time
                    import jax
                    import jax.numpy as jnp
                    assert jax.process_count() == 2
                    for _ in range(600):
                        out = jax.pmap(
                            lambda v: jax.lax.psum(v, "i"),
                            axis_name="i")(
                            jnp.ones(jax.local_device_count()))
                        out.block_until_ready()
                        time.sleep(0.1)


            def create_workflow(launcher):
                return PsumLoop(None, name="mh_chaos")


            def run(launcher):
                launcher.create_workflow(create_workflow)
                launcher.initialize()
                launcher.run()
        """))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    snap_dir = os.path.join(d, "snaps")
    procs = []
    for pid in range(2):
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
            "JAX_COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "JAX_NUM_PROCESSES": "2",
            "JAX_PROCESS_ID": str(pid),
            "HOME": d,   # the emergency snapshot lands under $HOME
            "VELES_FAULTS": "multihost.peer_exit@process=1&after=2",
        })
        env.pop("VELES_PLOTS_DIR", None)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "veles_tpu", "--multihost",
             "-b", "cpu", wf],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, cwd=REPO, env=env))
    del snap_dir
    rcs = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=180)
            rcs.append((p.returncode, err))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    rc0, err0 = rcs[0]
    rc1, _ = rcs[1]
    assert rc1 == 17, f"peer did not die as injected (rc={rc1})"
    assert rc0 == EXIT_MULTIHOST_ABORT, \
        f"survivor rc={rc0}, wanted clean abort " \
        f"{EXIT_MULTIHOST_ABORT}; stderr: {err0[-800:]}"
    assert "aborting cleanly" in err0, err0[-800:]
    snaps = []
    for root, _, files in os.walk(d):
        # Phoenix named the emergency snapshot INTO the Snapshotter
        # lineage (<prefix>_final_multihost-abort_pid<pid>...), so
        # --snapshot/--supervise resume discovery finds it
        snaps += [f for f in files if "_final_multihost" in f]
    assert snaps, "no final snapshot written by the survivor"
    # the survivor's journal (its own process wrote journal-<pid>.jsonl
    # into the shared metrics dir it inherited via $VELES_METRICS_DIR)
    # must carry the abort record — the drill verifies REPORTING, not
    # just recovery
    from veles_tpu import telemetry
    mdir = telemetry.metrics_dir()
    evs = journal_events_from_dir(
        mdir, events.EV_MULTIHOST_EMERGENCY_SNAPSHOT) if mdir else []
    assert evs, "survivor journal lacks the abort record"
    return {"survivor_exit": rc0, "final_snapshot": snaps[0],
            "journal_event": events.EV_MULTIHOST_EMERGENCY_SNAPSHOT}


# -- Phoenix drills (preemption + supervisor) --------------------------

_PHX_WF = """
import json
import os

import numpy as np

from veles_tpu import prng
from veles_tpu.datasets import synthetic_classification
from veles_tpu.loader import ArrayLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow


def create_workflow(launcher):
    prng.seed_all(1357)
    train, valid, _ = synthetic_classification(
        2400, 400, (8, 8, 1), n_classes=4, seed=7)
    gd = {"learning_rate": 0.1, "gradient_moment": 0.9}
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid, minibatch_size=24,
            name="loader"),
        layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 24}, "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": gd},
        ],
        decision_config={"max_epochs": int(os.environ["PHX_EPOCHS"]),
                         "fail_iterations": 10000},
        snapshotter_config={"directory": os.environ["PHX_SNAP_DIR"],
                            "prefix": "phx", "interval": 1000},
        name="phx_wf")


def run(launcher):
    launcher.create_workflow(create_workflow)
    launcher.initialize()
    launcher.run()
    w = launcher.workflow
    hist = [[h["class"], int(h["n_err"]), float(h["loss"])]
            for h in w.decision.history]
    ws = float(np.abs(np.asarray(
        w.forwards[0].weights.map_read()).astype(np.float64)).sum())
    print(json.dumps({
        "epochs": len([h for h in hist if h[0] == "validation"]),
        "hist": hist, "wsum": ws}))
"""


def _phx_env(d, metrics, epochs, **extra):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PHX_SNAP_DIR": os.path.join(d, "snaps"),
                "PHX_EPOCHS": str(epochs),
                "VELES_METRICS_DIR": metrics})
    env.pop("VELES_FAULTS", None)
    env.pop("VELES_RESUME_MANIFEST", None)
    env.update(extra)
    return env


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def drill_preempt__sigterm_resume():
    """The Phoenix headline: a real SIGTERM lands mid-training (the
    injected preemption notice); the run must stop at the next
    dispatch boundary, write a final snapshot into the Snapshotter
    lineage INSIDE the grace deadline, and exit 14; the supervisor
    must auto-resume it from that snapshot — and the completed
    trajectory must match the uninterrupted oracle f32-exactly."""
    import subprocess
    d = tempfile.mkdtemp(prefix="chaos_preempt_")
    wf = os.path.join(d, "wf.py")
    with open(wf, "w") as f:
        f.write(_PHX_WF)
    epochs, grace = 200, 20.0

    oracle = subprocess.run(
        [sys.executable, "-m", "veles_tpu", "-b", "cpu", wf],
        env=_phx_env(d, os.path.join(d, "m_oracle"), epochs),
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert oracle.returncode == 0, oracle.stderr[-800:]
    ref = _last_json(oracle.stdout)
    assert ref["epochs"] == epochs, ref["epochs"]

    mdir = os.path.join(d, "m_supervised")
    res = subprocess.run(
        [sys.executable, "-m", "veles_tpu", "--supervise",
         "-b", "cpu", wf],
        env=_phx_env(
            d, mdir, epochs,
            VELES_FAULTS="preempt.sigterm@attempt=0&after=1.5",
            VELES_PREEMPT_GRACE=str(grace)),
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, \
        f"supervised run rc={res.returncode}: {res.stderr[-800:]}"
    got = _last_json(res.stdout)

    # the preempted child left its final snapshot in the lineage
    snaps = [f for f in os.listdir(os.path.join(d, "snaps"))
             if f.startswith("phx_final_preempt")]
    assert snaps, os.listdir(os.path.join(d, "snaps"))
    # journal: requested -> final snapshot (inside grace, never the
    # watchdog's hard path) -> supervisor resumed -> done
    req = journal_events_from_dir(mdir, events.EV_PREEMPT_REQUESTED)
    fin = journal_events_from_dir(mdir,
                                  events.EV_PREEMPT_FINAL_SNAPSHOT)
    assert req and fin, journal_events_from_dir(mdir)
    assert not journal_events_from_dir(
        mdir, events.EV_PREEMPT_DEADLINE_EXCEEDED)
    snapshot_sec = fin[-1]["ts"] - req[-1]["ts"]
    assert 0 <= snapshot_sec <= grace, snapshot_sec
    resumed = journal_events_from_dir(mdir, events.EV_SUPERVISOR_RESUMED)
    assert resumed and resumed[-1]["source"] == "snapshot", resumed
    assert journal_events_from_dir(mdir, events.EV_SUPERVISOR_DONE)

    # trajectory parity: f32-exact on CPU, incl. the weight checksum.
    # Asserted piecewise with a row-level diff — the old single
    # `hist == hist and wsum == wsum` assert could only say "something
    # differed", which made its load-sensitive failure mode (PR 9's
    # noted flake) undiagnosable from the drill output alone.  The
    # flake itself was NOT wall-clock noise: under load the SIGTERM
    # lands mid-class (a legal stop boundary) and the fused runner's
    # on-device metric accumulator used to be dropped by the snapshot,
    # so the interrupted epoch's history row undercounted while the
    # weights stayed bit-exact.  Fixed at the root (FusedStepRunner
    # __getstate__ now carries _acc/_conf; pinned by
    # test_supervisor.py::test_mid_class_stop_preserves_partial_
    # metrics), so exact parity holds at ANY stop point — idle or
    # loaded box alike.
    assert got["wsum"] == ref["wsum"], \
        f"weight checksum diverged: {got['wsum']} != {ref['wsum']}"
    assert got["epochs"] == ref["epochs"], (got["epochs"],
                                            ref["epochs"])
    if got["hist"] != ref["hist"]:
        diffs = [(i, g, r) for i, (g, r) in
                 enumerate(zip(got["hist"], ref["hist"])) if g != r]
        raise AssertionError(
            f"history diverged in {len(diffs)} of {len(ref['hist'])} "
            f"rows (lengths {len(got['hist'])}/{len(ref['hist'])}); "
            f"first: row {diffs[0][0] if diffs else '?'} "
            f"got={diffs[0][1] if diffs else None} "
            f"ref={diffs[0][2] if diffs else None}")
    return {"journal_event": events.EV_PREEMPT_FINAL_SNAPSHOT,
            "trajectory_match": True,
            "preempt_snapshot_sec": round(snapshot_sec, 2),
            "resume_downtime_sec": resumed[-1].get("downtime"),
            "final_snapshot": snaps[0]}


def drill_supervisor__sigkill_ga_resume():
    """A GA run is SIGKILLed mid-generation (after the generation's
    evaluations, before its checkpoint lands — the worst case); the
    supervisor must resume it from the per-generation --ga-state
    checkpoint and the finished run must be bit-identical to the
    uninterrupted oracle (same best/fitness AND the same final
    checkpoint file, RNG state included)."""
    import subprocess
    d = tempfile.mkdtemp(prefix="chaos_sigkill_ga_")
    wf, cfg = _wine_ga_files(d)

    def run_ga(state, metrics, fault=None):
        env = _phx_env(d, metrics, 0)
        if fault:
            env["VELES_FAULTS"] = fault
        cmd = [sys.executable, "-m", "veles_tpu"]
        if fault:
            cmd.append("--supervise")
        cmd += ["--optimize", "5:2", "-b", "cpu",
                "--ga-workers", "2", "--ga-state", state, wf, cfg]
        res = subprocess.run(cmd, env=env, capture_output=True,
                             text=True, timeout=420, cwd=REPO)
        assert res.returncode == 0, \
            f"rc={res.returncode}: {res.stderr[-800:]}"
        return _last_json(res.stdout)

    ref = run_ga(os.path.join(d, "oracle.json"),
                 os.path.join(d, "m_oracle"))
    mdir = os.path.join(d, "m_supervised")
    got = run_ga(os.path.join(d, "state.json"), mdir,
                 fault="supervisor.child_crash@attempt=0&gen=2")
    assert got == ref, (got, ref)
    # the final checkpoints must be bit-identical too: population,
    # fitnesses, history, and the GA RNG state all replayed exactly
    with open(os.path.join(d, "oracle.json")) as f:
        st_ref = json.load(f)
    with open(os.path.join(d, "state.json")) as f:
        st_got = json.load(f)
    assert st_got == st_ref, "resumed GA checkpoint diverged"
    restarts = journal_events_from_dir(mdir,
                                       events.EV_SUPERVISOR_RESTART)
    assert restarts and restarts[-1]["kind"] == "crash", restarts
    resumed = journal_events_from_dir(mdir, events.EV_SUPERVISOR_RESUMED)
    assert resumed and resumed[-1]["source"] == "ga_state", resumed
    assert journal_events_from_dir(mdir, events.EV_GA_RESUMED)
    return {"journal_event": events.EV_SUPERVISOR_RESUMED,
            "bit_identical_resume": True,
            "resume_downtime_sec": resumed[-1].get("downtime")}


# -- Sentinel drills (fleet gray failures) -----------------------------

_FLEET_WF = """
from veles_tpu import prng
from veles_tpu.datasets import synthetic_classification
from veles_tpu.loader import ArrayLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow

def create_workflow(launcher):
    prng.seed_all(4242)
    train, valid, _ = synthetic_classification(
        64, 16, (6, 6, 1), n_classes=3, seed=5)
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid, minibatch_size=16,
            name="loader"),
        layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": 12},
             "<-": {"learning_rate": 0.1}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.1}},
        ],
        decision_config={"max_epochs": 2}, name="chaos_fleet_wf")
"""


def _fleet_pkg(d):
    """One tiny Forge ensemble package + its host oracle (the
    test_fleet recipe) for the gray-failure fleet drills."""
    from veles_tpu import prng
    from veles_tpu.backends import NumpyDevice
    from veles_tpu.ensemble.packaging import pack_ensemble
    from veles_tpu.launcher import load_workflow_module

    wf_path = os.path.join(d, "fleet_wf.py")
    with open(wf_path, "w") as f:
        f.write(_FLEET_WF)
    mod = load_workflow_module(wf_path)

    class FL:
        workflow = None

    prng.seed_all(11)
    w = mod.create_workflow(FL())
    w.initialize(device=NumpyDevice())
    base = {fw.name: {k: np.asarray(v) for k, v in
                      fw.gather_params().items()}
            for fw in w.forwards}
    rng = np.random.default_rng(11)
    members = []
    for _ in range(3):
        params = {fn: {pn: (a + 0.05 * rng.standard_normal(a.shape)
                            .astype(np.float32))
                       for pn, a in p.items()}
                  for fn, p in base.items()}
        members.append({"params": params, "valid_error": 0.0,
                        "seed": 11,
                        "forward_names": [fw.name
                                          for fw in w.forwards],
                        "values": None})
    pkg = os.path.join(d, "m.vpkg")
    pack_ensemble(pkg, "m", members, wf_path)

    def oracle(x):
        acc = None
        for m in members:
            out = np.asarray(x, np.float32)
            for fw in w.forwards:
                p = {k: np.asarray(v)
                     for k, v in m["params"][fw.name].items()}
                out, _ = fw.apply_fwd(p, out, rng=None, train=False)
            out = np.asarray(out)
            acc = out if acc is None else acc + out
        return acc / len(members)

    return pkg, oracle


#: metric dirs the fleet drills pointed replicas at — the lock
#: witness pass unions their lockwitness-<pid>.json files at the end
WITNESS_DIRS: list = []


def _gray_fleet(fault, d, **kw):
    """A REAL 2-replica fleet with replica 0 armed via a per-replica
    VELES_FAULTS override (replica 1 explicitly disarmed)."""
    from veles_tpu.serve.router import FleetRouter
    pkg, oracle = _fleet_pkg(d)
    defaults = dict(
        n_replicas=2, backend="cpu", max_batch=16, max_wait_ms=5,
        metrics_dir=os.path.join(d, "metrics"), cwd=REPO,
        env={"VELES_FAULTS": ""},
        env_overrides={0: {"VELES_FAULTS": fault}})
    defaults.update(kw)
    WITNESS_DIRS.append(defaults["metrics_dir"])
    return FleetRouter({"m": pkg}, **defaults), oracle


def _ctr(name):
    from veles_tpu import telemetry
    return telemetry.counter(name).value


def drill_hive__slow_dispatch():
    """The tail-at-scale drill: one replica dispatches at 1.5s while
    staying alive and heartbeating.  Hedges must bridge the detection
    window (every answer clean and fast), the sentinel must EJECT the
    outlier, and — once the fault budget exhausts under probing — the
    probe/reinstate lifecycle must bring it back."""
    d = tempfile.mkdtemp(prefix="chaos_gray_slow_")
    router, oracle = _gray_fleet(
        "hive.slow_dispatch@label=m&times=6&seconds=1.5", d,
        deadline_ms=8000, hedge_min_ms=60, hedge_budget=1.0,
        probe_interval=0.2, probe_ok=2, probe_backoff_cap=0.4)
    hedges0 = _ctr(events.CTR_FLEET_HEDGES)
    eject0 = _ctr(events.CTR_FLEET_EJECTIONS)
    reinst0 = _ctr(events.CTR_FLEET_REINSTATEMENTS)
    try:
        x = np.ones((1, 6, 6, 1), np.float32)
        want = oracle(x)
        for _ in range(30):
            r = router.request("m", x, timeout=30)
            assert "probs" in r, r
            assert np.abs(np.asarray(r["probs"], np.float32)
                          - want).max() < 1e-4
            if _ctr(events.CTR_FLEET_EJECTIONS) > eject0:
                break
        assert _ctr(events.CTR_FLEET_HEDGES) > hedges0
        assert _ctr(events.CTR_FLEET_EJECTIONS) == eject0 + 1
        # post-ejection p99 is bounded: nothing waits out the stall
        post = []
        for _ in range(10):
            t0 = time.monotonic()
            assert "probs" in router.request("m", x, timeout=30)
            post.append(time.monotonic() - t0)
        assert max(post) < 1.0, post
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline \
                and _ctr(events.CTR_FLEET_REINSTATEMENTS) <= reinst0:
            time.sleep(0.25)
        assert _ctr(events.CTR_FLEET_REINSTATEMENTS) == reinst0 + 1
        ev = assert_journal_event(events.EV_FLEET_REPLICA_EJECTED)
        assert ev["replica"] == 0, ev
        assert_journal_event(events.EV_FLEET_REPLICA_REINSTATED)
        return {"hedged": _ctr(events.CTR_FLEET_HEDGES) - hedges0,
                "post_eject_max_ms": round(1000 * max(post), 1),
                "ejected_and_reinstated": True,
                "journal_event": events.EV_FLEET_REPLICA_EJECTED}
    finally:
        router.close(kill=True)


def drill_hive__wedge():
    """A wedged batcher: requests vanish unanswered while heartbeats
    and stats keep flowing — invisible to the heartbeat monitor.  The
    sentinel must detect it (hedge losses), eject it WITHOUT any
    heartbeat loss, and keep it out (probes are swallowed too)."""
    d = tempfile.mkdtemp(prefix="chaos_gray_wedge_")
    router, _oracle = _gray_fleet(
        "hive.wedge@times=*", d,
        deadline_ms=5000, hedge_min_ms=60, hedge_budget=1.0,
        probe_interval=0.25, probe_ok=2, probe_backoff_cap=0.5,
        heartbeat_every=0.2)
    eject0 = _ctr(events.CTR_FLEET_EJECTIONS)
    probe_fail0 = _ctr(events.CTR_FLEET_PROBES_FAILED)
    try:
        x = np.ones((1, 6, 6, 1), np.float32)
        for _ in range(25):
            assert "probs" in router.request("m", x, timeout=30)
            if _ctr(events.CTR_FLEET_EJECTIONS) > eject0:
                break
        assert _ctr(events.CTR_FLEET_EJECTIONS) == eject0 + 1
        # detection WITHOUT heartbeat loss: the monitor saw no death
        assert router.replicas[0].deaths == 0
        assert router.replicas[0].healthy
        assert router.replicas[0].client.heartbeats > 0
        # the wedged replica can never pass its canary probe
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline \
                and _ctr(events.CTR_FLEET_PROBES_FAILED) \
                <= probe_fail0:
            time.sleep(0.1)
        assert _ctr(events.CTR_FLEET_PROBES_FAILED) > probe_fail0
        st = router.sentinel.status(router.replicas[0])
        assert st["state"] in ("ejected", "probing"), st
        ev = assert_journal_event(events.EV_FLEET_REPLICA_EJECTED)
        assert ev["replica"] == 0, ev
        return {"heartbeats_flowed": router.replicas[0]
                .client.heartbeats,
                "deaths": 0, "stays_ejected": True,
                "journal_event": events.EV_FLEET_REPLICA_EJECTED}
    finally:
        router.close(kill=True)


def drill_hive__garbage_response():
    """Corrupt responses: a replica garbles every probability payload
    AFTER its crc echo was computed from the clean one.  The router's
    integrity check must strike + retry on the peer so ZERO corrupt
    answers reach a client (oracle parity held), and the replica must
    eject and stay out (its probes read garbage too)."""
    d = tempfile.mkdtemp(prefix="chaos_gray_garbage_")
    router, oracle = _gray_fleet(
        "hive.garbage_response@times=*", d,
        deadline_ms=8000, hedge_budget=0.0,
        probe_interval=0.25, probe_ok=2, probe_backoff_cap=0.5)
    strikes0 = _ctr(events.CTR_FLEET_INTEGRITY_STRIKES)
    eject0 = _ctr(events.CTR_FLEET_EJECTIONS)
    try:
        x = np.ones((2, 6, 6, 1), np.float32)
        want = oracle(x)
        corrupt_served = 0
        for _ in range(20):
            r = router.request("m", x, timeout=30)
            assert "probs" in r, r
            if np.abs(np.asarray(r["probs"], np.float32)
                      - want).max() >= 1e-4:
                corrupt_served += 1
        assert corrupt_served == 0, \
            f"{corrupt_served} corrupt answers reached a client"
        assert _ctr(events.CTR_FLEET_INTEGRITY_STRIKES) > strikes0
        assert _ctr(events.CTR_FLEET_EJECTIONS) == eject0 + 1
        st = router.sentinel.status(router.replicas[0])
        assert st["state"] in ("ejected", "probing"), st
        assert st["reinstatements"] == 0, st
        ev = assert_journal_event(events.EV_FLEET_REPLICA_EJECTED)
        assert ev["replica"] == 0, ev
        return {"corrupt_served": 0,
                "integrity_strikes":
                    _ctr(events.CTR_FLEET_INTEGRITY_STRIKES)
                    - strikes0,
                "journal_event": events.EV_FLEET_REPLICA_EJECTED}
    finally:
        router.close(kill=True)


# -- Evergreen drills (online learning) --------------------------------

def _online_hive(d, fault_env, margin="5.0"):
    """A REAL --serve-models --online hive over the tiny fleet
    package, with the learner's knobs tightened for drill speed."""
    from veles_tpu.serve.client import HiveClient
    pkg, oracle = _fleet_pkg(d)
    mdir = os.path.join(d, "metrics")
    WITNESS_DIRS.append(mdir)
    env = {
        "VELES_ONLINE_MICRO_BATCH": "8",
        "VELES_ONLINE_MIN_STEPS": "4",
        "VELES_ONLINE_LR_SCALE": "1.0",
        "VELES_ONLINE_PROMOTE_MARGIN": margin,
        "VELES_ONLINE_HOLDOUT_EVERY": "6",
        "VELES_ONLINE_IDLE_MS": "1",
        "VELES_FAULTS": fault_env,
    }
    client = HiveClient({"m": pkg}, backend="cpu", max_batch=8,
                        max_wait_ms=2, online=True, metrics_dir=mdir,
                        env=env, cwd=REPO)
    return client, oracle, mdir


def _online_rows():
    """The packaged model's own training rows + labels (regenerated —
    synthetic_classification is seed-deterministic)."""
    from veles_tpu.datasets import synthetic_classification
    train, _valid, _ = synthetic_classification(
        64, 16, (6, 6, 1), n_classes=3, seed=5)
    return train


def drill_online__poison_batch():
    """Corrupted tapped labels (the training slot only — the held-out
    slice stays honest, as a trusted-slice deployment would keep it)
    must be CAUGHT BY THE GATE: with clean traffic the incumbent is
    near-perfect on the held-out slice, the garbage-trained shadow
    cannot beat it, and nothing is ever promoted."""
    d = tempfile.mkdtemp(prefix="chaos_online_poison_")
    client, oracle, mdir = _online_hive(
        d, "online.poison_batch@slot=train&times=*")
    try:
        xs, _ys = _online_rows()
        deadline = time.monotonic() + 90
        row = None
        i = 0
        while time.monotonic() < deadline:
            for _ in range(8):
                x = xs[i % len(xs)][None]
                i += 1
                # CLEAN labels: the ensemble's own answer — the
                # incumbent cannot be beaten on this distribution
                lab = [int(np.argmax(oracle(x), axis=-1)[0])]
                r = client.wait_for(
                    client.submit("m", x, label=lab), timeout=60)
                assert "error" not in r, r
            row = client.learn().get("m")
            if row and row["steps"] >= 12 and \
                    row["shadow_error_pct"] is not None:
                break
            time.sleep(0.05)
        assert row and row["steps"] >= 12, row
        assert row["shadow_error_pct"] is not None, row
        assert row["promotions"] == 0, \
            f"poisoned training labels were PROMOTED: {row}"
        gates = journal_events_from_dir(mdir, events.EV_ONLINE_GATE)
        assert gates, "no online.gate round in the journal"
        assert all(g["verdict"] != "promote" for g in gates), gates
        return {"steps": row["steps"],
                "shadow_error_pct": row["shadow_error_pct"],
                "incumbent_error_pct": row["incumbent_error_pct"],
                "promotions": 0,
                "journal_event": events.EV_ONLINE_GATE}
    finally:
        client.close()


def drill_online__swap_mid_request():
    """Promotion races live dispatches (the injected stall widens the
    swap window to 0.5s while a closed loop hammers the model): every
    answer over the whole drill must equal the frozen-package oracle
    or the ONE post-promotion answer — a third distinct payload would
    be torn params."""
    d = tempfile.mkdtemp(prefix="chaos_online_swap_")
    client, oracle, mdir = _online_hive(
        d, "online.swap_mid_request@model=m&seconds=0.5")
    try:
        xs, ys = _online_rows()
        probe = xs[:2]
        want_old = oracle(probe)
        answers = []
        deadline = time.monotonic() + 90
        i = 0
        promoted = False
        while time.monotonic() < deadline:
            for _ in range(6):
                j = i % len(xs)
                i += 1
                # drifted truth: the frozen model is consistently
                # wrong, so the gate has something real to promote
                lab = [int((ys[j] + 1) % 3)]
                r = client.wait_for(
                    client.submit("m", xs[j][None], label=lab),
                    timeout=60)
                assert "error" not in r, r
            r = client.request("m", probe, timeout=60)
            assert "probs" in r, r
            answers.append(np.asarray(r["probs"], np.float32))
            row = client.learn().get("m")
            if row and row["promotions"] >= 1:
                promoted = True
                break
            time.sleep(0.05)
        assert promoted, "promotion never fired under the stall"
        # settle: the post-swap serving answer
        want_new = np.asarray(
            client.request("m", probe, timeout=60)["probs"],
            np.float32)
        assert np.abs(want_new - want_old).max() >= 1e-4, \
            "promotion did not change the served params"
        torn = [a for a in answers
                if np.abs(a - want_old).max() >= 1e-4
                and np.abs(a - want_new).max() >= 1e-4]
        assert not torn, f"{len(torn)} torn answer(s) mid-swap"
        promos = journal_events_from_dir(mdir,
                                         events.EV_ONLINE_PROMOTED)
        assert promos and promos[-1]["model"] == "m", promos
        row = client.learn()["m"]
        return {"answers_checked": len(answers), "torn": 0,
                "time_to_serve_ms": row.get("time_to_serve_ms"),
                "journal_event": events.EV_ONLINE_PROMOTED}
    finally:
        client.close()


def drill_fleet__replica_flap():
    """The Gauntlet's pathological member: replica 0 SIGKILLs itself
    shortly after EVERY hello (``times=*`` — the respawn inherits the
    arming and flaps again, forever).  The respawn backoff and the
    scale controller's cooldown must COMPOSE: the monitor's
    exponential backoff bounds the spawn rate (backoffs grow, never a
    spawn hot-loop), the healthy peer answers every request with zero
    loss, and the autoscaler — watching the least-loaded HEALTHY
    pressure — takes no scale action at all (a flapping member is a
    health problem, not a capacity signal)."""
    from veles_tpu.serve.autoscale import (FleetAutoscaler,
                                           ScaleController)
    d = tempfile.mkdtemp(prefix="chaos_flap_")
    mdir = os.path.join(d, "metrics")
    router, oracle = _gray_fleet(
        "fleet.replica_flap@times=*&after=0.6", d,
        respawn_backoff=0.4, heartbeat_every=0.2,
        heartbeat_deadline=2.0)
    scaler = FleetAutoscaler(
        router,
        controller=ScaleController(
            min_replicas=2, max_replicas=3, up_ms=400.0,
            down_ms=10.0, up_sustain_s=1.0, down_sustain_s=2.0,
            cooldown_s=3.0),
        interval_s=0.2)
    try:
        x = np.ones((1, 6, 6, 1), np.float32)
        want = oracle(x)
        scaler.start()
        window = 12.0
        stop_at = time.monotonic() + window
        answered = 0
        while time.monotonic() < stop_at:
            r = router.request("m", x, timeout=30)
            assert "probs" in r, r
            assert np.abs(np.asarray(r["probs"], np.float32)
                          - want).max() < 1e-4
            answered += 1
            time.sleep(0.05)
        deaths = [e for e in journal_events_from_dir(
            mdir, events.EV_FLEET_REPLICA_DIED)
            if e.get("replica") == 0]
        assert len(deaths) >= 2, \
            f"replica 0 flapped only {len(deaths)}x in {window}s"
        # the backoff GROWS with consecutive deaths — no spawn storm:
        # each flap costs >= after + the current backoff, so the
        # window bounds the death count from above too
        backoffs = [e.get("backoff", 0.0) for e in deaths]
        assert backoffs == sorted(backoffs), backoffs
        assert backoffs[-1] > backoffs[0], backoffs
        assert len(deaths) <= int(window / 0.6) + 1, \
            f"{len(deaths)} deaths in {window}s is a spawn hot-loop"
        # the cooldown composes: a flapping member never reads as a
        # capacity signal, so the fleet's shape is untouched
        assert not journal_events_from_dir(
            mdir, events.EV_FLEET_SCALE_UP)
        assert not journal_events_from_dir(
            mdir, events.EV_FLEET_SCALE_DOWN)
        assert len(router.replicas) == 2
        assert answered > 0
        return {"answered": answered, "lost": 0,
                "flap_deaths": len(deaths),
                "backoff_first_s": round(backoffs[0], 2),
                "backoff_last_s": round(backoffs[-1], 2),
                "scale_actions": 0,
                "journal_event": events.EV_FLEET_REPLICA_DIED}
    finally:
        scaler.close()
        router.close(kill=True)


DRILLS = [
    drill_snapshot__torn_write,
    drill_checkpoint__corrupt,
    drill_stream__corrupt_file,
    drill_device__oom_on_put_stream,
    drill_device__oom_on_put_resident,
    drill_evaluator__hang_and_garbage,
    drill_multihost__peer_exit,
    drill_preempt__sigterm_resume,
    drill_supervisor__sigkill_ga_resume,
    drill_hive__slow_dispatch,
    drill_hive__wedge,
    drill_hive__garbage_response,
    drill_online__poison_batch,
    drill_online__swap_mid_request,
    drill_fleet__replica_flap,
]


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(prog="chaos_drill")
    p.add_argument("--json", action="store_true",
                   help="stdout carries ONLY the final JSON record")
    p.add_argument("--only", default=None,
                   help="substring filter on drill names")
    args = p.parse_args(argv)

    # every drill also verifies its fault REPORTS into the Sightline
    # journal; arm a scratch metrics dir when the caller did not
    # (child processes inherit it through $VELES_METRICS_DIR)
    from veles_tpu import telemetry
    if telemetry.metrics_dir() is None:
        telemetry.configure(tempfile.mkdtemp(prefix="chaos_metrics_"))
    log(f"journal/metrics dir: {telemetry.metrics_dir()}")

    # Lockstep pass: the whole matrix runs under the lock-order
    # witness — every child process inherits the arming, and at the
    # end every runtime-observed acquisition edge must be declared in
    # the static locking law (analysis/lock_order.json)
    os.environ.setdefault("VELES_LOCK_WITNESS", "1")

    todo = [f for f in DRILLS
            if not args.only or args.only in f.__name__]
    results = [drill(f) for f in todo]
    ok = all(r["ok"] for r in results)

    from veles_tpu.analysis import flow, witness
    observed = set(witness.observed_edges())
    for mdir in [telemetry.metrics_dir()] + WITNESS_DIRS:
        if mdir and os.path.isdir(mdir):
            observed |= set(witness.read_snapshots(mdir))
    law = flow.load_lock_order(os.path.join(
        REPO, "veles_tpu", "analysis", "lock_order.json"))
    undeclared = sorted(observed - flow.declared_edges(law or {}))
    witness_ok = law is not None and not undeclared
    if undeclared:
        log(f"LOCK WITNESS: undeclared runtime edges {undeclared} — "
            f"the static locking law has a gap")
    else:
        log(f"lock witness: {len(observed)} observed edge(s), all "
            f"declared in the locking law")
    ok = ok and witness_ok

    # Flightline pass: the flight recorder is always armed, so every
    # ejection / promotion / rollback the matrix provoked must have
    # left a flightrec-*.json dump next to its journal — a verdict
    # with no dump means the crash-proof ring is not actually wired
    # to that trigger
    import glob as _glob

    from veles_tpu import events as _events
    reason_of = {_events.EV_FLEET_REPLICA_EJECTED: "ejection",
                 _events.EV_ONLINE_PROMOTED: "promote",
                 _events.EV_ONLINE_ROLLBACK: "rollback"}
    dirs = []
    for mdir in [telemetry.metrics_dir()] + WITNESS_DIRS:
        if mdir and os.path.isdir(mdir):
            real = os.path.realpath(mdir)
            if real not in dirs:
                dirs.append(real)
    # drop dirs nested under another (the recursive walk below would
    # double count their journals and dumps)
    dirs = [d for d in dirs
            if not any(d != o and (d + os.sep).startswith(o + os.sep)
                       for o in dirs)]
    need: dict = {}
    dump_reasons: list = []
    for mdir in dirs:
        for jf in _glob.glob(os.path.join(mdir, "**",
                                          "journal-*.jsonl"),
                             recursive=True):
            try:
                with open(jf) as f:
                    for line in f:
                        try:
                            ev = json.loads(line)
                        except ValueError:
                            continue
                        r = reason_of.get(ev.get("event"))
                        if r:
                            need[r] = need.get(r, 0) + 1
            except OSError:
                continue
        for fp in _glob.glob(os.path.join(mdir, "**",
                                          "flightrec-*.json"),
                             recursive=True):
            try:
                with open(fp) as f:
                    dump_reasons.append(json.load(f).get("reason"))
            except (OSError, ValueError):
                continue
    missing = {r: n for r, n in sorted(need.items())
               if dump_reasons.count(r) < n}
    flightrec_ok = not missing
    if missing:
        log(f"FLIGHT RECORDER: events without a matching dump "
            f"{missing} (dumps on disk: {sorted(dump_reasons)})")
    else:
        log(f"flight recorder: {len(dump_reasons)} dump(s) cover "
            f"{sum(need.values())} eject/promote/rollback event(s)")
    ok = ok and flightrec_ok

    record = {
        "fault_drill_ok": ok,
        "fault_drill_journal_verified": bool(results) and all(
            r.get("journal_event") or r.get("skipped")
            for r in results),
        "lock_witness_ok": witness_ok,
        "lock_witness_edges": len(observed),
        "flight_recorder_ok": flightrec_ok,
        "flight_recorder_dumps": len(dump_reasons),
        "results": results,
    }
    print(json.dumps(record), flush=True)
    if not args.json:
        log(f"{'ALL OK' if ok else 'FAILURES'} "
            f"({sum(r['ok'] for r in results)}/{len(results)})")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
