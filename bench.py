"""Headline benchmark: ImageNet AlexNet training throughput,
images/sec/chip (BASELINE.json primary metric, config #4).

Runs the production path — StandardWorkflow's fused jitted train step
(forward + backward + SGD update in one XLA computation, batch rows
gathered from the HBM-resident dataset) — on the TPU, which it asks
for by name and fails without (the ``--X-only`` modes below pin
XLA:CPU and say so), and prints ONE JSON line per completed phase.  ``vs_baseline`` is null: the reference
published no number (BASELINE.json "published": {}, see BASELINE.md).

Reporting contract (round-3 VERDICT next #1: the round-3 run measured
a 49% MFU result and then LOST it to the driver's timeout because the
single JSON print came after every phase):

- The COMPLETE record is printed immediately after the resident
  measurement, with the not-yet-measured fields null, and re-printed
  enriched after each later phase.  The driver parses the last valid
  line, so a timeout can only truncate enrichment — never erase the
  headline.
- Phases run cheapest-information-first: resident (the headline) ->
  MNIST-conv-to-99% (seconds on chip; BASELINE's secondary metric) ->
  the real-chip test tier (tests_tpu/, in-process, counted into the
  record) -> streaming (link-bound where the host link is thin).
- The resident dataset is born ON the device
  (loader.synthetic.DeviceSyntheticLoader): round 3 spent 619.7s of
  the driver's budget generating ImageNet-scale pixels on a single
  host core and uploading them; device generation is milliseconds.
- The WHOLE streaming phase (build + compile + warmup + floors +
  windows) runs under one BENCH_STREAM_SECONDS deadline; the firing
  size is chosen from a raw link probe so measurement windows hold
  several firings (a pipelined steady state).  The primary efficiency
  is the pipeline's transfer-busy fraction — intrinsic to the window,
  because a host link can be violently non-stationary (rounds 3-5's
  remote link: 33 MB/s..1.3 GB/s across adjacent windows, earlier
  set-up, not re-measured) and any cross-window
  floor ratio measures the link's mood; put-only reference windows
  and raw per-sample times ship in the record as the cross-check.
  The host-side dataset is n_base distinct images tiled to full
  length — identical bytes moved per step, a fraction of the
  single-core generation cost.

Honesty contract (round-1 VERDICT weak #1/#2 fixes):

- The timing barrier is ``np.asarray(fused._acc)`` — the fused scan's
  donated metric carry, a data dependency of every dispatched step.
  The old evaluator-Vector fetch depended on nothing; this fetch
  cannot complete before the last step's arithmetic has, on any
  platform (``block_until_ready`` on the directly attached chip was
  re-tested in PR 21: tests_tpu TestHonestBarrier).
- Images are counted from the SAME carry: ``_acc[2]`` is the mask-sum
  of samples actually processed since reset, so superstep grouping
  (k minibatches per loader firing) and remainder padding are counted
  exactly, not estimated as steps*mb.
- The JSON line carries the analytic training FLOPs/image and the
  implied **MFU** (veles_tpu/profiling.py); a value over 100% MFU is
  impossible, so the number polices itself.  Median of ``repeats``
  timed runs, with the per-run values included for a stability check.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

SUPERSTEP = int(os.environ.get("BENCH_SUPERSTEP", "8"))
#: wall-clock cap for the WHOLE streaming phase — build + compile +
#: warmup + floor puts + measurement windows, everything (round-4
#: VERDICT weak #1: the old 75s "cap" bounded only the windows while
#: the phase consumed 23 minutes of driver budget), seconds
STREAM_SECONDS = float(os.environ.get("BENCH_STREAM_SECONDS", "240"))
#: wall-clock cap for the MNIST-conv-to-99% run, seconds
SECONDARY_SECONDS = float(os.environ.get("BENCH_SECONDARY_SECONDS",
                                         "240"))
#: the streaming instrument's own configuration: firings must be cheap
#: enough that a measurement window holds several even on a slow
#: link, so the double-buffer + prefetch overlap is actually
#: exercised (round-4 VERDICT next #1: one 128s firing per window
#: measured the pipeline serialized).  The superstep is chosen at run
#: time from a raw link probe so one firing costs ~TARGET_FIRING_SEC
#: of link time.
STREAM_MB = int(os.environ.get("BENCH_STREAM_MB", "128"))
TARGET_FIRING_SEC = 4.0
MIN_WINDOW_FIRINGS = 3


def build(mb, n_train, image, n_classes, streaming=False,
          superstep=None, quantized=False):
    from veles_tpu import prng
    from veles_tpu.loader.synthetic import DeviceSyntheticLoader
    from veles_tpu.models.alexnet import alexnet_layers
    from veles_tpu.ops.standard_workflow import StandardWorkflow

    prng.seed_all(1234)
    if streaming:
        def loader_factory(wf, _q=quantized):
            cls = _tiled_loader_class()
            kw = {}
            if _q:
                # uint8 wire: bytes are re-encoded pixels, the linear
                # normalizer maps them back to ~[0, 1] on device
                kw = {"normalization_type": "linear",
                      "normalization_parameters": {"lo": 0.0,
                                                   "hi": 1.0}}
            ld = cls(wf, name="loader", minibatch_size=mb,
                     n_train=n_train, n_valid=0, shape=image,
                     n_classes=n_classes, seed=227227,
                     max_resident_bytes=0, **kw)
            ld.quantized = _q
            return ld
    else:
        # resident: the dataset is generated in HBM by the device
        loader_factory = lambda wf: DeviceSyntheticLoader(  # noqa: E731
            wf, name="loader", minibatch_size=mb, n_train=n_train,
            n_valid=0, shape=image, n_classes=n_classes, seed=227227)
    w = StandardWorkflow(
        loader_factory=loader_factory,
        layers=alexnet_layers(n_classes),
        loss_function="softmax",
        decision_config={"max_epochs": 10 ** 9},
        superstep=SUPERSTEP if superstep is None else superstep,
        name="AlexNetBench")
    w.evaluator.compute_confusion = False
    return w


import functools


@functools.lru_cache(maxsize=1)
def _tiled_loader_class():
    """Streaming-bench host dataset loader: N_BASE distinct synthetic
    images tiled out to n_train rows.  The streaming measurement times
    host assembly + transfer + compute — bytes moved per step are what
    matter, and tiled rows move exactly the same bytes as distinct
    rows while skipping minutes of single-core generation (this host:
    1 core).  Class built lazily so importing bench.py stays free of
    framework imports."""
    from veles_tpu import datasets
    from veles_tpu.loader.synthetic import SyntheticClassificationLoader

    class TiledSyntheticLoader(SyntheticClassificationLoader):
        N_BASE = 512
        #: True = store the tiled pixels as uint8 (the quantized-wire
        #: streaming phase): 1 byte/pixel on the link, dequantized by
        #: the fused step's on-device prologue
        quantized = False

        def load_data(self) -> None:
            a = self.gen_args
            n_base = min(self.N_BASE, a["n_train"])
            (bx, by), _, _ = datasets.synthetic_classification(
                n_base, 0, a["shape"], n_classes=a["n_classes"],
                noise=a["noise"], max_shift=a["max_shift"],
                seed=a["seed"])
            n = a["n_train"]
            reps = -(-n // n_base)
            self.class_lengths[:] = [0, 0, n]
            if self.quantized:
                bx = np.round(np.clip(np.asarray(bx), 0.0, 1.0)
                              * 255.0).astype(np.uint8)
            self.original_data.mem = np.tile(
                bx, (reps,) + (1,) * (bx.ndim - 1))[:n]
            self.original_labels.mem = np.tile(by, reps)[:n].astype(
                np.int32)

    return TiledSyntheticLoader


def sync_images(fused) -> float:
    """Force a device->host fetch of the step-dependent metric carry
    (the honest barrier) and return the cumulative processed-sample
    count.  The count comes from the host-side float64
    ``processed_images`` counter, not the float32 on-device carry,
    which silently loses integer precision past 2^24 images."""
    np.asarray(fused._acc)  # data-dependent sync barrier only
    return float(fused.processed_images)


def secondary_metric(max_seconds=SECONDARY_SECONDS):
    """BASELINE's secondary metric — MNIST-conv wall-clock seconds to
    99% validation accuracy — measured on real MNIST IDX files.  This
    image ships none (no network), so the deterministic synthetic
    stand-in is materialized AS IDX files first (idempotent; genuine
    pre-placed files are left untouched — datasets.generate_mnist_idx),
    and the whole real-file path (IDX parse -> loader -> fused train)
    is what gets timed.  Capped at ``max_seconds`` wall-clock and 40
    epochs; returns None (with a stderr reason) when the cap is hit."""
    if os.environ.get("BENCH_SKIP_SECONDARY"):
        return None  # sweep/profiling runs re-measure only the primary
    from veles_tpu import datasets, prng
    if datasets.try_load_real_mnist() is None:
        try:
            datasets.generate_mnist_idx()
        except FileExistsError as e:
            print(f"secondary metric skipped: {e}", file=sys.stderr)
            return None
    if datasets.try_load_real_mnist() is None:
        return None  # unreachable unless the data dir is unwritable
    from veles_tpu.backends import make_device
    from veles_tpu.models import mnist7

    class _FL:
        workflow = None

    prng.seed_all(1234)
    w = mnist7.create_workflow(_FL(), decision={"max_epochs": 40})
    w.initialize(device=make_device("tpu"))
    orig_run = w.decision.run
    t0 = time.perf_counter()
    deadline = t0 + max_seconds

    def run_with_target():
        orig_run()
        hist = [h for h in w.decision.history
                if h["class"] == "validation"]
        if hist and hist[-1]["error_pct"] <= 1.0:
            w.decision.complete.set(True)
        elif time.perf_counter() > deadline:
            print(f"secondary metric capped at {max_seconds}s before "
                  f"reaching 99% (best so far: "
                  f"{min(h['error_pct'] for h in hist) if hist else '?'}"
                  f"% err)", file=sys.stderr)
            w.decision.complete.set(True)
    w.decision.run = run_with_target
    w.run()
    dt = time.perf_counter() - t0
    hist = [h for h in w.decision.history if h["class"] == "validation"]
    reached = bool(hist) and hist[-1]["error_pct"] <= 1.0
    w.stop()
    return round(dt, 2) if reached else None


def measure_rate(w, firings, repeats, warmup=3):
    """Median images/sec over ``repeats`` timed windows, bracketed by
    the data-dependent metric-carry sync (the resident-path
    instrument; the streaming phase has its own paired-window loop in
    streaming_metric)."""
    loader, fused = w.loader, w.fused

    def fire():
        loader.run()
        fused.run()

    for _ in range(warmup):
        fire()
    sync_images(fused)
    rates = []
    for _ in range(repeats):
        images0 = sync_images(fused)
        t0 = time.perf_counter()
        for _ in range(firings):
            fire()
        images1 = sync_images(fused)          # the honest barrier
        dt = time.perf_counter() - t0
        rates.append((images1 - images0) / dt)
    return float(np.median(rates)), rates


def run_tpu_tests():
    """Run the real-chip test tier (tests_tpu/) IN-PROCESS and return
    (passed, failed) for the bench record — the driver-visible proof
    the tier ran on the chip (round-4 VERDICT next #2; the tier was
    green every round but only judge-run, never on the record).

    In-process (pytest.main with a counting plugin) rather than a
    subprocess: the bench already owns the chip's jax client, and a
    chip belongs to one process at a time.  For the same reason the
    one module that must START a chip-owning child from a process
    that holds no backend (tests_tpu/test_0_ga_parent.py) is left
    out here; `python -m pytest tests_tpu/` runs it.  Runs AFTER the
    headline is emitted, so a failure here can only cost these two
    fields.  (None, None) = skipped."""
    if os.environ.get("BENCH_SKIP_TPU_TESTS"):
        return None, None
    try:
        import pytest

        class Counter:
            """Counts unique TESTS, not reports: a test emits up to
            three reports (setup/call/teardown) and a call failure
            plus a teardown error must still count as ONE failure."""

            def __init__(self):
                self._passed = set()
                self._failed = set()

            def pytest_runtest_logreport(self, report):
                if report.failed:
                    self._failed.add(report.nodeid)
                elif report.when == "call" and report.passed:
                    self._passed.add(report.nodeid)

            @property
            def passed(self):
                return len(self._passed - self._failed)

            @property
            def failed(self):
                return len(self._failed)

        counter = Counter()
        here = os.path.dirname(os.path.abspath(__file__))
        import contextlib
        # stdout carries ONLY the JSON record (the driver parses it
        # line-wise) — pytest's progress/summary must go to stderr
        with contextlib.redirect_stdout(sys.stderr):
            rc = pytest.main(
                ["-q", "--tb=line", "-p", "no:cacheprovider",
                 "--ignore", os.path.join(here, "tests_tpu",
                                          "test_0_ga_parent.py"),
                 os.path.join(here, "tests_tpu")],
                plugins=[counter])
        print(f"tests_tpu: {counter.passed} passed, "
              f"{counter.failed} failed (pytest rc={rc})",
              file=sys.stderr)
        if rc not in (0, 1) or (counter.passed == 0
                                and counter.failed == 0):
            # collection/usage error, or nothing ran to completion
            # (e.g. the tier auto-skipped on a CPU-only run): a tier
            # that never RAN must not read as "ran clean"
            return None, None
        return counter.passed, counter.failed
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"tests_tpu tier failed to run: {e}", file=sys.stderr)
        return None, None


def multichip_dryrun_record():
    """Run the CPU-pinned multichip dryrun in a SUBPROCESS and record
    whether it passed (round-5 VERDICT next #7): the bench record then
    carries its own multichip verdict, so a driver-side failure in
    MULTICHIP_r*.json is distinguishable from a framework one.  A
    subprocess because this process's jax client belongs to the chip;
    the child pins JAX_PLATFORMS=cpu before its first jax import
    (__graft_entry__.dryrun_multichip does the pinning itself — the
    env here is belt-and-suspenders)."""
    if os.environ.get("BENCH_SKIP_DRYRUN"):
        return None
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    try:
        res = subprocess.run(
            [sys.executable, os.path.join(here, "__graft_entry__.py"),
             "2"], env=env, capture_output=True, text=True,
            timeout=600)
        ok = res.returncode == 0
        if not ok:
            print(f"multichip dryrun failed (rc={res.returncode}): "
                  f"{res.stderr[-1500:]}", file=sys.stderr)
        return ok
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"multichip dryrun did not run: {e}", file=sys.stderr)
        return False


def fault_drill_metric(phase):
    """Run the Faultline chaos drill (scripts/chaos_drill.py) as a
    recorded phase: the full fault matrix — evaluator hang + garbage
    line, torn snapshot, corrupt GA checkpoint, corrupt stream files,
    device OOM, multihost peer death, SIGTERM preemption -> graceful
    stop -> supervisor resume, SIGKILLed GA -> checkpoint resume —
    injected on CPU and recovered from, with per-fault recovery
    seconds.  Robustness gets a measured
    trajectory in BENCH_r* exactly like performance does.  A
    subprocess (CPU-pinned) because this process's jax client belongs
    to the chip."""
    if os.environ.get("BENCH_SKIP_FAULT_DRILL"):
        return None
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "chaos_drill.py"),
             "--json"],
            env=env, capture_output=True, text=True, timeout=900)
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        results = rec["results"]
        out = {
            "fault_drill_ok": bool(rec["fault_drill_ok"]),
            "fault_drill_recovery_sec": {
                r["fault"]: r["recovery_sec"] for r in results},
            "fault_drill_failures": [
                r["fault"] for r in results if not r["ok"]] or None,
            # every injected fault must also leave its expected event
            # in the Sightline journal — detection AND reporting
            "fault_drill_journal_verified": rec.get(
                "fault_drill_journal_verified"),
        }
        for r in results:
            if r["fault"] == "evaluator.hang_and_garbage" and r["ok"]:
                out["fault_drill_hang_detect_sec"] = \
                    r.get("hang_detect_sec")
            # Phoenix resume fields: SIGTERM -> final snapshot inside
            # the grace deadline -> supervisor auto-resume, trajectory
            # f32-exact vs the uninterrupted oracle (plus the GA
            # SIGKILL drill's downtime) — robustness of RESUME gets a
            # measured trajectory in BENCH_r*, like recovery did
            if r["fault"] == "preempt.sigterm_resume" and r["ok"]:
                out["preempt_snapshot_sec"] = \
                    r.get("preempt_snapshot_sec")
                out["resume_downtime_sec"] = \
                    r.get("resume_downtime_sec")
                out["resume_trajectory_match"] = \
                    r.get("trajectory_match")
        phase(f"fault drill: ok={out['fault_drill_ok']} "
              f"{out['fault_drill_recovery_sec']}")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"fault drill failed to run: {e}", file=sys.stderr)
        return None


def lint_metric(phase):
    """Full-repo veleslint scan (veles_tpu/analysis) as a recorded
    phase: BENCH_r06+ carries the static-analysis record next to the
    fault drill — zero new findings is an invariant with a measured
    trajectory, exactly like recovery and performance."""
    try:
        from veles_tpu.analysis import repo_scan, repo_root
        from veles_tpu.analysis import flow
        new, baseline = repo_scan()
        if new:
            for f in new[:20]:
                print(f"veleslint: {f.format()}", file=sys.stderr)
        by_rule = {}
        for f in new:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
        law = flow.load_lock_order(os.path.join(
            repo_root(), "veles_tpu", "analysis",
            "lock_order.json")) or {}
        phase(f"veleslint: {len(new)} new finding(s), "
              f"{len(baseline)} baselined; locking law "
              f"{len(law.get('nodes', []))} locks / "
              f"{len(law.get('edges', []))} edges")
        return {"lint_findings_new": len(new),
                "lint_findings_new_by_rule": by_rule,
                "lint_baseline_count": len(baseline),
                "lock_order_nodes": len(law.get("nodes", [])),
                "lock_order_edges": len(law.get("edges", []))
                + len(law.get("manual_edges", []))}
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"veleslint did not run: {e}", file=sys.stderr)
        return None


def ensemble_metric(device, phase):
    """Device-resident ensemble inference (ISSUE 3 tentpole): an
    N-member AlexNet-scale ensemble served as ONE vmapped jitted
    dispatch per batch (ops/fused.py EnsembleEvalEngine) vs the host
    numpy member-loop oracle it replaced.  The headline unit is
    member-images/sec (members x images/sec): the engine runs N
    forward passes per dispatch, so the fair cross-engine rate is
    per member-inference.  The host oracle is timed on a slice (its
    per-member-image cost is batch-linear; AlexNet on one host core
    is seconds/image, which is the point) and both are quoted as
    rates.  (None, None)-style null fields when skipped."""
    if os.environ.get("BENCH_SKIP_ENSEMBLE"):
        return None
    n_members = int(os.environ.get("BENCH_ENSEMBLE_MEMBERS", "4"))
    mb = int(os.environ.get("BENCH_ENSEMBLE_MB", "64"))
    host_images = int(os.environ.get("BENCH_ENSEMBLE_HOST_IMAGES",
                                     "2"))
    dispatches = int(os.environ.get("BENCH_ENSEMBLE_DISPATCHES", "8"))
    try:
        from veles_tpu import prng
        from veles_tpu.backends import NumpyDevice
        from veles_tpu.loader.synthetic import \
            SyntheticClassificationLoader
        from veles_tpu.models.alexnet import alexnet_layers
        from veles_tpu.ops.fused import EnsembleEvalEngine
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        phase(f"ensemble: building AlexNet template "
              f"({n_members} members)")
        prng.seed_all(1234)
        w = StandardWorkflow(
            loader_factory=lambda wf: SyntheticClassificationLoader(
                wf, name="loader", minibatch_size=8, n_train=8,
                n_valid=0, shape=(227, 227, 3), n_classes=1000,
                seed=227227),
            layers=alexnet_layers(1000), loss_function="softmax",
            decision_config={"max_epochs": 1}, name="EnsembleBench")
        w.initialize(device=NumpyDevice())   # host init: shapes+params
        forwards = list(w.forwards)
        base = {f.name: {k: np.asarray(v) for k, v in
                         f.gather_params().items()} for f in forwards}
        rng = np.random.default_rng(7)
        members = [
            {fn: {pn: (a + rng.standard_normal(a.shape)
                       .astype(np.float32) * 0.01)
                  for pn, a in d.items()} for fn, d in base.items()}
            for _ in range(n_members)]
        x = rng.standard_normal((mb, 227, 227, 3)).astype(np.float32)

        engine = EnsembleEvalEngine(forwards, members, device)
        # the RESIDENT variant is the measured one: pixels upload once
        # (attach_dataset) and each dispatch ships only indices up and
        # the averaged (mb, 1000) probs down — over a thin link a
        # per-dispatch pixel upload would measure the link, not the
        # engine (the streaming variant is what --ensemble-test uses
        # and is parity-tested; its wire cost is the loader's story)
        engine.attach_dataset(x)
        phase("ensemble: compiling the vmapped member-stacked step")
        idx = np.arange(mb, dtype=np.int32)
        engine.predict_proba_resident(idx)   # compile + warmup
        t0 = time.perf_counter()
        for _ in range(dispatches):
            p = engine.predict_proba_resident(idx)  # fetch IS the sync
        dt = time.perf_counter() - t0
        assert np.isfinite(p).all()
        dev_rate = dispatches * mb / dt
        engine.release()

        phase(f"ensemble: device {dev_rate:.1f} img/s x {n_members} "
              f"members; timing host oracle ({host_images} images)")
        xs = x[:host_images]
        t0 = time.perf_counter()
        acc = None
        for m in members:                    # the predictor's oracle
            out = xs                         # loop, verbatim shape
            for f in forwards:
                out, _ = f.apply_fwd(
                    {k: np.asarray(v) for k, v in m[f.name].items()},
                    out, rng=None, train=False)
            out = np.asarray(out)
            acc = out if acc is None else acc + out
        host_dt = time.perf_counter() - t0
        host_rate = host_images * n_members / host_dt
        return {
            "ensemble_members": n_members,
            "ensemble_minibatch": mb,
            "ensemble_device_images_per_sec": round(dev_rate, 2),
            "ensemble_device_member_images_per_sec": round(
                dev_rate * n_members, 2),
            "ensemble_host_member_images_per_sec": round(
                host_rate, 4),
            "ensemble_speedup_vs_host": round(
                dev_rate * n_members / host_rate, 1),
        }
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"ensemble metric failed: {e}", file=sys.stderr)
        return None


def ga_metric(phase):
    """Population-batched GA training (ISSUE 4 acceptance): the SAME
    float-tune population evaluated through the chip-owning serve
    evaluator per-genome (the PR-3 path) and as ONE vmapped cohort
    (PopulationTrainEngine), reported as genomes/sec each.  The
    evaluator child is asked for ``-b cpu``: every caller of this
    phase either holds the chip itself (the headline run) or pins
    XLA:CPU, so the CPU is what a child can get — said once on the
    phase line, and ``ga_eval_platform`` carries it.  Fitness parity
    between the two paths is asserted, not assumed."""
    if os.environ.get("BENCH_SKIP_GA"):
        return None
    import tempfile
    import textwrap

    from veles_tpu.genetics.pool import ChipEvaluatorPool

    n = int(os.environ.get("BENCH_GA_POPULATION", "8"))
    try:
        tmp = tempfile.mkdtemp(prefix="bench_ga_")
        wf = os.path.join(tmp, "wf.py")
        with open(wf, "w") as f:
            f.write(textwrap.dedent("""
                from veles_tpu.models import wine

                def create_workflow(launcher):
                    return wine.create_workflow(launcher)

                def run(launcher):
                    launcher.create_workflow(create_workflow)
                    launcher.initialize()
                    launcher.run()
            """))
        cfg = os.path.join(tmp, "cfg.py")
        with open(cfg, "w") as f:
            f.write(textwrap.dedent("""
                from veles_tpu.config import root
                from veles_tpu.genetics import Tune

                root.wine.decision = {"max_epochs": 4}
                root.wine.layers = [
                    {"type": "all2all_tanh",
                     "->": {"output_sample_shape": 8},
                     "<-": {"learning_rate": Tune(0.3, 0.01, 1.0)}},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 3},
                     "<-": {"learning_rate": 0.3}},
                ]
            """))
        lr_path = "wine.layers[0]['<-']['learning_rate']"
        values = [{lr_path: round(0.05 + 0.9 * i / max(n - 1, 1), 4)}
                  for i in range(n)]
        pool = ChipEvaluatorPool(
            [sys.executable, "-m", "veles_tpu.genetics.worker",
             "--serve", wf, cfg, "-b", "cpu", "-s", "1234"],
            workers=2, timeout=600)
        with pool:
            phase(f"ga: serve evaluator asked for -b cpu (a chip "
                  f"this process holds admits no child), up on "
                  f"{pool.platform}; {n} genomes per-genome (the "
                  f"PR-3 serial path)")
            t0 = time.perf_counter()
            serial = pool.evaluate_many(values)
            t_serial = time.perf_counter() - t0
            phase(f"ga: serial {n / t_serial:.2f} genomes/s; same "
                  f"population as ONE cohort")
            t0 = time.perf_counter()
            batched = pool.evaluate_cohort(values)
            t_batched = time.perf_counter() - t0
        max_diff = float(np.max(np.abs(np.asarray(serial)
                                       - np.asarray(batched))))
        phase(f"ga: batched {n / t_batched:.2f} genomes/s "
              f"(max fitness diff vs serial: {max_diff})")
        # supervision fields come off the Sightline registry snapshot
        # (the pool feeds ga.* counters), not per-object attributes
        from veles_tpu import telemetry
        snap = telemetry.snapshot()["counters"]
        return {
            "ga_hangs_detected": int(snap.get("ga.hangs_detected", 0)),
            "ga_evaluator_restarts": int(snap.get(
                "ga.evaluator_restarts", 0)),
            "ga_population": n,
            "ga_cohort_size": n,
            "ga_eval_platform": pool.platform,
            "ga_genomes_per_sec_serial": round(n / t_serial, 3),
            "ga_genomes_per_sec_batched": round(n / t_batched, 3),
            "ga_cohort_speedup": round(t_serial / t_batched, 2),
            "ga_fitness_max_abs_diff": max_diff,
        }
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"ga metric failed: {e}", file=sys.stderr)
        return None


_HANDOFF_WF = """
from veles_tpu.models import wine

def create_workflow(launcher):
    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
         "<-": {"learning_rate": 0.3, "weight_decay": 0.001,
                "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 3},
         "<-": {"learning_rate": 0.3, "gradient_moment": 0.9}},
    ]
    return wine.create_workflow(
        launcher, layers=layers,
        decision={"max_epochs": 4, "fail_iterations": 1})
"""


def _handoff_wine(lr=0.3):
    """One wine fused workflow on XLA:CPU — the cohort substrate the
    GA handoff phase trains (the test_ga_cohort recipe)."""
    from veles_tpu import prng
    from veles_tpu.backends import JaxDevice
    from veles_tpu.models import wine

    class FL:
        workflow = None

    prng._streams.clear()
    prng.seed_all(1234)
    layers = [
        {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
         "<-": {"learning_rate": lr, "weight_decay": 0.001,
                "gradient_moment": 0.9}},
        {"type": "softmax", "->": {"output_sample_shape": 3},
         "<-": {"learning_rate": lr, "gradient_moment": 0.9}},
    ]
    w = wine.create_workflow(
        FL(), layers=layers,
        decision={"max_epochs": 4, "fail_iterations": 1})
    w.initialize(device=JaxDevice(platform="cpu"))
    return w


def handoff_metric(phase):
    """GA→serving handoff (ISSUE 18 acceptance, payoff b): time from
    the last generation's fitness landing to the FIRST served
    response.

    - **HBM path** (genetics/handoff.py): the serving scaffold — a
      registered model with a compiled+warmed engine — is pre-built
      from the cohort's init params OFF the critical path; the handoff
      itself is one jitted member-axis gather of the top-K trained
      members out of the cohort stack plus ``swap_params``.  Nothing
      touches the host (np.savez/save are tripwired during the
      window).
    - **Reload oracle** (the path it replaces): fetch the winners to
      host, write the members npz, pack a Forge package, spawn a
      fresh hive process, first answered request — the
      online_metric ``npz_roundtrip`` recipe applied to the GA.

    Both clocks start at the same event (fitness available, cohort
    stack still live).  Bitwise equality of the served stacked rows
    against the trained cohort rows is asserted, not assumed."""
    if os.environ.get("BENCH_SKIP_HANDOFF"):
        return None
    import tempfile

    client = None
    try:
        from veles_tpu.ensemble.packaging import pack_ensemble
        from veles_tpu.genetics.handoff import GAServingHandoff
        from veles_tpu.ops.fused import PopulationTrainEngine
        from veles_tpu.serve.client import HiveClient
        from veles_tpu.serve.residency import ResidencyManager

        n = int(os.environ.get("BENCH_HANDOFF_POPULATION", "8"))
        k = int(os.environ.get("BENCH_HANDOFF_TOPK", "3"))
        lrs = [round(0.05 + 0.9 * i / max(n - 1, 1), 4)
               for i in range(n)]

        phase(f"handoff: training a {n}-member wine cohort "
              f"(XLA:CPU), pre-building the K={k} serving scaffold")
        w = _handoff_wine()
        rates = np.asarray([[[lr, lr], [lr, lr]] for lr in lrs],
                           np.float32)
        decays = np.asarray([[[0.001, 0.0], [0.0, 0.0]]] * n,
                            np.float32)
        engine = PopulationTrainEngine(w, rates, decays)
        sample_shape = tuple(np.asarray(
            w.loader.original_data.map_read()).shape[1:])
        forward_names = [f.name for f in w.fused.forwards]
        init_members = [
            {fn: {pk: np.asarray(arr[i]) for pk, arr in d.items()}
             for fn, d in engine._params.items()}
            for i in range(k)]
        mgr = ResidencyManager(w.fused.device,
                               budget_bytes=512 << 20)
        t0 = time.perf_counter()
        ho = GAServingHandoff(mgr, "winner", w.fused.forwards,
                              init_members,
                              sample_shape=sample_shape)
        # the gather compile also overlaps training: prewarm against
        # the live (still-init) cohort stack
        ho.prewarm(engine)
        prebuild_sec = time.perf_counter() - t0

        t0 = time.perf_counter()
        fits = np.asarray(engine.run())
        train_sec = time.perf_counter() - t0
        idx = ho.top_k(fits)
        x = np.asarray(w.loader.original_data.map_read()[:4],
                       np.float32)

        # -- the HBM path, np.savez/save tripwired ------------------
        phase(f"handoff: HBM adopt of members {idx.tolist()} + "
              f"first served request")
        tripped = []
        saved = {fn: getattr(np, fn)
                 for fn in ("savez", "savez_compressed", "save")}
        for fn in saved:
            setattr(np, fn,
                    lambda *a, _n=fn, **kw: tripped.append(_n))
        try:
            t0 = time.perf_counter()
            serve_engine = ho.adopt_cohort(engine, fits)
            out = np.asarray(serve_engine.submit(x).result())
            hbm_ms = 1000.0 * (time.perf_counter() - t0)
        finally:
            for fn, f in saved.items():
                setattr(np, fn, f)
        assert out.shape[0] == 4 and np.all(np.isfinite(out))
        bitwise = True
        for fn, d in serve_engine.stacked_params.items():
            for pk, arr in d.items():
                want = np.asarray(engine._params[fn][pk])[idx]
                bitwise &= bool(np.array_equal(
                    np.asarray(arr)[:k], want))

        # -- the reload oracle --------------------------------------
        phase("handoff: reload oracle (host fetch -> npz -> Forge "
              "pack -> fresh hive -> first answer)")
        tmp = tempfile.mkdtemp(prefix="bench_handoff_")
        wf_path = os.path.join(tmp, "handoff_wf.py")
        with open(wf_path, "w") as f:
            f.write(_HANDOFF_WF)
        t0 = time.perf_counter()
        members = []
        for i in idx:
            members.append({
                "seed": 1234, "valid_error": float(fits[i]),
                "forward_names": forward_names,
                "values": {"lr": lrs[int(i)]},
                "params": {fn: {pk: np.asarray(arr[int(i)])
                                for pk, arr in d.items()}
                           for fn, d in engine._params.items()}})
        pkg = pack_ensemble(os.path.join(tmp, "winner.forge.tgz"),
                            "winner", members, wf_path)
        client = HiveClient(
            {"m": pkg}, backend="cpu", max_batch=mgr.max_batch,
            max_wait_ms=1000.0 * mgr.max_wait_s,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        assert "probs" in client.request("m", x[:1], timeout=120)
        reload_sec = time.perf_counter() - t0

        phase(f"handoff: HBM {hbm_ms:.1f}ms vs reload "
              f"{reload_sec:.2f}s "
              f"({reload_sec / (hbm_ms / 1000.0):.0f}x)")
        engine.release()
        mgr.close()
        w.stop()
        return {
            "ga_handoff_members": n,
            "ga_handoff_topk": k,
            "ga_handoff_train_sec": round(train_sec, 2),
            "ga_handoff_prebuild_sec": round(prebuild_sec, 2),
            "ga_handoff_hbm_ms": round(hbm_ms, 2),
            "ga_handoff_reload_sec": round(reload_sec, 2),
            "ga_handoff_speedup_x": round(
                reload_sec / (hbm_ms / 1000.0), 1),
            "ga_handoff_bitwise_equal": bitwise,
            "ga_handoff_npz_free": not tripped,
            "ga_handoff_platform": "cpu",
        }
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"handoff metric failed: {e}", file=sys.stderr)
        return None
    finally:
        if client is not None:
            client.close()


def cohort_streaming_metric(phase):
    """Streaming cohorts (ISSUE 18 acceptance, payoff a):
    ``PopulationTrainEngine`` on per-firing-uploaded data vs the
    HBM-resident baseline — the dataset-must-fit constraint lifted.
    The SAME synthetic classification cohort trains both ways;
    fitness parity is exact (pinned bitwise in
    tests/test_engine_core.py, re-asserted here) and the record
    carries the streaming path's throughput cost honestly."""
    if os.environ.get("BENCH_SKIP_COHORT_STREAMING"):
        return None
    try:
        from veles_tpu import prng
        from veles_tpu.backends import JaxDevice
        from veles_tpu.datasets import synthetic_classification
        from veles_tpu.loader import ArrayLoader
        from veles_tpu.ops.fused import PopulationTrainEngine
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        n = int(os.environ.get("BENCH_COHORT_POPULATION", "8"))
        n_train, n_valid, sample = 4096, 512, (16, 16, 1)
        lrs = [round(0.02 + 0.3 * i / max(n - 1, 1), 4)
               for i in range(n)]

        def run(streaming):
            prng._streams.clear()
            prng.seed_all(4242)
            train, valid, _ = synthetic_classification(
                n_train, n_valid, sample, n_classes=10, seed=77)
            gd = {"learning_rate": 0.1, "weight_decay": 0.0001,
                  "gradient_moment": 0.9}
            w = StandardWorkflow(
                loader_factory=lambda wf: ArrayLoader(
                    wf, train=train, valid=valid,
                    minibatch_size=64, name="loader"),
                layers=[
                    {"type": "all2all_tanh",
                     "->": {"output_sample_shape": 32}, "<-": gd},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 10}, "<-": gd},
                ],
                decision_config={"max_epochs": 3},
                name="bench_cohort")
            w.initialize(device=JaxDevice(platform="cpu"))
            if streaming:
                w.loader.device_resident = False
            rates = np.asarray(
                [[[lr, lr], [lr, lr]] for lr in lrs], np.float32)
            decays = np.asarray(
                [[[0.0001, 0.0], [0.0001, 0.0]]] * n, np.float32)
            engine = PopulationTrainEngine(w, rates, decays)
            assert engine.streaming == streaming
            t0 = time.perf_counter()
            fits = np.asarray(engine.run())
            dt = time.perf_counter() - t0
            engine.release()
            w.stop()
            ds_bytes = (n_train + n_valid) * 4 * int(
                np.prod(sample))
            return fits, dt, ds_bytes

        phase(f"cohort streaming: {n}-member synthetic cohort, "
              f"HBM-resident baseline (XLA:CPU)")
        fits_res, t_res, ds_bytes = run(streaming=False)
        phase(f"cohort streaming: resident {n / t_res:.2f} "
              f"genomes/s; same cohort on streaming "
              f"(per-firing upload) data")
        fits_str, t_str, _ = run(streaming=True)
        diff = float(np.max(np.abs(fits_res - fits_str)))
        phase(f"cohort streaming: streaming {n / t_str:.2f} "
              f"genomes/s, fitness max |diff| {diff} "
              f"(dataset {ds_bytes / 2**20:.1f} MiB never resident)")
        return {
            "cohort_streaming_members": n,
            "cohort_streaming_dataset_mib": round(
                ds_bytes / 2 ** 20, 2),
            "cohort_streaming_dataset_resident_bytes": 0,
            "cohort_resident_genomes_per_sec": round(n / t_res, 3),
            "cohort_streaming_genomes_per_sec": round(n / t_str, 3),
            "cohort_streaming_overhead_x": round(t_str / t_res, 2),
            "cohort_streaming_fitness_max_abs_diff": diff,
            "cohort_streaming_platform": "cpu",
        }
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"cohort streaming metric failed: {e}",
              file=sys.stderr)
        return None


def _zoo_som_run(fused, epochs_timed, som_cfg):
    """One Kohonen workflow driven loader->trainer for 1 warmup epoch
    + ``epochs_timed`` timed epochs; returns (seconds, final weights,
    post-warmup recompiles)."""
    from veles_tpu import prng
    from veles_tpu.backends import JaxDevice
    from veles_tpu.models import kohonen as kmod

    prng._streams.clear()
    prng.seed_all(4242)
    w = kmod.KohonenWorkflow(
        loader_cfg=dict(som_cfg), som_shape=(8, 8),
        trainer_cfg={"alpha0": 0.3, "alpha_min": 0.01,
                     "decay_epochs": 8},
        decision_cfg={"max_epochs": epochs_timed + 1},
        name="ZooSomBench")
    w.initialize(device=JaxDevice(platform="cpu"), fused=fused)
    ld, tr = w.loader, w.trainer
    while ld.epoch_number < 1:      # warmup: the one compile
        ld.run()
        tr.run()
    np.asarray(w.forward.weights.map_read())   # sync barrier
    caches = None
    if fused:
        caches = (tr._train_epoch._cache_size()
                  + tr._eval_epoch._cache_size())
    t0 = time.perf_counter()
    while ld.epoch_number < 1 + epochs_timed:
        ld.run()
        tr.run()
    wfinal = np.asarray(w.forward.weights.map_read())  # sync
    dt = time.perf_counter() - t0
    recompiles = 0
    if fused:
        recompiles = (tr._train_epoch._cache_size()
                      + tr._eval_epoch._cache_size()) - caches
    w.stop()
    return dt, wfinal, recompiles


def zoo_metric(phase):
    """Menagerie (ISSUE 19): the zoo's long tail on the engine core,
    measured on XLA:CPU (build box — dispatch/compile amortization is
    the story; docs/perf.md reads the numbers honestly).

    (a) SOM: one donated epoch scan (``engine_core.build_som_epoch``)
        vs the eager per-minibatch dispatch loop — images/s both ways
        over the SAME epochs after a warmup epoch each, final
        prototypes f32-BITWISE equal, zero post-warmup recompiles;
    (b) RBM: a CD-1 learning-rate cohort trained per-genome (P fused
        workflow runs, each paying its own trace+compile) vs ONE
        vmapped ``PopulationTrainEngine`` — genomes/s each, member
        params checked against the per-genome runs;
    (c) DBN: the greedy stage chain's inter-stage ``Device.h2d_bytes``
        delta (the =0 pin) on a real two-stage pretrain.
    """
    if os.environ.get("BENCH_SKIP_ZOO"):
        return None
    try:
        from veles_tpu.backends import JaxDevice

        # -- (a) fused SOM epoch vs the eager oracle ---------------
        som_cfg = {"minibatch_size": 32, "n_train": 6400,
                   "n_valid": 0, "shape": (8, 8, 1), "n_classes": 8,
                   "seed": 888}
        epochs = 4
        batches = -(-som_cfg["n_train"] // som_cfg["minibatch_size"])
        phase(f"zoo: SOM {som_cfg['n_train']} rows x {epochs} epochs,"
              f" eager oracle ({batches} dispatches/epoch)")
        t_eager, w_eager, _ = _zoo_som_run(False, epochs, som_cfg)
        phase(f"zoo: SOM eager "
              f"{epochs * som_cfg['n_train'] / t_eager:.0f} images/s;"
              f" fused epoch scan (1 dispatch/epoch)")
        t_fused, w_fused, recompiles = _zoo_som_run(True, epochs,
                                                    som_cfg)
        som_bitwise = bool(np.array_equal(w_fused, w_eager))
        phase(f"zoo: SOM fused "
              f"{epochs * som_cfg['n_train'] / t_fused:.0f} images/s "
              f"(bitwise={som_bitwise}, recompiles={recompiles})")

        # -- (b) CD-1 RBM cohort vs per-genome runs ----------------
        from veles_tpu import prng
        from veles_tpu.loader.synthetic import MnistLoader
        from veles_tpu.ops.fused import PopulationTrainEngine
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        lrs = [0.3, 0.1, 0.05, 0.8]

        def build_rbm(lr):
            prng._streams.clear()
            prng.seed_all(1234)
            w = StandardWorkflow(
                loader_factory=lambda wf: MnistLoader(
                    wf, name="loader", targets_from_data=True,
                    minibatch_size=50, n_train=400, n_valid=100),
                layers=[
                    {"type": "binarization", "->": {}, "<-": {}},
                    {"type": "rbm", "->": {"n_hidden": 32},
                     "<-": {"learning_rate": lr,
                            "gradient_moment": 0.5, "cd_k": 1}},
                ],
                loss_function="mse",
                decision_config={"max_epochs": 3},
                name="ZooRbmBench")
            w.initialize(device=JaxDevice(platform="cpu"))
            return w

        phase(f"zoo: RBM CD-1 cohort, {len(lrs)} genomes per-genome "
              f"(each pays its own trace+compile)")
        t0 = time.perf_counter()
        serial_params = []
        for lr in lrs:
            w = build_rbm(lr)
            w.run()
            serial_params.append(
                {k: np.array(v.map_read()) for k, v in
                 w.forwards[1].param_vectors().items()})
            w.stop()
        t_serial = time.perf_counter() - t0
        phase(f"zoo: RBM serial {len(lrs) / t_serial:.2f} genomes/s; "
              f"same genomes as ONE vmapped cohort")
        t0 = time.perf_counter()
        w = build_rbm(lrs[0])
        rates = np.asarray([[[lr, lr]] * len(w.gds) for lr in lrs],
                           np.float32)
        engine = PopulationTrainEngine(w, rates,
                                       np.zeros_like(rates))
        engine.run()
        stacked = engine._params[w.forwards[1].name]
        rbm_diff = 0.0
        for i, want in enumerate(serial_params):
            for pn, arr in want.items():
                rbm_diff = max(rbm_diff, float(np.max(np.abs(
                    np.asarray(stacked[pn][i]) - arr))))
        engine.release()
        w.stop()
        t_batched = time.perf_counter() - t0
        phase(f"zoo: RBM cohort {len(lrs) / t_batched:.2f} genomes/s "
              f"(param max |diff| vs per-genome: {rbm_diff})")

        # -- (c) DBN on-device stage chain -------------------------
        from veles_tpu.models import mnist_dbn
        prng.seed_all(7)
        stats = {}
        phase("zoo: DBN 2-stage greedy pretrain (device chain)")
        mnist_dbn.pretrain(
            device=JaxDevice(platform="cpu"),
            loader_cfg={"minibatch_size": 50, "n_train": 400,
                        "n_valid": 100},
            hidden=[32, 16], epochs=2, stats=stats)
        phase(f"zoo: DBN device_chain={stats['device_chain']} "
              f"interstage_h2d_bytes="
              f"{stats['interstage_h2d_bytes']}")

        return {
            "zoo_som_rows": som_cfg["n_train"],
            "zoo_som_epochs_timed": epochs,
            "zoo_som_dispatches_per_epoch_eager": batches,
            "zoo_som_dispatches_per_epoch_fused": 1,
            "zoo_som_images_per_sec_eager": round(
                epochs * som_cfg["n_train"] / t_eager, 1),
            "zoo_som_images_per_sec_fused": round(
                epochs * som_cfg["n_train"] / t_fused, 1),
            "zoo_som_fused_speedup_x": round(t_eager / t_fused, 2),
            "zoo_som_parity_bitwise": som_bitwise,
            "zoo_som_recompiles_post_warmup": int(recompiles),
            "zoo_rbm_cohort_size": len(lrs),
            "zoo_rbm_genomes_per_sec_serial": round(
                len(lrs) / t_serial, 3),
            "zoo_rbm_genomes_per_sec_batched": round(
                len(lrs) / t_batched, 3),
            "zoo_rbm_cohort_speedup_x": round(
                t_serial / t_batched, 2),
            "zoo_rbm_param_max_abs_diff": rbm_diff,
            "zoo_dbn_device_chain": bool(stats["device_chain"]),
            "zoo_dbn_interstage_h2d_bytes": int(
                stats["interstage_h2d_bytes"]),
            "zoo_dbn_stage_rows": [s["rows"]
                                   for s in stats["stages"]],
            "zoo_platform": "cpu",
        }
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"zoo metric failed: {e}", file=sys.stderr)
        return None


def _serve_hist_window(after, before):
    """Reconstruct the latency distribution of ONE measurement window
    from two cumulative histogram snapshots (bucket-wise subtraction;
    min/max approximated by the cumulative ones, which only widens the
    clamp range the quantile interpolation uses)."""
    from veles_tpu.telemetry import Histogram
    a, b = dict(after or {}), dict(before or {})
    h = Histogram("window")
    h.count = int(a.get("count", 0)) - int(b.get("count", 0))
    h.sum = float(a.get("sum", 0.0)) - float(b.get("sum", 0.0))
    if a.get("min") is not None:
        h.min = float(a["min"])
    if a.get("max") is not None:
        h.max = float(a["max"])
    bb = b.get("buckets") or {}
    for i, c in (a.get("buckets") or {}).items():
        d = int(c) - int(bb.get(i, 0))
        if d > 0:
            h.buckets[int(i)] += d
    return h


def serve_metric(phase):
    """Hive online serving (ISSUE 10 acceptance): sustained QPS of
    dynamically micro-batched serving vs a one-request-at-a-time loop
    over the SAME model and server, at equal correctness (both windows
    answer through the same fixed-shape dispatch; responses are
    oracle-checked before timing).  The serial loop pays one padded
    max_batch dispatch per ROW; the batched window pays it per
    coalesced micro-batch — the speedup is the measured batch fill.
    p50/p99 come from the server-side ``serve.request_seconds``
    histogram DELTA across the sustained window, and the compile
    counter delta across that window must be ZERO (warm steady state
    never recompiles)."""
    if os.environ.get("BENCH_SKIP_SERVE"):
        return None
    import tempfile
    import textwrap
    import threading

    threads = int(os.environ.get("BENCH_SERVE_THREADS", "16"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "32"))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", "2"))
    window = float(os.environ.get("BENCH_SERVE_WINDOW_SEC", "4"))
    members = int(os.environ.get("BENCH_SERVE_MEMBERS", "4"))
    hidden = int(os.environ.get("BENCH_SERVE_HIDDEN", "512"))
    try:
        from veles_tpu import prng
        from veles_tpu.backends import NumpyDevice
        from veles_tpu.ensemble.packaging import pack_ensemble
        from veles_tpu.launcher import load_workflow_module
        from veles_tpu.serve.client import HiveClient

        tmp = tempfile.mkdtemp(prefix="bench_serve_")
        wf = os.path.join(tmp, "wf.py")
        with open(wf, "w") as f:
            f.write(textwrap.dedent(f"""
                from veles_tpu import prng
                from veles_tpu.datasets import synthetic_classification
                from veles_tpu.loader import ArrayLoader
                from veles_tpu.ops.standard_workflow import \\
                    StandardWorkflow

                def create_workflow(launcher):
                    prng.seed_all(9191)
                    train, valid, _ = synthetic_classification(
                        64, 16, (8, 8, 1), n_classes=10, seed=3)
                    return StandardWorkflow(
                        loader_factory=lambda w: ArrayLoader(
                            w, train=train, valid=valid,
                            minibatch_size=16, name="loader"),
                        layers=[
                            {{"type": "all2all_tanh",
                              "->": {{"output_sample_shape": {hidden}}},
                              "<-": {{"learning_rate": 0.1}}}},
                            {{"type": "softmax",
                              "->": {{"output_sample_shape": 10}},
                              "<-": {{"learning_rate": 0.1}}}},
                        ],
                        decision_config={{"max_epochs": 1}},
                        name="serve_bench_wf")
            """))
        mod = load_workflow_module(wf)

        class _FL:
            workflow = None

        def build_members(seed):
            prng.seed_all(seed)
            w = mod.create_workflow(_FL())
            w.initialize(device=NumpyDevice())
            base = {fw.name: {k: np.asarray(v) for k, v in
                              fw.gather_params().items()}
                    for fw in w.forwards}
            rng = np.random.default_rng(seed)
            ms = [{"params": {fn: {pn: a + 0.02 * rng
                                   .standard_normal(a.shape)
                                   .astype(np.float32)
                                   for pn, a in p.items()}
                              for fn, p in base.items()},
                   "valid_error": 0.0, "seed": seed, "values": None,
                   "forward_names": [fw.name for fw in w.forwards]}
                  for _ in range(members)]
            return w, ms

        phase(f"serve: packing 2 ensemble packages ({members} members "
              f"x {hidden} hidden)")
        w_main, members_main = build_members(31)
        _, members_shadow = build_members(32)
        pkg_main = os.path.join(tmp, "primary.vpkg")
        pkg_shadow = os.path.join(tmp, "shadow.vpkg")
        pack_ensemble(pkg_main, "primary", members_main, wf)
        pack_ensemble(pkg_shadow, "shadow", members_shadow, wf)

        mdir = os.path.join(tmp, "metrics")
        phase(f"serve: spawning hive (max_batch={max_batch}, "
              f"max_wait={max_wait_ms}ms)")
        client = HiveClient(
            {"primary": pkg_main, "shadow": pkg_shadow},
            backend="cpu", max_batch=max_batch,
            max_wait_ms=max_wait_ms, metrics_dir=mdir,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        try:
            rng = np.random.default_rng(0)
            row = rng.standard_normal((1, 8, 8, 1)).astype(np.float32)
            # correctness gate: the served answer must equal the host
            # member-loop oracle before any throughput is quoted
            resp = client.request("primary", row, timeout=120)
            acc = None
            for m in members_main:
                out = row
                for fw in w_main.forwards:
                    out, _ = fw.apply_fwd(
                        {k: np.asarray(v)
                         for k, v in m["params"][fw.name].items()},
                        out, rng=None, train=False)
                out = np.asarray(out)
                acc = out if acc is None else acc + out
            want = acc / len(members_main)
            oracle_diff = float(np.abs(
                np.asarray(resp["probs"]) - want).max())
            assert oracle_diff < 1e-4, oracle_diff
            client.request("shadow", row, timeout=120)   # warm both
            for _ in range(8):                           # warm steady
                client.request("primary", row)

            phase("serve: one-request-at-a-time loop (the baseline)")
            t_end = time.perf_counter() + window
            n_serial = 0
            while time.perf_counter() < t_end:
                client.request("primary", row)
                n_serial += 1
            qps_serial = n_serial / window

            st_mid = client.stats()
            phase(f"serve: serial {qps_serial:.1f} qps; sustained "
                  f"window ({threads} concurrent clients)")
            counts = [0] * threads
            stop_at = time.perf_counter() + window

            def closed_loop(i):
                r = np.random.default_rng(i)
                x = r.standard_normal((1, 8, 8, 1)).astype(np.float32)
                while time.perf_counter() < stop_at:
                    res = client.request("primary", x, timeout=60)
                    assert "pred" in res, res
                    counts[i] += 1

            ts = [threading.Thread(target=closed_loop, args=(i,))
                  for i in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            qps = sum(counts) / window
            st_end = client.stats()
        finally:
            client.close()

        lat = _serve_hist_window(
            st_end["histograms"].get("serve.request_seconds"),
            st_mid["histograms"].get("serve.request_seconds"))
        batch_hist = st_end["histograms"].get("serve.batch_rows", {})
        c_end, c_mid = st_end["counters"], st_mid["counters"]
        rows_w = c_end.get("serve.rows", 0) - c_mid.get("serve.rows",
                                                        0)
        slots_w = c_end.get("serve.batch_slots", 0) - \
            c_mid.get("serve.batch_slots", 0)
        recompiles = c_end.get("serve.compiles", 0) - \
            c_mid.get("serve.compiles", 0)
        out = {
            "serve_qps_sustained": round(qps, 1),
            "serve_qps_unbatched": round(qps_serial, 1),
            "serve_speedup_vs_unbatched": round(
                qps / max(qps_serial, 1e-9), 2),
            "serve_p50_ms": round(1000 * (lat.quantile(0.5) or 0), 3),
            "serve_p99_ms": round(1000 * (lat.quantile(0.99) or 0),
                                  3),
            "serve_batch_efficiency": round(rows_w / slots_w, 4)
            if slots_w else None,
            "serve_batch_rows_max": batch_hist.get("max"),
            "serve_models_resident": int(
                st_end["gauges"].get("serve.models_resident", 0)),
            "serve_recompiles_post_warmup": int(recompiles),
            "serve_oracle_max_abs_diff": oracle_diff,
            "serve_concurrency": threads,
            "serve_max_batch": max_batch,
            "serve_max_wait_ms": max_wait_ms,
            "serve_window_sec": window,
            "serve_members": members,
            "serve_platform": "cpu",
        }
        phase(f"serve: sustained {qps:.1f} qps vs {qps_serial:.1f} "
              f"serial ({out['serve_speedup_vs_unbatched']}x), "
              f"p50 {out['serve_p50_ms']}ms p99 {out['serve_p99_ms']}"
              f"ms, batch fill {out['serve_batch_efficiency']}, "
              f"recompiles {recompiles}")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"serve metric failed: {e}", file=sys.stderr)
        return None


def serve_mesh_metric(phase):
    """Prism mesh serving (ISSUE 17 acceptance): a ``--mesh 8``
    replica (8 virtual XLA:CPU devices) with a per-device HBM budget
    UNDER one model's stacked bytes — both models must go
    member-sharded-RESIDENT (zero LRU spills where the 1-device
    replica thrashes), answer BITWISE what a plain 1-device replica
    answers, and hold zero post-warmup recompiles through a sustained
    window."""
    if os.environ.get("BENCH_SKIP_SERVE") or \
            os.environ.get("BENCH_SKIP_SERVE_MESH"):
        return None
    import tempfile
    import textwrap
    import threading

    threads = int(os.environ.get("BENCH_SERVE_THREADS", "16"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "32"))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", "2"))
    window = float(os.environ.get("BENCH_SERVE_WINDOW_SEC", "4"))
    members = int(os.environ.get("BENCH_SERVE_MEMBERS", "4"))
    hidden = int(os.environ.get("BENCH_SERVE_HIDDEN", "512"))
    mesh = int(os.environ.get("BENCH_SERVE_MESH", "8"))
    try:
        from veles_tpu import prng
        from veles_tpu.backends import NumpyDevice
        from veles_tpu.ensemble.packaging import pack_ensemble
        from veles_tpu.launcher import load_workflow_module
        from veles_tpu.serve.client import HiveClient

        tmp = tempfile.mkdtemp(prefix="bench_serve_mesh_")
        wf = os.path.join(tmp, "wf.py")
        with open(wf, "w") as f:
            f.write(textwrap.dedent(f"""
                from veles_tpu import prng
                from veles_tpu.datasets import synthetic_classification
                from veles_tpu.loader import ArrayLoader
                from veles_tpu.ops.standard_workflow import \\
                    StandardWorkflow

                def create_workflow(launcher):
                    prng.seed_all(9191)
                    train, valid, _ = synthetic_classification(
                        64, 16, (8, 8, 1), n_classes=10, seed=3)
                    return StandardWorkflow(
                        loader_factory=lambda w: ArrayLoader(
                            w, train=train, valid=valid,
                            minibatch_size=16, name="loader"),
                        layers=[
                            {{"type": "all2all_tanh",
                              "->": {{"output_sample_shape": {hidden}}},
                              "<-": {{"learning_rate": 0.1}}}},
                            {{"type": "softmax",
                              "->": {{"output_sample_shape": 10}},
                              "<-": {{"learning_rate": 0.1}}}},
                        ],
                        decision_config={{"max_epochs": 1}},
                        name="serve_mesh_wf")
            """))
        mod = load_workflow_module(wf)

        class _FL:
            workflow = None

        def build_members(seed):
            prng.seed_all(seed)
            w = mod.create_workflow(_FL())
            w.initialize(device=NumpyDevice())
            base = {fw.name: {k: np.asarray(v) for k, v in
                              fw.gather_params().items()}
                    for fw in w.forwards}
            rng = np.random.default_rng(seed)
            ms = [{"params": {fn: {pn: a + 0.02 * rng
                                   .standard_normal(a.shape)
                                   .astype(np.float32)
                                   for pn, a in p.items()}
                              for fn, p in base.items()},
                   "valid_error": 0.0, "seed": seed, "values": None,
                   "forward_names": [fw.name for fw in w.forwards]}
                  for _ in range(members)]
            return w, ms

        phase(f"serve_mesh: packing 2 packages ({members} members x "
              f"{hidden} hidden) for a {mesh}-device replica")
        _, members_main = build_members(41)
        _, members_shadow = build_members(42)
        pkg_main = os.path.join(tmp, "primary.vpkg")
        pkg_shadow = os.path.join(tmp, "shadow.vpkg")
        pack_ensemble(pkg_main, "primary", members_main, wf)
        pack_ensemble(pkg_shadow, "shadow", members_shadow, wf)
        bytes_one = sum(int(np.prod(a.shape)) * 4
                        for m in members_main
                        for p in m["params"].values()
                        for a in p.values())
        # per-device budget UNDER one model: a 1-device replica can
        # never hold both (LRU thrash); the mesh replica holds both
        # member-sharded at ~bytes_one/members per device each
        budget = bytes_one * 3 // 4

        phase(f"serve_mesh: spawning --mesh {mesh} hive (budget "
              f"{budget} B/device vs {bytes_one} B/model) + the "
              f"1-device reference")
        repo = os.path.dirname(os.path.abspath(__file__))
        client = HiveClient(
            {"primary": pkg_main, "shadow": pkg_shadow},
            backend="cpu", max_batch=max_batch,
            max_wait_ms=max_wait_ms, hbm_budget=budget,
            env={"VELES_SERVE_MESH_SHARD": "auto"}, mesh=mesh,
            cwd=repo)
        flat = HiveClient(
            {"primary": pkg_main, "shadow": pkg_shadow},
            backend="cpu", max_batch=max_batch,
            max_wait_ms=max_wait_ms, cwd=repo)
        try:
            h = client.hello
            assert h["devices"] == mesh, h
            sharded = sum(1 for m in h["models"].values()
                          if m.get("sharded"))
            resident = sum(1 for m in h["models"].values()
                           if m.get("resident"))
            assert sharded == 2 and resident == 2, h

            # correctness gate: BITWISE vs the 1-device replica (the
            # member-sharded build runs the identical add chain on an
            # exactly-replicated gather)
            rng = np.random.default_rng(0)
            bitwise_diff = 0.0
            for n in (1, 3, max_batch // 2):
                x = rng.standard_normal((n, 8, 8, 1)) \
                    .astype(np.float32)
                for name in ("primary", "shadow"):
                    rm = client.request(name, x, timeout=120)
                    rf = flat.request(name, x, timeout=120)
                    assert "probs" in rm and "probs" in rf, (rm, rf)
                    d = float(np.abs(
                        np.asarray(rm["probs"], np.float32) -
                        np.asarray(rf["probs"], np.float32)).max())
                    bitwise_diff = max(bitwise_diff, d)
            assert bitwise_diff == 0.0, bitwise_diff

            row = rng.standard_normal((1, 8, 8, 1)).astype(np.float32)
            for _ in range(8):   # warm steady state
                client.request("primary", row)
                client.request("shadow", row)
            st_mid = client.stats()
            # the sustained window drives ONE model: the capacity
            # claim is that BOTH stay resident regardless of traffic
            # (asserted below from the end-of-window gauges), while
            # interleaving two 8-program mesh dispatches on the
            # 1-core build box only measures co-tenant thrash
            mesh_threads = min(threads, 8)
            phase(f"serve_mesh: sustained window ({mesh_threads} "
                  f"clients on primary; shadow stays resident)")
            counts = [0] * mesh_threads
            stop_at = time.perf_counter() + window

            def closed_loop(i):
                r = np.random.default_rng(i)
                x = r.standard_normal((1, 8, 8, 1)).astype(np.float32)
                while time.perf_counter() < stop_at:
                    res = client.request("primary", x, timeout=60)
                    assert "pred" in res, res
                    counts[i] += 1

            ts = [threading.Thread(target=closed_loop, args=(i,))
                  for i in range(mesh_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            qps = sum(counts) / window
            st_end = client.stats()
        finally:
            client.close()
            flat.close()

        lat = _serve_hist_window(
            st_end["histograms"].get("serve.request_seconds"),
            st_mid["histograms"].get("serve.request_seconds"))
        c_end, c_mid = st_end["counters"], st_mid["counters"]
        recompiles = c_end.get("serve.compiles", 0) - \
            c_mid.get("serve.compiles", 0)
        g = st_end["gauges"]
        out = {
            "serve_mesh_devices": mesh,
            "serve_mesh_qps_sustained": round(qps, 1),
            "serve_mesh_p50_ms": round(
                1000 * (lat.quantile(0.5) or 0), 3),
            "serve_mesh_p99_ms": round(
                1000 * (lat.quantile(0.99) or 0), 3),
            "serve_mesh_models_resident": int(
                g.get("serve.models_resident", 0)),
            "serve_mesh_sharded_models": int(sharded),
            "serve_mesh_model_bytes": int(bytes_one),
            "serve_mesh_budget_bytes_per_device": int(budget),
            "serve_mesh_resident_bytes_per_device": int(
                g.get("serve.resident_bytes_per_device", 0)),
            "serve_mesh_spills": int(
                c_end.get("serve.spills", 0)),
            "serve_mesh_recompiles_post_warmup": int(recompiles),
            "serve_mesh_bitwise_max_abs_diff": bitwise_diff,
        }
        phase(f"serve_mesh: {qps:.1f} qps, {sharded} models "
              f"member-sharded resident "
              f"({out['serve_mesh_resident_bytes_per_device']} "
              f"B/device under {budget}), spills "
              f"{out['serve_mesh_spills']}, recompiles {recompiles}, "
              f"bitwise diff {bitwise_diff}")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"serve_mesh metric failed: {e}", file=sys.stderr)
        return None


def serve_adaptive_metric(phase):
    """Adaptive coalescing window (ISSUE 17 satellite): interleaved
    2s windows (the PR 16 pairing — single long windows swing with
    the box's mood) of the SAME bursty traffic against two hives
    serving the same package, one with the static window
    (`VELES_SERVE_ADAPTIVE_WAIT=0`) and one adaptive.  Bursty
    arrivals pace the batcher's gap estimator: the window stretches
    while a burst is filling (fill rises) and collapses the moment
    arrivals stall (the lull never inflates p99)."""
    if os.environ.get("BENCH_SKIP_SERVE") or \
            os.environ.get("BENCH_SKIP_SERVE_ADAPTIVE"):
        return None
    import tempfile
    import threading

    threads = int(os.environ.get("BENCH_ADAPTIVE_THREADS", "8"))
    max_batch = int(os.environ.get("BENCH_SERVE_MAX_BATCH", "32"))
    max_wait_ms = float(os.environ.get("BENCH_SERVE_MAX_WAIT_MS", "2"))
    window = float(os.environ.get("BENCH_ADAPTIVE_WINDOW_SEC", "8"))
    try:
        from veles_tpu.serve.client import HiveClient

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        from chaos_drill import _fleet_pkg

        tmp = tempfile.mkdtemp(prefix="bench_adaptive_")
        phase("adaptive: packing the drill ensemble + spawning the "
              "static/adaptive hive pair")
        pkg, _oracle = _fleet_pkg(tmp)
        repo = os.path.dirname(os.path.abspath(__file__))
        c_static = HiveClient(
            {"m": pkg}, backend="cpu", max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            env={"VELES_SERVE_ADAPTIVE_WAIT": "0"}, cwd=repo)
        c_adapt = HiveClient(
            {"m": pkg}, backend="cpu", max_batch=max_batch,
            max_wait_ms=max_wait_ms,
            env={"VELES_SERVE_ADAPTIVE_WAIT": "1"}, cwd=repo)
        try:
            x0 = np.ones((1, 6, 6, 1), np.float32)
            for c in (c_static, c_adapt):
                assert "probs" in c.request("m", x0, timeout=120)
                for _ in range(8):
                    c.request("m", x0)

            def bursty_window(client, seconds):
                """Fan-out bursts (the RPC-frontend shape): each
                client fires 4 submits back-to-back, waits for all
                four, then sleeps a 12ms lull.  Arrivals inside a
                burst keep pace (the adaptive window stretches and
                fills); the lull is a stall (it collapses)."""
                st0 = client.stats()
                stop_at = time.perf_counter() + seconds

                def loop(i):
                    r = np.random.default_rng(i)
                    x = r.standard_normal((1, 6, 6, 1)) \
                        .astype(np.float32)
                    while time.perf_counter() < stop_at:
                        jids = [client.submit("m", x)
                                for _ in range(4)]
                        for jid in jids:
                            res = client.wait_for(jid, timeout=60)
                            assert "pred" in res, res
                        time.sleep(0.012)

                ts = [threading.Thread(target=loop, args=(i,))
                      for i in range(threads)]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
                st1 = client.stats()
                lat = _serve_hist_window(
                    st1["histograms"].get("serve.request_seconds"),
                    st0["histograms"].get("serve.request_seconds"))
                c0, c1 = st0["counters"], st1["counters"]
                rows = c1.get("serve.rows", 0) - c0.get("serve.rows",
                                                        0)
                slots = c1.get("serve.batch_slots", 0) - \
                    c0.get("serve.batch_slots", 0)
                fill = rows / slots if slots else None
                return (1000.0 * (lat.quantile(0.99) or 0.0), fill)

            rounds = max(1, int(window / 2.0))
            phase(f"adaptive: {rounds}x interleaved 2s windows, "
                  f"static vs adaptive ({threads} bursty clients)")
            p99s_s, p99s_a, fills_s, fills_a = [], [], [], []
            for _r in range(rounds):
                p99, fill = bursty_window(c_static, 2.0)
                p99s_s.append(p99)
                fills_s.append(fill)
                p99, fill = bursty_window(c_adapt, 2.0)
                p99s_a.append(p99)
                fills_a.append(fill)
            st_a = c_adapt.stats()["counters"]
        finally:
            c_static.close()
            c_adapt.close()

        fills_s = [f for f in fills_s if f is not None]
        fills_a = [f for f in fills_a if f is not None]
        p99_s = float(np.median(p99s_s))
        p99_a = float(np.median(p99s_a))
        out = {
            "serve_adaptive_fill_static": round(
                float(np.median(fills_s)), 4) if fills_s else None,
            "serve_adaptive_fill": round(
                float(np.median(fills_a)), 4) if fills_a else None,
            "serve_adaptive_p99_static_ms": round(p99_s, 3),
            "serve_adaptive_p99_ms": round(p99_a, 3),
            "serve_adaptive_p99_ratio": round(
                p99_a / max(p99_s, 1e-9), 3),
            "serve_adaptive_stretched": int(
                st_a.get("serve.wait_stretched", 0)),
            "serve_adaptive_collapsed": int(
                st_a.get("serve.wait_collapsed", 0)),
            "serve_adaptive_rounds": rounds,
            "serve_adaptive_max_wait_ms": max_wait_ms,
        }
        phase(f"adaptive: fill {out['serve_adaptive_fill_static']} -> "
              f"{out['serve_adaptive_fill']}, p99 {p99_s:.1f} -> "
              f"{p99_a:.1f} ms (ratio "
              f"{out['serve_adaptive_p99_ratio']}), stretched "
              f"{out['serve_adaptive_stretched']} / collapsed "
              f"{out['serve_adaptive_collapsed']}")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"serve_adaptive metric failed: {e}", file=sys.stderr)
        return None


def online_metric(phase):
    """Evergreen online learning (ISSUE 14 acceptance): a REAL
    ``--serve-models --online`` hive under sustained drifted labeled
    traffic.  Measures (a) the scavenger's duty cycle — fine-tune
    steps/sec stolen from the gaps of a bursty closed loop — and the
    serving p99 with the learner active vs learner-off on the same
    box (bar: <= 1.2x, zero post-warmup recompiles); (b) the gated
    promotion: held-out error of the promoted shadow vs the frozen
    incumbent on the drifted stream; (c) ``online.time_to_serve`` —
    last fine-tune step to first request served on the promoted
    params, HBM-to-HBM — against the snapshot->npz->Forge->reload
    path it replaces (measured here as pack_ensemble + a fresh hive
    spawn to its first served answer).

    Method note for (a): p99 is compared as the MEDIAN over
    interleaved 2s sub-windows of two co-resident hives (learner-on
    and learner-off) — single long windows measured 1.3-1.8x purely
    from window-ordering noise on the build box (the first window
    after any pause runs cold), while interleaved medians are stable
    run to run."""
    if os.environ.get("BENCH_SKIP_ONLINE"):
        return None
    import tempfile

    window = float(os.environ.get("BENCH_ONLINE_WINDOW_SEC", "6"))
    micro_batch = int(os.environ.get("BENCH_ONLINE_MICRO_BATCH",
                                     "8"))
    max_batch = int(os.environ.get("BENCH_ONLINE_MAX_BATCH", "8"))
    max_wait_ms = float(os.environ.get("BENCH_ONLINE_MAX_WAIT_MS",
                                       "2"))
    try:
        from veles_tpu.datasets import synthetic_classification
        from veles_tpu.ensemble.packaging import pack_ensemble
        from veles_tpu.serve.client import HiveClient

        # the chaos/fleet drill model: tiny, 3 members, XLA:CPU
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        from chaos_drill import _fleet_pkg

        tmp = tempfile.mkdtemp(prefix="bench_online_")
        phase("online: packing the ensemble + measuring the npz "
              "round-trip it replaces")
        t0 = time.perf_counter()
        pkg, oracle = _fleet_pkg(tmp)
        pack_sec = time.perf_counter() - t0
        # the OLD model-update path: a new package reloads through a
        # fresh serving process; clock pack + spawn + first answer
        t0 = time.perf_counter()
        c0 = HiveClient({"m": pkg}, backend="cpu",
                        max_batch=max_batch, max_wait_ms=max_wait_ms,
                        cwd=os.path.dirname(os.path.abspath(
                            __file__)))
        train, _valid, _ = synthetic_classification(
            64, 16, (6, 6, 1), n_classes=3, seed=5)
        xs, ys = train
        assert "probs" in c0.request("m", xs[:1], timeout=120)
        npz_roundtrip_sec = pack_sec + time.perf_counter() - t0

        def bursty_window(client, seconds, labeled):
            """One bursty closed loop (5 requests back-to-back, then
            a 10ms lull) — live traffic has gaps; the gaps are the
            resource the scavenger exists to steal."""
            st0 = client.stats()
            n = 0
            i = 0
            t_end = time.perf_counter() + seconds
            while time.perf_counter() < t_end:
                for _ in range(5):
                    j = i % len(xs)
                    i += 1
                    lab = [int((ys[j] + 1) % 3)] if labeled else None
                    r = client.wait_for(client.submit(
                        "m", xs[j][None], label=lab), timeout=60)
                    assert "error" not in r, r
                    n += 1
                time.sleep(0.01)
            st1 = client.stats()
            lat = _serve_hist_window(
                st1["histograms"].get("serve.request_seconds"),
                st0["histograms"].get("serve.request_seconds"))
            return st0, st1, lat, n

        mdir = os.path.join(tmp, "metrics")
        env = {
            "VELES_ONLINE_MICRO_BATCH": str(micro_batch),
            # a gate round costs several step-lengths of chip time:
            # space the rounds out so serving pays for one rarely
            "VELES_ONLINE_MIN_STEPS": "48",
            "VELES_ONLINE_LR_SCALE": "1.0",
            "VELES_ONLINE_PROMOTE_MARGIN": "5.0",
            "VELES_ONLINE_HOLDOUT_EVERY": "6",
            # parasitic settings: step only in REAL lulls (4ms quiet),
            # and rest 9x each step's cost — learning throughput is
            # worth nothing if it becomes the serving tail
            "VELES_ONLINE_IDLE_MS": "4",
            "VELES_ONLINE_DUTY": os.environ.get(
                "BENCH_ONLINE_DUTY", "0.1"),
            "VELES_FAULTS": "",
        }
        phase("online: spawning the learning hive")
        c = HiveClient({"m": pkg}, backend="cpu",
                       max_batch=max_batch, max_wait_ms=max_wait_ms,
                       online=True, metrics_dir=mdir, env=env,
                       cwd=os.path.dirname(os.path.abspath(
                           __file__)))
        try:
            assert c.hello.get("online") is True
            assert "probs" in c.request("m", xs[:1], timeout=120)
            phase("online: warm-up (first scavenged step compiles)")
            deadline = time.monotonic() + 120
            i = 0
            while time.monotonic() < deadline:
                j = i % len(xs)
                i += 1
                c.wait_for(c.submit("m", xs[j][None],
                                    label=[int((ys[j] + 1) % 3)]),
                           timeout=60)
                if i % 8 == 0:
                    if c.stats()["counters"].get("online.steps",
                                                 0) > 0:
                        break
                    time.sleep(0.05)

            rounds = max(1, int(window / 2.0))
            phase(f"online: {rounds}x interleaved 2s p99 windows, "
                  f"learner-off vs learner-on")
            p99s_off, p99s_on = [], []
            steps_w = 0
            n_on = 0
            recompiles = 0
            for _r in range(rounds):
                _, _, lat_off, _n = bursty_window(c0, 2.0, False)
                p99s_off.append(1000.0 * (lat_off.quantile(0.99)
                                          or 0.0))
                st0, st1, lat_on, n_w = bursty_window(c, 2.0, True)
                p99s_on.append(1000.0 * (lat_on.quantile(0.99)
                                         or 0.0))
                n_on += n_w
                c0w, c1w = st0["counters"], st1["counters"]
                steps_w += c1w.get("online.steps", 0) - \
                    c0w.get("online.steps", 0)
                recompiles += c1w.get("serve.compiles", 0) - \
                    c0w.get("serve.compiles", 0)
            p99_off = float(np.median(p99s_off))
            p99_on = float(np.median(p99s_on))
            window_on = 2.0 * rounds
            c0.close()

            phase("online: driving drift to promotion")
            deadline = time.monotonic() + 180
            row = None
            while time.monotonic() < deadline:
                for _ in range(8):
                    j = i % len(xs)
                    i += 1
                    c.wait_for(c.submit(
                        "m", xs[j][None],
                        label=[int((ys[j] + 1) % 3)]), timeout=60)
                row = c.learn().get("m")
                if row and row["promotions"] >= 1:
                    break
                time.sleep(0.05)
            assert row and row["promotions"] >= 1, row
            # one request on the promoted params pins time_to_serve
            assert "probs" in c.request("m", xs[:1], timeout=60)
            row = c.learn()["m"]
            st_end = c.stats()
        finally:
            c.close()
            if c0.proc.poll() is None:
                c0.close()

        steps_total = st_end["counters"].get("online.steps", 0)
        step_sec_total = st_end["counters"].get("online.step_seconds",
                                                0.0)
        out = {
            "online_steps_total": int(steps_total),
            "online_steps_in_window": int(steps_w),
            "online_steps_per_sec_window": round(
                steps_w / window_on, 2),
            "online_step_ms_avg": round(
                1000.0 * step_sec_total / steps_total, 2)
            if steps_total else None,
            "online_tapped_rows": int(st_end["counters"].get(
                "online.tapped_rows", 0)),
            "online_labeled_rows": int(st_end["counters"].get(
                "online.labeled_rows", 0)),
            "online_steps_skipped_busy": int(st_end["counters"].get(
                "online.steps_skipped_busy", 0)),
            "online_promotions": int(row["promotions"]),
            "online_rollbacks": int(row["rollbacks"]),
            "online_shadow_error_pct": row["shadow_error_pct"],
            "online_incumbent_error_pct": row["incumbent_error_pct"],
            "online_time_to_serve_ms": row["time_to_serve_ms"],
            "online_npz_roundtrip_sec": round(npz_roundtrip_sec, 2),
            "online_p99_ms_learner_on": round(p99_on, 3),
            "online_p99_ms_learner_off": round(p99_off, 3),
            "online_p99_ratio": round(p99_on / max(p99_off, 1e-9), 3),
            "online_recompiles_post_warmup": int(recompiles),
            "online_qps_window": round(n_on / window_on, 1),
            "online_micro_batch": micro_batch,
            "online_window_sec": window_on,
            "online_buffer_bytes": int(st_end["gauges"].get(
                "online.buffer_bytes", 0)),
            "online_platform": "cpu",
        }
        phase(f"online: {out['online_steps_per_sec_window']} "
              f"steps/s scavenged under load, p99 "
              f"{out['online_p99_ms_learner_on']}ms vs "
              f"{out['online_p99_ms_learner_off']}ms learner-off "
              f"({out['online_p99_ratio']}x), time_to_serve "
              f"{out['online_time_to_serve_ms']}ms vs npz round-trip "
              f"{out['online_npz_roundtrip_sec']}s, recompiles "
              f"{out['online_recompiles_post_warmup']}")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"online metric failed: {e}", file=sys.stderr)
        return None


def trace_metric(phase):
    """Flightline tracing (ISSUE 16 acceptance): one single-replica
    Swarm fleet over the tiny chaos-drill model, driven by the same
    closed loop with ``$VELES_TRACE_SAMPLE`` flipped 1/0 between
    interleaved sub-windows (the online_metric window-ordering-noise
    defense: the ratio is the MEDIAN over window PAIRS, not one long
    window each).  Bar: tracing-on p99 <= 1.05x tracing-off.  The
    sampled windows' journals are then assembled offline
    (obs.load_tree + assemble_traces) and the phase verifies the
    traces are COMPLETE — root trace.request, a trace.leg, and a
    cross-process trace.serve hop with a renderable critical path —
    and that the p99 tail exemplar buckets name real trace ids."""
    if os.environ.get("BENCH_SKIP_TRACE"):
        return None
    import tempfile

    window = float(os.environ.get("BENCH_TRACE_WINDOW_SEC", "2"))
    pairs = int(os.environ.get("BENCH_TRACE_PAIRS", "5"))
    try:
        from veles_tpu import telemetry
        from veles_tpu.obs import (assemble_traces, critical_path,
                                   load_tree, tail_exemplars)
        from veles_tpu.serve.router import FleetRouter

        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        from chaos_drill import _fleet_pkg

        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        pkg, _oracle = _fleet_pkg(tmp)
        mdir = os.path.join(tmp, "metrics")
        phase("trace: spawning 1-replica fleet (tiny model)")
        prev = os.environ.get("VELES_TRACE_SAMPLE")
        router = FleetRouter(
            {"m": pkg}, n_replicas=1, backend="cpu", max_batch=8,
            max_wait_ms=2.0, metrics_dir=mdir,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        try:
            rng = np.random.default_rng(7)
            row = rng.standard_normal((1, 6, 6, 1)).astype(np.float32)
            for _ in range(16):          # compile + steady state
                r = router.request("m", row, timeout=120)
                assert "error" not in r, r

            def one_window(rate):
                os.environ["VELES_TRACE_SAMPLE"] = str(rate)
                lats = []
                t_end = time.perf_counter() + window
                while time.perf_counter() < t_end:
                    t0 = time.perf_counter()
                    r = router.request("m", row, timeout=60)
                    assert "error" not in r, r
                    lats.append(time.perf_counter() - t0)
                return lats

            one_window(0)                # order-noise burn-in
            ratios, p_on, p_off, n_on = [], [], [], 0
            for i in range(pairs):
                on = one_window(1)
                off = one_window(0)
                n_on += len(on)
                p1 = float(np.percentile(on, 99))
                p0 = float(np.percentile(off, 99))
                p_on.append(p1)
                p_off.append(p0)
                ratios.append(p1 / max(p0, 1e-9))
                phase(f"trace: pair {i + 1}/{pairs} p99 "
                      f"{1000 * p1:.2f}ms on / {1000 * p0:.2f}ms off "
                      f"({p1 / max(p0, 1e-9):.3f}x)")
            ratio = float(np.median(ratios))
        finally:
            if prev is None:
                os.environ.pop("VELES_TRACE_SAMPLE", None)
            else:
                os.environ["VELES_TRACE_SAMPLE"] = prev
            router.close()
            telemetry.flush()

        reg, merged = load_tree(mdir)
        traces = assemble_traces(merged)
        complete = 0
        for evs in traces.values():
            names = {e.get("event") for e in evs}
            if not {"trace.request", "trace.leg",
                    "trace.serve"} <= names:
                continue
            if len({e.get("_pid") for e in evs}) < 2:
                continue        # router + replica: cross-process
            cp = critical_path(evs)
            if cp.get("total_s") is not None \
                    and cp.get("dispatch_s") is not None:
                complete += 1
        assembly_ok = bool(traces) and complete >= int(
            0.9 * len(traces))
        hist = (reg.snapshot().get("histograms") or {}).get(
            "fleet.request_seconds") or {}
        tail = tail_exemplars(reg, "fleet.request_seconds")
        out = {
            "trace_overhead_p99_ratio": round(ratio, 3),
            "trace_overhead_ok": bool(ratio <= 1.05),
            "trace_p99_ms_on": round(
                1000 * float(np.median(p_on)), 3),
            "trace_p99_ms_off": round(
                1000 * float(np.median(p_off)), 3),
            "trace_sampled_requests": n_on,
            "trace_assembled": len(traces),
            "trace_assembled_complete": complete,
            "trace_assembly_ok": bool(assembly_ok),
            "trace_exemplar_buckets": len(hist.get("exemplars")
                                          or {}),
            "trace_tail_exemplars": len(tail),
            "trace_window_sec": window,
            "trace_window_pairs": pairs,
            "trace_platform": "cpu",
        }
        phase(f"trace: p99 ratio {ratio:.3f}x "
              f"({'<=' if out['trace_overhead_ok'] else 'OVER'} "
              f"1.05 bar), {complete}/{len(traces)} traces complete "
              f"cross-process, {out['trace_exemplar_buckets']} "
              f"exemplar bucket(s), {len(tail)} in the p99 tail")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"trace metric failed: {e}", file=sys.stderr)
        return None


def fleet_metric(phase):
    """Swarm fleet serving (ISSUE 11 acceptance): sustained QPS vs
    replica count (1/2/4 replicas over the SAME model set, XLA:CPU),
    plus a spike test that saturates one replica's capacity and a
    SIGKILL failover mid-load.

    Sizing note (the one-core build box): a single hive in the bench
    regime is WINDOW-bound, not CPU-bound — with C closed-loop
    clients < max_batch, every dispatch waits the full max-wait
    window while the core idles (docs/perf.md round-6: "max_wait is a
    latency floor"), so replicas genuinely multiply throughput by
    firing their windows concurrently until the core saturates.  On a
    many-core host the same harness measures the CPU-parallel
    speedup; on a TPU mesh, one replica per chip.

    The spike drives far more closed-loop clients than the fleet's
    measured capacity with the SLO knob armed: admitted p99 must hold
    <= the SLO (set at BENCH_FLEET_SLO_MULT x the unloaded p99) while
    explicit `overloaded` sheds — never timeouts — absorb the
    overflow.  Mid-spike the canary split keeps flowing; a separate
    moderate-load window SIGKILLs one replica and counts lost
    requests (bar: zero — in-flight requests retry once on the
    peer)."""
    if os.environ.get("BENCH_SKIP_FLEET"):
        return None
    import tempfile
    import textwrap
    import threading

    replica_counts = [
        int(x) for x in os.environ.get(
            "BENCH_FLEET_REPLICAS", "1,2,4").split(",")]
    clients_per = int(os.environ.get(
        "BENCH_FLEET_CLIENTS_PER_REPLICA", "6"))
    window = float(os.environ.get("BENCH_FLEET_WINDOW_SEC", "3"))
    max_batch = int(os.environ.get("BENCH_FLEET_MAX_BATCH", "16"))
    max_wait_ms = float(os.environ.get(
        "BENCH_FLEET_MAX_WAIT_MS", "8"))
    members = int(os.environ.get("BENCH_FLEET_MEMBERS", "2"))
    hidden = int(os.environ.get("BENCH_FLEET_HIDDEN", "128"))
    spike_clients = int(os.environ.get(
        "BENCH_FLEET_SPIKE_CLIENTS", "96"))
    slo_mult = float(os.environ.get("BENCH_FLEET_SLO_MULT", "1.7"))
    canary_fraction = float(os.environ.get(
        "BENCH_FLEET_CANARY_FRACTION", "0.2"))
    try:
        from veles_tpu import events, prng, telemetry
        from veles_tpu.backends import NumpyDevice
        from veles_tpu.ensemble.packaging import pack_ensemble
        from veles_tpu.launcher import load_workflow_module
        from veles_tpu.serve.router import FleetRouter

        def model_ctr(model, what):
            # the fleet.model.<name>.* dynamic family (events.py)
            return f"fleet.model.{model}.{what}"

        tmp = tempfile.mkdtemp(prefix="bench_fleet_")
        wf = os.path.join(tmp, "wf.py")
        with open(wf, "w") as f:
            f.write(textwrap.dedent(f"""
                from veles_tpu import prng
                from veles_tpu.datasets import synthetic_classification
                from veles_tpu.loader import ArrayLoader
                from veles_tpu.ops.standard_workflow import \\
                    StandardWorkflow

                def create_workflow(launcher):
                    prng.seed_all(7171)
                    train, valid, _ = synthetic_classification(
                        64, 16, (8, 8, 1), n_classes=10, seed=4)
                    return StandardWorkflow(
                        loader_factory=lambda w: ArrayLoader(
                            w, train=train, valid=valid,
                            minibatch_size=16, name="loader"),
                        layers=[
                            {{"type": "all2all_tanh",
                              "->": {{"output_sample_shape": {hidden}}},
                              "<-": {{"learning_rate": 0.1}}}},
                            {{"type": "softmax",
                              "->": {{"output_sample_shape": 10}},
                              "<-": {{"learning_rate": 0.1}}}},
                        ],
                        decision_config={{"max_epochs": 1}},
                        name="fleet_bench_wf")
            """))
        mod = load_workflow_module(wf)

        class _FL:
            workflow = None

        def build_members(seed):
            prng.seed_all(seed)
            w = mod.create_workflow(_FL())
            w.initialize(device=NumpyDevice())
            base = {fw.name: {k: np.asarray(v) for k, v in
                              fw.gather_params().items()}
                    for fw in w.forwards}
            rng = np.random.default_rng(seed)
            ms = [{"params": {fn: {pn: a + 0.02 * rng
                                   .standard_normal(a.shape)
                                   .astype(np.float32)
                                   for pn, a in p.items()}
                              for fn, p in base.items()},
                   "valid_error": 0.0, "seed": seed, "values": None,
                   "forward_names": [fw.name for fw in w.forwards]}
                  for _ in range(members)]
            return w, ms

        phase(f"fleet: packing 2 ensemble packages ({members} "
              f"members x {hidden} hidden)")
        w_main, members_main = build_members(41)
        _, members_shadow = build_members(42)
        pkg_main = os.path.join(tmp, "primary.vpkg")
        pkg_shadow = os.path.join(tmp, "shadow.vpkg")
        pack_ensemble(pkg_main, "primary", members_main, wf)
        pack_ensemble(pkg_shadow, "shadow", members_shadow, wf)
        specs = {"primary": pkg_main, "shadow": pkg_shadow}
        here = os.path.dirname(os.path.abspath(__file__))
        row = np.random.default_rng(0).standard_normal(
            (1, 8, 8, 1)).astype(np.float32)

        def host_oracle(x):
            acc = None
            for m in members_main:
                out = x
                for fw in w_main.forwards:
                    out, _ = fw.apply_fwd(
                        {k: np.asarray(v)
                         for k, v in m["params"][fw.name].items()},
                        out, rng=None, train=False)
                out = np.asarray(out)
                acc = out if acc is None else acc + out
            return acc / len(members_main)

        def warm(router):
            # warm EVERY replica directly (least-loaded routing sends
            # all idle-fleet probes to replica 0): both models load,
            # the one fixed dispatch shape compiles once per replica
            for r in router.replicas:
                r.client.request("primary", row, timeout=120)
                r.client.request("shadow", row, timeout=120)
                for _ in range(4):
                    r.client.request("primary", row, timeout=120)

        def replica_compiles(router):
            out = []
            for st in router.replica_stats():
                out.append((st or {}).get("counters", {})
                           .get("serve.compiles", 0))
            return out

        def closed_loop_window(router, n_clients, seconds,
                               shed_backoff_s=0.005, timeout=60.0,
                               ramp_s=0.0):
            """n_clients closed-loop threads on 'primary'; returns
            (ok_latencies, sheds, timeouts, errors).  ``ramp_s``
            discards the leading transient (a spike's queues build —
            and the admission EMAs catch up — within the ramp; the
            quoted p99 is the steady overloaded state)."""
            lat = []
            sheds = [0]
            timeouts = [0]
            errors = [0]
            start = time.perf_counter()
            stop_at = start + seconds
            measure_from = start + ramp_s

            def loop(i):
                r = np.random.default_rng(i)
                x = r.standard_normal((1, 8, 8, 1)) \
                    .astype(np.float32)
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    res = router.request("primary", x,
                                         timeout=timeout)
                    dt = time.perf_counter() - t0
                    if res.get("overloaded"):
                        if t0 >= measure_from:
                            sheds[0] += 1
                        time.sleep(shed_backoff_s)
                    elif "error" in res:
                        if res.get("timeout") \
                                or "timeout" in res["error"]:
                            timeouts[0] += 1
                        else:
                            errors[0] += 1
                    elif t0 >= measure_from:
                        lat.append(dt)

            ts = [threading.Thread(target=loop, args=(i,))
                  for i in range(n_clients)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            return lat, sheds[0], timeouts[0], errors[0]

        # -- the replica-count curve ----------------------------------
        qps_by_n = {}
        oracle_diff = None
        recompiles_total = 0
        for n in replica_counts:
            phase(f"fleet: spawning {n} replica(s)")
            router = FleetRouter(
                specs, n_replicas=n, backend="cpu",
                max_batch=max_batch, max_wait_ms=max_wait_ms,
                metrics_dir=os.path.join(tmp, f"metrics-{n}"),
                cwd=here)
            try:
                warm(router)
                if oracle_diff is None:
                    resp = router.request("primary", row, timeout=120)
                    oracle_diff = float(np.abs(
                        np.asarray(resp["probs"])
                        - host_oracle(row)).max())
                    assert oracle_diff < 1e-4, oracle_diff
                compiles_before = replica_compiles(router)
                clients = clients_per * n
                phase(f"fleet: n={n} sustained window "
                      f"({clients} clients, {window}s)")
                lat, sheds, tmo, errs = closed_loop_window(
                    router, clients, window)
                qps = len(lat) / window
                compiles_after = replica_compiles(router)
                recompiles_total += sum(
                    a - b for a, b in zip(compiles_after,
                                          compiles_before))
                qps_by_n[n] = qps
                spread = router.routed_counts()
                phase(f"fleet: n={n} -> {qps:.1f} qps "
                      f"(spread {spread}, sheds {sheds}, "
                      f"timeouts {tmo}, errors {errs})")
            finally:
                router.close()
        n_lo, n_hi = min(qps_by_n), max(qps_by_n)
        efficiency = qps_by_n[n_hi] / (
            (n_hi / n_lo) * qps_by_n[n_lo])

        # -- spike + canary + failover on one 2-replica fleet ---------
        phase("fleet: spawning the 2-replica spike/canary fleet")
        router = FleetRouter(
            specs, n_replicas=2, backend="cpu",
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            canaries={"shadow": ("primary", canary_fraction)},
            metrics_dir=os.path.join(tmp, "metrics-spike"),
            cwd=here)
        try:
            warm(router)
            phase("fleet: unloaded window (canary split active)")
            req0 = telemetry.counter(
                model_ctr("primary", "requests")).value
            mir0 = telemetry.counter(
                model_ctr("shadow", "mirrored")).value
            lat, _, _, _ = closed_loop_window(
                router, max(2, clients_per // 2), window)
            unloaded_p50 = 1000 * float(np.percentile(lat, 50))
            unloaded_p99 = 1000 * float(np.percentile(lat, 99))
            d_req = telemetry.counter(
                model_ctr("primary", "requests")).value - req0
            d_mir = telemetry.counter(
                model_ctr("shadow", "mirrored")).value - mir0
            canary_observed = d_mir / d_req if d_req else None

            slo = slo_mult * unloaded_p99
            router.slo_p99_ms = slo
            ramp = min(1.0, window / 3)
            phase(f"fleet: spike window ({spike_clients} clients, "
                  f"SLO {slo:.1f}ms armed, {ramp:.1f}s ramp)")
            lat, sheds, tmo, errs = closed_loop_window(
                router, spike_clients, window + ramp,
                shed_backoff_s=0.02, ramp_s=ramp)
            spike_qps = len(lat) / window
            spike_p99 = 1000 * float(np.percentile(lat, 99)) \
                if lat else None
            shed_fraction = sheds / max(1, sheds + len(lat))
            router.slo_p99_ms = 0.0
            phase(f"fleet: spike -> {spike_qps:.1f} qps admitted, "
                  f"p99 {spike_p99 and round(spike_p99, 1)}ms vs "
                  f"unloaded {unloaded_p99:.1f}ms, {sheds} sheds, "
                  f"{tmo} timeouts")

            phase("fleet: SIGKILL one replica mid-load")
            retries0 = telemetry.counter(
                events.CTR_FLEET_RETRIES).value
            lost = [0]
            ok = [0]
            stop_at = time.perf_counter() + window

            def failover_loop(i):
                r = np.random.default_rng(1000 + i)
                x = r.standard_normal((1, 8, 8, 1)) \
                    .astype(np.float32)
                while time.perf_counter() < stop_at:
                    res = router.request("primary", x, timeout=60)
                    if "error" in res and not res.get("overloaded"):
                        lost[0] += 1
                    elif "probs" in res:
                        ok[0] += 1

            ts = [threading.Thread(target=failover_loop, args=(i,))
                  for i in range(clients_per * 2)]
            for t in ts:
                t.start()
            time.sleep(window / 3)
            killed_pid = router.replicas[0].pid
            router.replicas[0].client.proc.kill()
            for t in ts:
                t.join()
            failover_retries = telemetry.counter(
                events.CTR_FLEET_RETRIES).value - retries0
            deadline = time.monotonic() + 60
            respawned = False
            while time.monotonic() < deadline:
                if router.replicas[0].healthy \
                        and router.replicas[0].pid != killed_pid:
                    respawned = True
                    break
                time.sleep(0.25)
            phase(f"fleet: failover -> {ok[0]} ok, {lost[0]} lost, "
                  f"{failover_retries} retried on the peer, "
                  f"respawned={respawned}")
        finally:
            router.close(kill=True)

        # -- gray failure: one SLOW replica, sentinel armed ------------
        # (ISSUE 12 acceptance: with one replica injected slow, fleet
        # p99 <= 1.5x the healthy-fleet p99 — hedges bridge the
        # detection window, ejection removes the outlier, probes
        # reinstate it once the fault budget exhausts)
        gray_seconds = float(os.environ.get(
            "BENCH_FLEET_GRAY_SLOW_SEC", "1.5"))
        gray_times = int(os.environ.get("BENCH_FLEET_GRAY_TIMES",
                                        "12"))
        phase(f"fleet: gray drill — replica 0 slow "
              f"({gray_seconds}s/dispatch, {gray_times} firings)")
        hedges0 = telemetry.counter(events.CTR_FLEET_HEDGES).value
        wins0 = telemetry.counter(events.CTR_FLEET_HEDGE_WINS).value
        eject0 = telemetry.counter(events.CTR_FLEET_EJECTIONS).value
        reinst0 = telemetry.counter(
            events.CTR_FLEET_REINSTATEMENTS).value
        stale0 = telemetry.counter(
            events.CTR_FLEET_STALE_RESPONSES).value
        req0 = telemetry.counter(events.CTR_FLEET_REQUESTS).value
        router = FleetRouter(
            {"primary": pkg_main}, n_replicas=2, backend="cpu",
            max_batch=max_batch, max_wait_ms=max_wait_ms,
            metrics_dir=os.path.join(tmp, "metrics-gray"), cwd=here,
            env={"VELES_FAULTS": ""},
            env_overrides={0: {"VELES_FAULTS":
                               f"hive.slow_dispatch@label=primary"
                               f"&times={gray_times}"
                               f"&seconds={gray_seconds}"}},
            deadline_ms=8000.0, hedge_min_ms=50.0, hedge_budget=1.0,
            probe_interval=0.2, probe_ok=3, probe_backoff_cap=0.5)
        try:
            ramp = min(1.5, window / 2)
            phase(f"fleet: gray window ({max(2, clients_per // 2)} "
                  f"clients, {ramp:.1f}s ramp discarded)")
            lat, _g_sheds, g_tmo, g_errs = closed_loop_window(
                router, max(2, clients_per // 2), window + ramp,
                ramp_s=ramp)
            gray_p99 = 1000 * float(np.percentile(lat, 99)) \
                if lat else None
            gray_hedges = telemetry.counter(
                events.CTR_FLEET_HEDGES).value - hedges0
            gray_requests = telemetry.counter(
                events.CTR_FLEET_REQUESTS).value - req0
            gray_ejections = telemetry.counter(
                events.CTR_FLEET_EJECTIONS).value - eject0
            phase(f"fleet: gray -> p99 "
                  f"{gray_p99 and round(gray_p99, 1)}ms vs healthy "
                  f"{unloaded_p99:.1f}ms, {gray_hedges} hedges, "
                  f"{gray_ejections} ejections, {g_tmo} timeouts, "
                  f"{g_errs} errors")
            # the fault budget exhausts under probing; wait for the
            # probe/reinstate lifecycle to complete
            reinstated = False
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if telemetry.counter(
                        events.CTR_FLEET_REINSTATEMENTS).value \
                        > reinst0:
                    reinstated = True
                    break
                time.sleep(0.25)
            gray_status = router.fleet_status()
            phase(f"fleet: gray replica 0 "
                  f"{gray_status['replicas'][0]['sentinel']['state']}"
                  f" (reinstated={reinstated})")
        finally:
            router.close(kill=True)

        out = {
            "fleet_replica_counts": replica_counts,
            "fleet_qps_by_replicas": {
                str(n): round(q, 1) for n, q in qps_by_n.items()},
            "fleet_qps_1": round(qps_by_n.get(n_lo, 0), 1),
            "fleet_qps_max": round(qps_by_n.get(n_hi, 0), 1),
            "fleet_scaling_efficiency": round(efficiency, 3),
            "fleet_clients_per_replica": clients_per,
            "fleet_window_sec": window,
            "fleet_max_batch": max_batch,
            "fleet_max_wait_ms": max_wait_ms,
            "fleet_members": members,
            "fleet_hidden": hidden,
            "fleet_oracle_max_abs_diff": oracle_diff,
            "fleet_recompiles_post_warmup": int(recompiles_total),
            "fleet_unloaded_p50_ms": round(unloaded_p50, 3),
            "fleet_unloaded_p99_ms": round(unloaded_p99, 3),
            "fleet_slo_p99_ms": round(slo, 3),
            "fleet_spike_clients": spike_clients,
            "fleet_spike_qps": round(spike_qps, 1),
            "fleet_spike_p99_ms": round(spike_p99, 3)
            if spike_p99 is not None else None,
            "fleet_spike_p99_ratio": round(
                spike_p99 / unloaded_p99, 3)
            if spike_p99 is not None else None,
            "fleet_spike_sheds": int(sheds),
            "fleet_spike_shed_fraction": round(shed_fraction, 4),
            "fleet_spike_timeouts": int(tmo),
            "fleet_spike_errors": int(errs),
            "fleet_failover_ok": int(ok[0]),
            "fleet_failover_lost": int(lost[0]),
            "fleet_failover_retries": int(failover_retries),
            "fleet_failover_respawned": bool(respawned),
            "fleet_canary_fraction": canary_fraction,
            "fleet_canary_observed": round(canary_observed, 4)
            if canary_observed is not None else None,
            "fleet_gray_slow_seconds": gray_seconds,
            "fleet_gray_fault_times": gray_times,
            "fleet_gray_requests": int(gray_requests),
            "fleet_gray_p99_ms": round(gray_p99, 3)
            if gray_p99 is not None else None,
            "fleet_gray_p99_ratio": round(gray_p99 / unloaded_p99, 3)
            if gray_p99 is not None else None,
            "fleet_gray_hedges": int(gray_hedges),
            "fleet_gray_hedge_wins": int(telemetry.counter(
                events.CTR_FLEET_HEDGE_WINS).value - wins0),
            "fleet_gray_hedge_rate": round(
                gray_hedges / max(1, gray_requests), 4),
            "fleet_gray_ejections": int(gray_ejections),
            "fleet_gray_reinstatements": int(telemetry.counter(
                events.CTR_FLEET_REINSTATEMENTS).value - reinst0),
            "fleet_gray_stale_responses": int(telemetry.counter(
                events.CTR_FLEET_STALE_RESPONSES).value - stale0),
            "fleet_gray_timeouts": int(g_tmo),
            "fleet_gray_errors": int(g_errs),
            "fleet_gray_deadline_ms": 8000.0,
            "fleet_platform": "cpu",
        }
        phase(f"fleet: {out['fleet_qps_1']} qps @1 -> "
              f"{out['fleet_qps_max']} qps @{n_hi} (efficiency "
              f"{out['fleet_scaling_efficiency']}), spike p99 ratio "
              f"{out['fleet_spike_p99_ratio']}, canary "
              f"{out['fleet_canary_observed']} of "
              f"{canary_fraction}, gray p99 ratio "
              f"{out['fleet_gray_p99_ratio']} "
              f"({out['fleet_gray_ejections']} ejected / "
              f"{out['fleet_gray_reinstatements']} reinstated)")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"fleet metric failed: {e}", file=sys.stderr)
        return None


def roofline_metric(device, phase):
    """Run ``scripts/layer_roofline.py --measure`` as a recorded phase:
    each AlexNet conv's fwd+bwd timed ALONE on the device against its
    analytic floor (the instrument that replaced docs/perf.md's
    inferred ~62% conv-efficiency residual).  On an accelerator the
    production mb=512 shapes are measured; on a chipless build image a
    tiny sanity configuration exercises the instrument and is labeled
    as such by ``conv_roofline_minibatch``."""
    if os.environ.get("BENCH_SKIP_ROOFLINE"):
        return None
    try:
        import importlib.util
        here = os.path.dirname(os.path.abspath(__file__))
        spec = importlib.util.spec_from_file_location(
            "layer_roofline",
            os.path.join(here, "scripts", "layer_roofline.py"))
        lr = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(lr)
        on_chip = getattr(device, "platform", "cpu") != "cpu"
        mb = int(os.environ.get(
            "BENCH_ROOFLINE_MB", "512" if on_chip else "4"))
        iters = 8 if on_chip else 2
        repeats = 3 if on_chip else 1
        phase(f"roofline: measuring per-conv fwd+bwd (mb={mb}, "
              f"iters={iters})")
        w = lr.build_workflow(mb)
        rows = lr.layer_rows(w.forwards, mb)
        measured = lr.measure_conv_layers(w, rows, mb, iters=iters,
                                          repeats=repeats)
        w.stop()
        tot_floor = sum(r["floor_us"] for r in measured)
        tot_meas = sum(r["measured_us"] for r in measured)
        return {
            "conv_roofline_minibatch": mb,
            "conv_roofline_layers": [
                {"name": r["name"],
                 "floor_us": round(r["floor_us"], 2),
                 "measured_us": round(r["measured_us"], 2),
                 "efficiency": round(r["efficiency"], 4)}
                for r in measured],
            "conv_roofline_total_efficiency": round(
                tot_floor / tot_meas, 4) if tot_meas else None,
        }
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"roofline metric failed: {e}", file=sys.stderr)
        return None


def telemetry_overhead_metric(w, firings):
    """The Sightline acceptance number: fused-step throughput with the
    telemetry registry ON vs OFF, as a percent slowdown.  Paired short
    windows on the already-warm resident workflow (no compile in
    either), alternating off/on so clock drift cancels; the bar is
    < 2% — the per-firing cost is a handful of counter increments and
    one histogram record, so anything higher means a regression on
    the hot path.  Negative values are measurement noise (the
    difference is below the window's variance) and ship as-is."""
    from veles_tpu import telemetry
    try:
        probe_firings = max(6, firings // 4)
        on_rates, off_rates = [], []
        # interleave off/on windows over several rounds: the engine's
        # rate drifts on the seconds scale (cache warmth, host load),
        # and a single off-then-on pair hands one side the warmer
        # engine — the same lesson the streaming phase's paired
        # windows learned from a thin link
        for _ in range(3):
            telemetry.set_enabled(False)
            r_off, _ = measure_rate(w, probe_firings, 1, warmup=1)
            telemetry.set_enabled(True)
            r_on, _ = measure_rate(w, probe_firings, 1, warmup=1)
            off_rates.append(r_off)
            on_rates.append(r_on)
        on_rate = float(np.median(on_rates))
        off_rate = float(np.median(off_rates))
        return round(100.0 * (off_rate - on_rate) / off_rate, 3)
    except Exception as e:  # noqa: BLE001 — enrichment only
        telemetry.set_enabled(True)
        print(f"telemetry overhead probe failed: {e}",
              file=sys.stderr)
        return None


def streaming_metric(device, phase):
    """ImageNet cannot be HBM-resident: measure the host-assembled,
    prefetch-overlapped streaming path (round-2 VERDICT next #3) as a
    PIPELINE, against the environment's raw host->device floor.

    Round-5 instrument design (round-4 VERDICT next #1 — the old
    instrument collapsed to one 128s firing per window and measured
    everything serialized):

    - The firing is the unit of pipelining, so its cost is CHOSEN, not
      inherited from the headline config: a raw link probe (timed
      ``device_put``) picks the superstep so one mb=STREAM_MB firing
      costs ~TARGET_FIRING_SEC of link time, and every measurement
      window holds >= MIN_WINDOW_FIRINGS firings.
    - ONE deadline covers the WHOLE phase — workflow build, streaming
      trace compile, warmup, floor puts, windows.  When the budget
      cannot hold a real pipelined window the phase reports null (with
      a stderr reason), never a degenerate serialized sample.
    - The floor is a timed ``device_put`` of one assembled superstep
      batch — identical bytes and granularity to what the pipeline
      moves per firing, so ``rate / floor`` is the pipeline's overlap
      efficiency: how close prefetch (host assembly) + async upload +
      compute get to the link's physical capacity.

    Returns a dict of record fields, or None.  Any failure here must
    NOT lose the already-measured primary metric — the caller emits
    null fields.
    """
    if os.environ.get("BENCH_SKIP_STREAMING"):
        return None
    quantized = bool(os.environ.get("BENCH_STREAM_QUANTIZED"))
    deadline = time.perf_counter() + STREAM_SECONDS
    try:
        from veles_tpu.engine import core as engine_core
        mb = STREAM_MB
        # raw link probe: one superstep row's worth of bf16-ish bytes
        probe = np.zeros((8 << 20) // 4, np.float32)  # 8 MB
        engine_core.put(probe, device.jax_device).block_until_ready()
        t0 = time.perf_counter()
        engine_core.put(probe, device.jax_device).block_until_ready()
        link_mbps = 8.0 / max(time.perf_counter() - t0, 1e-4)
        # 1-byte probe: same byte count as uint8 elements — what the
        # quantized wire would see.  Ships in the record as the
        # 1-byte/pixel roofline next to the measured 2-byte floor.
        probe_u8 = np.zeros(8 << 20, np.uint8)  # 8 MB
        engine_core.put(probe_u8, device.jax_device).block_until_ready()
        t0 = time.perf_counter()
        engine_core.put(probe_u8, device.jax_device).block_until_ready()
        link_mbps_u8 = 8.0 / max(time.perf_counter() - t0, 1e-4)
        img_px = 227 * 227 * 3
        # projected floor at 1 byte/pixel from the uint8 probe
        floor_1byte = link_mbps_u8 / (img_px / 2 ** 20)
        # firing = k minibatches of mb images; pick k so the firing's
        # link time ~= TARGET_FIRING_SEC (wire: 1 byte/px quantized
        # uint8, else 2 bytes/px bf16)
        img_mb = (img_px * (1 if quantized else 2)) / 2 ** 20
        probe_rate = link_mbps_u8 if quantized else link_mbps
        k = int(round(TARGET_FIRING_SEC * probe_rate / (img_mb * mb)))
        k = max(1, min(16, k))
        phase(f"streaming: link ~{link_mbps:.0f} MB/s "
              f"(uint8 ~{link_mbps_u8:.0f}) -> superstep "
              f"{k} (firing = {k * mb} images"
              f"{', quantized wire' if quantized else ''})")
        w = build(mb=mb, n_train=2 * k * mb, image=(227, 227, 3),
                  n_classes=1000, streaming=True, superstep=k,
                  quantized=quantized)
        w.initialize(device=device)
        if not w.fused.streaming:
            raise RuntimeError(
                "residency budget did not force streaming")
        if quantized and w.loader.dequant is None:
            raise RuntimeError(
                "BENCH_STREAM_QUANTIZED set but the loader did not "
                "derive a dequantization affine")
        # first firing: assembles a superstep batch + compiles the
        # streaming trace (the phase deadline covers it)
        w.loader.run()
        batch = w.loader.superstep_data
        n_img = batch.shape[0] * batch.shape[1]
        wire_bpi = batch.nbytes / n_img
        w.fused.run()
        sync_images(w.fused)
        fused, loader = w.fused, w.loader

        def fire():
            loader.run()
            fused.run()

        # A remote link is not constant-rate: short single-put
        # floors measure its BURST credit (this session: one 3s put
        # clocked 160+ img/s while 15s sustained windows settled at
        # ~85-90), so judging a sustained pipeline against a burst
        # floor under-reports it structurally.  The honest floor is a
        # put-only WINDOW — the same firing count, batch, bytes, and
        # duration as a pipeline window, run adjacent to it — so both
        # sides of the ratio see the same link regime and drift
        # cancels.  Efficiency = pipeline window rate / paired
        # put-only window rate, median over rounds.
        phase("streaming: compiled; paired put/pipeline windows")
        fire()                    # warmup: prime prefetch+double-buffer
        sync_images(fused)

        # transfer-busy seconds come from the Sightline registry (the
        # fused runner's write site feeds the same counter bench used
        # to scrape off the object) — counters are monotonic, so the
        # window accounting below reads deltas
        from veles_tpu import events, telemetry

        def xfer_seconds() -> float:
            return float(telemetry.counter(
                events.CTR_FUSED_STREAM_TRANSFER_SECONDS).value)
        win_req = int(os.environ.get("BENCH_STREAM_WINDOW", "6"))
        win_firings = max(MIN_WINDOW_FIRINGS + 2, win_req)
        if win_firings != win_req:
            print(f"streaming: BENCH_STREAM_WINDOW={win_req} raised "
                  f"to {win_firings} (2 queue-refill firings are "
                  f"always discarded; windows must keep "
                  f">= {MIN_WINDOW_FIRINGS} steady samples)",
                  file=sys.stderr)
        #: per-sample durations, one list per round — the efficiency
        #: estimator is a ratio of MEDIANS pooled over the rounds that
        #: ran in the link's sustained regime (round 0 is discarded as
        #: a preconditioner when later rounds exist: such a link banks
        #: burst credit while idle, and whoever transfers first in the
        #: phase rides it — measured this session as a 2x spread
        #: between round-0 and round-1 put windows)
        put_times: list = []
        fire_times: list = []
        put_rounds: list = []
        fire_rounds: list = []

        def put_window() -> float:
            # the probe can catch the link's burst regime and
            # under-size firings by 10x+ — every window also enforces
            # the phase deadline between samples (overrun bounded by
            # one in-flight transfer), see pipe_window for the same
            t0 = time.perf_counter()
            done = 0
            for _ in range(win_firings):
                s = time.perf_counter()
                engine_core.put(batch, device.jax_device) \
                    .block_until_ready()
                put_times.append(time.perf_counter() - s)
                done += 1
                if time.perf_counter() > deadline:
                    break
            return done * n_img / (time.perf_counter() - t0)

        #: (transfer_seconds, wall_seconds) per pipeline window — the
        #: intrinsic efficiency accounting (see below)
        busy: list = []

        def pipe_window() -> float:
            # the first TWO firings of a window refill the drained
            # upload queue (the window boundary sync emptied it; the
            # deque's steady depth is 2), so their wall time is
            # transfer-free.  ALWAYS discarded — a refill dispatch
            # (~ms) in the pool would inflate the published rate by
            # orders of magnitude; win_firings is floored at
            # MIN_WINDOW_FIRINGS + 2 so every full window yields
            # >= MIN_WINDOW_FIRINGS steady samples.
            transient = 2
            images0 = sync_images(fused)
            tr0 = xfer_seconds()
            t0 = time.perf_counter()
            for i in range(win_firings):
                s = time.perf_counter()
                fire()
                if i >= transient:
                    # steady state: the double-buffer drain makes each
                    # firing's wall equal its transfer slot — directly
                    # comparable to a blocking put sample
                    fire_times.append(time.perf_counter() - s)
                if time.perf_counter() > deadline:
                    break   # partial window: rate/busy use actuals
            s_sync = time.perf_counter()
            images1 = sync_images(fused)       # the honest barrier
            wall = time.perf_counter() - t0
            # transfer-busy seconds inside this window: upload submit +
            # double-buffer drain (fused.stream_transfer_seconds
            # registry counter) plus the final sync's wait, which
            # drains the last transfers' backlog and the (tiny) compute
            transfer = (xfer_seconds() - tr0
                        + time.perf_counter() - s_sync)
            busy.append((min(transfer, wall), wall))
            return (images1 - images0) / wall

        # the deadline covers the WHOLE phase, including round 0: if
        # build + compile + warmup already ate the budget, shrink the
        # window toward MIN_WINDOW_FIRINGS before giving up — and give
        # up (null fields, stderr reason) rather than overrun
        est_fire = n_img * img_mb / max(link_mbps, 1.0)
        remaining = deadline - time.perf_counter()
        while win_firings > MIN_WINDOW_FIRINGS + 2 and \
                2.0 * win_firings * est_fire > remaining:
            win_firings -= 1
        min_win = MIN_WINDOW_FIRINGS + 2
        if 2.0 * min_win * est_fire > remaining:
            raise RuntimeError(
                f"phase budget ({STREAM_SECONDS:.0f}s) exhausted by "
                f"build/compile/warmup — {remaining:.0f}s left, one "
                f"round of {min_win}-firing windows needs "
                f"~{2.0 * min_win * est_fire:.0f}s")
        rates, floors = [], []
        for rnd in range(3):
            if time.perf_counter() > deadline and rates:
                break
            if floors:
                t_round = 2.0 * win_firings * n_img / min(
                    floors[-1], rates[-1])
                if time.perf_counter() + t_round > deadline:
                    break
            # ALTERNATE which window goes first: the link also drifts
            # on the tens-of-seconds scale, so a fixed put-then-pipe
            # order hands one side the cooler link every round.
            put_times.clear()
            fire_times.clear()
            if rnd % 2 == 0:
                put_rate = put_window()
                rate_w = pipe_window()
            else:
                rate_w = pipe_window()
                put_rate = put_window()
            put_rounds.append(list(put_times))
            fire_rounds.append(list(fire_times))
            rates.append(rate_w)
            floors.append(put_rate)
            phase(f"streaming: pipeline {rate_w:.0f} img/s vs "
                  f"put-only {put_rate:.0f}")
        w.stop()
        if not rates or not any(fire_rounds):
            print("streaming: no window fit the phase budget",
                  file=sys.stderr)
            return None
        # PRIMARY efficiency: the transfer-busy fraction of pipeline
        # wall — intrinsic to the pipeline, immune to the link's
        # non-stationarity.  Rounds 3-5's remote link was measured
        # swinging 33..1300 MB/s across adjacent windows, so ANY
        # ratio of a pipeline window against a separately-timed floor
        # window is regime noise (observed 0.47..2.23 run-to-run).
        # What the framework controls — and what this measures — is
        # keeping the link busy: wall not spent submitting/draining
        # transfers is framework overhead (host assembly on the
        # critical path, dispatch, bookkeeping).  The put/fire sample
        # pools still ship in the record as the cross-check.
        transfer_s = sum(t for t, _ in busy)
        wall_s = sum(w for _, w in busy)
        # put/fire reference pools from the sustained-regime rounds
        # (round 0 burns the link's idle burst credit)
        steady = slice(1, None) if len(rates) > 1 else slice(0, None)
        put_pool = [t for r in put_rounds[steady] for t in r]
        fire_pool = [t for r in fire_rounds[steady] for t in r]
        # round 0 rides the link's banked burst credit: pools that
        # come from it (deadline left no later round, or later rounds
        # produced no steady samples) are FLAGGED, not silently
        # published as a sustained-regime number
        regime = "steady" if len(rates) > 1 else "burst_round0"
        if not put_pool or not fire_pool:
            # only round 0 produced samples — it rides the link's
            # banked burst credit, so FLAG the record rather than
            # silently publishing it as a sustained-regime number
            regime = "burst_round0"
            print("streaming: steady-regime pools empty — publishing "
                  "round-0 (burst-credit) samples, flagged via "
                  "streaming_regime", file=sys.stderr)
            put_pool = [t for r in put_rounds for t in r]
            fire_pool = [t for r in fire_rounds for t in r]
        med_put = float(np.median(put_pool))
        med_fire = float(np.median(fire_pool))
        snap = telemetry.snapshot()["counters"]
        return {
            "streaming_images_per_sec": round(n_img / med_fire, 2),
            "streaming_oom_retries": int(snap.get(
                "fused.stream_oom_retries", 0)),
            "streaming_h2d_floor_images_per_sec": round(
                n_img / med_put, 2),
            "streaming_wire_format": str(batch.dtype),
            "streaming_wire_bytes_per_image": round(wire_bpi, 1),
            "streaming_link_mbps_probe": round(link_mbps, 1),
            "streaming_link_mbps_probe_1byte": round(link_mbps_u8, 1),
            "streaming_h2d_floor_images_per_sec_1byte": round(
                floor_1byte, 2),
            "streaming_transfer_busy_fraction": round(
                transfer_s / max(wall_s, 1e-9), 4),
            "streaming_window_efficiency": round(med_put / med_fire,
                                                 4),
            "streaming_minibatch_size": mb,
            "streaming_superstep": k,
            "streaming_window_firings": win_firings,
            "streaming_regime": regime,
            "streaming_window_rates": [round(r, 2) for r in rates],
            "streaming_window_floors": [round(f, 2) for f in floors],
            "streaming_put_samples_sec": [round(t, 2)
                                          for t in put_pool],
            "streaming_fire_samples_sec": [round(t, 2)
                                           for t in fire_pool],
        }
    except Exception as e:  # noqa: BLE001 — secondary measurement
        print(f"streaming metric failed: {e}", file=sys.stderr)
        return None


#: the mesh phase's forced virtual device count (the same
#: forced-host-device-count recipe tests/conftest.py and the dryrun
#: document) and its workload shape — FC-net scale: the phase measures
#: CAPACITY placement, not conv throughput
MESH_DEVICES = int(os.environ.get("BENCH_MESH_DEVICES", "8"))
MESH_ROWS_TRAIN, MESH_ROWS_VALID = 4096, 1025   # 5121: ragged tail
MESH_SAMPLE = (16, 16, 1)


def mesh_metric_record(phase):
    """The Lattice acceptance instrument (ISSUE 15), in-process on a
    forced MESH_DEVICES-device XLA:CPU mesh.  A one-core box cannot
    show compute scaling — every virtual device timeshares the same
    silicon — so this phase measures what DOES transfer to a real
    v5e-8: CAPACITY.  Per-device resident bytes sharded vs replicated
    (against scripts/scaling_model.py's analytic prediction), the
    over-one-device-budget dataset going resident instead of
    streaming, bitwise sharded-vs-unsharded trajectory parity, zero
    post-warmup recompiles, and the member-sharded cohort cap x N
    with f32-exact GA fitness parity."""
    import jax

    from scripts.scaling_model import sharded_residency_prediction
    from veles_tpu import prng
    from veles_tpu.backends import JaxDevice
    from veles_tpu.datasets import synthetic_classification
    from veles_tpu.genetics.worker import _hbm_cohort_cap
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.ops.fused import PopulationTrainEngine
    from veles_tpu.ops.standard_workflow import StandardWorkflow
    from veles_tpu.parallel import DataParallel, padded_rows

    n_dev = MESH_DEVICES
    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices("cpu")) >= n_dev, len(jax.devices("cpu"))
    rows = MESH_ROWS_TRAIN + MESH_ROWS_VALID
    row_bytes = int(np.prod(MESH_SAMPLE)) * 4
    total_bytes = rows * row_bytes

    def build_mesh_wf(**loader_kw):
        prng._streams.clear()
        prng.seed_all(777)
        train, valid, _ = synthetic_classification(
            MESH_ROWS_TRAIN, MESH_ROWS_VALID, MESH_SAMPLE,
            n_classes=10, seed=42)
        gd = {"learning_rate": 0.1, "weight_decay": 1e-4,
              "gradient_moment": 0.9}
        return StandardWorkflow(
            loader_factory=lambda w: ArrayLoader(
                w, train=train, valid=valid, minibatch_size=64,
                name="loader", **loader_kw),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 64}, "<-": gd},
                {"type": "softmax", "->": {"output_sample_shape": 10},
                 "<-": gd},
            ],
            decision_config={"max_epochs": 3}, name="mesh_bench")

    def run_one(**loader_kw):
        w = build_mesh_wf(**loader_kw)
        dp = DataParallel(w, n_dev)
        w.initialize(device=dp.install())
        t0 = time.perf_counter()
        w.run()
        wall = time.perf_counter() - t0
        hist = [(h["class"], float(h["n_err"]), float(h["loss"]))
                for h in w.decision.history]
        params = {f.name: {k: np.asarray(v) for k, v in
                           w.fused._params[f.name].items()}
                  for f in w.forwards}
        return w, wall, hist, params

    phase(f"mesh: replicated-residency oracle ({n_dev}-device CPU "
          f"mesh)")
    w_rep, wall_rep, hist_rep, params_rep = run_one(mesh_shard="never")
    dev_rep = w_rep.loader.original_data.devmem
    per_dev_rep = max(s.data.nbytes
                      for s in dev_rep.addressable_shards)
    assert dev_rep.is_fully_replicated
    w_rep.stop()

    # budget: over ONE device (total/2 < total) but fits at total/N —
    # pre-Lattice this exact configuration degraded to host streaming
    budget = total_bytes // 2
    phase("mesh: row-sharded residency (budget total/2 — used to "
          "stream)")
    w_sh, wall_sh, hist_sh, params_sh = run_one(
        max_resident_bytes=budget)
    sharded_resident = bool(w_sh.loader.shard_resident
                            and not w_sh.fused.streaming)
    dev_sh = w_sh.loader.original_data.devmem
    per_dev_sh = max(s.data.nbytes for s in dev_sh.addressable_shards)
    pad_rows = int(dev_sh.shape[0]) - rows

    # bitwise trajectory parity: sharded residency must not change a
    # single f32 of the history or the final params
    parity_diff = 0.0
    parity_exact = hist_rep == hist_sh
    for fn in params_rep:
        for k in params_rep[fn]:
            d = float(np.abs(params_rep[fn][k]
                             - params_sh[fn][k]).max())
            parity_diff = max(parity_diff, d)
            parity_exact = parity_exact and d == 0.0

    # post-warmup recompiles: the 3-epoch run above IS the warmup;
    # another epoch's worth of firings must add zero jit cache entries
    phase("mesh: recompile probe (one extra epoch of firings)")
    fused, loader = w_sh.fused, w_sh.loader
    firings = -(-MESH_ROWS_TRAIN // 64) + -(-MESH_ROWS_VALID // 64)
    size0 = (fused._train_step._cache_size()
             + fused._eval_step._cache_size())
    for _ in range(firings):
        loader.run()
        fused.run()
    np.asarray(fused._acc)
    recompiles = (fused._train_step._cache_size()
                  + fused._eval_step._cache_size()) - size0
    w_sh.stop()

    # analytic cross-check (scripts/scaling_model.py): measured
    # per-device shard bytes vs the ceil(R/N)*row_bytes prediction
    pred = sharded_residency_prediction(rows, row_bytes, n_dev)
    pred_delta_pct = round(
        100.0 * (per_dev_sh - pred["per_device_bytes"])
        / pred["per_device_bytes"], 4)

    # -- member-sharded cohort: cap x N + f32-exact fitness parity ----
    phase("mesh: member-sharded GA cohort (12 members, parity vs "
          "unsharded)")

    def build_cohort_wf():
        prng._streams.clear()
        prng.seed_all(1234)
        train, valid, _ = synthetic_classification(
            256, 96, (8, 8, 1), n_classes=4, seed=5)
        gd = {"learning_rate": 0.1, "weight_decay": 1e-3,
              "gradient_moment": 0.9}
        w = StandardWorkflow(
            loader_factory=lambda wf: ArrayLoader(
                wf, train=train, valid=valid, minibatch_size=32,
                name="loader"),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16}, "<-": gd},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": gd},
            ],
            decision_config={"max_epochs": 2, "fail_iterations": 1},
            name="mesh_cohort")
        w.initialize(device=JaxDevice(platform="cpu"))
        return w

    p_members = 12
    lrs = [0.05 + 0.05 * i for i in range(p_members)]
    rates = np.asarray([[[lr, lr], [lr, lr]] for lr in lrs],
                       np.float32)
    decays = np.asarray([[[1e-3, 0.0], [0.0, 0.0]]] * p_members,
                        np.float32)

    w_c = build_cohort_wf()
    cap1 = _hbm_cohort_cap(w_c, 0, n_devices=1)
    cap_n = _hbm_cohort_cap(w_c, 0, n_devices=n_dev)
    eng = PopulationTrainEngine(w_c, rates, decays)
    fits_un = np.asarray(eng.run())
    eng.release()
    w_c.stop()

    w_c = build_cohort_wf()
    from veles_tpu.parallel import make_mesh
    eng = PopulationTrainEngine(w_c, rates, decays,
                                mesh=make_mesh(n_dev))
    member_sharded = bool(eng.member_sharded)
    fits_sh = np.asarray(eng.run())
    eng.release()
    w_c.stop()
    fit_diff = float(np.abs(fits_un - fits_sh).max())

    return {
        "mesh_devices": n_dev,
        "mesh_platform": "cpu",
        "mesh_dataset_rows": rows,
        "mesh_dataset_bytes_total": total_bytes,
        "mesh_per_device_bytes_replicated": int(per_dev_rep),
        "mesh_per_device_bytes_sharded": int(per_dev_sh),
        "mesh_residency_reduction_x": round(
            per_dev_rep / per_dev_sh, 2),
        "mesh_padding_rows": pad_rows,
        "mesh_pred_per_device_bytes": pred["per_device_bytes"],
        "mesh_pred_delta_pct": pred_delta_pct,
        "mesh_over_budget_resident": sharded_resident,
        "mesh_budget_bytes": budget,
        "mesh_train_parity_exact": bool(parity_exact),
        "mesh_train_parity_max_abs_diff": parity_diff,
        "mesh_recompiles_post_warmup": int(recompiles),
        "mesh_wall_replicated_sec": round(wall_rep, 2),
        "mesh_wall_sharded_sec": round(wall_sh, 2),
        "mesh_cohort_members": p_members,
        "mesh_cohort_member_sharded": member_sharded,
        "mesh_cohort_cap_1dev": int(cap1),
        "mesh_cohort_cap_mesh": int(cap_n),
        "mesh_cohort_cap_x": round(cap_n / max(cap1, 1), 2),
        "mesh_cohort_fitness_max_abs_diff": fit_diff,
    }


def gauntlet_metric(phase):
    """Gauntlet production day (ISSUE 20 acceptance): one accountable
    open-loop day — diurnal+burst traffic, the autoscaler tracking the
    load curve, Evergreen armed, chaos (gray blip + a coordinated
    SIGTERM mid-burst) — run by ``scripts/gauntlet.py`` in its own
    XLA:CPU subprocess; its verdict record is adopted under
    ``gauntlet_*`` keys.  The bars live in the script: zero
    lost/corrupt answers, >=2 scale-ups and >=2 scale-downs, p99 held
    in the non-degraded windows, a bitwise-deterministic trace, and
    every fleet mutation explained by the merged journals."""
    if os.environ.get("BENCH_SKIP_GAUNTLET"):
        return None
    import subprocess
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    phase("gauntlet: the production day (scripts/gauntlet.py)")
    try:
        res = subprocess.run(
            [sys.executable,
             os.path.join(here, "scripts", "gauntlet.py"), "--json"],
            env=env, capture_output=True, text=True, timeout=1800,
            cwd=here)
        if not res.stdout.strip():
            print(f"gauntlet phase produced no record "
                  f"(rc={res.returncode}): {res.stderr[-2000:]}",
                  file=sys.stderr)
            return None
        rec = json.loads(res.stdout.strip().splitlines()[-1])
        acct = rec.get("accountability") or {}
        out = {("gauntlet_" + k if not k.startswith("gauntlet")
                else k): v
               for k, v in rec.items()
               if k not in ("accountability", "preemptions")}
        out["gauntlet_preemptions"] = len(rec.get("preemptions", []))
        out["gauntlet_events_explained"] = acct.get("explained")
        out["gauntlet_events_unexplained"] = len(
            acct.get("unexplained", []))
        out["gauntlet_accounted"] = acct.get("accounted")
        phase(f"gauntlet: ok={rec.get('gauntlet_ok')} "
              f"answered={rec.get('answered')} "
              f"lost={rec.get('lost')} ups={rec.get('scale_ups')} "
              f"downs={rec.get('scale_downs')} "
              f"accounted={acct.get('accounted')}")
        return out
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"gauntlet phase failed: {e}", file=sys.stderr)
        return None


def mesh_metric(phase):
    """Full-run wrapper: the mesh phase needs a CPU backend with
    MESH_DEVICES virtual devices, which the headline process (real
    chip, no forced host devices) cannot provide — so it runs
    ``bench.py --mesh-only`` in a pinned subprocess (the
    dryrun_multichip re-exec pattern) and adopts its record."""
    import subprocess
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{MESH_DEVICES}").strip()
    try:
        res = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--mesh-only"],
            env=env, capture_output=True, text=True, timeout=900)
        if res.returncode != 0:
            print(f"mesh phase failed (rc={res.returncode}): "
                  f"{res.stderr[-2000:]}", file=sys.stderr)
            return None
        return json.loads(res.stdout.strip().splitlines()[-1])
    except Exception as e:  # noqa: BLE001 — enrichment only
        print(f"mesh phase failed: {e}", file=sys.stderr)
        return None


def main() -> None:
    # the streaming phase re-derives its base set from the same args —
    # opt into the dataset memo (datasets._synth_cache)
    os.environ.setdefault("VELES_TPU_SYNTH_CACHE", "1")
    if "--serve-only" in sys.argv:
        # fast path: run ONLY the Hive serving phase (XLA:CPU, own
        # subprocess) and print its record — the serving acceptance
        # gate without the 227x227 headline build
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        rec = serve_metric(_phase) or {}
        rec.update(serve_mesh_metric(_phase) or {})
        rec.update(serve_adaptive_metric(_phase) or {})
        print(json.dumps(rec or None), flush=True)
        return
    if "--online-only" in sys.argv:
        # fast path: ONLY the Evergreen online-learning phase (one
        # XLA:CPU --online hive) — the ISSUE 14 acceptance gate
        # (scavenged duty cycle, p99 ratio, gated promotion,
        # time_to_serve vs the npz round-trip) without the headline
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        print(json.dumps(online_metric(_phase)), flush=True)
        return
    if "--handoff-only" in sys.argv:
        # fast path: ONLY the Keel phases (XLA:CPU, in-process) — the
        # ISSUE 18 acceptance gate (GA->serving handoff HBM vs reload
        # oracle + streaming-cohort parity/throughput) without the
        # headline build
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        rec = handoff_metric(_phase) or {}
        rec.update(cohort_streaming_metric(_phase) or {})
        print(json.dumps(rec or None), flush=True)
        return
    if "--zoo-only" in sys.argv:
        # fast path: ONLY the Menagerie zoo phase (XLA:CPU,
        # in-process) — the ISSUE 19 acceptance gate (fused SOM epoch
        # vs eager, CD-1 cohort vs serial, DBN inter-stage bytes)
        # without the headline build
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        print(json.dumps(zoo_metric(_phase)), flush=True)
        return
    if "--trace-only" in sys.argv:
        # fast path: ONLY the Flightline tracing phase (one XLA:CPU
        # replica) — the ISSUE 16 acceptance gate (tracing-on p99 <=
        # 1.05x off, cross-process assembly, p99 exemplars) without
        # the headline build
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        print(json.dumps(trace_metric(_phase)), flush=True)
        return
    if "--fleet-only" in sys.argv:
        # fast path: ONLY the Swarm fleet phase (N XLA:CPU replica
        # subprocesses) — the ISSUE 11 acceptance gate (replica-count
        # QPS curve + spike + failover) without the headline build
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        print(json.dumps(fleet_metric(_phase)), flush=True)
        return
    if "--gauntlet-only" in sys.argv:
        # fast path: ONLY the Gauntlet production day (an elastic
        # XLA:CPU fleet driven by scripts/gauntlet.py) — the ISSUE 20
        # acceptance gate (open-loop day, scale up AND down, chaos,
        # zero lost answers, 100% accountable) without the headline
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        print(json.dumps(gauntlet_metric(_phase)), flush=True)
        return
    if "--mesh-only" in sys.argv:
        # fast path: ONLY the Lattice mesh phase — forced
        # MESH_DEVICES-device XLA:CPU mesh (the ISSUE 15 acceptance
        # gate: per-device resident bytes, over-budget-goes-resident,
        # bitwise parity, recompiles, cohort cap x N).  The backend
        # must be pinned BEFORE the first jax import; when another
        # backend already initialized, re-exec pinned (the
        # dryrun_multichip pattern).
        want = f"--xla_force_host_platform_device_count={MESH_DEVICES}"
        if "jax" in sys.modules:
            import jax
            ok = jax.default_backend() == "cpu" and \
                len(jax.devices("cpu")) >= MESH_DEVICES
            if not ok:
                rec = mesh_metric(lambda m: print(
                    f"[bench] {m}", file=sys.stderr, flush=True))
                print(json.dumps(rec), flush=True)
                return
        else:
            os.environ["JAX_PLATFORMS"] = "cpu"
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (flags + " " + want).strip()
            import jax
            jax.config.update("jax_platforms", "cpu")
        t0 = time.perf_counter()

        def _phase(msg):
            print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}",
                  file=sys.stderr, flush=True)
        print(json.dumps(mesh_metric_record(_phase)), flush=True)
        return
    from veles_tpu import profiling
    from veles_tpu.backends import make_device

    # defaults = the measured-best configuration (docs/perf.md sweep):
    # mb=512 amortizes optimizer/weight traffic, superstep 8 amortizes
    # dispatch
    mb = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    firings = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    repeats = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    t_start = time.perf_counter()

    def phase(msg):
        print(f"[bench +{time.perf_counter() - t_start:6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    # one superstep group of variety: at mb=512 ss=8 that is 4096
    # distinct 227x227x3 rows (2.5 GB in HBM) — every firing gathers a
    # full superstep; more variety adds host/HBM cost for zero
    # measurement value
    n_train = mb * SUPERSTEP
    phase(f"building resident workflow (n_train={n_train}, "
          f"device-generated)")
    w = build(mb=mb, n_train=n_train, image=(227, 227, 3),
              n_classes=1000)
    device = make_device("tpu")   # the headline is a chip number or none
    w.initialize(device=device)
    # attribution line for the driver log: everything before this is
    # device datagen + host param fill + param upload; everything after
    # up to the first rate is trace + XLA compile + the timed firings
    phase("initialized (datagen + param init/upload done)")

    phase("measuring resident path (incl. compile)")
    images_per_sec, rates = measure_rate(w, firings, repeats)
    flops = profiling.model_flops_per_sample(w.forwards)
    jdev = device.jax_device
    u = profiling.mfu(images_per_sec, flops["train"], jdev)

    phase("telemetry overhead probe (registry on vs off)")
    overhead_pct = telemetry_overhead_metric(w, firings)
    w.stop()

    record = {
        "metric": "alexnet_train_images_per_sec_per_chip",
        "value": round(images_per_sec, 2),
        "unit": "images/sec",
        "vs_baseline": None,
        "minibatch_size": mb,
        "superstep": SUPERSTEP,
        "train_gflops_per_image": round(flops["train"] / 1e9, 3),
        "achieved_tflops": round(
            images_per_sec * flops["train"] / 1e12, 2),
        "mfu": round(u, 4) if u is not None else None,
        "telemetry_overhead_pct": overhead_pct,
        "device_kind": getattr(jdev, "device_kind", "unknown"),
        "runs_images_per_sec": [round(r, 2) for r in rates],
        # enrichment fields, filled by later phases; the record is
        # COMPLETE (and re-printed) after every phase so a timeout can
        # only ever truncate enrichment
        "mnist_conv_time_to_99_sec": None,
        "multichip_dryrun_ok": None,
        "fault_drill_ok": None,
        "fault_drill_recovery_sec": None,
        "fault_drill_hang_detect_sec": None,
        "fault_drill_failures": None,
        "fault_drill_journal_verified": None,
        "lint_findings_new": None,
        "lint_findings_new_by_rule": None,
        "lint_baseline_count": None,
        "lock_order_nodes": None,
        "lock_order_edges": None,
        "preempt_snapshot_sec": None,
        "resume_downtime_sec": None,
        "resume_trajectory_match": None,
        "tpu_tests_passed": None,
        "tpu_tests_failed": None,
        "ensemble_members": None,
        "ensemble_minibatch": None,
        "ensemble_device_images_per_sec": None,
        "ensemble_device_member_images_per_sec": None,
        "ensemble_host_member_images_per_sec": None,
        "ensemble_speedup_vs_host": None,
        "ga_hangs_detected": None,
        "ga_evaluator_restarts": None,
        "ga_population": None,
        "ga_cohort_size": None,
        "ga_eval_platform": None,
        "ga_genomes_per_sec_serial": None,
        "ga_genomes_per_sec_batched": None,
        "ga_cohort_speedup": None,
        "ga_fitness_max_abs_diff": None,
        "serve_qps_sustained": None,
        "serve_qps_unbatched": None,
        "serve_speedup_vs_unbatched": None,
        "serve_p50_ms": None,
        "serve_p99_ms": None,
        "serve_batch_efficiency": None,
        "serve_batch_rows_max": None,
        "serve_models_resident": None,
        "serve_recompiles_post_warmup": None,
        "serve_oracle_max_abs_diff": None,
        "serve_concurrency": None,
        "serve_max_batch": None,
        "serve_max_wait_ms": None,
        "serve_window_sec": None,
        "serve_members": None,
        "serve_platform": None,
        "fleet_replica_counts": None,
        "fleet_qps_by_replicas": None,
        "fleet_qps_1": None,
        "fleet_qps_max": None,
        "fleet_scaling_efficiency": None,
        "fleet_clients_per_replica": None,
        "fleet_window_sec": None,
        "fleet_max_batch": None,
        "fleet_max_wait_ms": None,
        "fleet_members": None,
        "fleet_hidden": None,
        "fleet_oracle_max_abs_diff": None,
        "fleet_recompiles_post_warmup": None,
        "fleet_unloaded_p50_ms": None,
        "fleet_unloaded_p99_ms": None,
        "fleet_slo_p99_ms": None,
        "fleet_spike_clients": None,
        "fleet_spike_qps": None,
        "fleet_spike_p99_ms": None,
        "fleet_spike_p99_ratio": None,
        "fleet_spike_sheds": None,
        "fleet_spike_shed_fraction": None,
        "fleet_spike_timeouts": None,
        "fleet_spike_errors": None,
        "fleet_failover_ok": None,
        "fleet_failover_lost": None,
        "fleet_failover_retries": None,
        "fleet_failover_respawned": None,
        "fleet_canary_fraction": None,
        "fleet_canary_observed": None,
        "fleet_gray_slow_seconds": None,
        "fleet_gray_fault_times": None,
        "fleet_gray_requests": None,
        "fleet_gray_p99_ms": None,
        "fleet_gray_p99_ratio": None,
        "fleet_gray_hedges": None,
        "fleet_gray_hedge_wins": None,
        "fleet_gray_hedge_rate": None,
        "fleet_gray_ejections": None,
        "fleet_gray_reinstatements": None,
        "fleet_gray_stale_responses": None,
        "fleet_gray_timeouts": None,
        "fleet_gray_errors": None,
        "fleet_gray_deadline_ms": None,
        "fleet_platform": None,
        "online_steps_total": None,
        "online_steps_in_window": None,
        "online_steps_per_sec_window": None,
        "online_step_ms_avg": None,
        "online_tapped_rows": None,
        "online_labeled_rows": None,
        "online_steps_skipped_busy": None,
        "online_promotions": None,
        "online_rollbacks": None,
        "online_shadow_error_pct": None,
        "online_incumbent_error_pct": None,
        "online_time_to_serve_ms": None,
        "online_npz_roundtrip_sec": None,
        "online_p99_ms_learner_on": None,
        "online_p99_ms_learner_off": None,
        "online_p99_ratio": None,
        "online_recompiles_post_warmup": None,
        "online_qps_window": None,
        "online_micro_batch": None,
        "online_window_sec": None,
        "online_buffer_bytes": None,
        "online_platform": None,
        "trace_overhead_p99_ratio": None,
        "trace_overhead_ok": None,
        "trace_p99_ms_on": None,
        "trace_p99_ms_off": None,
        "trace_sampled_requests": None,
        "trace_assembled": None,
        "trace_assembled_complete": None,
        "trace_assembly_ok": None,
        "trace_exemplar_buckets": None,
        "trace_tail_exemplars": None,
        "trace_window_sec": None,
        "trace_window_pairs": None,
        "trace_platform": None,
        "mesh_devices": None,
        "mesh_platform": None,
        "mesh_dataset_rows": None,
        "mesh_dataset_bytes_total": None,
        "mesh_per_device_bytes_replicated": None,
        "mesh_per_device_bytes_sharded": None,
        "mesh_residency_reduction_x": None,
        "mesh_padding_rows": None,
        "mesh_pred_per_device_bytes": None,
        "mesh_pred_delta_pct": None,
        "mesh_over_budget_resident": None,
        "mesh_budget_bytes": None,
        "mesh_train_parity_exact": None,
        "mesh_train_parity_max_abs_diff": None,
        "mesh_recompiles_post_warmup": None,
        "mesh_wall_replicated_sec": None,
        "mesh_wall_sharded_sec": None,
        "mesh_cohort_members": None,
        "mesh_cohort_member_sharded": None,
        "mesh_cohort_cap_1dev": None,
        "mesh_cohort_cap_mesh": None,
        "mesh_cohort_cap_x": None,
        "mesh_cohort_fitness_max_abs_diff": None,
        "conv_roofline_minibatch": None,
        "conv_roofline_layers": None,
        "conv_roofline_total_efficiency": None,
        "streaming_images_per_sec": None,
        "streaming_oom_retries": None,
        "streaming_ratio": None,
        "streaming_h2d_floor_images_per_sec": None,
        "streaming_wire_format": None,
        "streaming_wire_bytes_per_image": None,
        "streaming_link_mbps_probe": None,
        "streaming_link_mbps_probe_1byte": None,
        "streaming_h2d_floor_images_per_sec_1byte": None,
        "streaming_pipeline_efficiency": None,
        "streaming_efficiency_basis": None,
        "streaming_transfer_busy_fraction": None,
        "streaming_window_efficiency": None,
        "streaming_minibatch_size": None,
        "streaming_superstep": None,
        "streaming_window_firings": None,
        "streaming_regime": None,
        "streaming_window_rates": None,
        "streaming_window_floors": None,
        "streaming_put_samples_sec": None,
        "streaming_fire_samples_sec": None,
    }

    def emit():
        print(json.dumps(record), flush=True)

    phase(f"resident: {images_per_sec:.0f} img/s (emitting headline)")
    emit()

    # Release the resident workflow's HBM (dataset + params + metric
    # carries) before the later phases, or the buffers coexist with the
    # streaming workflow's and the 16 GB chip OOMs.  The unit graph is
    # cyclic, so dropping refs is not enough — collect explicitly.
    w.fused.release_device_state()
    w.loader.original_data.reset()
    w.loader.original_labels.reset()
    w.loader.original_targets.reset()
    del w
    import gc
    gc.collect()

    phase("secondary metric (MNIST-conv to 99% on IDX files)")
    record["mnist_conv_time_to_99_sec"] = secondary_metric()
    emit()

    phase("multichip dryrun (CPU-pinned subprocess)")
    record["multichip_dryrun_ok"] = multichip_dryrun_record()
    emit()

    phase("fault drill (chaos matrix, CPU-pinned subprocess)")
    fd = fault_drill_metric(phase)
    if fd:
        record.update(fd)
    emit()

    phase("veleslint (full-repo static analysis)")
    lint = lint_metric(phase)
    if lint:
        record.update(lint)
    emit()

    phase("running tests_tpu on the chip (in-process)")
    tpu_passed, tpu_failed = run_tpu_tests()
    record["tpu_tests_passed"] = tpu_passed
    record["tpu_tests_failed"] = tpu_failed
    emit()

    phase("measuring ensemble inference (vmapped multi-member)")
    ens = ensemble_metric(device, phase)
    if ens:
        record.update(ens)
    emit()

    phase("measuring GA genome throughput (serial vs cohort)")
    ga = ga_metric(phase)
    if ga:
        record.update(ga)
    emit()

    phase("measuring GA->serving handoff (Keel, HBM vs reload)")
    hof = handoff_metric(phase)
    if hof:
        record.update(hof)
    cs = cohort_streaming_metric(phase)
    if cs:
        record.update(cs)
    emit()

    phase("measuring the zoo long tail (Menagerie: SOM/RBM/DBN)")
    zoo = zoo_metric(phase)
    if zoo:
        record.update(zoo)
    emit()

    phase("measuring online serving (Hive, XLA:CPU subprocess)")
    sv = serve_metric(phase)
    if sv:
        record.update(sv)
    emit()

    phase("measuring mesh serving (Prism, --mesh 8 XLA:CPU replica)")
    svm = serve_mesh_metric(phase)
    if svm:
        record.update(svm)
    svad = serve_adaptive_metric(phase)
    if svad:
        record.update(svad)
    emit()

    phase("measuring fleet serving (Swarm, N XLA:CPU replicas)")
    fl = fleet_metric(phase)
    if fl:
        record.update(fl)
    emit()

    phase("measuring online learning (Evergreen, XLA:CPU --online "
          "hive)")
    ol = online_metric(phase)
    if ol:
        record.update(ol)
    emit()

    phase("running the Gauntlet production day (elastic XLA:CPU "
          "fleet, scripts/gauntlet.py subprocess)")
    ga_day = gauntlet_metric(phase)
    if ga_day:
        record.update(ga_day)
    emit()

    phase("measuring tracing overhead + assembly (Flightline, "
          "1-replica fleet)")
    tr = trace_metric(phase)
    if tr:
        record.update(tr)
    emit()

    phase(f"measuring mesh sharding (Lattice, forced {MESH_DEVICES}-"
          f"device CPU mesh subprocess)")
    ms = mesh_metric(phase)
    if ms:
        record.update(ms)
    emit()

    phase("measuring per-conv roofline (layer_roofline --measure)")
    roof = roofline_metric(device, phase)
    if roof:
        record.update(roof)
    emit()

    phase("measuring streaming")
    stream = streaming_metric(device, phase)
    if stream:
        record.update(stream)
        stream_rate = stream["streaming_images_per_sec"]
        h2d_rate = stream["streaming_h2d_floor_images_per_sec"]
        record["streaming_ratio"] = round(
            stream_rate / images_per_sec, 4)
        # Link-bound (a thin host link): the pipeline's efficiency is
        # its transfer-busy fraction — the share of wall spent
        # submitting/draining uploads; the remainder is framework
        # overhead.  Intrinsic to the window, so immune to a
        # link's violent bandwidth swings (any cross-window
        # floor-vs-pipeline ratio measured 0.47..2.23 run-to-run on
        # the same code).  Compute-bound (co-located host): judge
        # against the resident rate instead.  The basis field names
        # which definition produced the number — the two are NOT
        # comparable, and cross-run diffs silently were (round-5
        # records carried both meanings under one key).
        if h2d_rate <= images_per_sec:
            record["streaming_pipeline_efficiency"] = \
                stream["streaming_transfer_busy_fraction"]
            record["streaming_efficiency_basis"] = "transfer_busy"
        else:
            record["streaming_pipeline_efficiency"] = round(
                stream_rate / images_per_sec, 4)
            record["streaming_efficiency_basis"] = "vs_resident"
    phase("done")
    emit()


if __name__ == "__main__":
    main()
