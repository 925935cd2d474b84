"""Sightline telemetry core (ISSUE 7 acceptance).

- registry semantics: get-or-create identity, counters/gauges, the
  enable switch, in-place reset;
- histogram quantile accuracy against numpy on known distributions
  (the log-bucket + geometric-interpolation estimator), merge
  equivalence across snapshots;
- span nesting (thread-local stack, histogram feed, journal lineage);
- atomic snapshot writes under a concurrent-writer torture loop — a
  reader must never parse a torn file (the PR-6 tempfile+rename
  discipline, applied to metrics);
- parent merge of an evaluator child's snapshot in a REAL
  ``worker.py --serve`` round-trip, rendered by scripts/obs_report.py;
- the per-generation hang-descriptor reset in ChipEvaluatorPool
  (stale ``last_hang_*`` must not leak into the next generation);
- the fused runner's per-dispatch telemetry: first-call compile split,
  steady-state histograms, wire-byte property backed by the registry.
"""

import json
import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

from veles_tpu import telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry semantics ------------------------------------------------

class TestRegistry:
    def test_get_or_create_identity(self):
        c = telemetry.counter("t.c")
        assert telemetry.counter("t.c") is c
        h = telemetry.histogram("t.h")
        assert telemetry.histogram("t.h") is h
        g = telemetry.gauge("t.g")
        assert telemetry.gauge("t.g") is g

    def test_counter_and_gauge(self):
        c = telemetry.counter("t.c2")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        g = telemetry.gauge("t.g2")
        assert g.value is None
        g.set(7)
        g.set(3)
        assert g.value == 3

    def test_disabled_is_noop(self):
        telemetry.set_enabled(False)
        try:
            telemetry.counter("t.off").inc()
            telemetry.gauge("t.off").set(1)
            telemetry.histogram("t.off").record(1.0)
            telemetry.event("t.off_event")
            with telemetry.span("t.off_span"):
                assert telemetry.span_stack() == []
        finally:
            telemetry.set_enabled(True)
        assert telemetry.counter("t.off").value == 0
        assert telemetry.gauge("t.off").value is None
        assert telemetry.histogram("t.off").count == 0
        assert telemetry.recent_events("t.off_event") == []
        assert telemetry.histogram("t.off_span").count == 0

    def test_reset_zeroes_in_place(self):
        c = telemetry.counter("t.r")
        h = telemetry.histogram("t.rh")
        c.inc(9)
        h.record(1.0)
        telemetry.reset()
        # object identity survives: call sites holding a reference
        # stay wired to the registry after a reset
        assert telemetry.counter("t.r") is c
        assert c.value == 0
        assert h.count == 0
        c.inc()
        assert telemetry.counter("t.r").value == 1

    def test_snapshot_shape_and_merge(self):
        telemetry.counter("t.s").inc(4)
        telemetry.gauge("t.sg").set(2.0)
        telemetry.histogram("t.sh").record(0.5)
        snap = telemetry.snapshot()
        assert snap["counters"]["t.s"] == 4
        assert snap["gauges"]["t.sg"] == 2.0
        assert snap["histograms"]["t.sh"]["count"] == 1
        assert "p50" in snap["histograms"]["t.sh"]
        # merging the snapshot back in: counters add, histograms add,
        # gauges only fill where absent
        telemetry.gauge("t.sg").set(9.0)
        telemetry.merge_snapshot(snap)
        assert telemetry.counter("t.s").value == 8
        assert telemetry.histogram("t.sh").count == 2
        assert telemetry.gauge("t.sg").value == 9.0    # kept local
        assert telemetry.gauge("t.only_in_snap").value is None


# -- histogram quantiles ----------------------------------------------

class TestHistogramQuantiles:
    @pytest.mark.parametrize("dist,kw", [
        ("lognormal", {"mean": 0.0, "sigma": 1.0}),
        ("uniform", {"low": 0.001, "high": 10.0}),
        ("exponential", {"scale": 0.05}),
    ])
    def test_quantiles_match_numpy(self, dist, kw):
        rng = np.random.default_rng(7)
        xs = getattr(rng, dist)(size=20000, **kw)
        h = telemetry.Histogram(dist)
        for x in xs:
            h.record(x)
        for q in (0.5, 0.9, 0.99):
            got = h.quantile(q)
            want = float(np.quantile(xs, q))
            assert abs(got - want) / want < 0.08, (q, got, want)
        assert h.count == len(xs)
        assert h.min == xs.min() and h.max == xs.max()
        assert abs(h.sum - xs.sum()) < 1e-6 * abs(xs.sum())

    def test_merge_equals_combined_distribution(self):
        rng = np.random.default_rng(3)
        a = rng.lognormal(0, 0.5, 5000)
        b = rng.lognormal(1.0, 0.5, 5000)
        ha, hb, hall = (telemetry.Histogram(n) for n in "ab3")
        for x in a:
            ha.record(x)
        for x in b:
            hb.record(x)
        for x in np.concatenate([a, b]):
            hall.record(x)
        merged = telemetry.Histogram("m")
        merged.merge_dict(ha.to_dict())
        merged.merge_dict(hb.to_dict())
        assert merged.count == hall.count
        for q in (0.5, 0.9, 0.99):
            assert merged.quantile(q) == pytest.approx(
                hall.quantile(q), rel=1e-12)

    def test_edge_cases(self):
        h = telemetry.Histogram("e")
        assert h.quantile(0.5) is None
        h.record(0.0)       # underflow bucket; min stays exact
        h.record(1e12)      # overflow bucket; max stays exact
        assert h.quantile(0.0) == 0.0
        assert h.quantile(1.0) == 1e12
        assert h.count == 2

    def test_delta_quantile_tracks_the_window_not_history(self):
        """The sentinel's windowed read: the quantile of ONLY the
        samples since the snapshot — a load shift must show up
        immediately even against a long contrary history."""
        rng = np.random.default_rng(11)
        h = telemetry.Histogram("w")
        fast = rng.lognormal(np.log(0.005), 0.3, 10000)   # ~5ms era
        for x in fast:
            h.record(x)
        base = h.snapshot_buckets()
        slow = rng.lognormal(np.log(0.050), 0.3, 2000)    # ~50ms era
        for x in slow:
            h.record(x)
        got = h.delta_quantile(base, 0.95, min_count=20)
        want = float(np.quantile(slow, 0.95))
        # the windowed p95 reads the NEW era...
        assert abs(got - want) / want < 0.08, (got, want)
        # ...while the cumulative p95 is still dragged down by the
        # 10k-sample fast history (the lag the window exists to fix)
        assert h.quantile(0.95) < 0.8 * want
        # an empty/thin window reports None instead of a stale number
        base2 = h.snapshot_buckets()
        assert h.delta_quantile(base2, 0.95, min_count=20) is None
        for _ in range(5):
            h.record(0.01)
        assert h.delta_quantile(base2, 0.95, min_count=20) is None
        # one log bucket is ~7.5% wide and the windowed path has no
        # observed-min/max clamp to tighten it
        assert h.delta_quantile(base2, 0.95, min_count=5) \
            == pytest.approx(0.01, rel=0.1)


# -- spans -------------------------------------------------------------

class TestSpans:
    def test_nesting_and_histogram_feed(self):
        with telemetry.span("t.outer", journal=True):
            assert telemetry.span_stack() == ["t.outer"]
            with telemetry.span("t.inner", journal=True):
                assert telemetry.span_stack() == ["t.outer", "t.inner"]
                time.sleep(0.01)
            assert telemetry.span_stack() == ["t.outer"]
        assert telemetry.span_stack() == []
        assert telemetry.histogram("t.inner").count == 1
        assert telemetry.histogram("t.outer").count == 1
        assert telemetry.histogram("t.inner").min >= 0.01
        # outer wholly contains inner
        assert telemetry.histogram("t.outer").min >= \
            telemetry.histogram("t.inner").min

    def test_journal_lineage(self):
        with telemetry.span("t.a", journal=True, tag="x"):
            with telemetry.span("t.b", journal=True):
                pass
        ev_b = telemetry.recent_events("t.b")[-1]
        ev_a = telemetry.recent_events("t.a")[-1]
        assert ev_b["parent"] == "t.a" and ev_b["depth"] == 1
        assert ev_a["parent"] is None and ev_a["depth"] == 0
        assert ev_a["tag"] == "x"
        assert ev_a["seconds"] >= ev_b["seconds"]


# -- snapshot persistence ---------------------------------------------

class TestSnapshotFiles:
    def test_flush_writes_parseable_snapshot(self, tmp_path):
        telemetry.configure(str(tmp_path))
        telemetry.counter("t.f").inc(3)
        telemetry.event("t.flush_probe")
        path = telemetry.flush()
        assert path and os.path.basename(path) == \
            f"metrics-{os.getpid()}.json"
        snap = json.load(open(path))
        assert snap["counters"]["t.f"] == 3
        # the journal carries the event, one JSON object per line
        jf = os.path.join(str(tmp_path),
                          f"journal-{os.getpid()}.jsonl")
        lines = [json.loads(ln) for ln in open(jf)]
        assert any(ev["event"] == "t.flush_probe" for ev in lines)

    def test_concurrent_writer_torture(self, tmp_path):
        """Writers flushing in a loop while readers parse: every read
        of the snapshot file must yield complete JSON (the atomic
        tempfile+rename contract), and the metric values must be
        internally consistent."""
        telemetry.configure(str(tmp_path))
        c = telemetry.counter("t.torture")
        path = os.path.join(str(tmp_path),
                            f"metrics-{os.getpid()}.json")
        stop = threading.Event()
        errors = []

        def writer():
            while not stop.is_set():
                c.inc()
                telemetry.histogram("t.torture_h").record(0.01)
                telemetry.flush()

        def reader():
            seen = 0
            while not stop.is_set() or seen == 0:
                if not os.path.exists(path):
                    continue
                try:
                    with open(path) as f:
                        snap = json.load(f)
                except ValueError as e:      # a torn file
                    errors.append(repr(e))
                    return
                assert "counters" in snap
                seen += 1

        threads = [threading.Thread(target=writer) for _ in range(3)] \
            + [threading.Thread(target=reader) for _ in range(2)]
        for t in threads:
            t.start()
        time.sleep(1.0)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not errors, errors
        snap = json.load(open(path))
        assert snap["counters"]["t.torture"] > 0
        # no stray temp files survive the storm
        assert not glob.glob(os.path.join(str(tmp_path), "*.tmp"))

    def test_adopt_child_snapshot(self, tmp_path):
        telemetry.configure(str(tmp_path))
        child = {"pid": 99999, "counters": {"t.child": 7},
                 "histograms": {"t.ch": {
                     "count": 1, "sum": 0.5, "min": 0.5, "max": 0.5,
                     "buckets": {"1": 1}}}}
        cpath = os.path.join(str(tmp_path), "metrics-99999.json")
        json.dump(child, open(cpath, "w"))
        assert telemetry.adopt_child_snapshot(99999)
        assert telemetry.counter("t.child").value == 7
        assert telemetry.histogram("t.ch").count == 1
        # renamed so offline merging cannot double count it ...
        assert not os.path.exists(cpath)
        assert os.path.exists(cpath + ".merged")
        # ... and a second adopt is a no-op
        assert not telemetry.adopt_child_snapshot(99999)
        assert telemetry.counter("t.child").value == 7


# -- the per-generation hang reset (satellite) -------------------------

class _FakeProc:
    def poll(self):
        return None


class TestPoolGenerationReset:
    def test_last_hang_fields_reset_per_generation(self):
        """last_hang_kind/last_hang_wait described a hang from
        generations ago forever; evaluate_many must reset them so
        drill telemetry attributes hangs to the RIGHT generation
        (cumulative counts stay in the registry)."""
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        pool = ChipEvaluatorPool(["true"], workers=1)
        pool._note_hang("heartbeat", 12.0)    # generation N's hang
        assert pool.last_hang_kind == "heartbeat"
        assert pool.hangs_detected == 1
        pool._proc = _FakeProc()              # no real evaluator

        def fake_run_jobs(jobs, fits):
            for j in jobs:
                fits[j["id"]] = 1.0
            return {j["id"] for j in jobs}

        pool._run_jobs = fake_run_jobs
        fits = pool.evaluate_many([{"x": 1.0}])
        assert fits == [1.0]
        # generation N+1 saw no hang: the descriptors are fresh ...
        assert pool.last_hang_kind is None
        assert pool.last_hang_wait is None
        # ... while the cumulative registry count is untouched
        assert pool.hangs_detected == 1

    def test_registry_carries_hang_counters(self):
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        pool = ChipEvaluatorPool(["true"], workers=1)
        pool._note_hang("genome_deadline", 4.5)
        assert telemetry.counter("ga.hangs_detected").value == 1
        assert telemetry.gauge("ga.last_hang_wait").value == 4.5
        assert telemetry.recent_events("ga.hang_detected")
        # a second pool in the same process reports only its own share
        pool2 = ChipEvaluatorPool(["true"], workers=1)
        assert pool2.hangs_detected == 0
        assert pool.hangs_detected == 1


# -- fused runner telemetry -------------------------------------------

def _tiny_workflow(n_train=160, max_epochs=2, validation=True,
                   stream=False):
    from veles_tpu import prng
    from veles_tpu.datasets import synthetic_classification
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.ops.standard_workflow import StandardWorkflow
    prng.seed_all(1357)
    train, valid, _ = synthetic_classification(
        n_train, 40, (8, 8, 1), n_classes=4, seed=7)
    gd = {"learning_rate": 0.1}
    kw = {"max_resident_bytes": 0} if stream else {}
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid if validation else None,
            minibatch_size=20, name="loader", **kw),
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
             "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": gd},
        ],
        decision_config={"max_epochs": max_epochs}, name="tm_wf")


class TestFusedTelemetry:
    def test_dispatch_metrics_and_compile_split(self, tmp_path):
        from veles_tpu.backends import JaxDevice
        telemetry.configure(str(tmp_path))
        w = _tiny_workflow()
        w.initialize(device=JaxDevice(platform="cpu"))
        w.run()
        w.stop()
        snap = telemetry.snapshot()
        c = snap["counters"]
        assert c["fused.dispatches"] > 0
        assert c["fused.train_images"] == w.fused.processed_images
        assert c["fused.eval_images"] == w.fused.processed_eval_images
        assert c["loader.epochs"] == 2
        # compile/execute split: the first dispatch of each kind is a
        # gauge; the steady-state histogram holds the REST and its
        # p50/p99 are finite and ordered
        g = snap["gauges"]
        assert g["fused.first_train_submit_seconds"] > 0
        h = snap["histograms"]["fused.train_submit"]
        assert h["count"] > 0
        assert 0 < h["p50"] <= h["p99"] <= h["max"]
        # the first (compile) sample is far above the steady p99 on
        # any jitted backend
        assert g["fused.first_train_submit_seconds"] > h["p99"]
        assert telemetry.recent_events("fused.summary")
        # the flushed snapshot renders through obs_report
        telemetry.flush()
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        reg, snaps, journals, events = obs_report.load_dir(
            str(tmp_path))
        assert snaps and events
        text = obs_report.render(str(tmp_path), reg, snaps, journals,
                                 events)
        assert "fused.train_submit" in text
        assert "p99" in text and "fused train" in text
        # the sums of submit time are gone: on an asynchronous device
        # they are no denominator for a rate
        assert "fused.train_seconds" not in c

    def test_stream_bytes_property_backed_by_registry(self):
        from veles_tpu.backends import JaxDevice
        from veles_tpu.loader import ArrayLoader
        from veles_tpu import prng
        from veles_tpu.datasets import synthetic_classification
        from veles_tpu.ops.standard_workflow import StandardWorkflow
        prng.seed_all(1357)
        train, valid, _ = synthetic_classification(
            160, 40, (8, 8, 1), n_classes=4, seed=7)
        gd = {"learning_rate": 0.1}
        w = StandardWorkflow(
            loader_factory=lambda wf: ArrayLoader(
                wf, train=train, valid=valid, minibatch_size=20,
                name="loader", max_resident_bytes=0),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16}, "<-": gd},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": gd},
            ],
            decision_config={"max_epochs": 1}, name="tm_stream")
        w.initialize(device=JaxDevice(platform="cpu"))
        w.run()
        w.stop()
        assert w.fused.streaming
        # property and registry agree (single write site feeds both)
        assert w.fused.stream_transfer_bytes > 0
        assert telemetry.counter(
            "fused.stream_transfer_bytes").value == \
            w.fused.stream_transfer_bytes
        assert telemetry.counter(
            "fused.stream_transfer_seconds").value > 0
        # the property is read-only: the old mutation path is gone
        with pytest.raises(AttributeError):
            w.fused.stream_transfer_bytes = 0


# -- the training path's own spans (ISSUE 26) --------------------------

def _run_tiny(tmp_path=None, **kw):
    """A tiny StandardWorkflow through initialize/run/stop on XLA:CPU,
    its jitted steps wrapped to count the calls of each kind."""
    from veles_tpu.backends import JaxDevice
    if tmp_path is not None:
        telemetry.configure(str(tmp_path))
    w = _tiny_workflow(**kw)
    w.initialize(device=JaxDevice(platform="cpu"))
    calls = {"train": 0, "eval": 0, "fetch": 0}

    def counted(kind, fn):
        def call(*args):
            calls[kind] += 1
            return fn(*args)
        return call

    w.fused._train_step = counted("train", w.fused._train_step)
    w.fused._eval_step = counted("eval", w.fused._eval_step)
    w.fused.take_class_metrics = counted(
        "fetch", w.fused.take_class_metrics)
    w.run()
    w.stop()
    return w, calls


def _intervals(path, names):
    """{name: [(thread line, start_ns, end_ns)]} of the host-plane
    events with these names in a ``*.xplane.pb``."""
    import jax
    out = {n: [] for n in names}
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in out:
                    out[e.name].append(
                        (line.name, e.start_ns,
                         e.start_ns + e.duration_ns))
    return out


class TestTrainingPathSpans:
    def test_a_span_never_imports_jax(self):
        import subprocess
        code = ("import sys\n"
                "from veles_tpu import telemetry, units, workflow\n"
                "with telemetry.span('t.no_jax') as s:\n"
                "    pass\n"
                "assert telemetry.histogram('t.no_jax').count == 1\n"
                "assert 'jax' not in sys.modules, 'jax imported'\n")
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]

    @pytest.mark.parametrize("validation", [False, True],
                             ids=["train", "train+eval"])
    @pytest.mark.parametrize("stream", [False, True],
                             ids=["resident", "stream"])
    def test_counts_at_every_layer_boundary(self, validation, stream):
        w, calls = _run_tiny(max_epochs=3, validation=validation,
                             stream=stream)
        assert w.fused.streaming == stream
        h = telemetry.histogram
        assert h("workflow.initialize").count == 1
        assert h("workflow.run").count == 1
        assert h("workflow.run").sum == pytest.approx(w.wall_time)
        fired = [u for u in w.units if u.run_count]
        assert {"loader", "fused", "decision"} <= \
            {u.name for u in fired}
        for u in fired:
            assert h(u.name + ".run").count == u.run_count, u.name
            assert h(u.name + ".run").sum == \
                pytest.approx(u.run_time), u.name
            assert h("init." + u.name).count >= 1, u.name
        # the barrier of the loop: one fetch per class end; the host's
        # turnaround after each but the last, which ends the run
        class_ends = 3 * (2 if validation else 1)
        assert calls["fetch"] == class_ends
        assert h("fused.fetch_metrics").count == class_ends
        assert h("loop.turnaround").count == class_ends - 1
        g = telemetry.gauge
        for kind in ("train", "eval") if validation else ("train",):
            assert calls[kind] > 1
            assert g(f"fused.first_{kind}_submit_seconds").value > 0
            assert h(f"fused.first_{kind}_submit").count == 1
            assert h(f"fused.{kind}_submit").count == calls[kind] - 1
            assert telemetry.counter(
                f"fused.{kind}_wall_seconds").value > 0
        if not validation:
            assert h("fused.eval_submit").count == 0
        # (an attempt before the forwards are initialized fails with
        # AttributeError and Workflow.initialize retries it)
        assert h("fused.build_steps").count >= 1
        assert h("fused.ensure_params").count == 1
        assert h("fused.put_carry").count == w.fused.run_count

    def test_spans_nest_on_the_profilers_clock(self, tmp_path):
        import jax
        from veles_tpu.backends import JaxDevice
        w = _tiny_workflow(max_epochs=3)
        w.initialize(device=JaxDevice(platform="cpu"))
        jax.profiler.start_trace(str(tmp_path))
        try:
            w.run()
        finally:
            jax.profiler.stop_trace()
        w.stop()
        path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))[0]
        iv = _intervals(path, ["veles:workflow.run", "veles:fused.run",
                               "veles:fused.train_submit",
                               "veles:fused.fetch_metrics",
                               "veles:decision.run"])
        (line, lo, hi), = iv["veles:workflow.run"]
        assert len(iv["veles:fused.run"]) == w.fused.run_count
        assert iv["veles:fused.train_submit"]

        def inside(inner, outers):
            return any(o[0] == inner[0] and o[1] <= inner[1]
                       and inner[2] <= o[2] for o in outers)

        for sub in iv["veles:fused.train_submit"]:
            assert inside(sub, iv["veles:fused.run"]), sub
        for run in iv["veles:fused.run"]:
            assert inside(run, [(line, lo, hi)]), run
        for fetch in iv["veles:fused.fetch_metrics"]:
            assert inside(fetch, iv["veles:decision.run"]), fetch

    def test_every_layer_has_its_scope_in_the_lowered_step(self):
        import jax
        from veles_tpu.backends import JaxDevice
        w = _tiny_workflow(max_epochs=1)
        w.initialize(device=JaxDevice(platform="cpu"))
        step, seen = w.fused._train_step, []

        def capture(*args):
            seen.append(jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                               np.asarray(a).dtype),
                args))
            return step(*args)

        w.fused._train_step = capture
        w.run()
        w.stop()
        import re
        text = step.lower(*seen[0]).as_text(debug_info=True)
        scopes = [kind + f.name for f in w.forwards
                  for kind in ("fwd/", "bwd/", "update/")]
        # (``cast_params`` holds no op where the compute dtype is f32)
        for scope in scopes + ["gather", "loss"]:
            # the name stack of an op, whole or below the scan's body
            assert re.search(r'["/]' + re.escape(scope) + "/", text), \
                scope

    def test_compile_is_counted_with_the_spans_it_fell_in(self):
        import jax
        import jax.numpy as jnp
        from veles_tpu.engine import core as engine_core
        engine_core.watch_compiles()
        x = jnp.arange(8.0)
        salt = float(np.random.default_rng().integers(1, 1 << 30))
        fresh = jax.jit(lambda v: v * 3.0 + salt)
        n0 = telemetry.counter("xla.compiles").value
        with telemetry.span("t.compile_probe"):
            fresh(x).block_until_ready()
        mine = [e for e in telemetry.recent_events("xla.compile")
                if "t.compile_probe" in e["during"]]
        assert len(mine) == 1, mine
        assert mine[0]["seconds"] > 0 and mine[0]["cached"] is False
        assert telemetry.counter("xla.compiles").value == n0 + 1
        assert telemetry.counter("xla.compile_seconds").value > 0
        # outside any fused.* span: none of it is the step's
        assert telemetry.counter("fused.compile_seconds").value == 0
        for _ in range(10):
            fresh(x).block_until_ready()
        assert telemetry.counter("xla.compiles").value == n0 + 1

    def test_step_compiles_fall_inside_fused_spans(self):
        w, _ = _run_tiny(max_epochs=2)
        evs = [e for e in telemetry.recent_events("xla.compile")
               if "fused.first_train_submit" in e["during"]]
        assert evs and evs[0]["during"][:2] == ["workflow.run",
                                                "fused.run"]
        assert 0 < telemetry.counter("fused.compile_seconds").value \
            <= telemetry.counter("xla.compile_seconds").value
        # steady firings compile nothing: every step compile of the
        # run fell inside a first submit
        for e in telemetry.recent_events("xla.compile"):
            assert not any(s.endswith("_submit")
                           and ".first_" not in s
                           for s in e["during"]), e

    def test_disabled_records_nothing_and_opens_no_annotation(
            self, monkeypatch):
        import jax
        opened = []

        class Spy:
            def __init__(self, name):
                opened.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return None

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Spy)
        telemetry.set_enabled(False)
        try:
            with telemetry.span("t.off") as s:
                assert telemetry.span_stack() == []
        finally:
            telemetry.set_enabled(True)
        assert opened == [] and s.seconds >= 0
        assert telemetry.histogram("t.off").count == 0
        with telemetry.span("t.on"):
            pass
        assert opened == ["veles:t.on"]
        assert telemetry.histogram("t.on").count == 1

    def test_operators_train_row_divides_by_wall_time(self, tmp_path):
        """obs_report's "fused train" row is images over wall seconds
        between barriers — not over the host's submit seconds, which
        on an asynchronous device read orders of magnitude high."""
        import re
        from veles_tpu import obs
        w, _ = _run_tiny(tmp_path, n_train=320, max_epochs=12,
                         validation=False)
        telemetry.flush()
        reg, snaps, journals, evs = obs.load_dir(str(tmp_path))
        text = obs.render(str(tmp_path), reg, snaps, journals, evs)
        row = re.search(r"fused train: \S+ over \S+ engine-s -> "
                        r"(\S+) img/s", text)
        assert row, text
        shown = float(row.group(1).replace(",", ""))
        delivered = w.fused.processed_images / w.wall_time
        assert delivered / 2 < shown < delivered * 2
        c = telemetry.snapshot()["counters"]
        assert c["fused.train_images"] / c["fused.train_wall_seconds"] \
            == pytest.approx(shown, rel=0.01)

    def test_mfu_counts_mxu_work_only(self):
        """``mxu_train`` of the shipped AlexNet is the benchmark's
        independent count: 1 135 256 096 MACs x 2 x 3."""
        from veles_tpu import profiling
        from veles_tpu.backends import JaxDevice
        from veles_tpu.models import alexnet

        class Launcher:
            workflow = None

        w = alexnet.create_workflow(
            Launcher(),
            loader={"minibatch_size": 2, "n_train": 4, "n_valid": 2,
                    "shape": (227, 227, 3), "n_classes": 1000,
                    "noise": 0.5, "max_shift": 8, "seed": 1})
        w.initialize(device=JaxDevice(platform="cpu"))
        flops = profiling.model_flops_per_sample(w.forwards)
        assert flops["mxu_train"] == 6.0 * 1_135_256_096
        assert flops["mxu_train"] / 1e9 == pytest.approx(6.8115,
                                                         abs=5e-5)
        assert flops["train"] > flops["mxu_train"]
        w.stop()


# -- the real --serve round-trip merge --------------------------------

class TestServeChildMerge:
    def test_parent_merges_evaluator_child_snapshot(self, tmp_path,
                                                    monkeypatch):
        """A REAL chip-owning evaluator child (worker.py --serve)
        trains two genomes; its per-job telemetry (span histogram +
        the fused engine's own counters) flushes to the shared metrics
        dir and the pool folds it into the parent registry at close —
        one aggregate view for the whole GA process tree."""
        import textwrap

        from veles_tpu.genetics.pool import ChipEvaluatorPool
        mdir = tmp_path / "metrics"
        telemetry.configure(str(mdir))
        wf = tmp_path / "wf.py"
        wf.write_text(textwrap.dedent("""
            from veles_tpu.models import wine

            def run(launcher):
                launcher.create_workflow(wine.create_workflow)
                launcher.initialize()
                launcher.run()
        """))
        cfg = tmp_path / "cfg.py"
        cfg.write_text(textwrap.dedent("""
            from veles_tpu.config import root
            from veles_tpu.genetics import Tune

            root.wine.decision = {"max_epochs": 2}
            root.wine.layers = [
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 8},
                 "<-": {"learning_rate": Tune(0.3, 0.01, 1.0)}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.3}},
            ]
        """))
        lr = "wine.layers[0]['<-']['learning_rate']"
        pool = ChipEvaluatorPool(
            [sys.executable, "-m", "veles_tpu.genetics.worker",
             "--serve", str(wf), str(cfg), "-b", "cpu", "-s", "1234"],
            workers=2, timeout=600)
        with pool:
            child_pid = pool.hello["pid"]
            fits = pool.evaluate_many([{lr: 0.1}, {lr: 0.5}])
        assert all(np.isfinite(f) for f in fits), fits
        # the child's snapshot was merged and retired
        merged = os.path.join(str(mdir),
                              f"metrics-{child_pid}.json.merged")
        assert os.path.exists(merged), os.listdir(str(mdir))
        # parent registry now carries the child-side per-job record
        # AND the child's own fused-engine counters
        assert telemetry.counter("evaluator.jobs").value == 2
        assert telemetry.histogram(
            "evaluator.job_seconds").count == 2
        assert telemetry.counter("fused.dispatches").value > 0
        # per-genome distribution came from the parent's own clocking
        assert telemetry.histogram("ga.genome_seconds").count == 2
        assert telemetry.histogram(
            "ga.genome_seconds").quantile(0.99) > 0
        # the aggregate renders: per-genome p50/p99 + the child events
        telemetry.flush()
        sys.path.insert(0, os.path.join(REPO, "scripts"))
        try:
            import obs_report
        finally:
            sys.path.pop(0)
        reg, snaps, journals, events = obs_report.load_dir(str(mdir))
        text = obs_report.render(str(mdir), reg, snaps, journals,
                                 events)
        assert "ga.genome_seconds" in text
        assert "evaluator.job_seconds" in text
        assert reg.counters["evaluator.jobs"].value == 2
