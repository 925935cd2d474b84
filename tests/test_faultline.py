"""Faultline: deterministic fault injection + the supervision layer
it drills (ISSUE 6).

Covers: the registry's arming/matching semantics, CRC-checked
snapshot and GA-checkpoint persistence with newest-intact-predecessor
fallback, streaming-loader corrupt-file skip/count/threshold-abort,
OOM bounded degradation, and the headline acceptance: a HUNG (not
crashed) evaluator is detected and its genome re-dispatched within
the heartbeat deadline, with the generation completing at fitness
parity.
"""

import json
import os
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from veles_tpu import faults, prng
from veles_tpu.genetics import GeneticOptimizer, Tune
from veles_tpu.genetics.pool import ChipEvaluatorPool
from veles_tpu.snapshotter import (SnapshotCorruptError, load_workflow,
                                   save_workflow)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _disarm():
    """Every test starts and ends disarmed, whatever it armed."""
    faults.arm("")
    yield
    faults.arm("")


pytestmark = pytest.mark.chaos


class TestFaultRegistry:
    def test_disarmed_is_noop(self):
        assert not faults.active()
        assert faults.fire("evaluator.hang", seq=0) is None

    def test_qualifier_matching(self):
        faults.arm("stream.corrupt_file@index=7")
        assert faults.fire("stream.corrupt_file", index=3) is None
        hit = faults.fire("stream.corrupt_file", index=7)
        assert hit and hit["point"] == "stream.corrupt_file"

    def test_missing_context_key_never_matches(self):
        # @gen=2 must be inert at call sites that don't know gen
        faults.arm("checkpoint.corrupt@gen=2")
        assert faults.fire("checkpoint.corrupt") is None
        assert faults.fire("checkpoint.corrupt", gen=1) is None
        assert faults.fire("checkpoint.corrupt", gen=2)

    def test_times_budget_default_one(self):
        faults.arm("evaluator.garbage_line")
        assert faults.fire("evaluator.garbage_line", seq=0)
        assert faults.fire("evaluator.garbage_line", seq=1) is None

    def test_times_n_and_unlimited(self):
        faults.arm("evaluator.garbage_line@times=2,"
                   "snapshot.torn_write@times=*")
        assert faults.fire("evaluator.garbage_line")
        assert faults.fire("evaluator.garbage_line")
        assert faults.fire("evaluator.garbage_line") is None
        for _ in range(5):
            assert faults.fire("snapshot.torn_write")

    def test_knobs_ride_payload_not_matching(self):
        faults.arm("evaluator.hang@seq=1&silent=1&seconds=30")
        hit = faults.fire("evaluator.hang", seq=1)
        assert hit["silent"] == "1" and float(hit["seconds"]) == 30.0

    def test_unknown_point_rejected(self):
        with pytest.raises(ValueError, match="unknown injection"):
            faults.arm("evaluator.hagn")

    def test_env_inheritance(self, monkeypatch):
        """arm(None) reads the env var — what spawned children do at
        import."""
        monkeypatch.setenv(faults.ENV_VAR, "snapshot.torn_write")
        faults.arm(None)
        assert faults.fire("snapshot.torn_write", path="x")

    def test_garbage_is_deterministic_and_not_json(self):
        a = faults.garbage_text(point="evaluator")
        assert a == faults.garbage_text(point="evaluator")
        with pytest.raises(ValueError):
            json.loads(a)


class TestSnapshotIntegrity:
    def test_crc_roundtrip(self, tmp_path):
        p = str(tmp_path / "snap_epoch1.pickle.gz")
        save_workflow({"k": [1, 2, 3]}, p)
        assert load_workflow(p) == {"k": [1, 2, 3]}

    def test_torn_write_detected_and_falls_back(self, tmp_path):
        p1 = str(tmp_path / "snap_epoch1.pickle.gz")
        p2 = str(tmp_path / "snap_epoch2.pickle.gz")
        save_workflow({"marker": 1}, p1)
        faults.arm("snapshot.torn_write")
        save_workflow({"marker": 2}, p2)
        with pytest.raises(SnapshotCorruptError):
            load_workflow(p2)
        # fallback: newest INTACT predecessor, not a crash and not a
        # silent fresh start
        assert load_workflow(p2, fallback=True) == {"marker": 1}

    def test_bitflip_detected_by_crc(self, tmp_path):
        # uncompressed container so the flip hits the payload, not a
        # gzip header the codec would catch first
        p = str(tmp_path / "snap_epoch1.pickle")
        save_workflow({"marker": 1}, p)
        raw = bytearray(open(p, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(p, "wb").write(bytes(raw))
        with pytest.raises(SnapshotCorruptError):
            load_workflow(p)

    def test_no_intact_predecessor_raises(self, tmp_path):
        p = str(tmp_path / "snap_epoch1.pickle.gz")
        save_workflow({"marker": 1}, p)
        os.truncate(p, os.path.getsize(p) // 2)
        with pytest.raises(SnapshotCorruptError):
            load_workflow(p, fallback=True)

    def test_legacy_format_still_loads(self, tmp_path):
        import gzip
        import pickle

        from veles_tpu import prng as _prng
        p = str(tmp_path / "snap_epoch1.pickle.gz")
        payload = {"format": 1, "workflow": {"legacy": True},
                   "prng": _prng.snapshot_state(), "timestamp": 0.0}
        with gzip.open(p, "wb") as f:
            pickle.dump(payload, f)
        assert load_workflow(p) == {"legacy": True}

    def test_concurrent_writers_do_not_tear(self, tmp_path):
        """The old shared ``path + '.tmp'`` name let two writers tear
        each other; pid/thread-unique temp files + os.replace make
        concurrent saves atomic — the survivor is always intact."""
        p = str(tmp_path / "snap_epoch1.pickle")
        errors = []

        def writer(marker):
            try:
                for _ in range(10):
                    save_workflow({"marker": marker}, p)
            except BaseException as e:  # noqa: BLE001
                errors.append(e)

        ts = [threading.Thread(target=writer, args=(m,))
              for m in (1, 2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not errors
        assert load_workflow(p)["marker"] in (1, 2)
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".tmp")]


TUNES = {"x": Tune(5.0, -10.0, 10.0), "y": Tune(-3.0, -10.0, 10.0)}


def quad(v):
    return (v["x"] - 2.0) ** 2 + (v["y"] + 1.0) ** 2


class TestGACheckpointIntegrity:
    def test_corrupt_checkpoint_falls_back_bit_identically(
            self, tmp_path):
        state = str(tmp_path / "ga.json")
        prng.seed_all(4242)
        _, fit_ref = GeneticOptimizer(
            quad, TUNES, population=6, generations=4,
            state_path=str(tmp_path / "ref.json")).run()
        # run again with the FINAL checkpoint write torn by the
        # injected fault, then resume: .prev must carry it to the
        # same answer bit-identically
        prng.seed_all(4242)
        faults.arm("checkpoint.corrupt@gen=4")
        GeneticOptimizer(quad, TUNES, population=6, generations=4,
                         state_path=state).run()
        faults.arm("")
        prng.seed_all(999999)   # resume restores the rng from disk
        _, fit2 = GeneticOptimizer(quad, TUNES, population=6,
                                   generations=4,
                                   state_path=state).run()
        assert fit2 == pytest.approx(fit_ref, abs=0)

    def test_both_corrupt_raises_never_fresh_start(self, tmp_path):
        state = str(tmp_path / "ga.json")
        prng.seed_all(1)
        GeneticOptimizer(quad, TUNES, population=4, generations=2,
                         state_path=state).run()
        os.truncate(state, os.path.getsize(state) // 2)
        os.truncate(state + ".prev",
                    os.path.getsize(state + ".prev") // 2)
        with pytest.raises(SnapshotCorruptError):
            GeneticOptimizer(quad, TUNES, population=4, generations=2,
                             state_path=state).run()

    def test_state_file_is_plain_json_with_crc(self, tmp_path):
        state = str(tmp_path / "ga.json")
        prng.seed_all(1)
        GeneticOptimizer(quad, TUNES, population=4, generations=1,
                         state_path=state).run()
        st = json.load(open(state))
        assert st["generation"] == 1 and "crc32" in st

    def test_embedded_crc_catches_value_corruption(self, tmp_path):
        state = str(tmp_path / "ga.json")
        prng.seed_all(1)
        GeneticOptimizer(quad, TUNES, population=4, generations=1,
                         state_path=state).run()
        st = json.load(open(state))
        st["fits"][0] = 0.0    # a bit-flip that stays valid JSON
        json.dump(st, open(state, "w"))
        os.remove(state + ".prev")
        with pytest.raises(SnapshotCorruptError):
            GeneticOptimizer(quad, TUNES, population=4, generations=1,
                             state_path=state).run()


class TestLoaderCorruptFiles:
    @pytest.fixture
    def image_tree(self, tmp_path):
        PIL = pytest.importorskip("PIL.Image")
        rng = np.random.default_rng(7)
        paths = []
        for i in range(12):
            p = str(tmp_path / f"img_{i:02d}.png")
            PIL.fromarray(
                rng.integers(0, 255, (8, 8, 3), dtype="uint8")).save(p)
            paths.append((p, i % 3))
        return paths

    def _loader(self, paths, **kw):
        from veles_tpu.loader.image import FileListImageLoader
        kw.setdefault("corrupt_tolerance", 0.1)
        kw.setdefault("streaming", False)
        return FileListImageLoader(
            train=paths, minibatch_size=4, target_shape=(8, 8, 3),
            name="chaosldr", **kw)

    def test_corrupt_file_skipped_and_counted(self, image_tree):
        faults.arm("stream.corrupt_file@index=7")
        ld = self._loader(image_tree)
        ld.load_data()
        assert ld.corrupt_indices == {7}
        data = ld.original_data.mem
        assert not data[7].any()          # zero row substituted
        assert all(data[i].any() for i in range(12) if i != 7)

    def test_really_corrupt_file_skipped(self, image_tree, tmp_path):
        """No injection: an actually-truncated PNG takes the same
        path."""
        bad_path = image_tree[5][0]
        raw = open(bad_path, "rb").read()
        open(bad_path, "wb").write(raw[: len(raw) // 3])
        ld = self._loader(image_tree)
        ld.load_data()
        assert ld.corrupt_indices == {5}

    def test_over_threshold_aborts_loudly(self, image_tree):
        faults.arm("stream.corrupt_file@index=3,"
                   "stream.corrupt_file@index=4,"
                   "stream.corrupt_file@index=5")
        ld = self._loader(image_tree)
        with pytest.raises(RuntimeError, match="corrupt_tolerance"):
            ld.load_data()

    def test_zero_tolerance_aborts_on_first(self, image_tree):
        faults.arm("stream.corrupt_file@index=2")
        ld = self._loader(image_tree, corrupt_tolerance=0.0)
        with pytest.raises(RuntimeError, match="corrupt_tolerance"):
            ld.load_data()

    def test_streaming_mode_skips_mid_epoch(self, image_tree):
        """The streaming decode path (assemble_rows on the prefetch
        thread) skips-and-counts the same way."""
        faults.arm("stream.corrupt_file@index=9")
        ld = self._loader(image_tree, streaming=True)
        ld.load_data()
        assert ld._stream
        ld.post_load_data()
        data, labels, _ = ld.assemble_rows(np.arange(12))
        assert ld.corrupt_indices == {9}
        assert not data[9].any() and data[0].any()


class TestOOMDegradation:
    def _workflow(self, streaming):
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
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 16}, "<-": gd},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": gd},
            ],
            decision_config={"max_epochs": 2}, name="oom_wf")

    def test_resident_upload_oom_degrades_to_streaming(self):
        from veles_tpu.backends import JaxDevice
        w = self._workflow(streaming=False)
        faults.arm("device.oom_on_put@site=resident_dataset")
        w.initialize(device=JaxDevice(platform="cpu"))
        faults.arm("")
        assert not w.loader.device_resident
        assert w.fused.streaming
        w.run()
        hist = [h for h in w.decision.history
                if h["class"] == "validation"]
        assert hist and np.isfinite(hist[-1]["loss"])
        w.stop()

    def test_streaming_put_oom_drains_and_retries(self):
        from veles_tpu.backends import JaxDevice
        w = self._workflow(streaming=True)
        w.initialize(device=JaxDevice(platform="cpu"))
        faults.arm("device.oom_on_put@site=stream")
        w.run()
        faults.arm("")
        assert w.fused.stream_oom_retries == 1
        hist = [h for h in w.decision.history
                if h["class"] == "validation"]
        assert hist and np.isfinite(hist[-1]["loss"])
        w.stop()


HANG_WORKER = """
import json, os, sys, threading, time

hang_seq = int(sys.argv[1])        # job ordinal to hang on
silent = sys.argv[2] == "silent"   # stop heartbeats while hung
hb_every = float(sys.argv[3])
sentinel = sys.argv[4]             # hang only once across restarts
state = {"silent": False}
lock = threading.Lock()

def emit(o):
    with lock:
        print(json.dumps(o), flush=True)

emit({"ready": True, "pid": os.getpid(), "backend": "cpu",
      "platform": "cpu", "is_accelerator": False})

def hb():
    n = 0
    while True:
        time.sleep(hb_every)
        if not state["silent"]:
            emit({"hb": n, "pid": os.getpid()})
            n += 1

if hb_every > 0:
    threading.Thread(target=hb, daemon=True).start()

seq = 0
for line in sys.stdin:
    job = json.loads(line)
    if job.get("op") == "shutdown":
        break
    if seq == hang_seq and not os.path.exists(sentinel):
        open(sentinel, "w").close()
        state["silent"] = silent
        time.sleep(3600)           # the hang: alive but stuck
    time.sleep(0.2)                # "training"
    emit({"id": job["id"], "fitness": float(job["values"]["x"])})
    seq += 1
"""


class TestHungEvaluatorSupervision:
    """Acceptance: an injected evaluator HANG (process alive, no
    crash) is detected and the genome re-dispatched within the
    heartbeat deadline, the generation completes, and fitness parity
    is preserved."""

    def make_pool(self, tmp_path, hang_seq, mode, **kw):
        worker = tmp_path / "hang_worker.py"
        worker.write_text(HANG_WORKER)
        kw.setdefault("heartbeat_deadline", 2.0)
        kw.setdefault("restart_backoff", 0.1)
        return ChipEvaluatorPool(
            [sys.executable, str(worker), str(hang_seq), mode, "0.2",
             str(tmp_path / "hung_once")],
            workers=2, timeout=120, **kw)

    def test_silent_hang_caught_by_heartbeat_deadline(self, tmp_path):
        pool = self.make_pool(tmp_path, hang_seq=1, mode="silent",
                              min_genome_deadline=60)
        t0 = time.monotonic()
        with pool:
            fits = pool.evaluate_many(
                [{"x": 1.0}, {"x": 2.0}, {"x": 3.0}])
        wall = time.monotonic() - t0
        assert fits == [1.0, 2.0, 3.0]        # parity: no unfair inf
        assert pool.hangs_detected == 1
        assert pool.last_hang_kind == "heartbeat"
        # detection within the deadline (+ one 1s poll slice of slack)
        assert pool.last_hang_wait <= 2.0 + 1.5
        assert wall < 30.0

    def test_live_hang_caught_by_adaptive_deadline(self, tmp_path):
        """Heartbeats keep flowing (the process is alive, the genome
        is stuck) — the EMA-scaled per-genome deadline catches it
        without waiting for the 120s whole-genome timeout."""
        pool = self.make_pool(tmp_path, hang_seq=2, mode="live",
                              min_genome_deadline=1.0,
                              genome_deadline_factor=4.0)
        with pool:
            fits = pool.evaluate_many(
                [{"x": 1.0}, {"x": 2.0}, {"x": 3.0}, {"x": 4.0}])
        assert fits == [1.0, 2.0, 3.0, 4.0]
        assert pool.hangs_detected == 1
        assert pool.last_hang_kind == "genome_deadline"
        assert pool.genome_duration_ema < 2.0
        assert pool.last_hang_wait < 10.0

    def test_twice_hung_genome_scores_inf_and_queue_drains(
            self, tmp_path):
        # hang keyed on the GENOME (x == 1.0), not the job ordinal:
        # the poisoned genome hangs EVERY evaluator it reaches — lost
        # twice, it must score inf without condemning its neighbors
        worker = tmp_path / "hang_worker.py"
        worker.write_text(HANG_WORKER.replace(
            "if seq == hang_seq and not os.path.exists(sentinel):",
            "if job[\"values\"][\"x\"] == 1.0:"))
        pool = ChipEvaluatorPool(
            [sys.executable, str(worker), "0", "silent", "0.2",
             str(tmp_path / "unused")],
            workers=2, timeout=120, heartbeat_deadline=2.0,
            restart_backoff=0.1)
        with pool:
            fits = pool.evaluate_many([{"x": 1.0}, {"x": 2.0}])
        # the always-hanging genome 1 lost two evaluators -> inf; the
        # NEXT genome still resolves on the third evaluator
        assert fits[0] == float("inf")
        assert fits[1] == 2.0
        assert pool.hangs_detected >= 2

    def test_real_evaluator_hang_injected_via_env(self, tmp_path,
                                                  monkeypatch):
        """End to end on the REAL serve-mode evaluator: VELES_FAULTS
        hangs it silently mid-genome; the pool replaces it within the
        heartbeat deadline and the generation completes with finite
        fitnesses."""
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
        # job=2&seq=1: the hang fires when wire-job 2 runs as the
        # SECOND job of an evaluator — true on the first evaluator,
        # false on the replacement (where the retried job 2 comes
        # first), so the drill injects exactly one hang
        monkeypatch.setenv(
            "VELES_FAULTS",
            "evaluator.hang@job=2&seq=1&silent=1&seconds=600,"
            "evaluator.garbage_line@job=1")
        lr = "wine.layers[0]['<-']['learning_rate']"
        pool = ChipEvaluatorPool(
            [sys.executable, "-m", "veles_tpu.genetics.worker",
             "--serve", str(wf), str(cfg), "-b", "cpu", "-s", "1234",
             "--heartbeat-every", "0.5"],
            workers=2, timeout=600, heartbeat_deadline=8.0,
            restart_backoff=0.1)
        first_pid = None
        with pool:
            first_pid = pool.hello["pid"]
            fits = pool.evaluate_many(
                [{lr: 0.1}, {lr: 0.3}, {lr: 0.6}])
        assert all(np.isfinite(f) for f in fits), fits
        assert pool.hangs_detected == 1
        assert pool.last_hang_kind == "heartbeat"
        assert pool.last_hang_wait <= 8.0 + 2.0   # within the deadline
        assert pool.hello["pid"] != first_pid     # replaced

    def test_restart_backoff_applied_on_storms(self, tmp_path):
        """Consecutive restarts back off exponentially (with jitter):
        an evaluator that dies instantly cannot respawn-storm."""
        worker = tmp_path / "crash_worker.py"
        worker.write_text(textwrap.dedent("""
            import json, os, sys
            print(json.dumps({"ready": True, "pid": os.getpid(),
                              "backend": "cpu", "platform": "cpu",
                              "is_accelerator": False}), flush=True)
            for line in sys.stdin:
                os._exit(1)   # dies on EVERY job
        """))
        pool = ChipEvaluatorPool(
            [sys.executable, str(worker)], workers=1, timeout=30,
            heartbeat_deadline=5.0, restart_backoff=0.2,
            restart_backoff_cap=1.0, max_barren_restarts=3)
        t0 = time.monotonic()
        with pool:
            fits = pool.evaluate_many([{"x": 1.0}, {"x": 2.0}])
        wall = time.monotonic() - t0
        assert fits == [float("inf")] * 2
        assert pool.restarts >= 2
        # at least one backoff sleep happened (>= 0.75 * 0.2s), and
        # the bailout kept the whole thing bounded
        assert 0.15 < wall < 30.0


class TestGenerationTagging:
    def test_optimizer_exports_generation_env(self):
        gens = []

        def spy(values_list):
            gens.append(os.environ.get("VELES_GA_GENERATION"))
            return [quad(v) for v in values_list]

        prng.seed_all(7)
        GeneticOptimizer(quad, TUNES, population=4, generations=2,
                         evaluate_many=spy).run()
        assert gens == ["0", "1", "2"]


class TestCorruptCacheCounting:
    def test_cifar_corrupt_cache_counted_once(self, tmp_path):
        from veles_tpu import datasets
        from veles_tpu.config import root
        root.common.data_dir = str(tmp_path)
        d = tmp_path / "cifar10"
        d.mkdir()
        for name in ([b + ".bin" for b in
                      datasets._CIFAR10_TRAIN_BATCHES]
                     + [datasets._CIFAR10_TEST_BATCH + ".bin"]):
            (d / name).write_bytes(b"garbage" * 1000)
        before = datasets.corrupt_cache_count()
        assert datasets.try_load_real_cifar10() is None
        assert datasets.corrupt_cache_count() == before + 1
