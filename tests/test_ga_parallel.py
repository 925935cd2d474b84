"""Parallel, restartable GA tuner (round-2 VERDICT next #6):
subprocess-per-genome isolation, N workers, per-generation checkpoint,
resume after an uncontrolled kill."""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.genetics import GeneticOptimizer, Tune

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def quad(v):
    return (v["x"] - 2.0) ** 2 + (v["y"] + 1.0) ** 2


TUNES = {"x": Tune(5.0, -10.0, 10.0), "y": Tune(-3.0, -10.0, 10.0)}


class TestCheckpointResume:
    def test_interrupted_run_resumes_bit_identically(self, tmp_path):
        state = str(tmp_path / "ga.json")

        prng.seed_all(4242)
        best_ref, fit_ref = GeneticOptimizer(
            quad, TUNES, population=8, generations=6).run()

        # same seed, but die mid-generation-3 (KeyboardInterrupt is not
        # swallowed by the bad-gene guard)
        calls = {"n": 0}

        def dying(v):
            calls["n"] += 1
            if calls["n"] > 20:
                raise KeyboardInterrupt
            return quad(v)

        prng.seed_all(4242)
        with pytest.raises(KeyboardInterrupt):
            GeneticOptimizer(dying, TUNES, population=8, generations=6,
                             state_path=state).run()
        assert os.path.exists(state)
        gen_at_death = json.load(open(state))["generation"]
        assert 0 < gen_at_death < 6

        # resume: rng state comes from the file, so the completed run
        # must equal the uninterrupted one exactly
        prng.seed_all(999999)  # proves the stream seed is irrelevant
        best2, fit2 = GeneticOptimizer(
            quad, TUNES, population=8, generations=6,
            state_path=state).run()
        assert best2 == pytest.approx(best_ref)
        assert fit2 == pytest.approx(fit_ref)
        assert json.load(open(state))["generation"] == 6

    def test_stale_state_for_other_genes_rejected(self, tmp_path):
        state = str(tmp_path / "ga.json")
        prng.seed_all(1)
        GeneticOptimizer(quad, TUNES, population=4, generations=1,
                         state_path=state).run()
        with pytest.raises(ValueError, match="stale"):
            GeneticOptimizer(lambda v: v["z"],
                             {"z": Tune(0.0, -1.0, 1.0)},
                             population=4, generations=1,
                             state_path=state).run()

    def test_evaluate_many_used(self):
        batches = []

        def many(values_list):
            batches.append(len(values_list))
            return [quad(v) for v in values_list]

        prng.seed_all(7)
        GeneticOptimizer(quad, TUNES, population=6, generations=2,
                         evaluate_many=many).run()
        assert batches[0] == 6          # initial population as a batch
        assert all(b == 4 for b in batches[1:])  # pop - elite


class TestDeviceRacePolicy:
    """Parallel genome workers must never race to initialize an
    exclusive TPU chip (round-3 VERDICT next #8).  Since ISSUE 3 the
    ``auto`` answer is the chip-owning evaluator: ONE serve-mode
    subprocess owns the device, the N workers become host prep threads
    — the chip is used by default AND the race is structurally gone."""

    def test_auto_routes_to_chip_evaluator(self):
        from veles_tpu.__main__ import _resolve_ga_execution
        assert _resolve_ga_execution("auto", 4) == (4, "tpu-evaluator")
        assert _resolve_ga_execution("auto", 1) == (1, "tpu-evaluator")
        assert _resolve_ga_execution("tpu-evaluator", 3) == \
            (3, "tpu-evaluator")

    def test_explicit_tpu_parallel_serializes(self):
        from veles_tpu.__main__ import _resolve_ga_execution
        assert _resolve_ga_execution("tpu", 4) == (1, "tpu")

    def test_cpu_and_single_worker_unchanged(self):
        from veles_tpu.__main__ import _resolve_ga_execution
        assert _resolve_ga_execution("cpu", 4) == (4, "cpu")
        assert _resolve_ga_execution("numpy", 3) == (3, "numpy")
        assert _resolve_ga_execution("tpu", 1) == (1, "tpu")


@pytest.fixture
def tuned_workflow(tmp_path):
    wf = tmp_path / "wf.py"
    wf.write_text(textwrap.dedent("""
        from veles_tpu.models import mnist

        def run(launcher):
            launcher.create_workflow(mnist.create_workflow)
            launcher.initialize()
            launcher.run()
    """))
    cfg = tmp_path / "cfg.py"
    cfg.write_text(textwrap.dedent("""
        from veles_tpu.config import root
        from veles_tpu.genetics import Tune

        root.mnist.loader = {"minibatch_size": 25, "n_train": 100,
                             "n_valid": 40}
        root.mnist.decision = {"max_epochs": 1}
        root.mnist.layers = [
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": Tune(16, 8, 32)},
             "<-": {"learning_rate": Tune(0.1, 0.01, 1.0)}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": {"learning_rate": 0.1}},
        ]
    """))
    return str(wf), str(cfg)


def ga_cmd(wf, cfg, state, pop_gen="3:2", workers="2"):
    return [sys.executable, "-m", "veles_tpu", "-b", "cpu",
            "--optimize", pop_gen, "--ga-workers", workers,
            "--ga-state", state, wf, cfg]


class TestChipEvaluatorPool:
    """The tpu-evaluator execution mode (round-4/5 VERDICT weak:
    `_resolve_ga_execution("auto", N>1)` used to idle the chip):
    exactly one serve-mode evaluator process owns the device and
    evaluates every genome; prep workers are host threads."""

    def serve_cmd(self, wf, cfg, backend="cpu"):
        return [sys.executable, "-m", "veles_tpu.genetics.worker",
                "--serve", wf, cfg, "-b", backend, "-s", "1234"]

    def test_one_process_evaluates_all_genomes(self, tuned_workflow):
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        wf, cfg = tuned_workflow
        good = {"mnist.layers[0]['->']['output_sample_shape']": 16,
                "mnist.layers[0]['<-']['learning_rate']": 0.1}
        other = dict(good)
        other["mnist.layers[0]['<-']['learning_rate']"] = 0.3
        with ChipEvaluatorPool(self.serve_cmd(wf, cfg), workers=2,
                               timeout=300) as pool:
            hello = pool.hello
            assert hello["ready"] and hello["pid"] > 0
            # in the CPU suite the device is XLA:CPU — not an
            # accelerator, which is exactly what the `auto` fallback
            # policy keys on
            assert hello["platform"] == "cpu"
            assert not pool.is_accelerator
            fits = pool.evaluate_many([good, other])
            assert len(fits) == 2
            assert all(np.isfinite(f) for f in fits), fits
            # different genomes produced different trainings
            assert fits[0] != fits[1] or fits[0] >= 0
            # a later call reuses the SAME evaluator process
            pid_before = pool.hello["pid"]
            assert np.isfinite(pool.evaluate_one(good))
            assert pool.hello["pid"] == pid_before

    def test_bad_genome_scores_inf_and_evaluator_survives(
            self, tuned_workflow):
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        wf, cfg = tuned_workflow
        good = {"mnist.layers[0]['->']['output_sample_shape']": 16,
                "mnist.layers[0]['<-']['learning_rate']": 0.1}
        bad = dict(good)
        bad["mnist.layers[0]['->']['output_sample_shape']"] = -5
        with ChipEvaluatorPool(self.serve_cmd(wf, cfg), workers=2,
                               timeout=300) as pool:
            fits = pool.evaluate_many([good, bad, good])
            assert np.isfinite(fits[0])
            assert fits[1] == float("inf")
            assert np.isfinite(fits[2])  # the queue kept draining

    def test_cli_explicit_tpu_evaluator_needs_a_tpu(self, tuned_workflow):
        """`-b tpu-evaluator` names the chip: with none (this suite
        pins XLA:CPU) the run FAILS, naming the missing device — it
        neither trains on an XLA:CPU evaluator under the chip's name
        nor drops to the cpu fan-out the way the announced `auto`
        does."""
        wf, cfg = tuned_workflow
        res = subprocess.run(
            [sys.executable, "-m", "veles_tpu", "-b", "tpu-evaluator",
             "--optimize", "3:1", "--ga-workers", "2", wf, cfg],
            capture_output=True, text=True, cwd=REPO, timeout=600)
        assert res.returncode == 1, res.stderr[-2000:]
        assert "needs its evaluator on a TPU" in res.stderr
        assert "falling back" not in res.stderr
        assert "fitness" not in res.stdout

    def test_cli_auto_falls_back_without_accelerator(
            self, tuned_workflow):
        """`-b auto` probes the device ONLY inside the evaluator
        child; with no accelerator (this suite pins XLA:CPU) the run
        falls back to the classic cpu subprocess fan-out and still
        completes."""
        wf, cfg = tuned_workflow
        res = subprocess.run(
            [sys.executable, "-m", "veles_tpu", "-b", "auto",
             "--optimize", "2:1", "--ga-workers", "2", wf, cfg],
            capture_output=True, text=True, cwd=REPO, timeout=600)
        assert res.returncode == 0, res.stderr[-2000:]
        assert "falling back" in res.stderr
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert np.isfinite(out["fitness"])

    def test_respawn_on_another_platform_is_refused(self):
        """A replacement evaluator that finds the chip still held
        comes up on XLA:CPU; finishing the run there under the same
        fitness fields is the fallback PR 21 removed."""
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        fake = ("import json, os, sys; print(json.dumps({'ready': True,"
                " 'pid': os.getpid(), 'backend': 'jax', 'platform':"
                " sys.argv[1]}), flush=True); sys.stdin.read()")
        pool = ChipEvaluatorPool([sys.executable, "-c", fake, "tpu"],
                                 workers=1, timeout=60)
        try:
            assert pool.start()["platform"] == "tpu"
            pool._kill()
            pool.worker_cmd[-1] = "cpu"
            with pytest.raises(RuntimeError,
                               match="respawned on 'cpu'"):
                pool.start()
            assert pool.platform == "tpu"
        finally:
            pool.close()

    def test_tpu_evaluator_without_optimize_rejected(self):
        res = subprocess.run(
            [sys.executable, "-m", "veles_tpu", "-b", "tpu-evaluator",
             "nonexistent_wf.py"],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert res.returncode == 2
        assert "--optimize" in res.stderr


DYING_WORKER = """
import json, os, sys

sentinel = sys.argv[1]
print(json.dumps({"ready": True, "pid": os.getpid(),
                  "backend": "cpu", "platform": "cpu",
                  "is_accelerator": False}), flush=True)
for line in sys.stdin:
    job = json.loads(line)
    if job.get("op") == "shutdown":
        break
    if not os.path.exists(sentinel):
        # first delivery EVER: die mid-genome (simulates an
        # evaluator-side crash that is not the genome's fault)
        open(sentinel, "w").close()
        os._exit(1)
    print(json.dumps({"id": job["id"],
                      "fitness": float(job["values"]["x"])}),
          flush=True)
"""


class TestEvaluatorDeathRetry:
    """An evaluator-side death must not condemn the in-flight genome:
    it is retried ONCE on the fresh evaluator before scoring inf."""

    def make_pool(self, tmp_path, timeout=60):
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        worker = tmp_path / "dying_worker.py"
        worker.write_text(DYING_WORKER)
        sentinel = tmp_path / "died_once"
        return ChipEvaluatorPool(
            [sys.executable, str(worker), str(sentinel)],
            workers=2, timeout=timeout)

    def test_in_flight_genome_retried_once_then_scores(self, tmp_path):
        with self.make_pool(tmp_path) as pool:
            first_pid = pool.hello["pid"]
            fits = pool.evaluate_many([{"x": 1.5}, {"x": 2.5}])
            # the worker died on genome 1's first delivery; the retry
            # on the fresh evaluator succeeded — NO unfair inf
            assert fits == [1.5, 2.5]
            assert pool.hello["pid"] != first_pid   # restarted

    def test_twice_lost_genome_scores_inf(self, tmp_path):
        with self.make_pool(tmp_path) as pool:
            # poison pill: the worker dies whenever x is the string
            # "die" (float() raises -> worker crashes uncleanly)
            import os
            sentinel = tmp_path / "died_once"
            open(sentinel, "w").close()   # skip the one-time death
            assert os.path.exists(sentinel)
            fits = pool.evaluate_many([{"x": "die"}, {"x": 3.5}])
            assert fits[0] == float("inf")   # lost twice -> inf
            assert fits[1] == 3.5            # queue kept draining


class TestSubprocessGA:
    def test_worker_evaluates_one_genome(self, tuned_workflow):
        wf, cfg = tuned_workflow
        res = subprocess.run(
            [sys.executable, "-m", "veles_tpu.genetics.worker",
             wf, cfg, "-b", "cpu", "--values",
             json.dumps({"mnist.layers[0]['->']"
                         "['output_sample_shape']": 16,
                         "mnist.layers[0]['<-']"
                         "['learning_rate']": 0.1})],
            capture_output=True, text=True, cwd=REPO, timeout=300)
        assert res.returncode == 0, res.stderr[-2000:]
        fit = json.loads(res.stdout.strip().splitlines()[-1])["fitness"]
        assert np.isfinite(fit) and fit >= 0

    def test_parallel_ga_completes_and_resumes_after_kill(
            self, tuned_workflow, tmp_path):
        wf, cfg = tuned_workflow
        state = str(tmp_path / "ga_state.json")

        # start, then kill -9 once generation 1 is checkpointed
        proc = subprocess.Popen(ga_cmd(wf, cfg, state),
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True,
                                cwd=REPO)
        deadline = time.time() + 600
        killed = False
        while time.time() < deadline:
            if os.path.exists(state) and \
                    json.load(open(state))["generation"] >= 1:
                os.kill(proc.pid, signal.SIGKILL)
                killed = True
                break
            if proc.poll() is not None:
                break  # finished before we could kill: still fine
            time.sleep(0.5)
        proc.wait(timeout=60)
        assert killed or proc.returncode == 0

        # resume (or re-run) to completion
        res = subprocess.run(ga_cmd(wf, cfg, state),
                             capture_output=True, text=True, cwd=REPO,
                             timeout=600)
        assert res.returncode == 0, res.stderr[-2000:]
        out = json.loads(res.stdout.strip().splitlines()[-1])
        assert np.isfinite(out["fitness"])
        assert json.load(open(state))["generation"] == 2
        if killed:
            assert "resumed GA at generation" in res.stderr
