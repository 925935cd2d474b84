"""Population-batched GA training (ISSUE 4 tentpole): same-signature
genome cohorts train as ONE vmapped fused dispatch chain
(ops/fused.py PopulationTrainEngine), bucketed by shape signature in
GeneticOptimizer._fitness_many and dispatched through the chip-owning
evaluator's cohort jobs (genetics/worker.py --serve + pool.py
evaluate_cohort).  The per-genome path is the parity ORACLE: batched
fitnesses must match it to f32 tolerance."""

import json
import sys
import textwrap

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.genetics import (GeneticOptimizer, Tune, liftable_tune,
                                shape_signature)

LR = "wine.layers[0]['<-']['learning_rate']"
WIDTH = "wine.layers[0]['->']['output_sample_shape']"


class TestLiftableSignature:
    def test_float_lr_and_wd_are_liftable(self):
        assert liftable_tune("m.layers[0]['<-']['learning_rate']",
                             Tune(0.1, 0.01, 1.0))
        assert liftable_tune("m.layers[2]['<-']['weight_decay']",
                             Tune(0.001, 0.0, 0.1))
        assert liftable_tune("m.layers[1]['<-']['learning_rate_bias']",
                             Tune(0.1, 0.01, 1.0))

    def test_int_and_structural_tunes_are_not(self):
        # an integer gene always changes shapes — never liftable, even
        # on a learning_rate-looking path
        assert not liftable_tune("m.layers[0]['<-']['learning_rate']",
                                 Tune(1, 1, 8))
        assert not liftable_tune(
            "m.layers[0]['->']['output_sample_shape']", Tune(16, 8, 32))
        assert not liftable_tune("m.loader['minibatch_size']",
                                 Tune(32.0, 8.0, 64.0))

    def test_signature_keys_only_non_liftable(self):
        tunes = {WIDTH: Tune(16, 8, 32), LR: Tune(0.1, 0.01, 1.0)}
        a = shape_signature({WIDTH: 16, LR: 0.3}, tunes)
        b = shape_signature({WIDTH: 16, LR: 0.9}, tunes)
        c = shape_signature({WIDTH: 24, LR: 0.3}, tunes)
        assert a == b           # lr does not split cohorts
        assert a != c           # width does


class TestCohortBucketing:
    """_fitness_many buckets by signature and dispatches one cohort
    per bucket; decode failures score inf without poisoning their
    bucket; a failing bucket falls back to the per-genome oracle."""

    # lr range < 10x so the gene stays linear (log-scale genes would
    # decode these hand-written genomes through exp)
    TUNES = {WIDTH: Tune(16, 8, 32), LR: Tune(0.5, 0.2, 1.0)}

    def fitness_of(self, values):
        return values[WIDTH] + values[LR]

    def test_buckets_by_signature_singletons_included(self):
        calls = []

        def cohort(values_list):
            calls.append([v[WIDTH] for v in values_list])
            return [self.fitness_of(v) for v in values_list]

        opt = GeneticOptimizer(self.fitness_of, self.TUNES,
                               population=4, generations=1,
                               evaluate_cohort=cohort)
        genomes = np.asarray([
            [16.0, 0.3], [16.0, 0.9], [24.0, 0.5], [16.0, 0.25]])
        # "['->']" sorts before "['<-']" -> gene order (width, lr)
        assert opt.paths == [WIDTH, LR]
        fits = opt._fitness_many(genomes)
        expect = [16.3, 16.9, 24.5, 16.25]
        assert np.allclose(fits, expect)
        sizes = sorted(len(c) for c in calls)
        assert sizes == [1, 3]          # one cohort + one singleton
        assert sorted(opt.last_cohort_sizes) == [1, 3]

    def test_decode_failure_scores_inf_without_poisoning(self):
        class BoomTune(Tune):
            def clip(self, x):
                if x > 20:
                    raise ValueError("boom")
                return super().clip(x)

        tunes = {WIDTH: BoomTune(16, 8, 32), LR: Tune(0.5, 0.2, 1.0)}
        seen = []

        def cohort(values_list):
            seen.extend(v[WIDTH] for v in values_list)
            return [1.0 for _ in values_list]

        opt = GeneticOptimizer(self.fitness_of, tunes, population=3,
                               generations=1, evaluate_cohort=cohort)
        fits = opt._fitness_many(np.asarray(
            [[16.0, 0.3], [28.0, 0.3], [16.0, 0.5]]))
        assert fits[1] == float("inf")      # decode raised
        assert fits[0] == 1.0 and fits[2] == 1.0
        assert seen == [16, 16]             # bad genome never shipped

    def test_failed_bucket_falls_back_to_oracle(self):
        def cohort(values_list):
            raise RuntimeError("cohort path down")

        opt = GeneticOptimizer(self.fitness_of, self.TUNES,
                               population=2, generations=1,
                               evaluate_cohort=cohort)
        fits = opt._fitness_many(np.asarray([[16.0, 0.3], [16.0, 0.9]]))
        assert np.allclose(fits, [16.3, 16.9])  # oracle answered


class TestEngineParity:
    """The vmapped engine against per-genome full workflow runs,
    in-process — the core parity pin (float-tune cohort, shared init,
    per-member lr/wd, early-stop bookkeeping)."""

    def build(self, lr, wd=0.001, epochs=5, fail=100):
        from veles_tpu.backends import JaxDevice
        from veles_tpu.models import wine

        class FL:
            workflow = None

        prng._streams.clear()
        prng.seed_all(1234)
        layers = [
            {"type": "all2all_tanh", "->": {"output_sample_shape": 8},
             "<-": {"learning_rate": lr, "weight_decay": wd,
                    "gradient_moment": 0.9}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": lr, "gradient_moment": 0.9}},
        ]
        w = wine.create_workflow(
            FL(), layers=layers,
            decision={"max_epochs": epochs, "fail_iterations": fail})
        w.initialize(device=JaxDevice(platform="cpu"))
        return w

    def test_cohort_matches_per_genome_oracle(self):
        from veles_tpu.launcher import workflow_fitness
        from veles_tpu.ops.fused import PopulationTrainEngine

        lrs = [0.3, 0.05, 0.8]
        oracle = []
        for lr in lrs:
            w = self.build(lr, fail=1)   # small fail_iterations: some
            w.run()                      # members stop early
            oracle.append(workflow_fitness(w))
            w.stop()

        w = self.build(lrs[0], fail=1)
        rates = np.asarray([[[lr, lr], [lr, lr]] for lr in lrs],
                           np.float32)
        decays = np.asarray([[[0.001, 0.0], [0.0, 0.0]]] * len(lrs),
                            np.float32)
        engine = PopulationTrainEngine(w, rates, decays)
        fits = engine.run()
        engine.release()
        w.stop()
        assert np.allclose(fits, oracle, atol=1e-3), (fits, oracle)

    def test_streaming_cohort_matches_resident(self):
        """Streaming cohorts (host-assembled superstep batches, zero
        dataset residency — the PR 18 lift of the dataset-must-fit
        constraint) train bit-identically to resident ones: the Keel
        stream scan consumes the same rows the resident scan gathers
        on device."""
        from veles_tpu.ops.fused import PopulationTrainEngine

        lrs = [0.3, 0.05]
        rates = np.asarray([[[lr, lr], [lr, lr]] for lr in lrs],
                           np.float32)
        decays = np.asarray([[[0.001, 0.0], [0.0, 0.0]]] * len(lrs),
                            np.float32)

        w = self.build(lrs[0], fail=1)
        engine = PopulationTrainEngine(w, rates, decays)
        assert not engine.streaming
        resident = engine.run()
        engine.release()
        w.stop()

        w = self.build(lrs[0], fail=1)
        w.loader.device_resident = False    # force the streaming path
        engine = PopulationTrainEngine(w, rates, decays)
        assert engine.streaming
        stream = engine.run()
        engine.release()
        w.stop()
        assert np.array_equal(stream, resident), (stream, resident)


@pytest.fixture
def cohort_workflow(tmp_path):
    wf = tmp_path / "wf.py"
    wf.write_text(textwrap.dedent("""
        from veles_tpu.models import wine

        def create_workflow(launcher):
            return wine.create_workflow(launcher)

        def run(launcher):
            launcher.create_workflow(create_workflow)
            launcher.initialize()
            launcher.run()
    """))
    cfg = tmp_path / "cfg.py"
    cfg.write_text(textwrap.dedent("""
        from veles_tpu.config import root
        from veles_tpu.genetics import Tune

        root.wine.decision = {"max_epochs": 3}
        root.wine.layers = [
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": Tune(8, 4, 16)},
             "<-": {"learning_rate": Tune(0.3, 0.01, 1.0)}},
            {"type": "softmax", "->": {"output_sample_shape": 3},
             "<-": {"learning_rate": 0.3}},
        ]
    """))
    return str(wf), str(cfg)


class TestPoolCohortParity:
    """End to end through the serve-mode evaluator: batched-cohort
    fitnesses == the per-genome oracle (mixed signatures, a singleton
    bucket, and a structurally-bad member that scores inf without
    poisoning its cohort)."""

    def serve_cmd(self, wf, cfg):
        return [sys.executable, "-m", "veles_tpu.genetics.worker",
                "--serve", wf, cfg, "-b", "cpu", "-s", "1234"]

    def test_cohort_matches_oracle_and_isolates_bad_member(
            self, cohort_workflow):
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        wf, cfg = cohort_workflow
        cohort = [{WIDTH: 8, LR: 0.3}, {WIDTH: 8, LR: 0.05}]
        singleton = [{WIDTH: 12, LR: 0.3}]
        with ChipEvaluatorPool(self.serve_cmd(wf, cfg), workers=2,
                               timeout=300) as pool:
            oracle = pool.evaluate_many(cohort + singleton)
            batched = pool.evaluate_cohort(cohort)
            batched += pool.evaluate_cohort(singleton)
            assert all(np.isfinite(f) for f in oracle), oracle
            assert np.allclose(batched, oracle, atol=1e-3), \
                (batched, oracle)
            # a member whose decode produces a DIFFERENT structure
            # scores inf; the rest of the cohort still matches the
            # oracle (no poisoning, evaluator survives)
            mixed = pool.evaluate_cohort(
                [cohort[0], {WIDTH: -5, LR: 0.1}, cohort[1]])
            assert mixed[1] == float("inf")
            assert np.allclose([mixed[0], mixed[2]], oracle[:2],
                               atol=1e-3)

    def test_optimizer_buckets_cohorts_through_the_pool(
            self, cohort_workflow):
        """run_optimizer's wiring (optimizer <-> evaluator pool, cohort
        batching on) without its device policy: mixed-signature
        generations bucket through the REAL pool and finish with a
        finite best fitness.  The CLI spelling, `-b tpu-evaluator
        --optimize`, needs a TPU since PR 21 and is driven on the chip
        by tests_tpu/test_0_ga_parent.py."""
        from veles_tpu.config import root
        from veles_tpu.genetics import GeneticOptimizer, find_tunes
        from veles_tpu.genetics.pool import ChipEvaluatorPool
        from veles_tpu.launcher import apply_config_file
        wf, cfg = cohort_workflow
        apply_config_file(cfg)
        with ChipEvaluatorPool(self.serve_cmd(wf, cfg), workers=2,
                               timeout=300) as pool:
            opt = GeneticOptimizer(
                pool.evaluate_one, find_tunes(root), population=4,
                generations=1, evaluate_many=pool.evaluate_many,
                evaluate_cohort=pool.evaluate_cohort)
            _, fitness = opt.run()
        assert opt.last_cohort_sizes         # the batched path ran
        assert np.isfinite(fitness)


class TestRbmCohortParity:
    """The zoo's long tail through the SAME engine (Menagerie): a CD-k
    RBM learning-rate cohort trains as ONE vmapped
    PopulationTrainEngine dispatch chain, and every member's trained
    params match a per-genome fused oracle run — the CD sampling
    draws ride the shared (seed, step) PRNG contract, so stochastic
    layers batch without drifting.  On a single-device backend the
    match is f32-bitwise; under the suite's 8-virtual-device XLA
    config vmap picks different matmul fusions, so the pin here is
    ulp-tight allclose (the SAME tolerance story as the SOM cohort,
    tests/test_zoo_fused.py)."""

    LCFG = {"minibatch_size": 50, "n_train": 200, "n_valid": 50}
    LRS = [0.3, 0.05, 0.8]

    def build(self, lr, cd_k):
        from veles_tpu.backends import JaxDevice
        from veles_tpu.loader.synthetic import MnistLoader
        from veles_tpu.ops.standard_workflow import StandardWorkflow

        prng._streams.clear()
        prng.seed_all(1234)
        w = StandardWorkflow(
            loader_factory=lambda wf: MnistLoader(
                wf, name="loader", targets_from_data=True,
                **self.LCFG),
            layers=[
                {"type": "binarization", "->": {}, "<-": {}},
                {"type": "rbm", "->": {"n_hidden": 16},
                 "<-": {"learning_rate": lr, "gradient_moment": 0.5,
                        "cd_k": cd_k}},
            ],
            loss_function="mse",
            decision_config={"max_epochs": 2},
            name="RbmCohortWf")
        w.initialize(device=JaxDevice(platform="cpu"))
        return w

    @pytest.mark.parametrize("cd_k", [1, 2])
    def test_member_params_bitwise_vs_per_genome_oracle(self, cd_k):
        from veles_tpu.ops.fused import PopulationTrainEngine

        oracle = []
        for lr in self.LRS:
            w = self.build(lr, cd_k)
            w.run()
            oracle.append({k: np.array(v.map_read()) for k, v in
                           w.forwards[1].param_vectors().items()})
            w.stop()

        w = self.build(self.LRS[0], cd_k)
        n_gds = len(w.gds)
        rates = np.asarray([[[lr, lr]] * n_gds for lr in self.LRS],
                           np.float32)
        decays = np.zeros_like(rates)
        engine = PopulationTrainEngine(w, rates, decays)
        engine.run()
        stacked = engine._params[w.forwards[1].name]
        for i, want in enumerate(oracle):
            for pn, arr in want.items():
                got = np.asarray(stacked[pn][i])
                assert np.allclose(got, arr, rtol=1e-4, atol=5e-6), \
                    (cd_k, i, pn,
                     float(np.max(np.abs(got - arr))))
        engine.release()
        w.stop()
