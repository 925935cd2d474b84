"""On-chip smoke tests (SURVEY.md §7 "stochastic ops parity"): bf16
fused-vs-numpy agreement, AlexNet step health, on-device RNG
determinism, and the honest-benchmark barrier guard — the behaviours
only the TPU (bf16 MXU compute, async dispatch, donation, compiled
Pallas) can actually exercise."""

import time

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import NumpyDevice
from veles_tpu.loader.synthetic import SyntheticClassificationLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow


def mlp_workflow(mb=50, n_train=400, n_valid=100, max_epochs=4):
    prng.seed_all(777)
    gd = {"learning_rate": 0.05, "gradient_moment": 0.9}
    return StandardWorkflow(
        loader_factory=lambda wf: SyntheticClassificationLoader(
            wf, name="loader", minibatch_size=mb, n_train=n_train,
            n_valid=n_valid, shape=(12, 12, 1), n_classes=6, seed=55),
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 48},
             "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 6},
             "<-": gd}],
        decision_config={"max_epochs": max_epochs},
        name="TpuMlp")


def stochastic_conv_workflow(max_epochs=2):
    prng.seed_all(31415)
    gd = {"learning_rate": 0.02, "gradient_moment": 0.9}
    return StandardWorkflow(
        loader_factory=lambda wf: SyntheticClassificationLoader(
            wf, name="loader", minibatch_size=25, n_train=200,
            n_valid=50, shape=(14, 14, 1), n_classes=4, seed=99),
        layers=[
            {"type": "conv_relu",
             "->": {"n_kernels": 8, "kx": 3, "ky": 3, "padding": 1},
             "<-": gd},
            {"type": "stochastic_pooling",
             "->": {"kx": 2, "ky": 2}, "<-": {}},
            {"type": "dropout", "->": {"dropout_ratio": 0.4}, "<-": {}},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": gd}],
        decision_config={"max_epochs": max_epochs},
        name="TpuStochastic")


def history(w, klass="validation"):
    return [h["loss"] for h in w.decision.history
            if h["class"] == klass]


class TestFusedVsNumpyOnChip:
    def test_mlp_trajectory_agrees_at_bf16_tolerance(self, tpu_device):
        """The fused bf16 TPU step must track the f32 numpy oracle's
        loss trajectory — divergence means an f32/bf16 wiring bug, not
        noise (deterministic data + init)."""
        w_np = mlp_workflow()
        w_np.initialize(device=NumpyDevice())
        w_np.run()

        w_tpu = mlp_workflow()
        w_tpu.initialize(device=tpu_device)
        assert not w_tpu.fused.streaming
        w_tpu.run()

        a, b = history(w_np), history(w_tpu)
        assert len(a) == len(b) == 4
        for la, lb in zip(a, b):
            assert abs(la - lb) / max(abs(la), 1e-9) < 0.08, (a, b)
        # both learn
        assert a[-1] < a[0] and b[-1] < b[0]


class TestAlexNetStep:
    def test_one_train_step_finite_and_updating(self, tpu_device):
        from veles_tpu.models.alexnet import alexnet_layers
        prng.seed_all(1234)
        w = StandardWorkflow(
            loader_factory=lambda wf: SyntheticClassificationLoader(
                wf, name="loader", minibatch_size=32, n_train=64,
                n_valid=0, shape=(227, 227, 3), n_classes=1000,
                seed=227227),
            layers=alexnet_layers(1000),
            loss_function="softmax",
            decision_config={"max_epochs": 10 ** 9},
            superstep=2, name="AlexNetSmoke")
        w.evaluator.compute_confusion = False
        w.initialize(device=tpu_device)
        fused, loader = w.fused, w.loader
        fused._ensure_params()
        before = np.asarray(
            fused._params["fwd0_conv_relu"]["weights"]).copy()
        loader.run()
        fused.run()
        n_err, loss, count, _ = fused.take_class_metrics()
        assert count == 64.0  # superstep=2 x mb=32, mask-counted
        assert np.isfinite(loss)
        after = np.asarray(fused._params["fwd0_conv_relu"]["weights"])
        assert np.isfinite(after).all()
        assert np.abs(after - before).max() > 0

    def test_compute_dtype_is_bf16(self, tpu_device):
        import jax.numpy as jnp
        assert jnp.dtype(tpu_device.compute_dtype) == jnp.bfloat16


class TestOnDeviceRngDeterminism:
    def test_two_seeded_runs_identical(self, tpu_device):
        """dropout + stochastic pooling: the traced per-step keys must
        make reruns bit-identical — metric histories compare EQUAL."""
        runs = []
        for _ in range(2):
            w = stochastic_conv_workflow()
            w.initialize(device=tpu_device)
            w.run()
            runs.append([(h["class"], h["n_err"], h["loss"])
                         for h in w.decision.history])
        assert runs[0] == runs[1]


class TestStreamingOnChip:
    def test_bf16_streaming_trains(self, tpu_device):
        """The host-streaming input path on the real chip: batches
        assembled in the compute dtype by the prefetch thread,
        double-buffered uploads, convergence on a small convnet."""
        prng.seed_all(1234)
        gd = {"learning_rate": 0.02, "gradient_moment": 0.9}
        w = StandardWorkflow(
            loader_factory=lambda wf: SyntheticClassificationLoader(
                wf, name="loader", minibatch_size=64, n_train=1024,
                n_valid=256, shape=(32, 32, 3), n_classes=10, seed=777,
                max_resident_bytes=0),
            layers=[
                {"type": "conv_relu",
                 "->": {"n_kernels": 16, "kx": 5, "ky": 5,
                        "padding": 2}, "<-": gd},
                {"type": "max_pooling", "->": {"kx": 2, "ky": 2},
                 "<-": {}},
                {"type": "softmax", "->": {"output_sample_shape": 10},
                 "<-": gd}],
            decision_config={"max_epochs": 3},
            superstep=4, name="StreamSmoke")
        w.initialize(device=tpu_device)
        assert w.fused.streaming
        assert w.loader.stream_dtype == np.dtype("bfloat16")
        w.run()
        hist = [h["error_pct"] for h in w.decision.history
                if h["class"] == "validation"]
        assert hist[-1] < hist[0], hist
        assert len(w.fused._inflight) <= 2


def _alexnet_probe(tpu_device, name):
    """An AlexNet-1000 workflow small enough to fire by hand (mb 64,
    superstep 2) whose queued steps are real work for the barrier and
    profiler probes below."""
    from veles_tpu.models.alexnet import alexnet_layers
    prng.seed_all(1234)
    w = StandardWorkflow(
        loader_factory=lambda wf: SyntheticClassificationLoader(
            wf, name="loader", minibatch_size=64, n_train=128,
            n_valid=0, shape=(227, 227, 3), n_classes=1000,
            seed=227227),
        layers=alexnet_layers(1000),
        loss_function="softmax",
        decision_config={"max_epochs": 10 ** 9},
        superstep=2, name=name)
    w.evaluator.compute_confusion = False
    w.initialize(device=tpu_device)

    def fire():
        w.loader.run()
        w.fused.run()

    fire()  # compile
    np.asarray(w.fused._acc)
    return w, fire


class TestHonestBarrier:
    def test_sync_is_data_dependent(self, tpu_device):
        """Regression guard for the round-1 fake benchmark: fetching
        the metric carry must BLOCK on queued training work (async
        dispatch means cheap fire calls, expensive sync)."""
        w, fire = _alexnet_probe(tpu_device, "BarrierProbe")
        fused = w.fused

        t0 = time.perf_counter()
        np.asarray(fused._acc)     # idle sync: nothing queued
        idle = time.perf_counter() - t0

        n = 8
        t0 = time.perf_counter()
        for _ in range(n):
            fire()
        dispatch = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(fused._acc)     # must wait for all n steps
        busy = time.perf_counter() - t0

        # n AlexNet supersteps are >=100ms of real work; an idle fetch
        # is ~1ms.  If the barrier were fake, busy ~= idle.
        assert busy > max(5 * idle, 0.05), (idle, dispatch, busy)

    def test_block_until_ready_blocks(self, tpu_device):
        """The fact the benchmark's barriers (benchmarks/) and
        docs/perf.md rely on, re-established on this installation (jax 0.9.0, libtpu
        0.0.34, a directly attached chip): ``block_until_ready`` on the
        metric carry WAITS for the queued steps — after it returns,
        the data-dependent fetch finds nothing left to wait for."""
        w, fire = _alexnet_probe(tpu_device, "BlockProbe")
        fused = w.fused
        for _ in range(8):
            fire()
        t0 = time.perf_counter()
        fused._acc.block_until_ready()
        blocked = time.perf_counter() - t0
        t0 = time.perf_counter()
        np.asarray(fused._acc)
        fetch_after = time.perf_counter() - t0
        print(f"FACT block_until_ready: blocked {blocked:.4f}s on 8 "
              f"queued supersteps, fetch after it {fetch_after:.4f}s")
        assert blocked > 0.05, (blocked, fetch_after)
        assert fetch_after < 0.2 * blocked, (blocked, fetch_after)


class TestProfilerTrace:
    def test_trace_carries_a_device_plane(self, tpu_device, tmp_path):
        """A jax.profiler trace taken by the chip-owning process holds
        a TPU device plane with op events, not only the host timeline
        — what ROADMAP S1's per-layer reduction will read."""
        import glob

        import jax

        w, fire = _alexnet_probe(tpu_device, "TraceProbe")
        with jax.profiler.trace(str(tmp_path)):
            for _ in range(2):
                fire()
            np.asarray(w.fused._acc)
        paths = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                              / "*.xplane.pb"))
        assert paths, list(tmp_path.rglob("*"))
        data = jax.profiler.ProfileData.from_file(paths[0])
        planes = {p.name: sum(len(list(ln.events)) for ln in p.lines)
                  for p in data.planes}
        print(f"FACT profiler planes (events): {planes}")
        device = {n: c for n, c in planes.items()
                  if n.startswith("/device:TPU")}
        assert device and max(device.values()) > 0, planes


class TestDeviceBornDataset:
    def test_device_synthetic_loader_trains_on_chip(self, tpu_device):
        """The device-generating loader: the dataset must be born
        in HBM (devmem bound, no host copy) and a fused training
        firing must consume it."""
        from veles_tpu.loader.synthetic import DeviceSyntheticLoader
        prng.seed_all(1234)
        w = StandardWorkflow(
            loader_factory=lambda wf: DeviceSyntheticLoader(
                wf, name="loader", minibatch_size=25, n_train=100,
                n_valid=25, shape=(12, 12, 1), n_classes=4, seed=7),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 32},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05}}],
            decision_config={"max_epochs": 3},
            name="TpuDeviceBorn")
        w.initialize(device=tpu_device)
        ld = w.loader
        assert ld.original_data.devmem is not None
        assert ld.original_data._mem is None  # never touched the host
        w.run()
        losses = history(w)
        assert len(losses) == 3
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0]  # it learns


class TestSnapshotResumeOnChip:
    def test_resume_matches_straight_run(self, tpu_device, tmp_path):
        """Checkpoint/resume equivalence ON THE CHIP (SURVEY.md §5.4):
        a bf16 fused run snapshotted mid-way and resumed must land on
        the identical metric history as an uninterrupted run — pickles
        round-trip HBM state (params, momentum, PRNG chains) through
        host Vectors."""
        from veles_tpu.snapshotter import load_workflow, save_workflow

        def build(max_epochs):
            # mlp_workflow seeds all streams itself (777)
            return mlp_workflow(max_epochs=max_epochs)

        w_ref = build(4)
        w_ref.initialize(device=tpu_device)
        w_ref.run()
        ref_hist = [(h["class"], h["n_err"])
                    for h in w_ref.decision.history]
        w_ref.stop()

        w1 = build(2)
        w1.initialize(device=tpu_device)
        w1.run()
        path = str(tmp_path / "snap.pickle.gz")
        save_workflow(w1, path)
        w1.stop()

        w2 = load_workflow(path)
        w2.decision.max_epochs = 4
        w2.decision.complete.set(False)
        w2.initialize(device=tpu_device)
        w2.run()
        got_hist = [(h["class"], h["n_err"])
                    for h in w2.decision.history]
        w2.stop()
        assert got_hist == ref_hist


class TestEnsembleEngineOnChip:
    def test_vmapped_ensemble_matches_host_oracle_at_bf16(
            self, tpu_device):
        """ISSUE 3 tentpole on the real chip: N members served as ONE
        vmapped bf16 dispatch must agree with the f32 numpy member
        loop at bf16 tolerance, in both data paths."""
        from veles_tpu.datasets import synthetic_classification
        from veles_tpu.ensemble import EnsemblePredictor, \
            EnsembleTrainer
        from veles_tpu.loader import ArrayLoader

        prng.seed_all(4321)
        train, valid, _ = synthetic_classification(
            200, 60, (12, 12, 1), n_classes=4, seed=13)

        def factory():
            return StandardWorkflow(
                loader_factory=lambda wf: ArrayLoader(
                    wf, train=train, valid=valid, minibatch_size=50,
                    name="loader"),
                layers=[
                    {"type": "conv_relu",
                     "->": {"n_kernels": 8, "kx": 3, "ky": 3,
                            "padding": 1},
                     "<-": {"learning_rate": 0.05}},
                    {"type": "max_pooling",
                     "->": {"kx": 2, "ky": 2, "sliding": 2},
                     "<-": {}},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.1}}],
                decision_config={"max_epochs": 2}, name="member")

        trainer = EnsembleTrainer(factory, lambda: tpu_device,
                                  n_members=3, base_seed=888)
        members = trainer.train()
        pred = EnsemblePredictor(factory, lambda: tpu_device, members)
        assert pred.engine is not None          # auto -> chip engine
        x, y = valid
        p_dev = pred.predict_proba(x[:50])
        p_host = pred.predict_proba_host(x[:50])
        # bf16 matmuls vs f32 host: the fused-vs-numpy trajectory
        # tolerance discipline, per-element on probabilities
        np.testing.assert_allclose(p_dev, p_host, rtol=0.05,
                                   atol=0.02)
        np.testing.assert_allclose(p_dev.sum(-1), 1.0, atol=0.02)
        # both engines score the same split within bf16 slack
        e_dev = pred.error_pct(x, y)
        eng = pred.engine
        eng.attach_dataset(x, y)
        e_res = eng.error_pct_resident()
        assert abs(e_dev - e_res) <= 5.0, (e_dev, e_res)


class TestPopulationTrainOnChip:
    def test_cohort_engine_matches_oracle_at_bf16(self, tpu_device):
        """ISSUE 4 tentpole on the real chip: a float-tune cohort
        trained as ONE vmapped dispatch chain lands within a few
        validation errors of the per-genome oracle (bf16 compute puts
        counts, not exact equality, in reach on chip)."""
        from veles_tpu.launcher import workflow_fitness
        from veles_tpu.models import wine
        from veles_tpu.ops.fused import PopulationTrainEngine

        class FL:
            workflow = None

        def build(lr):
            prng._streams.clear()
            prng.seed_all(1234)
            layers = [
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 8},
                 "<-": {"learning_rate": lr}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": lr}},
            ]
            w = wine.create_workflow(FL(), layers=layers,
                                     decision={"max_epochs": 4})
            w.initialize(device=tpu_device)
            return w

        lrs = [0.3, 0.05]
        oracle = []
        for lr in lrs:
            w = build(lr)
            w.run()
            oracle.append(workflow_fitness(w))
            w.stop()
        w = build(lrs[0])
        rates = np.asarray([[[lr, lr], [lr, lr]] for lr in lrs],
                           np.float32)
        engine = PopulationTrainEngine(
            w, rates, np.zeros_like(rates))
        fits = engine.run()
        engine.release()
        w.stop()
        assert np.all(np.isfinite(fits)), fits
        assert np.allclose(fits, oracle, atol=3.0), (fits, oracle)


class TestImagePipelineOnChip:
    def test_prepared_tree_streams_through_fused_step(self, tpu_device,
                                                      tmp_path):
        """Chip-tier twin of tests/test_pipeline_rehearsal.py: an
        on-disk image tree through prepare_imagenet -> streaming
        ImageDirectoryLoader -> the fused step on the REAL chip, with
        live transfer accounting."""
        import os

        from PIL import Image

        from veles_tpu.datasets import prepare_imagenet
        from veles_tpu.loader.image import ImageDirectoryLoader

        rng = np.random.default_rng(17)
        src = tmp_path / "src"
        for c in range(2):
            d = src / f"cls_{c}"
            os.makedirs(d)
            for i in range(12):
                arr = np.clip(rng.integers(0, 120, (24, 24, 3))
                              + 100 * c, 0, 255)
                Image.fromarray(arr.astype(np.uint8)).save(
                    d / f"im{i:02d}.png")
        prepared = str(tmp_path / "prepared")
        prepare_imagenet(str(src), prepared, image_size=20,
                         valid_frac=0.25, progress_every=0)

        prng.seed_all(1234)
        w = StandardWorkflow(
            loader_factory=lambda wf: ImageDirectoryLoader(
                wf, name="loader", data_dir=prepared,
                target_shape=(20, 20, 3), minibatch_size=6,
                streaming=True),
            layers=[
                {"type": "conv_relu",
                 "->": {"n_kernels": 4, "kx": 5, "ky": 5,
                        "sliding": 2},
                 "<-": {"learning_rate": 0.02}},
                {"type": "max_pooling", "->": {"kx": 2, "ky": 2},
                 "<-": {}},
                {"type": "softmax", "->": {"output_sample_shape": 2},
                 "<-": {"learning_rate": 0.02}},
            ],
            loss_function="softmax",
            decision_config={"max_epochs": 2},
            superstep=2, name="ChipRehearsal")
        w.initialize(device=tpu_device)
        assert w.fused.streaming
        w.run()
        w.stop()
        for h in w.decision.history:
            assert np.isfinite(h["loss"]), w.decision.history
        assert w.fused.stream_transfer_bytes > 0


class TestStreamingAccountingOnChip:
    def test_streaming_trains_and_accounts_transfers(self, tpu_device):
        """The streaming path on the real chip: residency budget
        forces host-assembled superstep batches, training proceeds,
        and the transfer accounting (``stream_transfer_seconds`` /
        ``_bytes``) is live."""
        prng.seed_all(2026)
        w = StandardWorkflow(
            loader_factory=lambda wf: SyntheticClassificationLoader(
                wf, name="loader", minibatch_size=20, n_train=160,
                n_valid=40, shape=(10, 10, 1), n_classes=4, seed=11,
                max_resident_bytes=0),  # force streaming
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 24},
                 "<-": {"learning_rate": 0.05,
                        "gradient_moment": 0.9}},
                {"type": "softmax", "->": {"output_sample_shape": 4},
                 "<-": {"learning_rate": 0.05}}],
            decision_config={"max_epochs": 3},
            superstep=2, name="TpuStreaming")
        w.initialize(device=tpu_device)
        assert w.fused.streaming
        assert not w.loader.device_resident
        w.run()
        losses = history(w)
        assert len(losses) == 3
        assert losses[-1] < losses[0]
        assert w.fused.stream_transfer_seconds > 0.0
        w.stop()
