"""SPMD data parallelism (veles_tpu/parallel/): the sharded fused step
on an 8-device virtual CPU mesh must reproduce the single-device
training trajectory — the allreduce-in-compiler replacement for the
reference's master--slave aggregation (SURVEY.md §3.4, §5.8)."""

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import JaxDevice
from veles_tpu.datasets import synthetic_classification
from veles_tpu.loader import ArrayLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow
from veles_tpu.parallel import (DataParallel, MeshJaxDevice, batch_sharding,
                                make_mesh, replicated_sharding)


def build_workflow(mb=48, max_epochs=2, momentum=0.9, superstep=8,
                   hidden=32, **loader_kw):
    prng.seed_all(777)
    train, valid, _ = synthetic_classification(
        480, 192, (12, 12, 1), n_classes=10, seed=42)
    gd = {"learning_rate": 0.1, "weight_decay": 0.0001,
          "gradient_moment": momentum}
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid, minibatch_size=mb,
            name="loader", **loader_kw),
        layers=[
            {"type": "all2all_tanh",
             "->": {"output_sample_shape": hidden}, "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": gd},
        ],
        decision_config={"max_epochs": max_epochs},
        superstep=superstep, name="dp_test")


def valid_history(w):
    return [h for h in w.decision.history if h["class"] == "validation"]


class TestMesh:
    def test_make_mesh(self):
        mesh = make_mesh(8)
        assert mesh.devices.size == 8
        assert mesh.axis_names == ("data",)

    def test_make_mesh_too_many(self):
        with pytest.raises(ValueError, match="need 64 devices"):
            make_mesh(64)

    def test_shardings(self):
        import jax
        mesh = make_mesh(4)
        x = jax.device_put(np.zeros((8, 3), np.float32),
                           batch_sharding(mesh))
        assert not x.is_fully_replicated
        r = jax.device_put(np.zeros((8, 3), np.float32),
                           replicated_sharding(mesh))
        assert r.is_fully_replicated


class TestDataParallel:
    def test_install_rejects_indivisible(self):
        w = build_workflow(mb=50)
        dp = DataParallel(w, 8)
        with pytest.raises(ValueError, match="not divisible"):
            dp.install()

    def test_rejects_clamped_static_batch(self):
        """minibatch_size divisible but every class smaller: the static
        shape clamps to max_minibatch_size — must fail with a clear
        error at initialize, not crash inside device_put."""
        prng.seed_all(777)
        train, valid, _ = synthetic_classification(
            100, 40, (8, 8, 1), n_classes=4, seed=1)
        w = StandardWorkflow(
            loader_factory=lambda wf: ArrayLoader(
                wf, train=train, valid=valid, minibatch_size=128,
                name="loader"),
            layers=[{"type": "softmax", "->": {"output_sample_shape": 4},
                     "<-": {"learning_rate": 0.1}}],
            decision_config={"max_epochs": 1}, name="clamped")
        dp = DataParallel(w, 8)
        dev = dp.install()   # passes: 128 % 8 == 0
        with pytest.raises(ValueError, match="max_minibatch_size"):
            w.initialize(device=dev)

    def test_mesh_device_put_replicates(self):
        dev = MeshJaxDevice(make_mesh(8))
        buf = dev.put(np.arange(16, dtype=np.float32))
        assert buf.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(buf), np.arange(16))

    def test_dp_matches_single_device(self):
        """The sharded global-minibatch step must follow the same
        trajectory as the unsharded fused step (same seed)."""
        w1 = build_workflow()
        w1.initialize(device=JaxDevice(platform="cpu"))
        w1.run()

        w8 = build_workflow()
        dp = DataParallel(w8, 8)
        w8.initialize(device=dp.install())
        w8.run()

        h1, h8 = valid_history(w1), valid_history(w8)
        assert len(h1) == len(h8) == 2
        for a, b in zip(h1, h8):
            assert abs(a["loss"] - b["loss"]) < 5e-3, (a, b)
            assert abs(a["n_err"] - b["n_err"]) <= 3, (a, b)

    def test_dp_learns_and_params_replicated(self):
        w = build_workflow(max_epochs=8)
        dp = DataParallel(w, 8)
        w.initialize(device=dp.install())
        w.run()
        assert w.decision.epoch_error_pct[1] < 40.0, \
            w.decision.epoch_error_pct
        # updated weights must still be replicated across the mesh
        # (anything else means the partitioner failed to allreduce)
        wts = w.fused._params[w.forwards[0].name]["weights"]
        assert wts.is_fully_replicated
        assert np.isfinite(np.asarray(wts)).all()

    def test_dp_snapshot_roundtrip(self, tmp_path):
        """Mesh never reaches the pickle; resumed run re-installs DP."""
        import pickle
        w = build_workflow(max_epochs=1)
        dp = DataParallel(w, 4)
        w.initialize(device=dp.install())
        w.run()
        blob = pickle.dumps(w)
        w2 = pickle.loads(blob)
        assert w2.fused.mesh is None
        dp2 = DataParallel(w2, 4)
        w2.decision.max_epochs = 2
        w2.initialize(device=dp2.install())
        w2.run()
        assert len(valid_history(w2)) >= 1


class TestLauncherDP:
    def test_launcher_dp_flag(self):
        from veles_tpu.launcher import Launcher
        launcher = Launcher(backend="cpu", seed=777, dp=8)
        launcher.create_workflow(lambda l: build_workflow(max_epochs=1))
        launcher.initialize()
        assert isinstance(launcher.device, MeshJaxDevice)
        launcher.run()
        assert len(valid_history(launcher.workflow)) == 1


class TestStreamingDataParallel:
    def test_streaming_dp_matches_single_device_streaming(self):
        """The combination: host-streaming batches (no HBM-resident
        dataset) entering the SHARDED fused step.  _run_streaming
        device_puts the assembled superstep batch with the mesh's
        batch sharding; trajectory must match single-device streaming
        (the dp story cannot be resident-only — ImageNet-scale data is
        why streaming exists)."""
        w1 = build_workflow(max_resident_bytes=0)
        w1.initialize(device=JaxDevice(platform="cpu"))
        assert w1.fused.streaming
        w1.run()

        w8 = build_workflow(max_resident_bytes=0)
        dp = DataParallel(w8, 8)
        w8.initialize(device=dp.install())
        assert w8.fused.streaming
        w8.run()

        h1, h8 = valid_history(w1), valid_history(w8)
        assert len(h1) == len(h8) == 2
        for a, b in zip(h1, h8):
            assert abs(a["loss"] - b["loss"]) < 5e-3, (a, b)
            assert abs(a["n_err"] - b["n_err"]) <= 3, (a, b)
        wts = w8.fused._params[w8.forwards[0].name]["weights"]
        assert wts.is_fully_replicated


FEEDS = {"resident": {}, "streaming": {"max_resident_bytes": 0},
         "row_sharded": {"mesh_shard": "always"}}


def host_copies(leaf):
    """One host array per device of a mesh array."""
    return [np.asarray(s.data) for s in leaf.addressable_shards]


#: hidden width of the first layer -> how its gradient is exchanged:
#: 144 x 32 weights are fewer bytes than 48 rows of (144 + 2 x 32)
#: activations, 144 x 512 are more
HOW = {"reduced": 32, "gathered": 512}


def exchange_event():
    from veles_tpu import events, telemetry
    return telemetry.recent_events(events.EV_DP_GRAD_EXCHANGE)


class TestGradExchange:
    """ISSUE 31: on a mesh a layer's gradient becomes the global
    minibatch's either by the partitioner's all-reduce or from
    gathered activations, chosen from shapes; the mathematics and the
    one-chip program stay the parent's."""

    @pytest.mark.parametrize("how", sorted(HOW))
    @pytest.mark.parametrize("feed", sorted(FEEDS))
    @pytest.mark.parametrize("superstep", [1, 8])
    def test_dp_trajectory_is_the_single_device_one(self, superstep,
                                                    feed, how):
        single = {k: v for k, v in FEEDS[feed].items()
                  if k != "mesh_shard"}
        w1 = build_workflow(superstep=superstep, hidden=HOW[how],
                            **single)
        w1.initialize(device=JaxDevice(platform="cpu"))
        w1.run()
        w4 = build_workflow(superstep=superstep, hidden=HOW[how],
                            **FEEDS[feed])
        w4.initialize(device=DataParallel(w4, 4).install())
        assert w4.fused.streaming == (feed == "streaming")
        assert w4.fused.data_sharded == (feed == "row_sharded")
        (ev,) = exchange_event()
        assert [g["how"] for g in ev["groups"]] == ["reduced", how]
        w4.run()
        h1, h4 = valid_history(w1), valid_history(w4)
        assert len(h1) == len(h4) == 2
        for a, b in zip(h1, h4):
            assert abs(a["loss"] - b["loss"]) < 5e-3, (a, b)
            assert abs(a["n_err"] - b["n_err"]) <= 3, (a, b)

    @pytest.mark.parametrize("how", sorted(HOW))
    @pytest.mark.parametrize("superstep", [1, 8])
    def test_every_device_holds_the_same_state_after_a_firing(
            self, superstep, how):
        w = build_workflow(superstep=superstep, max_epochs=1,
                           hidden=HOW[how])
        w.initialize(device=DataParallel(w, 4).install())
        while not w.fused.processed_images:    # up to a TRAIN firing
            w.loader.run()
            w.fused.run()
        state = {"params": w.fused._params, "opt": w.fused._opt}
        import jax
        leaves = jax.tree_util.tree_leaves(state)
        assert len(leaves) == 8           # 2 layers x (w, b) x 2
        for leaf in leaves:
            first, *others = host_copies(leaf)
            assert len(others) == 3 and np.abs(first).sum() > 0
            for other in others:
                np.testing.assert_array_equal(first, other)

    @pytest.mark.parametrize("how", sorted(HOW))
    def test_exchange_is_journaled_once_in_walk_order(self, how):
        """``dp.grad_exchange``: once a build, the softmax head first
        (the walk's order), bytes that sum to the parameters' (f32 on
        XLA:CPU), no compiler option off the TPU."""
        from veles_tpu import events, telemetry
        hidden = HOW[how]
        w = build_workflow(max_epochs=1, hidden=hidden)
        w.initialize(device=DataParallel(w, 4).install())
        (ev,) = exchange_event()
        assert (ev["devices"], ev["leaves"], ev["dtype"]) == \
            (4, 4, "float32")
        assert ev["options"] == {}
        head, first = ev["groups"]
        assert (head["layer"], first["layer"]) == (
            w.forwards[1].name, w.forwards[0].name)
        assert head["bytes"] == (hidden * 10 + 10) * 4
        assert first["bytes"] == (144 * hidden + hidden) * 4
        n_params = sum(int(np.prod(v.shape)) for f in w.forwards
                       for v in f.param_vectors().values())
        assert ev["bytes"] == head["bytes"] + first["bytes"] \
            == n_params * 4
        # what each device receives: 3/4 of the gathered activations
        # (48 rows of 144 in, `hidden` out and as much error), or of
        # an all-reduce's two passes over the gradient
        assert head["wire_bytes"] == 2 * head["bytes"] * 3 // 4
        assert first["wire_bytes"] == (
            48 * (144 + 2 * hidden) * 4 if how == "gathered"
            else 2 * first["bytes"]) * 3 // 4
        assert telemetry.gauge(
            events.GAUGE_DP_GRAD_EXCHANGE_GROUPS).value == 2
        w.loader.run()
        w.fused.run()
        assert len(exchange_event()) == 1     # the run adds none

    def test_without_a_mesh_the_step_is_the_parents(self, monkeypatch):
        """No mesh: no sharding constraint, no collective and no
        barrier in the traced step, nothing but the donation handed to
        ``jax.jit``, and no ``dp.grad_exchange`` in the journal."""
        import jax

        from veles_tpu import events, telemetry
        handed = []
        real_jit = jax.jit

        def jit(fn, **kw):
            handed.append((getattr(fn, "__name__", "?"), kw))
            return real_jit(fn, **kw)

        monkeypatch.setattr(jax, "jit", jit)
        w = build_workflow(max_epochs=1)
        w.initialize(device=JaxDevice(platform="cpu"))
        train = [kw for name, kw in handed if name == "train_step"]
        assert train == [{"donate_argnums": (0, 1, 2, 3)}], train
        fused, ld = w.fused, w.loader
        ld.run()
        fused._ensure_params()
        acc, conf = fused._fresh_acc()
        k = ld.superstep_indices.shape[0]
        text = str(jax.make_jaxpr(fused._train_step)(
            fused._params, fused._opt, acc, conf,
            ld.original_data.unmap(), fused._target_store(),
            ld.superstep_indices, ld.superstep_mask,
            fused._lr_rates_array(k), 0))
        for word in ("sharding_constraint", "optimization_barrier",
                     "psum", "all_gather", "all_reduce", "ppermute",
                     "reduce_scatter", "shard_map"):
            assert word not in text, word
        assert "dot_general" in text          # the parser sees the step
        assert telemetry.recent_events(events.EV_DP_GRAD_EXCHANGE) == []
