"""Keel (ISSUE 18): ONE execution core under every engine loop.

The four engine loops — ``FusedStepRunner``, ``EnsembleEvalEngine``,
``PopulationTrainEngine`` (ops/fused.py) and the online scavenger's
``ShadowTrainer`` (online/trainer.py) — are thin adapters over
``veles_tpu.engine.core``: shared trace builders + one placement /
donation / arbiter surface.  Pins:

- the core primitives: the ``put`` / ``donating_jit`` seam, pytree
  byte accounting, and the process-arbiter charge/discharge ledger;
- the **engine-equivalence matrix**: for each loop, every combination
  of the orthogonal execution flags (streaming vs resident data,
  row-sharded vs replicated residency, member-sharded vs unsharded
  cohorts, on-mesh vs off) trains/scores **f32-BITWISE** identically —
  the flags select placement, never math;
- ``ShadowTrainer`` == a raw Keel-builder composition, bitwise — the
  adapter adds plumbing, not arithmetic;
- the GA→serving handoff (genetics/handoff.py): the final cohort's
  top-K members become a served ensemble with ZERO host round trips —
  no npz is ever written, the served stacked params are bitwise-equal
  to the trained cohort rows, and the ledger shows the serve charge.
"""

import glob
import os

import numpy as np
import pytest

from veles_tpu import prng
from veles_tpu.backends import JaxDevice
from veles_tpu.datasets import synthetic_classification
from veles_tpu.engine import core as engine_core
from veles_tpu.loader import ArrayLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow
from veles_tpu.parallel import (DataParallel, MeshJaxDevice,
                                make_mesh)
from veles_tpu.serve import residency


@pytest.fixture(autouse=True)
def _fresh_process_arbiter():
    """Each test sees a clean process-arbiter singleton (charges from
    one test's cores must not leak into another's ledger reads)."""
    saved = residency._process_arbiter
    residency._process_arbiter = None
    yield
    residency._process_arbiter = saved


# -- shared builders -----------------------------------------------------

N_TRAIN, N_VALID = 240, 57            # not divisible by the 8-mesh
SAMPLE = (10, 10, 1)


def build_workflow(mb=24, max_epochs=2, **loader_kw):
    prng._streams.clear()
    prng.seed_all(4242)
    train, valid, _ = synthetic_classification(
        N_TRAIN, N_VALID, SAMPLE, n_classes=7, seed=99)
    gd = {"learning_rate": 0.1, "weight_decay": 0.0001,
          "gradient_moment": 0.9}
    return StandardWorkflow(
        loader_factory=lambda w: ArrayLoader(
            w, train=train, valid=valid, minibatch_size=mb,
            name="loader", **loader_kw),
        layers=[
            {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
             "<-": gd},
            {"type": "softmax", "->": {"output_sample_shape": 7},
             "<-": gd},
        ],
        decision_config={"max_epochs": max_epochs},
        name="keel_matrix")


def build_wine(lr, epochs=4, fail=1):
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
        decision={"max_epochs": epochs, "fail_iterations": fail})
    w.initialize(device=JaxDevice(platform="cpu"))
    return w


def wine_cohort(lrs):
    rates = np.asarray([[[lr, lr], [lr, lr]] for lr in lrs],
                       np.float32)
    decays = np.asarray([[[0.001, 0.0], [0.0, 0.0]]] * len(lrs),
                        np.float32)
    return rates, decays


def host_params(w):
    return {f.name: {k: np.asarray(v)
                     for k, v in w.fused._params[f.name].items()}
            for f in w.forwards}


# -- core primitives -----------------------------------------------------

class TestCorePrimitives:
    def test_put_roundtrips_values_and_dtype(self):
        dev = JaxDevice(platform="cpu")
        core = engine_core.ExecutionCore(dev, None)
        x = np.arange(24, dtype=np.uint8).reshape(4, 6)
        buf = core.put(x)
        assert np.asarray(buf).dtype == np.uint8      # wire-preserving
        assert np.array_equal(np.asarray(buf), x)

    def test_donate_flag_is_droppable(self):
        """A core built with donate=False compiles the SAME adapter
        code without donation: the input buffer stays readable after
        the call (the debugging escape hatch)."""
        dev = JaxDevice(platform="cpu")
        core = engine_core.ExecutionCore(dev, None, donate=False)
        step = core.jit(lambda a: a + 1.0, donate=(0,))
        buf = core.put(np.float32([1.0, 2.0]))
        out = step(buf)
        assert np.array_equal(np.asarray(buf), [1.0, 2.0])  # not donated
        assert np.array_equal(np.asarray(out), [2.0, 3.0])

    def test_tree_nbytes_counts_nested_leaves(self):
        tree = {"a": {"w": np.zeros((3, 4), np.float32)},
                "b": {"w": np.zeros(8, np.float32),
                      "v": np.zeros(2, np.uint8)}}
        assert engine_core.tree_nbytes(tree) == 3 * 4 * 4 + 8 * 4 + 2

    def test_charge_lands_on_the_process_ledger(self):
        mgr = residency.install_process_arbiter(
            residency.ResidencyManager(None, budget_bytes=1 << 30))
        core = engine_core.ExecutionCore(None, None, pool="cohort",
                                         name="matrix-test")
        core.charge(12345)
        assert mgr.ledger()["cohort"] == 12345
        core.charge(777)                    # re-charge replaces
        assert mgr.ledger()["cohort"] == 777
        core.release()
        assert mgr.ledger()["cohort"] == 0

    def test_unknown_pool_is_rejected(self):
        mgr = residency.ResidencyManager(None, budget_bytes=1)
        with pytest.raises(ValueError):
            mgr.reserve("x", 1, pool="hbm2")


# -- the engine-equivalence matrix ---------------------------------------

class TestFusedMatrix:
    """FusedStepRunner: streaming / resident / row-sharded / mesh are
    pure placement flags — every combination yields the bitwise-same
    parameter trajectory."""

    def run_single(self, **loader_kw):
        w = build_workflow(**loader_kw)
        w.initialize(device=JaxDevice(platform="cpu"))
        w.run()
        params = host_params(w)
        hist = list(w.decision.history)
        streaming = bool(w.fused.streaming)
        w.stop()
        return params, hist, streaming

    def run_mesh(self, n=8, **loader_kw):
        w = build_workflow(**loader_kw)
        dp = DataParallel(w, n)
        w.initialize(device=dp.install())
        w.run()
        params = host_params(w)
        hist = list(w.decision.history)
        shard = bool(w.loader.shard_resident)
        stream = bool(w.fused.streaming)
        w.stop()
        return params, hist, shard, stream

    @staticmethod
    def assert_bitwise(pa, pb):
        for fn in pa:
            for k in pa[fn]:
                assert np.array_equal(pa[fn][k], pb[fn][k]), \
                    (fn, k)

    def test_streaming_matches_resident_single_device(self):
        p_res, h_res, s_res = self.run_single()
        p_str, h_str, s_str = self.run_single(max_resident_bytes=0)
        assert not s_res and s_str
        assert h_res == h_str
        self.assert_bitwise(p_res, p_str)

    def test_row_sharded_matches_replicated_on_mesh(self):
        p_rep, h_rep, sh_rep, _ = self.run_mesh()
        p_sh, h_sh, sh_sh, stream = self.run_mesh(
            max_resident_bytes=(N_TRAIN + N_VALID) * 4
            * int(np.prod(SAMPLE)) // 4)
        assert not sh_rep and sh_sh and not stream
        assert h_rep == h_sh
        self.assert_bitwise(p_rep, p_sh)

    def test_mesh_streaming_matches_mesh_resident(self):
        p_rep, h_rep, _, stream_rep = self.run_mesh()
        p_str, h_str, _, stream = self.run_mesh(max_resident_bytes=0)
        assert not stream_rep and stream
        assert h_rep == h_str
        self.assert_bitwise(p_rep, p_str)


class TestOneExecutablePerStep:
    """PR 21 (found on the chip: the second dispatch of a cold AlexNet
    run re-compiled for 23 s): jit lowers one executable per pattern
    of committed/uncommitted arguments, so host-fresh metric carries
    at class starts, device carries mid-class, and uncommitted
    device-born momentum on the very first call were three compiles
    of the one train program.  Every dispatch now presents the same
    placement."""

    @pytest.mark.parametrize("loader_kw", [{}, {"max_resident_bytes": 0}])
    def test_class_starts_and_mid_class_share_one_executable(
            self, loader_kw):
        w = build_workflow(mb=8, max_epochs=3, **loader_kw)
        w.superstep = 2          # several dispatches per class
        w.initialize(device=JaxDevice(platform="cpu"))
        w.run()
        fused = w.fused
        dispatches_per_class = N_TRAIN // 8 // 2
        assert dispatches_per_class > 1
        assert fused._train_step._cache_size() == 1
        assert fused._eval_step._cache_size() == 1
        w.stop()


class TestCohortMatrix:
    """PopulationTrainEngine: the full streaming x member-sharded
    grid returns bitwise-identical fitness vectors — the PR 18 lift
    of dataset-must-fit composes with the Lattice mesh placement."""

    LRS = [0.3, 0.05, 0.8]

    def run_cohort(self, streaming=False, mesh_n=0):
        from veles_tpu.ops.fused import PopulationTrainEngine
        w = build_wine(self.LRS[0])
        if streaming:
            w.loader.device_resident = False
        rates, decays = wine_cohort(self.LRS)
        engine = PopulationTrainEngine(
            w, rates, decays, mesh=make_mesh(mesh_n) if mesh_n
            else None)
        assert engine.streaming == streaming
        assert engine.member_sharded == bool(mesh_n)
        fits = np.asarray(engine.run())
        engine.release()
        w.stop()
        return fits

    def test_full_flag_grid_is_bitwise_identical(self):
        oracle = self.run_cohort()
        for streaming in (False, True):
            for mesh_n in (0, 8):
                if not streaming and not mesh_n:
                    continue
                got = self.run_cohort(streaming, mesh_n)
                assert np.array_equal(got, oracle), \
                    (streaming, mesh_n, got, oracle)


class TestEnsembleMatrix:
    """EnsembleEvalEngine: member-sharded serving scores bitwise like
    unsharded — the fixed left-to-right add chain in
    ``build_mean_probs`` is placement-independent by construction."""

    def predictions(self, member_sharded):
        from veles_tpu.ops.fused import EnsembleEvalEngine
        w = build_wine(0.3, epochs=2, fail=100)
        w.run()
        members = [host_params(w) for _ in range(3)]
        rng = np.random.default_rng(7)
        for i, mp in enumerate(members):
            for fn, d in mp.items():
                for k in d:
                    d[k] = d[k] + np.float32(0.01 * (i + 1)) \
                        * rng.standard_normal(d[k].shape) \
                        .astype(np.float32)
        device = MeshJaxDevice(make_mesh(8)) if member_sharded \
            else JaxDevice(platform="cpu")
        engine = EnsembleEvalEngine(
            w.forwards, members, device,
            shard_members=member_sharded)
        x = np.asarray(w.loader.original_data.map_read()[:16],
                       np.float32)
        probs = np.asarray(engine.predict_proba(x))
        engine.release()
        w.stop()
        return probs

    def test_member_sharded_predict_is_bitwise(self):
        p_un = self.predictions(member_sharded=False)
        p_sh = self.predictions(member_sharded=True)
        assert np.array_equal(p_un, p_sh)


class TestShadowTrainerIsKeelComposition:
    """One ShadowTrainer micro-step == the raw Keel-builder
    composition (build_forward + build_backward vmapped over members),
    bitwise — the online adapter adds plumbing, not arithmetic."""

    def test_step_matches_raw_builders(self):
        import jax
        import jax.numpy as jnp

        from veles_tpu.online.trainer import ShadowTrainer
        from veles_tpu.ops import batching

        w = build_wine(0.1, epochs=2, fail=100)
        w.run()
        base = host_params(w)
        rng = np.random.default_rng(5)
        members = [{fn: {k: v + np.float32(0.02)
                         * rng.standard_normal(v.shape)
                         .astype(np.float32)
                         for k, v in d.items()}
                    for fn, d in base.items()} for _ in range(2)]
        device = w.fused.device
        stacked = batching.stack_member_params(w.forwards, members,
                                               device)
        B = 8
        x = np.asarray(w.loader.original_data.map_read()[:B],
                       np.float32)
        labels = np.asarray(
            w.loader.original_labels.map_read()[:B], np.int32)

        tr = ShadowTrainer(w.forwards, w.gds, w.evaluator, device,
                           stacked, seed=33, lr_scale=0.1,
                           micro_batch=B)
        tr.step(x, labels, version=0)
        got = {fn: {k: np.asarray(v) for k, v in d.items()}
               for fn, d in tr._params.items()}

        # the oracle: the same Keel bodies composed by hand
        cd = batching.resolve_compute_dtype(None, device)
        cast = batching.make_caster(cd)
        fwd = engine_core.build_forward(w.forwards, 33, cd)
        bwd = engine_core.build_backward(w.forwards, w.gds, cd)
        evaluator = w.evaluator

        def member_step(params, opt, lr, xb, lb, mask, rc):
            cparams = cast(params)
            out, residuals = fwd(cparams, xb, rc, True)
            m = evaluator.metrics_fn(out.astype(jnp.float32), lb,
                                     mask)
            new_params, new_opt = bwd(cparams, params, opt,
                                      residuals, m["err_output"], lr)
            return new_params, new_opt

        stacked2 = batching.stack_member_params(w.forwards, members,
                                                device)
        opt2 = {gd.name: {k: device.zeros((2,) + tuple(v.shape),
                                          np.float32)
                          for k, v in gd.accumulated_grads.items()}
                for gd in w.gds
                if gd is not None and gd.accumulated_grads}
        step = jax.jit(jax.vmap(member_step,
                                in_axes=(0, 0, None, None, None,
                                         None, None)))
        lr = np.asarray([[gd.learning_rate * 0.1,
                          gd.learning_rate_bias * 0.1]
                         if gd is not None else [0.0, 0.0]
                         for gd in w.gds], np.float32)
        want, _ = step(stacked2, opt2, lr, x, labels,
                       np.ones(B, np.float32), 0)
        for fn, d in got.items():
            for k, v in d.items():
                assert np.array_equal(v, np.asarray(want[fn][k])), \
                    (fn, k)
        w.stop()


# -- the GA -> serving handoff -------------------------------------------

class TestGAHandoff:
    """The zero-host-round-trip handoff: the trained cohort's top-K
    members become a served ensemble through one jitted device gather
    + ``swap_params`` — no snapshot, no npz, no Forge package, no
    host copy of the params on the critical path."""

    LRS = [0.3, 0.05, 0.8]
    K = 2

    def _handoff(self, tmp_path, monkeypatch, mesh_n=0):
        from veles_tpu.genetics.handoff import GAServingHandoff
        from veles_tpu.ops.fused import PopulationTrainEngine

        monkeypatch.chdir(tmp_path)
        # any host-side snapshot write on the handoff path is a bug —
        # np.savez/save tripping proves a host round trip sneaked in
        for fname in ("savez", "savez_compressed", "save"):
            monkeypatch.setattr(
                np, fname,
                lambda *a, **k: (_ for _ in ()).throw(AssertionError(
                    "handoff touched the host: np.%s called" % fname)))

        w = build_wine(self.LRS[0])
        mesh = None
        if mesh_n:
            # cohort and serving tier share ONE device set (the mesh):
            # the adopt gather is a single jitted program over both
            mesh = make_mesh(mesh_n)
            serve_device = MeshJaxDevice(mesh)
            monkeypatch.setenv("VELES_SERVE_MESH_SHARD", "always")
        else:
            serve_device = w.fused.device
        sample_shape = tuple(np.asarray(
            w.loader.original_data.map_read()).shape[1:])
        rates, decays = wine_cohort(self.LRS)
        engine = PopulationTrainEngine(w, rates, decays, mesh=mesh)

        # the scaffold pre-builds (register + compile + warm) from the
        # cohort's INIT params — this overlaps training in production
        init_members = [
            {fn: {k: np.asarray(arr[i]) for k, arr in d.items()}
             for fn, d in engine._params.items()}
            for i in range(self.K)]
        mgr = residency.ResidencyManager(serve_device,
                                         budget_bytes=1 << 30)
        ho = GAServingHandoff(mgr, "winner", w.fused.forwards,
                              init_members,
                              sample_shape=sample_shape)
        fits = np.asarray(engine.run())
        serve_engine = ho.adopt_cohort(engine, fits)
        idx = ho.top_k(fits)

        # bitwise: every served member row equals the trained cohort's
        # (a member-sharded stack carries mesh-padding rows past K —
        # never read by the fixed-order mean, so only K rows matter)
        for fn, d in serve_engine.stacked_params.items():
            for k, arr in d.items():
                want = np.asarray(engine._params[fn][k])[idx]
                got = np.asarray(arr)[:self.K]
                assert np.array_equal(got, want), (fn, k)

        # the engine is LIVE: a request flows through the batcher
        x = np.asarray(w.loader.original_data.map_read()[:4],
                       np.float32)
        out = np.asarray(serve_engine.submit(x).result())
        assert out.shape == (4, 3)
        assert np.all(np.isfinite(out))

        # refresh_host is the OFF-critical-path host copy; the ledger
        # carries the serve charge for the adopted stack
        ho.refresh_host()
        assert mgr.ledger()["serve"] > 0
        engine.release()
        w.stop()
        assert glob.glob(os.path.join(str(tmp_path), "**", "*.npz"),
                         recursive=True) == []
        return fits, idx

    def test_handoff_serves_trained_members_without_npz(
            self, tmp_path, monkeypatch):
        fits, idx = self._handoff(tmp_path, monkeypatch)
        # top_k is the stable best-first order of min-is-best fitness
        order = np.argsort(fits, kind="stable")[:self.K]
        assert np.array_equal(idx, order.astype(np.int32))

    def test_handoff_onto_member_sharded_serving(
            self, tmp_path, monkeypatch):
        """The adopt gather lands member-sharded when the serving
        replica shards its member axis (the Prism placement)."""
        self._handoff(tmp_path, monkeypatch, mesh_n=8)

    def test_handoff_event_journaled(self, tmp_path, monkeypatch):
        from veles_tpu import events, telemetry
        self._handoff(tmp_path, monkeypatch)
        evs = telemetry.recent_events(events.EV_GA_HANDOFF)
        assert evs and evs[-1]["members"] == self.K

    def test_adopt_after_release_is_refused(self):
        from veles_tpu.genetics.handoff import GAServingHandoff
        from veles_tpu.ops.fused import PopulationTrainEngine

        w = build_wine(self.LRS[0])
        sample_shape = tuple(np.asarray(
            w.loader.original_data.map_read()).shape[1:])
        rates, decays = wine_cohort(self.LRS)
        engine = PopulationTrainEngine(w, rates, decays)
        members = [
            {fn: {k: np.asarray(arr[i]) for k, arr in d.items()}
             for fn, d in engine._params.items()}
            for i in range(self.K)]
        mgr = residency.ResidencyManager(w.fused.device,
                                         budget_bytes=1 << 30)
        ho = GAServingHandoff(mgr, "late", w.fused.forwards, members,
                              sample_shape=sample_shape, warm_rows=0)
        fits = np.asarray(engine.run())
        engine.release()
        with pytest.raises(RuntimeError):
            ho.adopt_cohort(engine, fits)
        w.stop()
