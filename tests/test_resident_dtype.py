"""ISSUE 27: the resident dataset sits in HBM in the dtype the fused
step ingests — cast ONCE at set-up (``FullBatchLoader.reside_as``,
asked for by ``FusedStepRunner.initialize``), not re-cast whole on
every superstep.

On XLA:CPU the device's policy is float32, so the mechanism is driven
here by setting ``compute_dtype=bfloat16`` on the runner (what a TPU's
policy resolves to); the device layout half of ``reside_as`` is a
no-op on this backend (rows are major-most by default) and is pinned
on the chip by ``tests_tpu/``.
"""

import gc
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from veles_tpu import events, faults, prng, telemetry
from veles_tpu.backends import JaxDevice
from veles_tpu.datasets import synthetic_classification
from veles_tpu.loader import ArrayLoader
from veles_tpu.loader.fullbatch import DeviceArrayLoader
from veles_tpu.memory import Vector
from veles_tpu.ops.standard_workflow import StandardWorkflow
from veles_tpu.parallel import DataParallel

BF16 = np.dtype(jnp.bfloat16)
SAMPLE = (8, 8, 3)
N_TRAIN, N_VALID, MB = 192, 64, 32
GD = {"learning_rate": 0.05, "weight_decay": 0.0005,
      "gradient_moment": 0.9}
CONV_NET = [
    {"type": "conv_relu", "->": {"n_kernels": 4, "kx": 3, "ky": 3},
     "<-": GD},
    {"type": "max_pooling", "->": {"kx": 2, "ky": 2, "sliding": 2},
     "<-": {}},
    {"type": "softmax", "->": {"output_sample_shape": 4}, "<-": GD},
]
AUTOENCODER = [
    {"type": "all2all_tanh", "->": {"output_sample_shape": 16},
     "<-": GD},
    {"type": "all2all", "->": {"output_sample_shape": int(
        np.prod(SAMPLE))}, "<-": GD},
]


def _splits(dtype=np.float32, flat=False):
    train, valid, _ = synthetic_classification(
        N_TRAIN, N_VALID, SAMPLE, n_classes=4, seed=5)
    if flat:
        train = (train[0].reshape(N_TRAIN, -1), train[1])
        valid = (valid[0].reshape(N_VALID, -1), valid[1])
    if dtype == np.uint8:
        as_bytes = lambda x: np.clip(  # noqa: E731
            x * 64 + 128, 0, 255).astype(np.uint8)
        train = (as_bytes(train[0]), train[1])
        valid = (as_bytes(valid[0]), valid[1])
    return train, valid


def build(compute_dtype=jnp.bfloat16, loader_factory=None,
          layers=CONV_NET, loss="softmax", max_epochs=2, **loader_kw):
    prng.seed_all(4242)
    if loader_factory is None:
        train, valid = _splits(loader_kw.pop("source_dtype", np.float32),
                               loader_kw.pop("source_flat", False))
        loader_factory = lambda w: ArrayLoader(  # noqa: E731
            w, train=train, valid=valid, minibatch_size=MB,
            name="loader", **loader_kw)
    w = StandardWorkflow(
        loader_factory=loader_factory, layers=layers, loss_function=loss,
        decision_config={"max_epochs": max_epochs}, superstep=2,
        name="resident_dtype_test")
    w.fused.compute_dtype = compute_dtype
    return w


def decisions():
    return telemetry.recent_events(events.SPAN_LOADER_RESIDENT_DTYPE)


def casts():
    return telemetry.counter(events.CTR_LOADER_RESIDENT_CASTS).value


def spy_on_store(w):
    """dtypes of the data store as each jitted train step got it."""
    seen = []
    step = w.fused._train_step

    def train_step(params, opt, acc, conf, dataset, *rest):
        seen.append(np.dtype(dataset.dtype))
        return step(params, opt, acc, conf, dataset, *rest)

    w.fused._train_step = train_step
    return seen


class TestEngages:
    def test_step_gets_bf16_store_cast_once(self):
        """(a) after initialize the array handed to the step is bf16,
        and however many supersteps fire the store was cast once."""
        w = build()
        w.initialize(device=JaxDevice("cpu"))
        store = w.loader.original_data
        assert np.dtype(store.devmem.dtype) == BF16
        assert store.mem.dtype == np.float32     # the host copy's own
        assert store.dtype == np.float32
        seen = spy_on_store(w)
        w.run()
        assert w.loader.epoch_number >= 2
        assert len(seen) >= 3 and set(seen) == {BF16}
        assert casts() == 1
        (ev,) = decisions()
        assert (ev["from"], ev["to"]) == ("float32", "bfloat16")
        assert ev["bytes_after"] * 2 == ev["bytes_before"] \
            == store.mem.nbytes
        assert "reason" not in ev and ev["seconds"] > 0
        assert ev["parent"] == "init.fused"
        assert telemetry.gauge(
            events.GAUGE_LOADER_RESIDENT_BYTES).value \
            == ev["bytes_after"]
        # labels keep their dtype; the store is the one it was cast to
        assert np.dtype(w.loader.original_labels.devmem.dtype) == np.int32
        assert w.loader.original_data.unmap() is store.devmem

    def test_bitwise_equal_to_the_f32_store(self):
        """(b) an elementwise convert commutes with a row gather:
        parameters, momentum and the metric carry after 2 supersteps
        are the bits of a run that hands the store over as float32."""
        def two_supersteps(engage):
            w = build()
            if not engage:
                w.loader.reside_as = lambda dtype: None
            w.initialize(device=JaxDevice("cpu"))
            for _ in range(2):
                w.loader.run()
                w.fused.run()
            f = w.fused
            got = jax.device_get((f._params, f._opt, f._acc))
            return np.dtype(w.loader.original_data.devmem.dtype), got

        dt_cast, cast = two_supersteps(True)
        dt_wide, wide = two_supersteps(False)
        assert (dt_cast, dt_wide) == (BF16, np.dtype(np.float32))
        flat_c, tree_c = jax.tree_util.tree_flatten(cast)
        flat_w, tree_w = jax.tree_util.tree_flatten(wide)
        assert tree_c == tree_w and len(flat_c) >= 9
        for a, b in zip(flat_c, flat_w):
            assert a.dtype == b.dtype
            assert a.tobytes() == b.tobytes()
        assert float(cast[2][2]) == 2 * 2 * MB   # the carry counted

    @pytest.mark.parametrize("asked", [None, jnp.float32])
    def test_program_compiled_ahead_for_the_devices_dtype(self, asked):
        """The loader starts compiling the cast at ITS initialize, for
        the device's compute dtype; ``reside_as`` takes that program
        when the step asks for that dtype and drops it otherwise."""
        w = build(compute_dtype=asked)
        ahead = []
        reside_as = w.loader.reside_as

        def spy(dtype):
            ahead.append(w.loader._reside_ahead)
            reside_as(dtype)

        w.loader.reside_as = spy
        w.initialize(device=JaxDevice("cpu", compute_dtype=jnp.bfloat16))
        (dtype, future), = ahead
        assert dtype == BF16 and future.result(timeout=60)
        assert w.loader._reside_ahead is None
        store = w.loader.original_data.devmem
        if asked is None:
            assert np.dtype(store.dtype) == BF16 and casts() == 1
        else:
            assert np.dtype(store.dtype) == np.float32 and casts() == 0
            assert decisions()[0]["reason"] == "same_dtype"
        del w.loader.reside_as      # the spy does not pickle
        pickle.dumps(w.loader)
        w.run()
        assert np.isfinite(w.decision.history[-1]["loss"])

    @pytest.mark.parametrize("mode", ["replicated", "row_sharded"])
    def test_placement_survives_on_a_mesh(self, mode):
        """The cast keeps the buffer's own sharding: replicated, or
        row-sharded with its padded tail (256 rows + 1 over 8)."""
        kw = {"mesh_shard": "always"} if mode == "row_sharded" else {}
        w = build(**kw)
        wide = {}
        reside_as = w.loader.reside_as

        def spy(dtype):
            dev = w.loader.original_data.devmem
            wide.update(sharding=dev.sharding, shape=dev.shape)
            reside_as(dtype)

        w.loader.reside_as = spy
        w.initialize(device=DataParallel(w, 8).install())
        dev = w.loader.original_data.devmem
        assert np.dtype(dev.dtype) == BF16
        assert dev.sharding == wide["sharding"]
        assert dev.shape == wide["shape"]
        assert dev.is_fully_replicated == (mode == "replicated")
        assert w.loader.shard_resident == (mode == "row_sharded")
        w.run()
        assert casts() == 1
        assert np.isfinite(w.decision.history[-1]["loss"])


class TestBypasses:
    """(c) each bypass leaves the store untouched and names why."""

    @pytest.mark.parametrize("reason,kw", [
        ("same_dtype", {"compute_dtype": jnp.float32}),
        ("dequant", {"source_dtype": np.uint8}),
        ("streaming", {"max_resident_bytes": 0}),
        ("targets_alias", {"targets_from_labels": True,
                           "source_flat": True,
                           "layers": AUTOENCODER, "loss": "mse"}),
        ("oom", {}),
    ])
    def test_store_untouched_and_reason_named(self, reason, kw):
        w = build(**kw)
        if reason == "oom":
            faults.arm("device.oom_on_put@site=resident_cast")
        try:
            w.initialize(device=JaxDevice("cpu"))
        finally:
            faults.arm("")
        store = w.loader.original_data
        (ev,) = decisions()
        assert ev["reason"] == reason
        assert casts() == 0
        assert ev["from"] == ev["to"]
        assert ev["bytes_before"] == ev["bytes_after"]
        if reason == "streaming":
            assert store.devmem is None and ev["bytes_after"] == 0
            assert w.fused.streaming
            # the streaming counterpart still gets its dtype
            assert w.loader.stream_dtype == BF16
        else:
            want = np.uint8 if reason == "dequant" else np.float32
            assert np.dtype(store.devmem.dtype) == want
            assert ev["from"] == np.dtype(want).name
            assert ev["bytes_after"] == store.mem.nbytes
        if reason == "targets_alias":
            assert np.dtype(w.loader.original_targets.devmem.dtype) \
                == np.float32
        w.run()         # and the run goes on as it did
        assert casts() == 0
        assert np.isfinite(w.decision.history[-1]["loss"])

    def test_device_born_aliased_targets(self):
        """targets that ARE the data's device buffer (DBN stages)."""
        x = jnp.asarray(_splits(flat=True)[0][0])
        w = build(
            loader_factory=lambda w: DeviceArrayLoader(
                w, train=x, targets_from_data=True, minibatch_size=MB,
                name="loader"),
            layers=AUTOENCODER[:1] + [
                {"type": "all2all", "<-": GD,
                 "->": {"output_sample_shape": int(x.shape[1])}}],
            loss="mse")
        w.initialize(device=JaxDevice("cpu"))
        assert decisions()[0]["reason"] == "targets_alias"
        assert w.loader.original_data.devmem is x

    def test_second_initialize_does_not_cast_twice(self):
        w = build()
        w.initialize(device=JaxDevice("cpu"))
        w.loader.reside_as(BF16)
        assert casts() == 1
        assert [e.get("reason") for e in decisions()] \
            == [None, "same_dtype"]


class TestHostCoherence:
    def test_host_write_reuploads_narrow_without_recompile(self):
        """(d) a stale store never puts f32 back in front of the
        step: after a host write ``unmap()`` uploads bf16, cast on the
        host; the step does not recompile; ``mem`` stays f32."""
        w = build(max_epochs=1)
        dev = JaxDevice("cpu")
        w.initialize(device=dev)
        w.run()
        store = w.loader.original_data
        before = store.devmem
        h2d = dev.h2d_bytes
        store.map_write()[0] = 0.5
        fresh = store.unmap()
        assert fresh is not before
        assert np.dtype(fresh.dtype) == BF16
        assert dev.h2d_bytes - h2d == store.mem.nbytes // 2
        assert store.mem.dtype == np.float32
        assert np.all(np.asarray(fresh[0], np.float32) == 0.5)
        np.testing.assert_array_equal(
            np.asarray(fresh[1:]), np.asarray(before[1:]))
        compiles = telemetry.counter(events.CTR_XLA_COMPILES).value
        w.decision.complete.set(False)
        w.decision.max_epochs = 2
        w.run()
        assert w.loader.epoch_number == 2
        assert telemetry.counter(events.CTR_XLA_COMPILES).value \
            == compiles
        assert casts() == 1

    def test_device_born_store_reads_back_float32(self):
        """(e) no host copy: the store simply becomes the narrower
        array, and host consumers keep their dtype."""
        x, y = _splits()[0]
        w = build(loader_factory=lambda w: _DeviceBorn(
            w, x=x, y=y, minibatch_size=MB, name="loader"))
        w.initialize(device=JaxDevice("cpu"))
        store = w.loader.original_data
        assert np.dtype(store.devmem.dtype) == BF16
        assert store.mem is None and store.dtype == BF16
        rows = w.loader.normalized_host_rows(np.arange(5))
        assert rows.dtype == np.float32
        np.testing.assert_array_equal(
            rows, x[:5].astype(BF16).astype(np.float32))
        w.loader.host_fill_enabled = True
        w.loader.run()      # the eager fill takes them too
        assert w.loader.minibatch_data.mem.dtype == np.float32

    def test_runner_pickles_and_release_frees_both_buffers(self):
        """(f) neither the wide nor the narrow buffer outlives
        ``release_device_state()`` + ``drop_devmem()``."""
        w = build(max_epochs=1)
        w.initialize(device=JaxDevice("cpu"))
        w.run()
        store = w.loader.original_data
        shape = tuple(store.devmem.shape)
        clone = pickle.loads(pickle.dumps(w.fused))
        assert clone._train_step is None
        loader = pickle.loads(pickle.dumps(w.loader))
        assert loader.original_data.mem.dtype == np.float32
        assert loader.original_data.devmem is None
        w.fused.release_device_state()
        store.drop_devmem()
        gc.collect()
        assert store.devmem is None
        assert [a.dtype for a in jax.live_arrays()
                if tuple(a.shape) == shape] == []
        # the valid host copy survived, and uploads narrow again
        assert np.dtype(store.unmap().dtype) == BF16


class TestVectorMirror:
    def _vector(self):
        v = Vector(np.arange(12, dtype=np.float32).reshape(4, 3))
        v.initialize(JaxDevice("cpu"))
        v.retype_devmem(v.devmem.astype(jnp.bfloat16))
        return v

    def test_retype_is_not_a_device_write(self):
        v = self._vector()
        assert np.dtype(v.devmem.dtype) == BF16
        assert v.map_read().dtype == np.float32     # no fetch: valid
        assert v.nbytes == 48 and v.dtype == np.float32
        assert v.unmap() is v.devmem                # and no upload

    @pytest.mark.parametrize("how", ["mem", "devmem", "reset",
                                     "pickle"])
    def test_mirror_ends_with_the_buffers_it_described(self, how):
        v = self._vector()
        if how == "mem":
            v.mem = np.ones((4, 3), np.float32)
        elif how == "devmem":
            v.devmem = jnp.ones((4, 3), jnp.float32)
            v.map_write()
        elif how == "reset":
            v.reset(np.ones((4, 3), np.float32))
        else:
            device = v.device
            v = pickle.loads(pickle.dumps(v))
            v.initialize(device, upload=False)
            v.map_write()
        assert np.dtype(v.unmap().dtype) == np.float32

    def test_mirror_outlives_drop_devmem(self):
        v = self._vector()
        v.drop_devmem()
        assert np.dtype(v.unmap().dtype) == BF16


class _DeviceBorn(ArrayLoader):
    """Rows born on the device, no host copy (the benchmark's
    ``SeededResidentLoader``, ``DeviceSyntheticLoader``)."""

    def __init__(self, workflow=None, x=None, y=None, **kw):
        super().__init__(workflow, train=(x, y), **kw)

    def load_data(self) -> None:
        from veles_tpu.loader.base import TRAIN
        x, y = self._splits[TRAIN]
        self.class_lengths[TRAIN] = len(x)
        self.original_data.devmem = jnp.asarray(x)
        self.original_labels.devmem = jnp.asarray(y, jnp.int32)
