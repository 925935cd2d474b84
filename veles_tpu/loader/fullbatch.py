"""Full-batch loaders: the whole dataset memory-resident.

Reference parity: veles/loader/fullbatch.py — ``FullBatchLoader`` keeps
all samples in one array (optionally on device) and slices minibatches
out of it; ``FullBatchLoaderMSE`` adds regression targets.

TPU-first: ``original_data`` lives in HBM as one ``jax.Array``; the
fused step receives minibatch *indices* and gathers rows on-device
(``jnp.take``) — minibatch assembly never touches the host after
initialization.  The host ``fill_minibatch`` path remains for the numpy
backend.

In which form the store resides: the loader uploads it as loaded
(float32, or uint8 under quantized ingest) and owns budget and
placement; the consumer then names the dtype its step ingests
(``FusedStepRunner.initialize`` -> ``reside_as``) and the loader casts
the device buffer ONCE, at set-up, laying whole rows out contiguously
for the in-step gather.  The host copy, labels and targets keep their
dtype.
"""

from __future__ import annotations

from typing import Any, Optional

import numpy as np

from veles_tpu.loader.base import Loader, TEST, VALID, TRAIN
from veles_tpu.memory import Vector


class FullBatchLoader(Loader):
    """Dataset fully resident; subclasses fill ``original_data`` /
    ``original_labels`` in ``load_data``.  On a jax device the data
    store sits in HBM in the dtype of the step that reads it, decided
    at set-up by ``reside_as`` (bfloat16 under a bf16 step; float32 on
    an f32 device, for a quantized uint8 store, and where the targets
    alias the data)."""

    def __init__(self, workflow=None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        #: all samples, laid out [test | valid | train] on axis 0
        self.original_data = Vector(name="original_data")
        #: integer class labels (classification) — may stay empty
        self.original_labels = Vector(name="original_labels")
        #: regression targets (MSE workflows) — may stay empty
        self.original_targets = Vector(name="original_targets")
        self.on_device = kwargs.get("on_device", True)
        #: PER-DEVICE HBM residency budget for the dataset (bytes).
        #: Datasets over budget switch to the streaming path: host
        #: arrays stay, the fused step consumes prefetched superstep
        #: batches instead of gathering from an HBM-resident copy.
        #: Overridable per loader or via $VELES_MAX_RESIDENT_BYTES;
        #: default 8 GiB.  On a device mesh the budget is charged per
        #: device: a replicated dataset costs its full size on EVERY
        #: device, and a dataset over one device's budget tries the
        #: row-sharded placement (1/N rows per device) before
        #: degrading to streaming — see ``mesh_shard``.
        self.max_resident_bytes = kwargs.get("max_resident_bytes", None)
        #: mesh residency policy override ("auto"/"always"/"never");
        #: None reads $VELES_MESH_SHARD_DATA.  "auto" row-shards the
        #: resident dataset only when it exceeds one device's budget
        #: but fits at total/N per device.
        self.mesh_shard = kwargs.get("mesh_shard", None)
        #: True = the resident dataset is ROW-SHARDED over the device
        #: mesh (each device holds 1/N of the rows); the fused step
        #: then gathers minibatches via the shard_map local-gather +
        #: psum path instead of a plain on-device take.
        self.shard_resident = False
        #: input normalization (reference: loaders own a Normalizer,
        #: veles/normalization.py) — fitted on the TRAIN split once,
        #: state rides in snapshots so resume does not refit
        self.normalization_type = kwargs.get("normalization_type",
                                             "none")
        self.normalization_parameters = kwargs.get(
            "normalization_parameters", {})
        self.normalizer = None
        #: uint8 ingest codec mode (loader/quantize.py): "auto" keeps
        #: byte-sourced (dtype uint8) datasets as uint8 — 1 byte/pixel
        #: on the streaming wire, 4x less HBM when resident — and fuses
        #: dequantization + normalization into the jitted step; True
        #: additionally re-encodes any byte-RANGED source (integer or
        #: integral-float values in [0, 255], validated); False always
        #: pre-normalizes to float32 (the classic path).
        self.quantized_ingest = kwargs.get("quantized_ingest", "auto")
        #: mem -> float-view convention for quantized sources: the
        #: float path computes ``normalizer.apply(mem * pre_scale)``
        #: (image decoders set 1/255; raw-byte arrays leave 1.0)
        self._quant_pre_scale = 1.0
        #: (dtype, Future) of ``reside_as``'s program, compiling ahead
        #: on a thread since ``initialize`` (see _compile_reside_ahead)
        self._reside_ahead = None

    _unpicklable = Loader._unpicklable + ("_reside_ahead",)

    def __setstate__(self, state: dict) -> None:
        super().__setstate__(state)
        # attrs introduced after a snapshot was written must default
        self.__dict__.setdefault("quantized_ingest", "auto")
        self.__dict__.setdefault("_quant_pre_scale", 1.0)
        self.__dict__.setdefault("mesh_shard", None)
        self.__dict__.setdefault("shard_resident", False)
        self.__dict__.setdefault("_reside_ahead", None)

    @property
    def has_labels(self) -> bool:
        return bool(self.original_labels)

    @property
    def has_targets(self) -> bool:
        return bool(self.original_targets)

    def post_load_data(self) -> None:
        from veles_tpu.loader.quantize import (derive_dequant,
                                               quantizable_source,
                                               to_uint8)
        self.dequant = None
        pre = self.original_data.mem if self.original_data else None
        want = self.quantized_ingest
        targets_alias_data = pre is not None and \
            bool(self.original_targets) and \
            self.original_targets.mem is pre
        # Decide quantization BEFORE normalizing — the point is never
        # materializing the float copy.  Autoencoder-style aliased
        # targets stay float: the trace consumes targets undequantized
        # (f32 loss), so a uint8 target store would change the loss.
        quantize = bool(want) and pre is not None \
            and not targets_alias_data \
            and quantizable_source(pre, strict=(want == "auto"))
        if want is True and pre is not None and not quantize:
            why = "targets alias the input data" if targets_alias_data \
                else f"dtype {pre.dtype} is not byte-ranged"
            raise ValueError(
                f"{self.name}: quantized_ingest=True but the dataset "
                f"cannot ride the uint8 codec ({why})")
        pre_scale = self._quant_pre_scale
        if self.normalization_type == "none" and self.normalizer is None:
            if quantize:
                self.original_data.mem = to_uint8(pre)
                self.dequant = derive_dequant(None, pre_scale)
            elif pre is not None and pre_scale != 1.0:
                # raw-byte load_data but no codec: recover the float
                # view the rest of the framework expects
                self.original_data.mem = \
                    pre.astype(np.float32) * np.float32(pre_scale)
            return
        from veles_tpu.normalization import make_normalizer
        from veles_tpu.loader.base import TRAIN
        if self.normalizer is None:
            if self.class_lengths[TRAIN] == 0:
                raise ValueError(
                    f"{self.name}: normalization_type="
                    f"{self.normalization_type!r} needs a TRAIN split "
                    f"to fit on (class_lengths={self.class_lengths})")
            self.normalizer = make_normalizer(
                self.normalization_type, **self.normalization_parameters)
            fit_view = pre[self.class_offset(TRAIN):]
            if pre_scale != 1.0:
                # the normalizer's statistics must describe the FLOAT
                # view (raw * pre_scale) its affine will reproduce
                fit_view = fit_view.astype(np.float32) * \
                    np.float32(pre_scale)
            self.normalizer.fit(fit_view)
        if quantize:
            dq = derive_dequant(self.normalizer, pre_scale)
            if dq is not None:
                # bytes stay bytes; normalization folds into the fused
                # step's on-device dequantization prologue
                self.original_data.mem = to_uint8(pre)
                self.dequant = dq
                return
            if want is True:
                raise ValueError(
                    f"{self.name}: quantized_ingest=True but "
                    f"normalizer {self.normalizer.kind!r} exposes no "
                    f"affine_params() to fold into the dequantization")
        if pre_scale != 1.0:
            pre = pre.astype(np.float32) * np.float32(pre_scale)
        self.original_data.mem = self.normalizer.apply(pre)
        if targets_alias_data:  # autoencoder: target = normalized input
            self.original_targets.mem = self.original_data.mem

    def getstate_dropping(self, *vector_names: str) -> dict:
        """__getstate__ minus the bulk of named Vectors — for loaders
        whose load_data regenerates content (files, synthetic)."""
        import copy
        d = super().__getstate__()
        for key in vector_names:
            vec = d.get(key)
            if vec is not None:
                vec = copy.copy(vec)
                vec.__setstate__({"name": vec.name, "mem": None})
                d[key] = vec
        return d

    def _resident_budget(self) -> int:
        if self.max_resident_bytes is not None:
            return int(self.max_resident_bytes)
        import os
        return int(os.environ.get("VELES_MAX_RESIDENT_BYTES",
                                  8 << 30))

    @staticmethod
    def _mesh_of(device):
        """The device's mesh when it actually multiplies capacity
        (>1 device) — the row-sharded residency precondition."""
        mesh = getattr(device, "mesh", None)
        if mesh is not None and getattr(device, "is_jax", False) \
                and int(mesh.devices.size) > 1:
            return mesh
        return None

    def _sharded_per_device_bytes(self, n_devices: int) -> int:
        """Per-device HBM cost of the row-sharded placement: every
        resident vector padded to a whole per-device tile, 1/N rows
        each — what the residency budget charges instead of the full
        replicated size."""
        from veles_tpu.parallel.mesh import padded_rows
        total = 0
        for v in (self.original_data, self.original_labels,
                  self.original_targets):
            if v and v.mem is not None and len(v.mem):
                rows = len(v.mem)
                total += (padded_rows(rows, n_devices) // n_devices) \
                    * (v.nbytes // rows)
        return total

    def _decide_residency(self, device) -> None:
        """Charge the residency budget PER DEVICE and pick the
        placement: replicated when the dataset fits one device's
        budget, row-sharded on a mesh when only total/N does (the
        Lattice capacity unlock — N x one chip's budget still goes
        resident), streaming otherwise."""
        if not (self.original_data
                and self.original_data.mem is not None):
            return
        budget = self._resident_budget()
        data_bytes = self.original_data.nbytes
        over = data_bytes > budget
        mesh = self._mesh_of(device)
        if mesh is not None:
            from veles_tpu import events, knobs, telemetry
            from veles_tpu.parallel.mesh import shard_mode
            mode = shard_mode(
                self.mesh_shard if self.mesh_shard is not None
                else knobs.get(knobs.MESH_SHARD_DATA))
            if mode != "never" and (over or mode == "always"):
                n = int(mesh.devices.size)
                per_dev = self._sharded_per_device_bytes(n)
                if per_dev <= budget:
                    self.shard_resident = True
                    telemetry.event(
                        events.EV_LOADER_SHARD_RESIDENT,
                        devices=n, total_bytes=int(data_bytes),
                        per_device_bytes=int(per_dev))
                    self.info(
                        "dataset %.1f MiB row-sharded over %d devices "
                        "(%.1f MiB/device vs the %.1f MiB/device "
                        "budget a replicated copy would need)",
                        data_bytes / 2 ** 20, n, per_dev / 2 ** 20,
                        budget / 2 ** 20)
                    return
        if over:
            self.device_resident = False
            self.info("dataset %.1f GiB (%s) exceeds the %.1f GiB "
                      "per-device HBM residency budget — streaming "
                      "superstep batches from host",
                      data_bytes / 2 ** 30,
                      self.original_data.mem.dtype,
                      budget / 2 ** 30)

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self.shard_resident = False
        self._decide_residency(device)
        resident = self.on_device and self.device_resident
        if resident and device is not None and device.is_jax:
            try:
                from veles_tpu import faults
                if faults.fire("device.oom_on_put",
                               site="resident_dataset"):
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: fault-injected OOM on "
                        "the resident dataset upload")
                for v in (self.original_data, self.original_labels,
                          self.original_targets):
                    if v:
                        if self.shard_resident:
                            v.upload_row_sharded(device)
                        else:
                            v.initialize(device)
                            v.unmap()  # one-time HBM upload
                self._compile_reside_ahead(device)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 — degrade, see below
                if "RESOURCE_EXHAUSTED" not in str(e):
                    raise
                # bounded degradation: the budget said the dataset
                # fits but the device disagreed (fragmentation, other
                # tenants) — stream superstep batches from host
                # instead of dying at initialize
                from veles_tpu import events, telemetry
                telemetry.counter(events.CTR_DEVICE_OOM_DEGRADED).inc()
                telemetry.event(events.EV_DEVICE_OOM_DEGRADED,
                                site="resident_dataset")
                self.warning(
                    "dataset upload hit device OOM (%s) — falling "
                    "back to host streaming", e)
                self.device_resident = False
                self.shard_resident = False
                for v in (self.original_data, self.original_labels,
                          self.original_targets):
                    if v:
                        v.drop_devmem()
                resident = False
        for v in (self.original_data, self.original_labels,
                  self.original_targets):
            if v:
                v.initialize(device if resident else None)

    def _reside_bypass(self, dtype) -> Optional[str]:
        """Why ``reside_as(dtype)`` leaves the store as it is (None:
        it does not) — all of it read off the loader's own state."""
        import jax.numpy as jnp
        dev = self.original_data.devmem
        if not self.device_resident or dev is None:
            return "streaming"
        if self.dequant is not None:
            # a uint8 store is 1 byte a pixel already, and its ingest
            # is f32 arithmetic
            return "dequant"
        if not jnp.issubdtype(dev.dtype, jnp.floating):
            # token ids and other integer rows are no activations: the
            # step reads them as they are (320 ids do not fit bfloat16)
            return "integer"
        if np.dtype(dev.dtype).itemsize <= np.dtype(dtype).itemsize:
            return "same_dtype"
        targets = self.original_targets
        if targets and (targets.devmem is dev or (
                targets.mem is not None
                and targets.mem is self.original_data.mem)):
            # autoencoders: the loss reads the SAME rows as f32 targets
            return "targets_alias"
        return None

    def reside_as(self, dtype) -> None:
        """Keep the resident ``original_data`` in HBM in the form the
        step reads it: cast to ``dtype`` — the step's ingest dtype —
        ONCE, here, on the device, with whole rows contiguous (axis 0
        major-most, a row in the device's own layout of one sample).
        Left to the step, the compiler hoists both out of its scan and
        converts and transposes the WHOLE store on every superstep.
        The values the step gathers are bit for bit the same: an
        elementwise convert commutes with a row gather.

        Placement survives (replicated, or row-sharded and padded),
        labels and targets are not touched, a valid host copy stays
        valid and float32.  Engages on what the loader can observe (no
        knob): a floating store wider than ``dtype``, resident, not
        quantized, not aliased by the targets.  If the device cannot
        hold both stores for the moment of the cast, the wide one
        stays.  Either way the decision is journaled
        (``loader.resident_dtype``)."""
        from veles_tpu import events, telemetry
        store = self.original_data

        def device_buffer():
            dev = store.devmem if self.device_resident else None
            if dev is None:
                return None, 0
            return np.dtype(dev.dtype).name, int(dev.nbytes)

        with telemetry.span(events.SPAN_LOADER_RESIDENT_DTYPE,
                            journal=True) as decision:
            have, nbytes = device_buffer()
            reason = self._reside_bypass(dtype)
            if reason is None:
                reason = self._cast_resident(dtype)
            self._reside_ahead = None
            now, after = device_buffer()
            telemetry.gauge(events.GAUGE_LOADER_RESIDENT_BYTES).set(after)
            decision.fields.update({
                "from": have, "to": now,
                "bytes_before": nbytes, "bytes_after": after})
            if reason is not None:
                decision.fields["reason"] = reason

    def _compile_reside_ahead(self, device) -> None:
        """Start compiling ``reside_as``'s program on a thread, for
        the device's own compute dtype — what a step asks for unless
        told otherwise.  The program may not come from the persistent
        cache (``engine_core.not_persisted``) and a process's first
        fresh compile takes half a second on the chip; begun here, it
        runs beside the units' host-side parameter fill instead of in
        front of the first step."""
        dtype = np.dtype(device.compute_dtype)
        if self._reside_bypass(dtype) is not None:
            return
        from concurrent.futures import ThreadPoolExecutor
        pool = ThreadPoolExecutor(
            1, thread_name_prefix=f"{self.name}-reside")
        self._reside_ahead = (dtype, pool.submit(
            _reside_program, self.original_data.devmem, dtype))
        pool.shutdown(wait=False)

    def _cast_resident(self, dtype) -> Optional[str]:
        """``reside_as``'s one pass over the store: a jitted ``astype``
        whose output keeps the buffer's sharding and lies rows
        major-most; the Vector's device buffer is rebound to it and the
        wide buffer goes with its last reference.  ``"oom"`` where the
        device cannot hold both for the moment (the wide store stays,
        as ``initialize`` degrades an upload), else None."""
        import jax

        from veles_tpu import events, faults, telemetry
        store = self.original_data
        dev = store.devmem
        ahead = self._reside_ahead
        if ahead is not None and ahead[0] == dtype:
            reside, where = ahead[1].result()
        else:
            reside, where = _reside_program(dev, dtype)
        try:
            if faults.fire("device.oom_on_put", site="resident_cast"):
                raise RuntimeError(
                    "RESOURCE_EXHAUSTED: fault-injected OOM on the "
                    "resident dataset cast")
            narrow = reside(dev)
            jax.block_until_ready(narrow)
        except (KeyboardInterrupt, SystemExit):
            raise
        except Exception as e:  # noqa: BLE001 — degrade, see above
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            self.warning("casting the resident dataset to %s hit "
                         "device OOM (%s) — it stays %s", dtype, e,
                         dev.dtype)
            return "oom"
        # a re-upload after a host write lands in the same layout; of
        # a row-sharded store it goes replicated through
        # ``Device.put`` (see Vector.upload_row_sharded)
        store.retype_devmem(
            narrow, None if self.shard_resident else where)
        telemetry.counter(events.CTR_LOADER_RESIDENT_CASTS).inc()
        self.info("resident dataset cast %s -> %s on the device: "
                  "%.1f -> %.1f MiB", dev.dtype, narrow.dtype,
                  dev.nbytes / 2 ** 20, narrow.nbytes / 2 ** 20)
        return None

    def create_minibatch_data(self) -> None:
        mb = self.max_minibatch_size
        shape = (mb,) + tuple(self.original_data.shape[1:])
        # host minibatches are always the dequantized float view — the
        # eager/numpy units were built for normalized pixels, not bytes
        mb_dtype = np.float32 if self.dequant is not None \
            else self.original_data.dtype
        self.minibatch_data.mem = np.zeros(shape, mb_dtype)
        if self.has_labels:
            self.minibatch_labels.mem = np.zeros(mb, np.int32)
        if self.has_targets:
            tshape = (mb,) + tuple(self.original_targets.shape[1:])
            self.minibatch_targets = Vector(
                np.zeros(tshape, self.original_targets.dtype),
                name="minibatch_targets")
        # staging buffers: fill_minibatch overwrites them before any
        # read, and the fused device path never touches them — the
        # eager upload of their zeros (mb x sample = 100s of MB at
        # AlexNet scale) bought nothing
        for v in (self.minibatch_data, self.minibatch_labels):
            if v:
                v.initialize(self.device, upload=False)

    def fill_minibatch(self) -> None:
        # map_read, not .mem: a device-born dataset (DeviceSynthetic
        # Loader, incl. on a mesh) has no host copy until fetched —
        # the eager wiring must still be able to fill host minibatches
        idx = self.minibatch_indices.map_read()
        self.minibatch_data.map_invalidate()[:] = \
            self.normalized_host_rows(idx)
        if self.has_labels:
            self.minibatch_labels.map_invalidate()[:] = \
                self.original_labels.map_read()[idx]
        if self.has_targets:
            self.minibatch_targets.map_invalidate()[:] = \
                self.original_targets.map_read()[idx]

    def normalized_host_rows(self, indices) -> np.ndarray:
        """Float32 normalized rows for GLOBAL ``indices`` (or a
        slice), regardless of the ingest codec — for host consumers
        (eager minibatch fill, ensemble prediction, DBN pretraining)
        that would otherwise read raw uint8 under quantized ingest."""
        rows = self.original_data.map_read()[indices]
        if self.dequant is not None:
            rows = self.dequant.apply_host(rows)
        elif rows.dtype.itemsize < 4 and rows.dtype.kind not in "iub":
            # a device-born store that resides narrower than float32
            # (``reside_as``) reads back in the device's dtype
            rows = rows.astype(np.float32)
        return rows

    def assemble_rows(self, indices: np.ndarray):
        """Streaming-mode assembly: slice the host arrays (already
        normalized by post_load_data — or raw uint8 under quantized
        ingest, which IS the wire format; the fused step dequantizes
        on device)."""
        data = self.original_data.mem[indices]
        labels = self.original_labels.mem[indices] \
            if self.has_labels else None
        targets = self.original_targets.mem[indices] \
            if self.has_targets else None
        return data, labels, targets


def _reside_program(dev, dtype):
    """(program, where): ``reside_as``'s cast compiled for ``dev``'s
    shape and sharding — ``program(dev)`` is ``dev`` as ``dtype`` with
    rows major-most — and the ``Format`` a re-upload of such a store
    goes to (None: the device's default layout serves)."""
    import jax

    from veles_tpu.engine import core as engine_core
    layout = _row_major_layout(dev, dtype)
    out = dev.sharding
    if layout is not None:
        from jax.experimental.layout import Format
        out = Format(layout, out)

    def reside(rows):
        return rows.astype(dtype)

    # an output in a layout of its own: see not_persisted
    with engine_core.not_persisted():
        program = engine_core.donating_jit(
            reside, out_shardings=out).lower(jax.ShapeDtypeStruct(
                dev.shape, dev.dtype, sharding=dev.sharding)).compile()
    return program, out if layout is not None else None


def _row_major_layout(dev, dtype):
    """The device layout in which a store of ``dev``'s shape lies best
    for a row gather: axis 0 major-most, each row in the layout the
    device gives ONE sample of ``dtype`` by default (its padding-free
    choice).  The device's default for the whole array is the other
    way round wherever the row count tiles better than the sample
    (TPU: rows minor-most for every rank-4 array), and a gather of
    whole rows then re-lays the whole store out.  None where that
    default has rows major-most already (XLA:CPU), or the backend
    shows no layouts."""
    from jax.experimental.layout import Layout

    from veles_tpu.engine import core as engine_core
    sample = engine_core.put(
        np.zeros(dev.shape[1:], dtype),
        next(iter(dev.sharding.device_set))).format.layout
    whole = dev.format.layout
    if sample is None or whole is None:
        return None
    major_to_minor = (0,) + tuple(
        1 + axis for axis in sample.major_to_minor)
    if major_to_minor == tuple(whole.major_to_minor):
        return None
    return Layout(major_to_minor=major_to_minor, tiling=sample.tiling)


class DeviceArrayLoader(FullBatchLoader):
    """FullBatchLoader over splits that are ALREADY device-resident
    jax arrays — the DBN stage-chaining loader (Menagerie).

    Stage k+1 of greedy DBN pretraining trains on the hidden
    representations stage k computes; handing those through host numpy
    costs a dataset-sized d2h fetch plus a dataset-sized h2d re-upload
    per stage.  This loader accepts the device arrays verbatim:
    ``load_data`` concatenates them ON DEVICE in the canonical
    [test | valid | train] layout and binds ``original_data.devmem``
    directly — ``original_data.mem`` stays ``None``, no host copy ever
    materializes, and ``ingest_h2d_bytes`` (the ``Device.h2d_bytes``
    delta across ``load_data``) pins the handoff at zero transfer.

    ``targets_from_data=True`` aliases ``original_targets`` to the same
    device buffer (autoencoder/RBM reconstruction targets).  The fused
    path consumes the resident array as usual; the eager host wiring
    still works (``map_read`` fetches on demand) but defeats the point.
    """

    def __init__(self, workflow=None,
                 train: Any = None,
                 valid: Any = None,
                 test: Any = None,
                 targets_from_data: bool = False,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self._splits = {TRAIN: train, VALID: valid, TEST: test}
        self.targets_from_data = targets_from_data
        #: ``Device.h2d_bytes`` consumed ingesting the dataset (the
        #: ``load_data`` window) — the zero-copy-handoff pin reads
        #: this.  The companion invariant is ``original_data.mem is
        #: None`` after initialize: with no host copy in existence,
        #: nothing can re-upload the dataset behind this counter.
        self.ingest_h2d_bytes = 0

    def load_data(self) -> None:
        import jax.numpy as jnp
        if self.device is None or not getattr(self.device, "is_jax",
                                              False):
            raise ValueError(
                f"{self.name}: DeviceArrayLoader needs a jax device "
                "(its splits are device arrays by contract)")
        before = int(getattr(self.device, "h2d_bytes", 0) or 0)
        xs = []
        for klass in (TEST, VALID, TRAIN):
            x = self._splits[klass]
            if x is None:
                self.class_lengths[klass] = 0
                continue
            self.class_lengths[klass] = int(x.shape[0])
            xs.append(x)
        if not xs:
            raise ValueError(f"{self.name}: no splits given")
        data = xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=0)
        self.original_data.devmem = data
        if self.targets_from_data:
            self.original_targets.devmem = data
        self._splits = {TRAIN: None, VALID: None, TEST: None}
        self.ingest_h2d_bytes = \
            int(getattr(self.device, "h2d_bytes", 0) or 0) - before


class ArrayLoader(FullBatchLoader):
    """FullBatchLoader over in-memory numpy arrays per split.

    ``train=(x, y)`` required; ``valid``/``test`` optional.  This is the
    loader the synthetic datasets and most tests use.
    """

    def __init__(self, workflow=None,
                 train: Optional[tuple] = None,
                 valid: Optional[tuple] = None,
                 test: Optional[tuple] = None,
                 targets_from_labels: bool = False,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self._splits = {TRAIN: train, VALID: valid, TEST: test}
        self._targets_from_labels = targets_from_labels

    def load_data(self) -> None:
        xs, ys, ts = [], [], []
        for klass in (TEST, VALID, TRAIN):
            split = self._splits[klass]
            if split is None:
                self.class_lengths[klass] = 0
                continue
            x = np.asarray(split[0])
            self.class_lengths[klass] = len(x)
            xs.append(x)
            if len(split) > 1 and split[1] is not None:
                ys.append(np.asarray(split[1]))
            if len(split) > 2 and split[2] is not None:
                ts.append(np.asarray(split[2]))
        self.original_data.mem = np.concatenate(xs, axis=0)
        if ys:
            self.original_labels.mem = \
                np.concatenate(ys, axis=0).astype(np.int32)
        if ts:
            self.original_targets.mem = np.concatenate(ts, axis=0)
        elif self._targets_from_labels:
            # autoencoder-style: target is the input itself
            self.original_targets.mem = self.original_data.mem
