"""The fused training step — the production TPU execution path.

The reference executes one OpenCL/CUDA kernel per unit per minibatch
(veles/accelerated_units.py execute_kernel).  Translating that 1:1
would dispatch dozens of tiny XLA computations per step and lose badly
(SURVEY.md §7 "hard parts").  Instead, the whole iteration —

    gather minibatch rows from the HBM-resident dataset
    -> every forward unit's apply
    -> evaluator metrics + err_output
    -> every gradient unit's backward + SGD update

— is traced into ONE jitted function, and a ``lax.scan`` over up to
``loader.superstep`` same-class minibatches runs MANY iterations per
device dispatch (amortizing per-execute latency).  Metrics and the confusion matrix accumulate
ON DEVICE in donated carry buffers; the host fetches 12 bytes once per
class end instead of 3 scalars per minibatch.  Matmuls/convs run in
the device's ``compute_dtype`` (bfloat16 on TPU — the MXU's native
format) against float32 master weights.

The input arrives in that dtype too, and the adapter asks for it at
``initialize``: a streaming loader assembles its batches in it
(``loader.stream_dtype``), a resident loader casts its HBM store to it
once (``loader.reside_as``; the loader decides and owns the store).
The trace's own ``astype`` is then a no-op on every row — traced over
a float32 store it is hoisted out of the scan and re-casts the whole
store on every superstep.

``FusedStepRunner`` is a drop-in graph node: it sits where the
forwards+evaluator+gds chain would, reads the loader's minibatch
indices, and rebinds every unit's Vectors (weights, output, metrics) to
the step outputs — so Decision, Snapshotter, and plotters observe
exactly what they would in eager mode.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from veles_tpu.accelerated_units import AcceleratedUnit
from veles_tpu.loader.base import TRAIN
from veles_tpu import events, prng, telemetry
from veles_tpu.ops import batching

#: ``fused.first_dispatch``'s split of the first call: field -> the
#: counter whose growth over the call it is (engine/core.py's listener)
_FIRST_CALL_PARTS = {
    "trace_seconds": events.CTR_FUSED_TRACE_SECONDS,
    "lower_seconds": events.CTR_FUSED_LOWER_SECONDS,
    "compile_seconds": events.CTR_FUSED_COMPILE_SECONDS,
    "cold": events.CTR_FUSED_COLD_COMPILES,
}


class FusedStepRunner(AcceleratedUnit):
    def __init__(self, workflow=None, loader=None, forwards=None,
                 evaluator=None, gds=None, rng_stream: str = "fused",
                 compute_dtype: Any = None, **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        self.loader = loader
        self.forwards: List[Any] = forwards or []
        self.evaluator = evaluator
        #: gds[i] is the GradientUnit of forwards[i] (may contain None
        #: for frozen/param-less layers that still need err routing)
        self.gds: List[Any] = gds or []
        self.rng_stream = rng_stream
        #: None = the device's policy (bf16 on TPU, f32 elsewhere)
        self.compute_dtype = compute_dtype
        #: a jax.sharding.Mesh when DataParallel is installed — the
        #: steps are then jitted with the minibatch sharded over the
        #: mesh's data axis and params replicated (parallel/ package)
        self.mesh = None
        #: True = the loader's resident dataset is ROW-SHARDED over
        #: the mesh (each device holds 1/N of the rows; set from
        #: loader.shard_resident at initialize).  The in-trace gather
        #: then runs as a shard_map local gather + psum assembly
        #: (batching.make_sharded_row_gather) — f32-exact vs the
        #: replicated placement — and the dataset/target step args are
        #: jitted with the row-sharded in_sharding.
        self.data_sharded = False
        self._train_step = None
        self._eval_step = None
        #: the forward-only program of ``probe_units`` (None: not built
        #: yet; False: no unit of the chain has a probe)
        self._probe = None
        #: the Keel ExecutionCore (engine/core.py): every placement /
        #: donation / compile decision this runner makes goes through
        #: it, and it charges the params+opt footprint to the process
        #: HBM arbiter's `train` pool.  Built with the steps (the mesh
        #: must be resolved first).
        self._core = None
        self._params: Optional[Dict[str, Dict[str, Any]]] = None
        self._opt: Optional[Dict[str, Dict[str, Any]]] = None
        self._rng_counter = 0
        #: True = the loader's dataset is not HBM-resident; the step
        #: consumes host-assembled superstep batches (resolved at
        #: initialize from loader.device_resident)
        self.streaming = False
        #: on-device metric accumulator [n_err, loss_sum, count] and
        #: confusion accumulator, reset at each take_class_metrics()
        self._acc: Any = None
        self._conf: Any = None
        #: per-minibatch ABSOLUTE learning rates, shape (k, n_gd, 2)
        #: [(lr_weights, lr_bias)], written by LearningRateAdjust as a
        #: traced argument (no retrace).  None = read the live gd unit
        #: rates each firing (constant within the superstep).  Absolute
        #: rates, not scales: the traced step must never bake a
        #: schedule-mutated rate as its base (that made every scale
        #: multiply the wrong constant once lr_adjust ran before the
        #: first train dispatch).
        self.lr_rates = None
        #: cumulative samples dispatched (host-side mask sums), train
        #: and eval separately — feed the end-of-run MFU report
        #: (veles_tpu/profiling.py): train costs fwd+bwd, eval fwd only
        self.processed_images = 0.0
        self.processed_eval_images = 0.0
        #: streaming upload double-buffer: the last two device_put
        #: batches; the third dispatch blocks on the oldest transfer
        from collections import deque
        self._inflight: Any = deque()
        #: cumulative seconds this runner spent submitting streaming
        #: uploads and blocked on their drain — the transfer-busy
        #: numerator of the input pipeline's efficiency accounting
        #: (the ``fused.stream_transfer_seconds`` counter): on a
        #: link-bound host a perfect pipeline spends ~all its wall
        #: here, and the remainder is framework overhead
        self.stream_transfer_seconds = 0.0
        #: this runner's share of the process-wide
        #: ``fused.stream_transfer_bytes`` registry counter — the ONE
        #: write site (_run_streaming) increments both, so the
        #: ``stream_transfer_bytes`` property keeps its per-runner
        #: meaning while the registry carries the process aggregate
        self._stream_bytes = 0
        #: times a streaming upload OOMed and recovered by draining
        #: the double-buffer (Faultline telemetry; see _run_streaming)
        self.stream_oom_retries = 0
        #: which step kinds ("train"/"eval") have dispatched — the
        #: first submit of each traces + compiles (or loads) and is
        #: recorded apart from the steady-state submit histogram
        self._dispatch_seen: set = set()
        #: (kind, perf_counter at its first submit) of the class now
        #: in flight; take_class_metrics closes it into
        #: ``fused.<kind>_wall_seconds``
        self._class_open: Optional[Tuple[str, float]] = None
        #: perf_counter when the last class-end fetch returned, until
        #: the next submit records ``loop.turnaround``
        self._fetch_returned: Optional[float] = None
        #: monotonic timestamp of the first firing (end-of-run
        #: throughput/MFU summary, see _record_telemetry_summary)
        self._first_run_ts = None

    _unpicklable = AcceleratedUnit._unpicklable + (
        "_train_step", "_eval_step", "_probe", "_params", "_opt", "mesh",
        "_batch_sharding", "_acc", "_conf", "_inflight", "_core")

    @property
    def stream_transfer_bytes(self) -> int:
        """Cumulative host->device bytes THIS runner's streaming path
        shipped (pixel batches + targets/labels) — the wire-format
        accounting: divided by processed images it certifies what the
        codec actually moved per sample.  The former plain attribute
        (and its ``__setstate__`` back-compat shim) is now a read-only
        view over the accounting that also feeds the process-wide
        ``fused.stream_transfer_bytes`` registry counter."""
        return self._stream_bytes

    # -- pytree assembly ----------------------------------------------

    def _collect_params(self) -> Dict[str, Dict[str, Any]]:
        return {f.name: f.gather_params() for f in self.forwards}

    def _collect_opt(self) -> Dict[str, Dict[str, Any]]:
        opt = {}
        for gd in self.gds:
            if gd is None:
                continue
            gd.reconcile_velocities()   # param shapes may have changed
            opt[gd.name] = {k: v.unmap()
                            for k, v in gd.accumulated_grads.items()}
        return opt

    def _scatter_params(self, params, opt) -> None:
        """Rebind unit Vectors to the donated-step outputs so the rest
        of the framework observes updated weights."""
        for f in self.forwards:
            p = params[f.name]
            for pname, vec in f.param_vectors().items():
                if pname in p:
                    vec.devmem = p[pname]
        for gd in self.gds:
            if gd is None:
                continue
            for k, vec in gd.accumulated_grads.items():
                vec.devmem = opt[gd.name][k]

    # -- trace construction -------------------------------------------

    def _has_targets(self) -> bool:
        return hasattr(self.evaluator, "target")

    def _targets_are_rows(self) -> bool:
        """A next-token loss reads its targets off the data rows."""
        return getattr(self.evaluator, "targets_from_data", False)

    def _want_confusion(self) -> bool:
        ev = self.evaluator
        return bool(getattr(ev, "compute_confusion", False)) and \
            getattr(ev, "n_classes", None) is not None

    def _conf_shape(self) -> Tuple[int, int]:
        if self._want_confusion():
            n = self.evaluator.n_classes
            return (n, n)
        return (1, 1)

    def _resolved_dtype(self):
        return batching.resolve_compute_dtype(self.compute_dtype,
                                              self.device)

    def _build_steps(self) -> None:
        from jax import lax

        from veles_tpu.engine import core as engine_core

        evaluator = self.evaluator
        seed = prng.get(self.rng_stream).seed
        cd = self._resolved_dtype()
        out_shape = self._out_shape = tuple(
            self.forwards[-1].output.shape)
        streaming = self.streaming
        if self._core is not None:    # invalidate_trace rebuild: the
            self._core.release()      # old ledger entry must not leak
        core = self._core = engine_core.ExecutionCore(
            self.device, self.mesh, pool="train", name=self.name)
        # the shared Keel trace bodies — quantized wire ingest, the
        # forward chain with residuals, the backward+SGD walk — and
        # the ONE scan that composes them (engine/core.py); what is
        # decided here is how the data reaches it and where it lies
        ingest = engine_core.build_ingest(
            getattr(self.loader, "dequant", None))
        # what is decided from shapes alone, before anything is jitted
        # (on a mesh: how each layer's gradients become the global
        # minibatch's — gathered activations or an all-reduce — and
        # what the chip's compiler is told with the step)
        exchange = None
        with telemetry.span(events.SPAN_FUSED_PLAN):
            recompute = self._decide_recompute(cd)
            loss_blocks = self._decide_loss_blocks(cd)
            if core.on_mesh:
                exchange = engine_core.GradExchange(
                    core.mesh, self.forwards, self.gds, cd)
                telemetry.event(events.EV_DP_GRAD_EXCHANGE,
                                **exchange.describe())
                telemetry.gauge(
                    events.GAUGE_DP_GRAD_EXCHANGE_GROUPS).set(
                    len(exchange.groups))
        blocked_head = None
        if loss_blocks:
            blocked_head = engine_core.build_blocked_head(
                self.forwards[-1], evaluator, loss_blocks)
            out_shape = None      # no whole output exists to keep
        forward_pass = engine_core.build_forward(
            self.forwards, seed, cd, recompute,
            head_apart=bool(loss_blocks))
        backward_update = engine_core.build_backward(
            self.forwards, self.gds, cd, seed, exchange)

        data_sharded = self.data_sharded and self.mesh is not None
        gather = None if streaming else engine_core.take_rows
        if data_sharded:
            # row-sharded residency: the gather crosses device shards
            # (local gather + exact psum), and the assembled minibatch
            # re-enters the SAME batch sharding the replicated-data
            # path uses — identical downstream program, so residency
            # placement cannot change the numerics
            sharded_gather = batching.make_sharded_row_gather(self.mesh)
            mb_rows = core.row_sharding

            def gather(dataset, target_store, indices):
                x, t = sharded_gather(indices, dataset, target_store)
                return (lax.with_sharding_constraint(x, mb_rows),
                        lax.with_sharding_constraint(t, mb_rows))

        train_step, eval_step = engine_core.build_scan_steps(
            ingest, forward_pass, backward_update, cd,
            evaluator.metrics_fn, gather=gather,
            n_classes=evaluator.n_classes if self._want_confusion()
            else None, out_shape=out_shape, blocked_head=blocked_head)
        self._probe = None
        train_in = eval_in = None
        if self.mesh is not None:
            # SPMD data parallelism: minibatch rows sharded over the
            # data axis, params replicated.  mask.sum() and the
            # per-param batch reductions cross the sharded axis, so the
            # partitioner emits the gradient allreduce (ICI psum) —
            # this IS the master-slave aggregation, in-compiler —
            # except where ``exchange`` gathers a layer's activations
            # instead (engine/core.py GradExchange).
            repl = core.replicated
            # every scanned array rides the batch sharding — each
            # device receives only its slice of every minibatch
            batch = self._batch_sharding = core.batch_sharding
            # the resident store enters row-sharded under Lattice (1/N
            # rows per device), replicated otherwise — the ONLY
            # in_sharding difference between the two modes
            store = core.row_sharding if data_sharded else repl
            feed = (batch,) * 3 if streaming \
                else (store, store, batch, batch)
            train_in = (repl,) * 4 + feed + (repl, repl)
            eval_in = (repl,) * 3 + feed + (repl,)
        self._train_step = core.jit(
            train_step, donate=(0, 1, 2, 3), in_shardings=train_in,
            compiler_options=exchange.options if exchange else None)
        self._eval_step = core.jit(eval_step, donate=(1, 2),
                                   in_shardings=eval_in)

    def _device_bytes_limit(self) -> Optional[int]:
        """One device's memory as its allocator reports it (None where
        it reports none: XLA:CPU)."""
        jdev = getattr(self.device, "jax_device", None)
        stats = jdev.memory_stats() if jdev is not None else None
        return int(stats["bytes_limit"]) \
            if stats and stats.get("bytes_limit") else None

    def _state_bytes(self, cd) -> int:
        """What the device holds beside a step's activations: the
        units' parameters and optimiser state as they hold them, the
        parameters' copy in the compute dtype, a resident store."""
        state = sum(v.nbytes + v.size * np.dtype(cd).itemsize
                    for f in self.forwards
                    for v in f.param_vectors().values() if v)
        state += sum(gd.opt_nbytes() for gd in self.gds
                     if gd is not None)
        ld = self.loader
        if not self.streaming and ld.original_data:
            state += ld.original_data.nbytes
        return state

    def _decide_loss_blocks(self, cd) -> int:
        """Into how many blocks of positions the head's product, the
        loss and the head's backward are cut (0: the whole, as ever).
        Decided from what the program can observe, no knob: the loss
        holds about four arrays shaped like the logits (logits,
        log-probabilities, one-hot targets, error) in f32; whole they
        may take a quarter of what the device has left beside the state
        (half of it is the kept residuals', :meth:`_decide_recompute`;
        this is half of the backward's working half), else a block may
        take a sixteenth.  Only a head that acts on each position
        alone, under an evaluator that scores a block, can be cut.
        Journaled either way where the loss could be (``loss.blocked``)."""
        head, ev = self.forwards[-1], self.evaluator
        if not getattr(head, "per_position", False) \
                or not hasattr(ev, "block_metrics") \
                or getattr(head, "residual_of", None) is not None \
                or self.gds[-1] is None:
            return 0
        shape = tuple(int(d) for d in head.output.shape)
        n_dev = int(self.mesh.devices.size) if self.mesh is not None \
            else 1
        whole = 4 * 4 * int(np.prod(shape)) // n_dev
        limit, t = self._device_bytes_limit(), shape[1]
        free = None if limit is None else limit - self._state_bytes(cd)
        blocks = 0
        if limit is None:
            reason = "no_limit"
        elif self.mesh is not None:
            reason = "not_blockable"
        elif 4 * whole <= free:
            reason = "fits"
        else:
            reason, blocks = "whole_exceeds_free", 2
            while 16 * whole > blocks * free and t % (2 * blocks) == 0:
                blocks *= 2
            if t % blocks:
                reason, blocks = "not_blockable", 0
        telemetry.event(
            events.EV_LOSS_BLOCKED, blocks=blocks, bytes_whole=whole,
            bytes_block=whole // blocks if blocks else whole,
            limit_bytes=limit, reason=reason)
        return blocks

    def _decide_recompute(self, cd) -> bool:
        """Whether the chain's residual entries keep only their inputs
        and re-run their forward inside the backward walk.  Decided
        from what the program can observe, no knob: the bytes of
        residuals the chain would keep (from shapes) against what the
        device has left beside the state — the units' parameters and
        optimiser state as they hold them, the parameters' copy in the
        compute dtype, a resident store.  Kept residuals may take half
        of that (the other half is the backward's own working set).
        Journaled either way (``fused.recompute``)."""
        from veles_tpu.engine import core as engine_core
        if not engine_core.has_residual(self.forwards):
            return False
        import jax

        n_dev = int(self.mesh.devices.size) if self.mesh is not None \
            else 1
        ld = self.loader
        x = jax.ShapeDtypeStruct(
            (max(1, ld.max_minibatch_size // n_dev),)
            + tuple(ld.minibatch_data.shape[1:]),
            ld.minibatch_data.dtype)
        cparams = {
            f.name: {k: jax.ShapeDtypeStruct(tuple(v.shape), cd)
                     for k, v in f.param_vectors().items() if v}
            for f in self.forwards}
        kept, kept_recomputing = engine_core.kept_activation_bytes(
            self.forwards, cd, cparams, x)
        state = self._state_bytes(cd)
        limit = self._device_bytes_limit()
        if limit is None:
            recompute, reason = False, "no_limit"
        elif 2 * kept <= limit - state:
            recompute, reason = False, "fits"
        else:
            recompute, reason = True, "kept_exceeds_free"
        blocks = sum(isinstance(e, tuple)
                     for e in engine_core.chain_of(self.forwards))
        now = kept_recomputing if recompute else kept
        telemetry.gauge(events.GAUGE_FUSED_KEPT_ACTIVATION_BYTES).set(now)
        telemetry.event(
            events.EV_FUSED_RECOMPUTE,
            policy="recompute" if recompute else "keep",
            blocks=blocks if recompute else 0, kept_bytes=int(now),
            recomputed_bytes=int(kept - now) if recompute else 0,
            state_bytes=int(state), limit_bytes=limit, reason=reason)
        return recompute

    # -- lifecycle -----------------------------------------------------

    def initialize(self, device=None, **kwargs) -> None:
        """Resolve how the data reaches the step — streaming or
        resident, replicated or row-sharded: the loader's decisions,
        followed here — build the jitted steps, and tell the loader
        the dtype the step ingests: ``stream_dtype`` for the batches a
        streaming loader assembles, ``reside_as`` for the HBM store of
        a resident one, which from then on IS in that dtype."""
        super().initialize(device=device, **kwargs)
        if not any(self.loader.class_lengths):
            # Workflow.initialize retries on AttributeError — the
            # loader must load first so its residency mode is known
            raise AttributeError(
                f"{self.name}: loader has not loaded its data yet")
        self.streaming = not getattr(self.loader, "device_resident",
                                     True)
        if self.streaming and self.device.is_jax:
            if getattr(self.loader, "dequant", None) is None and \
                    self.loader.minibatch_data.dtype.kind not in "iub":
                # assemble streaming batches directly in the compute
                # dtype (prefetch thread): the trace's first op is this
                # cast anyway, and doing it host-side halves H2D bytes
                # on the bf16 platforms where the transfer bottlenecks
                # (never an integer store: token ids stay ids)
                self.loader.stream_dtype = \
                    np.dtype(self._resolved_dtype())
            # else: quantized ingest — the wire is uint8 (1 byte/px,
            # half the bf16 wire) and the traced prologue dequantizes;
            # a stream_dtype cast would widen the bytes back out
        # row-sharded residency (Lattice): resolved by the loader's
        # per-device budget accounting — the runner only follows
        self.data_sharded = bool(
            self.mesh is not None and not self.streaming
            and getattr(self.loader, "shard_resident", False))
        if self.mesh is not None:
            # the STATIC minibatch shape is max_minibatch_size, which
            # clamps below minibatch_size when every class is smaller —
            # DataParallel.install() can only check minibatch_size
            # (load_data hasn't run yet there)
            n = int(self.mesh.devices.size)
            mb = self.loader.max_minibatch_size
            if mb % n:
                raise ValueError(
                    f"static minibatch shape {mb} (loader "
                    f"max_minibatch_size) not divisible by mesh size "
                    f"{n}; lower minibatch_size or pad the dataset")
        if self._train_step is None:
            with telemetry.span(events.SPAN_FUSED_BUILD_STEPS):
                self._build_steps()
        reside_as = getattr(self.loader, "reside_as", None)
        if reside_as is not None and self.device.is_jax:
            # the resident counterpart of ``stream_dtype``; the loader
            # decides whether it engages (never while streaming) and
            # journals why not.  Left as uploaded, the store is cast
            # and re-laid out WHOLE on every superstep: XLA hoists both
            # out of the scan (AlexNet, v5e: 14.7 % of device time)
            reside_as(np.dtype(self._resolved_dtype()))

    def _target_store(self):
        ld = self.loader
        if self._targets_are_rows():
            return ld.original_data.unmap()    # the one store, twice
        if self._has_targets():
            return ld.original_targets.unmap()
        return ld.original_labels.unmap()

    def _fresh_acc(self) -> Tuple[np.ndarray, np.ndarray]:
        return (np.zeros(3, np.float32),
                np.zeros(self._conf_shape(), np.int32))

    def _superstep_arrays(self) -> Tuple[np.ndarray, np.ndarray]:
        ld = self.loader
        if ld.superstep_k and ld.superstep_indices is not None:
            return ld.superstep_indices, ld.superstep_mask
        # generic loaders (zmq slave jobs) provide one minibatch
        return (np.asarray(ld.minibatch_indices.map_read())[None],
                np.asarray(ld.minibatch_mask.map_read())[None])

    def run(self) -> None:
        ld = self.loader
        self._ensure_params()
        if self._train_step is None:   # invalidated (e.g. a resize)
            with telemetry.span(events.SPAN_FUSED_BUILD_STEPS):
                self._build_steps()
        if self._acc is None:
            self._acc, self._conf = self._fresh_acc()
        if self.mesh is None:
            # every dispatch presents the SAME argument placement: jit
            # lowers — and XLA compiles — one executable per pattern
            # of committed/uncommitted inputs, so host-fresh metric
            # carries at class starts (and device-born momentum zeros,
            # see JaxDevice.zeros) used to cost up to three compiles
            # of the one train program.  PR 21, on the chip: the
            # SECOND dispatch of a cold AlexNet run re-compiled for
            # 23 s.  A committed device array passes through as is.
            with telemetry.span(events.SPAN_FUSED_PUT_CARRY):
                self._acc = self._core.put(self._acc)
                self._conf = self._core.put(self._conf)
        indices, mask = self._superstep_arrays()
        k = indices.shape[0]
        train = ld.minibatch_class == TRAIN
        images = float(np.sum(mask))
        if train:
            self.processed_images += images
        else:
            self.processed_eval_images += images
        if self._first_run_ts is None:
            self._first_run_ts = time.monotonic()
        first_train = train and "train" not in self._dispatch_seen
        if self.streaming:
            self._run_streaming(ld, k, mask, train)
        else:
            self._run_resident(ld, k, indices, mask, train)
            if first_train and self.mesh is None:
                self._report_probes(indices[0])
        self._rng_counter += k
        telemetry.counter(events.CTR_FUSED_DISPATCHES).inc()
        telemetry.counter(
            f"fused.{'train' if train else 'eval'}_images").inc(images)
        if train and self._targets_are_rows():
            # an "image" of a sequence model is a row: one packed
            # sequence of this many tokens
            telemetry.counter(events.CTR_FUSED_TRAIN_TOKENS).inc(
                images * int(np.prod(ld.minibatch_data.shape[1:])))

    def probe_units(self, rows) -> Dict[str, Any]:
        """What the units that have a ``probe`` say the minibatch
        ``rows`` (as the step ingests them) puts on them, under the
        parameters as they stand: ``{unit name: its answer}`` on the
        host, ``{}`` where no unit has one.  A forward-only program of
        its own (``engine/core.py`` ``build_probe``), built on first
        use; it waits for the device, so it belongs to set-up."""
        import jax

        from veles_tpu.engine import core as engine_core
        with telemetry.span(events.SPAN_FUSED_PROBE):
            if self._probe is None:
                fn = engine_core.build_probe(self.forwards,
                                             self._resolved_dtype())
                self._probe = self._core.jit(fn) if fn else False
            if not self._probe:
                return {}
            self._ensure_params()
            return jax.device_get(self._probe(self._params, rows))

    def _report_probes(self, indices) -> None:
        """Once, right after the first train firing: the probed units
        report what its first minibatch put on them (a mixture of
        experts: ``moe.load``)."""
        import jax.numpy as jnp
        if not any(hasattr(f, "probe") for f in self.forwards):
            return
        got = self.probe_units(jnp.take(
            self.loader.original_data.unmap(), jnp.asarray(indices),
            axis=0))
        for f in self.forwards:
            if f.name in got:
                f.report_probe(got[f.name])

    @contextlib.contextmanager
    def _submit(self, kind: str, k: int):
        """Round the jitted call alone: the ``fused.<kind>_submit``
        span, host SUBMIT time of one superstep — on an asynchronous
        backend the device works on after it; the barrier is the
        class-end fetch (``fused.fetch_metrics``).  The FIRST call of
        a kind traces + compiles (or loads the cached program) and
        uploads the parameters, so it is a span of its own name and a
        gauge (and a journal event), kept out of the steady-state
        histogram the p50/p99 report reads.  A context manager, so
        that the jitted call stays in its caller's frame (see
        Workflow.initialize)."""
        first = kind not in self._dispatch_seen
        now = time.perf_counter()
        if self._class_open is None:
            self._class_open = (kind, now)
        if self._fetch_returned is not None:
            telemetry.histogram(events.HIST_LOOP_TURNAROUND).record(
                now - self._fetch_returned)
            self._fetch_returned = None
        was = {field: telemetry.counter(n).value
               for field, n in _FIRST_CALL_PARTS.items()} if first else {}
        with telemetry.span(f"fused.first_{kind}_submit" if first
                            else f"fused.{kind}_submit") as span:
            yield
        if first:
            self._dispatch_seen.add(kind)
            telemetry.gauge(
                f"fused.first_{kind}_submit_seconds").set(span.seconds)
            # the run record's "where and what": the device as JAX
            # reports it and the static step shape, next to the one
            # call that traced + lowered + compiled (or loaded) the
            # program, and how its seconds split
            telemetry.event(events.EV_FUSED_FIRST_DISPATCH, kind=kind,
                            seconds=round(span.seconds, 4),
                            **{field: round(
                                telemetry.counter(n).value - was[field],
                                6) for field, n in
                               _FIRST_CALL_PARTS.items()},
                            streaming=bool(self.streaming),
                            minibatches=k,
                            batch_shape=list(
                                self.loader.minibatch_data.shape),
                            output_shape=list(self._out_shape),
                            **self.device.describe())

    def _run_resident(self, ld, k, indices, mask, train: bool) -> None:
        dataset = ld.original_data.unmap()
        targets = self._target_store()
        if self.mesh is not None:
            # Vectors upload replicated (MeshJaxDevice.put); the batch
            # args must enter the step sharded over the data axis —
            # replicated->sharded is a local slice, no communication.
            indices = self._core.put(indices, self._batch_sharding)
            mask = self._core.put(mask, self._batch_sharding)
        if train:
            lr = self._lr_rates_array(k)
            with self._submit("train", k):
                self._params, self._opt, self._acc, self._conf = \
                    self._train_step(
                        self._params, self._opt, self._acc, self._conf,
                        dataset, targets, indices, mask, lr,
                        self._rng_counter)
            self._scatter_params(self._params, self._opt)
        else:
            with self._submit("eval", k):
                self._acc, self._conf, out = self._eval_step(
                    self._params, self._acc, self._conf, dataset,
                    targets, indices, mask, self._rng_counter)
            if out is not None:       # a blocked head keeps no output
                self.forwards[-1].output.devmem = out

    def _run_streaming(self, ld, k, mask, train: bool) -> None:
        """Dispatch over the loader's host-assembled superstep batch.
        The dispatch is async: while the device chews on this group the
        loader's prefetch thread is already assembling the next one —
        that concurrency IS the input pipeline (no resident dataset).

        The upload is an explicit double-buffered ``device_put``: at
        most two superstep batches are in flight, so a device that
        falls behind the host (or a link that falls behind the
        dispatch loop) back-pressures the loop instead of piling
        unsent host batches into RAM without bound."""
        xb = ld.superstep_data
        if self._targets_are_rows():
            tb = xb
        else:
            tb = ld.superstep_targets if self._has_targets() \
                else ld.superstep_labels
        if xb is None or tb is None:
            raise RuntimeError(
                f"{self.name}: streaming mode but the loader produced "
                f"no superstep batch (superstep_data/"
                f"{'targets' if self._has_targets() else 'labels'})")
        dst = self._batch_sharding if self.mesh is not None \
            else self.device.jax_device
        # wire-byte accounting BEFORE the upload rebinds xb/tb: what
        # the codec actually ships per sample (uint8 ingest = 1
        # byte/pixel; bf16 = 2; f32 = 4) — the codec tests divide
        # this by processed images
        n_wire = int(xb.nbytes) + int(tb.nbytes)
        self._stream_bytes += n_wire
        telemetry.counter(
            events.CTR_FUSED_STREAM_TRANSFER_BYTES).inc(n_wire)
        t_transfer = time.perf_counter()
        for attempt in (1, 2):
            try:
                from veles_tpu import faults
                if faults.fire("device.oom_on_put", site="stream"):
                    raise RuntimeError(
                        "RESOURCE_EXHAUSTED: fault-injected OOM on "
                        "the streaming upload")
                xb_dev = self._core.put(xb, dst)
                tb_dev = self._core.put(tb, dst)
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:  # noqa: BLE001 — transient HBM
                # pressure: the double-buffer may still hold two
                # superstep batches worth of HBM — drain it and retry
                # ONCE before giving up (bounded degradation, not an
                # unbounded retry loop)
                if "RESOURCE_EXHAUSTED" not in str(e) or attempt == 2:
                    raise
                self.warning(
                    "streaming upload hit device OOM (%s); draining "
                    "the in-flight double-buffer and retrying once", e)
                self.stream_oom_retries += 1
                telemetry.counter(
                    events.CTR_FUSED_STREAM_OOM_RETRIES).inc()
                telemetry.event(events.EV_DEVICE_OOM_RETRY, site="stream")
                while self._inflight:
                    for buf in self._inflight.popleft():
                        buf.block_until_ready()
        xb, tb = xb_dev, tb_dev
        if self.mesh is not None:
            mask = self._core.put(mask, self._batch_sharding)
        self._inflight.append((xb, tb))
        if len(self._inflight) > 2:
            for buf in self._inflight.popleft():
                buf.block_until_ready()
        dt_transfer = time.perf_counter() - t_transfer
        self.stream_transfer_seconds += dt_transfer
        telemetry.counter(
            events.CTR_FUSED_STREAM_TRANSFER_SECONDS).inc(
            dt_transfer)
        if train:
            lr = self._lr_rates_array(k)
            with self._submit("train", k):
                self._params, self._opt, self._acc, self._conf = \
                    self._train_step(
                        self._params, self._opt, self._acc, self._conf,
                        xb, tb, mask, lr, self._rng_counter)
            self._scatter_params(self._params, self._opt)
        else:
            with self._submit("eval", k):
                self._acc, self._conf, out = self._eval_step(
                    self._params, self._acc, self._conf, xb, tb, mask,
                    self._rng_counter)
            if out is not None:
                self.forwards[-1].output.devmem = out

    def _lr_rates_array(self, k: int) -> np.ndarray:
        """``lr_rates`` as the (k, n_gd, 2) scanned input.  With no
        schedule installed, read the gd units' live rates (constant
        within the superstep, mutable between firings without retrace);
        a 3-D array (per-iteration schedule, written by
        LearningRateAdjust) must match the superstep exactly."""
        if self.lr_rates is None:
            row = np.asarray(
                [[gd.learning_rate, gd.learning_rate_bias]
                 if gd is not None else [0.0, 0.0] for gd in self.gds],
                np.float32)
            return np.broadcast_to(row, (k,) + row.shape)
        lr = np.asarray(self.lr_rates, np.float32)
        if lr.ndim == 2:
            return np.broadcast_to(lr, (k,) + lr.shape)
        if lr.shape[0] != k:
            raise ValueError(
                f"lr_rates has {lr.shape[0]} rows but the superstep "
                f"has {k} minibatches — the schedule and loader "
                f"disagree")
        return lr

    def invalidate_trace(self) -> None:
        """Drop the traced steps and cached pytrees — required after
        anything changes a parameter SHAPE (ResizableAll2All.resize).
        Current param values are synced back to the unit Vectors first
        so nothing is lost; the next firing re-collects and re-jits."""
        self.sync_params_to_vectors()
        self._params = None
        self._opt = None
        self._train_step = None
        self._eval_step = None

    # -- metric intake (Decision / zmq slave) --------------------------

    def stop(self) -> None:
        self._record_telemetry_summary()
        self._inflight.clear()  # release the upload double-buffer
        super().stop()

    def _record_telemetry_summary(self) -> None:
        """End-of-run summary (``fused.summary``): wall-clock images/sec
        since the first firing and — where the device's peak is known
        — achieved MFU via profiling.py (also the gauge ``fused.mfu``),
        over MXU work alone (conv +
        dense MACs: pool/LRN/activation passes are HBM traffic and
        would raise a utilisation).  Wall includes host time between
        dispatches, so this is the run's DELIVERED rate (a lower bound
        on engine efficiency), the number an operator reads off
        obs_report; the measured engine rate is the benchmark's
        barriered window (benchmarks/run.py)."""
        if self._first_run_ts is None or not telemetry.enabled():
            return
        elapsed = time.monotonic() - self._first_run_ts
        self._first_run_ts = None   # stop() may run more than once
        images = self.processed_images
        if elapsed <= 0 or images <= 0:
            return
        rate = images / elapsed
        try:
            from veles_tpu import profiling
            flops = profiling.model_flops_per_sample(
                self.forwards)["mxu_train"]
            jdev = getattr(self.device, "jax_device", None)
            u = profiling.mfu(rate, flops, jdev) \
                if jdev is not None else None
            if u is not None:
                telemetry.gauge(events.GAUGE_FUSED_MFU).set(round(u, 5))
            telemetry.event(
                events.EV_FUSED_SUMMARY, images=images,
                images_per_sec_wall=round(rate, 2),
                mfu=round(u, 5) if u is not None else None,
                streaming=bool(self.streaming),
                device_memory=self._device_memory())
        except Exception:  # noqa: BLE001 — summary is best-effort
            pass

    def _device_memory(self) -> List[Dict[str, Any]]:
        """Per-device allocator readings for every device this runner
        drives (all mesh devices under --dp): live bytes show which
        chips hold the params, peak above live shows which executed.
        XLA:CPU reports no stats and yields an empty list."""
        devs = list(self.mesh.devices.flat) if self.mesh is not None \
            else [self.device.jax_device]
        rows = []
        for d in devs:
            stats = d.memory_stats()
            if stats:
                rows.append({
                    "id": int(d.id),
                    "bytes_in_use": int(stats["bytes_in_use"]),
                    "peak_bytes_in_use":
                        int(stats["peak_bytes_in_use"])})
        return rows

    def release_device_state(self, sync: bool = False) -> None:
        """Drop every device buffer this runner (and its forwards)
        holds — params, optimizer state, metric carries, the upload
        double-buffer, and the units' param/output device copies.  For
        callers that build several workflows in one process (the GA
        evaluator and the Hive across models, the benchmark before its
        reference runs): the unit graph is cyclic,
        so dropping the workflow reference alone frees nothing until
        a gc cycle collection, and the chip OOMs first.  Kept HERE so
        new device-resident fields get added to the release next to
        their definitions.

        ``sync=True`` pulls the live params to host first, so the
        runner keeps training correctly after the release (a later
        run() re-uploads); ``sync=False`` skips the device->host fetch
        for workflows about to be discarded."""
        if sync:
            self.sync_params_to_vectors()
            for gd in self.gds:
                if gd is not None:  # momentum must survive the release
                    for v in gd.accumulated_grads.values():
                        v.map_read()
        self._params = self._opt = None
        self._acc = self._conf = None
        self._inflight.clear()
        if self._core is not None:
            self._core.release()
        for f in self.forwards:
            for v in f.param_vectors().values():
                if v:
                    v.drop_devmem()
            f.output.drop_devmem()
        for gd in self.gds:
            if gd is None:
                continue
            # optimizer velocity is as large as the params themselves
            for v in gd.accumulated_grads.values():
                v.drop_devmem()

    def take_class_metrics(self) -> Tuple[float, float, float,
                                          Optional[np.ndarray]]:
        """(n_err, loss_sum, count, confusion) accumulated since the
        last call — ONE small device fetch, then reset."""
        if self._acc is None:
            return 0.0, 0.0, 0.0, None
        with telemetry.span(events.SPAN_FUSED_FETCH_METRICS):
            acc = np.asarray(self._acc)
            conf = np.asarray(self._conf) \
                if self._want_confusion() else None
        # the fetch drained every queued superstep of the class: its
        # images over first submit -> now is the delivered rate, and
        # from now to the next submit the device waits for the host
        self._fetch_returned = time.perf_counter()
        if self._class_open is not None:
            kind, t_open = self._class_open
            self._class_open = None
            telemetry.counter(f"fused.{kind}_wall_seconds").inc(
                self._fetch_returned - t_open)
            if kind == "train":
                # the first epoch is done: the program's own end of
                # set-up (only the first call seals)
                telemetry.seal_setup()
        self._acc, self._conf = self._fresh_acc()
        return float(acc[0]), float(acc[1]), float(acc[2]), conf

    # -- zmq DCN compat mode (server.py / client.py) -------------------

    def _ensure_params(self) -> None:
        if self._params is not None:
            return
        with telemetry.span(events.SPAN_FUSED_ENSURE_PARAMS):
            self._params = self._collect_params()
            self._opt = self._collect_opt()
            if self._core is not None:
                # params + optimizer velocities are this runner's HBM
                # footprint: ledger it in the arbiter's train pool
                from veles_tpu.engine.core import tree_nbytes
                self._core.charge(tree_nbytes(self._params)
                                  + tree_nbytes(self._opt))

    def host_params(self) -> Dict[str, Dict[str, np.ndarray]]:
        """Current parameters as host numpy arrays (slave -> diff)."""
        self._ensure_params()
        return {fn: {pn: np.asarray(v) for pn, v in d.items()}
                for fn, d in self._params.items()}

    def set_host_params(self, params) -> None:
        """Adopt master-provided parameters (device upload; velocities
        stay local, as in the reference's slave)."""
        self._ensure_params()
        self._params = {
            fn: {pn: self.device.put(np.asarray(params[fn][pn]))
                 for pn in d}
            for fn, d in self._params.items()}
        self._scatter_params(self._params, self._opt or {})

    # -- snapshot support ---------------------------------------------

    def sync_params_to_vectors(self) -> None:
        """Pull the current param pytree into host Vectors (snapshot)."""
        if self._params is None:
            return
        self._scatter_params(self._params, self._opt or {})
        for f in self.forwards:
            for v in f.param_vectors().values():
                if v:
                    v.map_read()

    def __getstate__(self) -> dict:
        self.sync_params_to_vectors()
        d = super().__getstate__()
        # the wire-byte count snapshots under its public name (older
        # snapshots carried the plain attribute); dispatch bookkeeping
        # is process-local
        d.pop("_stream_bytes", None)
        for k in ("_dispatch_seen", "_first_run_ts", "_class_open",
                  "_fetch_returned"):
            d.pop(k, None)
        d["stream_transfer_bytes"] = self.stream_transfer_bytes
        # the on-device metric/confusion accumulators are device
        # buffers (hence _unpicklable), but their VALUES are run state:
        # a graceful-stop snapshot taken mid-class (Phoenix preemption
        # lands at any iteration boundary, not only at class ends)
        # would otherwise silently zero the partial class metrics and
        # the resumed epoch's history row undercounts — the
        # chaos-drill hist-parity flake.  Host-materialize them into
        # the snapshot; __setstate__ feeds them back as the carry.
        if self._acc is not None:
            d["_acc_carry"] = np.asarray(self._acc)
            d["_conf_carry"] = np.asarray(self._conf)
        return d

    def __setstate__(self, state: dict) -> None:
        super().__setstate__(state)
        # attrs added after a snapshot was written must default
        self.__dict__.setdefault("processed_images", 0.0)
        self.__dict__.setdefault("processed_eval_images", 0.0)
        self.__dict__.pop("lr_scales", None)  # pre-rename snapshots
        self.__dict__.setdefault("lr_rates", None)
        self.__dict__.setdefault("streaming", False)
        self.__dict__.setdefault("data_sharded", False)
        self.__dict__.setdefault("stream_transfer_seconds", 0.0)
        self.__dict__.setdefault("stream_oom_retries", 0)
        # the snapshotted byte count (0 for pre-field snapshots):
        # `stream_transfer_bytes` is a property now, so the plain dict
        # entry the pickle carried must be consumed here
        restored = self.__dict__.pop("stream_transfer_bytes", 0) or 0
        self._stream_bytes = int(restored)
        self._dispatch_seen = set()
        self._first_run_ts = None
        self._class_open = None
        self._fetch_returned = None
        # mid-class metric carry written by __getstate__ (absent in
        # pre-fix snapshots): plain numpy arrays are exactly what
        # run() hands a fresh dispatch, so resume continues the class
        # accumulation where the stop left it
        acc = self.__dict__.pop("_acc_carry", None)
        conf = self.__dict__.pop("_conf_carry", None)
        if acc is not None:
            self._acc, self._conf = acc, conf
        from collections import deque
        if self.__dict__.get("_inflight") is None:  # dropped by pickle
            self._inflight = deque()
        self.__dict__.setdefault("_core", None)


class EnsembleEvalEngine:
    """Device-resident multi-member fused inference.

    The host predictor (ensemble/core.py) iterates members x layers
    calling ``apply_fwd`` with numpy arrays — N x L Python dispatches
    per batch, bypassing the execution engine entirely.  This engine
    stacks every member's param pytree along a leading MEMBER axis,
    ``jax.vmap``s the same pure forward chain over that axis, and
    averages the member probability outputs ON DEVICE, so an N-member
    ensemble prediction is ONE jitted dispatch.  Matmuls/convs run in
    the device's compute dtype (bf16 on TPU) against the f32 stacked
    params, exactly like the fused eval step; probabilities accumulate
    in f32.

    Two data paths, mirroring the training engine's residency split:

    - **streaming**: :meth:`predict_proba` / :meth:`error_pct` upload
      each host batch through ``device.put`` (so ``Device.h2d_bytes``
      accounting stays live) and dispatch once per batch;
    - **resident**: :meth:`attach_dataset` uploads the split ONCE; the
      ``*_resident`` methods then gather minibatch rows from HBM by
      index — repeated evaluation (GA scoring, sweeps) never re-ships
      pixels.

    Error scoring accumulates ``[n_wrong, count]`` in a donated device
    carry across fixed-shape chunks (one compile, no retraces from a
    ragged tail — the tail is mask-padded), and the host fetches 8
    bytes at the end.
    """

    def __init__(self, forwards: List[Any],
                 member_params: List[Dict[str, Dict[str, Any]]],
                 device: Any, compute_dtype: Any = None,
                 shard_members: bool = False) -> None:
        if not member_params:
            raise ValueError("empty ensemble")
        if device is None or not getattr(device, "is_jax", False):
            raise ValueError(
                "EnsembleEvalEngine needs a jax device (TPU or "
                "XLA:CPU); use the host predictor path on numpy")
        mesh = getattr(device, "mesh", None)
        if shard_members and (mesh is None
                              or int(mesh.devices.size) < 2):
            raise ValueError(
                "shard_members needs a mesh device (MeshJaxDevice) "
                "with >= 2 devices")
        self.forwards = list(forwards)
        self.device = device
        self.n_members = len(member_params)
        self.compute_dtype = compute_dtype
        #: True = the stacked member axis is split P/N over the
        #: replica's mesh (the Prism serving placement): each device
        #: holds a whole tile of members, request rows replicate, and
        #: an over-one-device's-budget ensemble serves RESIDENT at
        #: padded/N bytes per device instead of LRU-spilling
        self.member_sharded = bool(shard_members)
        from veles_tpu.engine import core as engine_core
        #: the Keel core: placement + compile seam (the arbiter charge
        #: for served models stays with ResidencyManager, which owns
        #: the admission decision — pool `serve` is ITS ledger row)
        self._core = engine_core.ExecutionCore(
            device, mesh if self.member_sharded else None,
            pool="serve")
        if self.member_sharded:
            n = int(mesh.devices.size)
            pad = (-(-self.n_members // n) * n) - self.n_members
            # padded members repeat member 0: computed harmlessly
            # under vmap, never read by the fixed-order mean below
            member_params = list(member_params) + \
                [member_params[0]] * pad
        #: stacked member-axis length including mesh padding
        self._n_stacked = len(member_params)
        #: stacked params: {fwd_name: {pname: (n_members, ...)}} in HBM
        self._params = batching.stack_member_params(
            self.forwards, member_params, device,
            put=self._put_members if self.member_sharded else None)
        #: HBM bytes the stacked f32 params occupy — the serving
        #: tier's residency-budget accounting (real members, unpadded)
        self.param_bytes = batching.stacked_param_bytes(
            member_params[:self.n_members])
        #: the residency charge PER DEVICE: a member-sharded stack
        #: costs padded/N on each device, a replicated one costs the
        #: full stack everywhere
        if self.member_sharded:
            self.param_bytes_per_device = batching.stacked_param_bytes(
                member_params) // int(mesh.devices.size)
        else:
            self.param_bytes_per_device = self.param_bytes
        self._dataset = None
        self._labels = None
        #: real (unpadded) attached rows; row-sharded attachment pads
        #: the device store to a whole per-device tile
        self._dataset_rows = 0
        #: True = the attached split is row-sharded over the device's
        #: mesh (attach_dataset(shard=...)): per-device HBM is
        #: total/N and the resident gather runs through the shared
        #: shard_map local-gather + psum seam
        self._dataset_sharded = False
        self._predict = None
        self._score = None
        self._predict_resident = None
        self._score_resident = None
        #: request-level serving facade (attach_batcher); dispatch
        #: shapes seen so far split the compile firing out of the
        #: steady-state latency histogram (the PR-7 convention)
        self._batcher = None
        self._served_shapes: set = set()
        #: one-shot post-promotion hook: called (and cleared) by the
        #: next _serve_dispatch — the online.time_to_serve probe
        self._on_next_dispatch = None
        self._build()

    def _resolved_dtype(self):
        return batching.resolve_compute_dtype(self.compute_dtype,
                                              self.device)

    def _put_members(self, array):
        """Member-sharded stacked-param upload: P_pad/N members per
        device through the ONE sharding seam, charging the padded
        total once (not xN like a replicated put)."""
        return self._core.put_members(array)

    def _build(self) -> None:
        import jax.numpy as jnp

        from veles_tpu.engine import core as engine_core

        cd = self._resolved_dtype()
        # the shared Keel body: vmapped member forward + the
        # fixed-order f32 member average (bitwise across placements —
        # on a mesh the replicated constraint all_gathers the member
        # axis first, so both programs run the identical add chain)
        mean_probs = engine_core.build_mean_probs(
            self.forwards, self.n_members, cd,
            replicated=self._core.replicated
            if self.member_sharded else None)

        def score(params, acc, x, labels, mask):
            p = mean_probs(params, x)
            pred = jnp.argmax(p, axis=-1)
            wrong = jnp.sum((pred != labels).astype(jnp.float32) * mask)
            return acc + jnp.stack([wrong, jnp.sum(mask)])

        self._predict = self._core.jit(mean_probs)
        self._score = self._core.jit(score, donate=(1,))
        self._mean_probs = mean_probs
        self._score_fn = score
        self._build_resident(sharded=False)

    def _build_resident(self, sharded: bool) -> None:
        """The resident-gather dispatchers, for either placement.
        Replicated: a plain on-device take.  Row-sharded: the shared
        shard_map local-gather + psum seam
        (batching.make_sharded_row_gather) — each device holds 1/N of
        the attached rows and the assembled minibatch is f32-exact vs
        the replicated gather, so scoring parity is bitwise."""
        import jax.numpy as jnp

        mean_probs, score = self._mean_probs, self._score_fn
        if sharded:
            gather = batching.make_sharded_row_gather(self.device.mesh)
        else:
            def gather(indices, *stores):
                out = tuple(jnp.take(s, indices, axis=0)
                            for s in stores)
                return out[0] if len(stores) == 1 else out

        def predict_resident(params, dataset, indices):
            return mean_probs(params, gather(indices, dataset))

        def score_resident(params, acc, dataset, label_store, indices,
                           mask):
            x, labels = gather(indices, dataset, label_store)
            return score(params, acc, x, labels, mask)

        self._dataset_sharded = sharded
        self._predict_resident = self._core.jit(predict_resident)
        self._score_resident = self._core.jit(score_resident,
                                              donate=(1,))

    # -- streaming path ------------------------------------------------

    def predict_proba(self, x: np.ndarray) -> np.ndarray:
        """Mean member probabilities for a host batch — one vmapped
        dispatch (a distinct batch shape compiles once)."""
        import time
        t0 = time.perf_counter()
        xb = self.device.put(np.asarray(x, np.float32))
        out = np.asarray(self._predict(self._params, xb))
        self._record_dispatch(time.perf_counter() - t0, len(out))
        return out

    def _record_dispatch(self, dt: float, images: int) -> None:
        """One fetched (host-synchronous) ensemble dispatch: the
        np.asarray/acc fetch IS the barrier, so this wall time covers
        upload + the full vmapped member sweep."""
        if not telemetry.enabled():
            return
        telemetry.histogram(
            events.HIST_ENSEMBLE_DISPATCH_SECONDS).record(dt)
        telemetry.counter(events.CTR_ENSEMBLE_CHUNKS).inc()
        telemetry.counter(events.CTR_ENSEMBLE_SECONDS).inc(dt)
        telemetry.counter(events.CTR_ENSEMBLE_IMAGES).inc(images)
        telemetry.counter(events.CTR_ENSEMBLE_MEMBER_IMAGES).inc(
            images * self.n_members)

    def error_pct(self, x: np.ndarray, labels: np.ndarray,
                  chunk: int = 256) -> float:
        """Classification error % of the averaged ensemble over a host
        split, chunked at a fixed shape with a donated [wrong, count]
        device carry."""
        import time
        x = np.asarray(x, np.float32)
        labels = np.asarray(labels, np.int32)
        chunk = max(1, min(chunk, len(x)))
        acc = self.device.zeros(2, np.float32)
        t0 = time.perf_counter()
        n_chunks = 0
        for i in range(0, len(x), chunk):
            xb, lb, mask = batching.pad_chunk(x[i:i + chunk],
                                              labels[i:i + chunk],
                                              chunk)
            acc = self._score(self._params, acc, self.device.put(xb),
                              self.device.put(lb),
                              self.device.put(mask))
            n_chunks += 1
        acc = np.asarray(acc)
        self._record_score(time.perf_counter() - t0, n_chunks, len(x))
        return 100.0 * float(acc[0]) / max(float(acc[1]), 1.0)

    def _record_score(self, dt: float, chunks: int,
                      images: int) -> None:
        """One whole scoring pass (chunks are dispatched async; the
        donated-carry fetch at the end is the sync, so only the
        pass-level wall is honest)."""
        if not telemetry.enabled():
            return
        telemetry.histogram(
            events.HIST_ENSEMBLE_SCORE_SECONDS).record(dt)
        telemetry.counter(events.CTR_ENSEMBLE_CHUNKS).inc(chunks)
        telemetry.counter(events.CTR_ENSEMBLE_SECONDS).inc(dt)
        telemetry.counter(events.CTR_ENSEMBLE_IMAGES).inc(images)
        telemetry.counter(events.CTR_ENSEMBLE_MEMBER_IMAGES).inc(
            images * self.n_members)

    # -- resident path -------------------------------------------------

    def attach_dataset(self, x: np.ndarray,
                       labels: Optional[np.ndarray] = None,
                       shard: Any = "auto") -> None:
        """Upload an evaluation split ONCE; the ``*_resident`` methods
        gather rows from HBM by index afterwards.

        ``shard``: on a mesh device, ``True`` row-shards the split
        (1/N rows per device — N x the attachable split at the same
        per-device budget), ``False`` replicates it, and ``"auto"``
        follows ``$VELES_MESH_SHARD_DATA`` + the per-device
        ``$VELES_MAX_RESIDENT_BYTES`` budget exactly like the training
        loaders' streaming-vs-resident decision."""
        x = np.asarray(x, np.float32)
        labels = None if labels is None else np.asarray(labels,
                                                       np.int32)
        mesh = getattr(self.device, "mesh", None)
        if mesh is None or int(mesh.devices.size) < 2:
            sharded = False
        elif shard == "auto":
            from veles_tpu import knobs
            from veles_tpu.parallel.mesh import shard_mode
            mode = shard_mode(knobs.get(knobs.MESH_SHARD_DATA))
            sharded = mode == "always" or (
                mode == "auto"
                and x.nbytes > knobs.get(knobs.MAX_RESIDENT_BYTES))
        else:
            sharded = bool(shard)
        if sharded != self._dataset_sharded:
            self._build_resident(sharded=sharded)
        self._dataset_rows = len(x)
        if sharded:
            self._dataset = self.device.put_sharded(x)
            self._labels = None if labels is None else \
                self.device.put_sharded(labels)
        else:
            self._dataset = self.device.put(x)
            self._labels = None if labels is None else \
                self.device.put(labels)

    def predict_proba_resident(self, indices) -> np.ndarray:
        if self._dataset is None:
            raise RuntimeError("attach_dataset() first")
        import time
        t0 = time.perf_counter()
        idx = self.device.put(np.asarray(indices, np.int32))
        out = np.asarray(self._predict_resident(
            self._params, self._dataset, idx))
        self._record_dispatch(time.perf_counter() - t0, len(out))
        return out

    def error_pct_resident(self, n: Optional[int] = None,
                           chunk: int = 256) -> float:
        """Error % over the first ``n`` attached rows (default: all),
        gathered on device — zero pixel re-upload per call."""
        if self._dataset is None or self._labels is None:
            raise RuntimeError("attach_dataset(x, labels) first")
        import time
        # the REAL attached row count — a row-sharded store is padded
        # to a whole per-device tile and the tail must never score
        total = self._dataset_rows if n is None else int(n)
        chunk = max(1, min(chunk, total))
        acc = self.device.zeros(2, np.float32)
        t0 = time.perf_counter()
        n_chunks = 0
        for i in range(0, total, chunk):
            idx, mask = batching.padded_index_chunk(
                i, min(i + chunk, total), chunk)
            acc = self._score_resident(
                self._params, acc, self._dataset, self._labels,
                self.device.put(idx), self.device.put(mask))
            n_chunks += 1
        acc = np.asarray(acc)
        self._record_score(time.perf_counter() - t0, n_chunks, total)
        return 100.0 * float(acc[0]) / max(float(acc[1]), 1.0)

    # -- request-level serving (Hive) ----------------------------------

    def attach_batcher(self, max_batch: int, max_wait_s: float,
                       label: str = "ensemble", sample_shape=None):
        """Arm the request-level API: concurrent :meth:`submit` calls
        coalesce into ONE fixed-shape mask-padded dispatch of up to
        ``max_batch`` rows, flushed after ``max_wait_s`` at the
        latest.  The serving tier (veles_tpu/serve) drives every model
        through this facade; ``submit`` raises until it is armed."""
        from veles_tpu.serve.batcher import MicroBatcher
        if self._batcher is not None:
            return self._batcher
        self._batcher = MicroBatcher(self._serve_dispatch,
                                     max_batch=max_batch,
                                     max_wait_s=max_wait_s,
                                     label=label,
                                     sample_shape=sample_shape)
        return self._batcher

    def submit(self, rows: np.ndarray, deadline_ms=None, ctx=None):
        """Request-level inference: enqueue ``rows`` (one request of
        one or more samples) and return a ``concurrent.futures.Future``
        resolving to the mean member probabilities for exactly those
        rows.  The micro-batching loop coalesces concurrent requests —
        this is the serving tier's whole-dataset-free entry point.
        ``deadline_ms`` (absolute unix-epoch ms) lets the batcher drop
        the request unanswered once nobody is waiting for it; ``ctx``
        (a Flightline :class:`~veles_tpu.trace.TraceContext`) rides
        through so the batcher can attribute queue wait vs device
        dispatch to the request's trace."""
        if self._batcher is None:
            raise RuntimeError("attach_batcher() first — submit() is "
                               "the micro-batched serving API")
        return self._batcher.submit(rows, deadline_ms=deadline_ms,
                                    ctx=ctx)

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every submitted request has resolved (the
        SIGTERM drain path).  Returns False on timeout."""
        if self._batcher is None:
            return True
        return self._batcher.drain(timeout)

    def _serve_dispatch(self, xb: np.ndarray) -> np.ndarray:
        """One fixed-shape serving dispatch (the batcher's flush
        callback).  The FIRST firing of each batch shape traces +
        compiles and lands in its own gauge/journal entry (the PR-7
        compile split), so the steady-state latency histogram stays
        clean — and a nonzero ``serve.compiles`` delta across a warm
        window is a recompile regression."""
        import time
        t0 = time.perf_counter()
        out = np.asarray(self._predict(self._params,
                                       self.device.put(xb)))
        dt = time.perf_counter() - t0
        if telemetry.enabled():
            shape = tuple(xb.shape)
            if shape not in self._served_shapes:
                self._served_shapes.add(shape)
                telemetry.counter(events.CTR_SERVE_COMPILES).inc()
                telemetry.gauge(
                    events.GAUGE_SERVE_FIRST_DISPATCH_SECONDS).set(
                    round(dt, 4))
                telemetry.event(events.EV_SERVE_FIRST_DISPATCH,
                                rows=int(shape[0]),
                                seconds=round(dt, 4))
            else:
                telemetry.histogram(
                    events.HIST_SERVE_DISPATCH_SECONDS).record(dt)
            telemetry.counter(events.CTR_SERVE_MEMBER_ROWS).inc(
                int(xb.shape[0]) * self.n_members)
        cb, self._on_next_dispatch = self._on_next_dispatch, None
        if cb is not None:
            try:
                cb()
            except Exception:  # noqa: BLE001 — a probe must never
                pass           # fail the dispatch it observed
        return out

    def spill_params(self) -> None:
        """Drop the stacked device params (LRU residency spill) while
        keeping the compiled dispatchers — :meth:`restore_params`
        re-uploads without retracing, so a restored model's first
        request pays one H2D transfer, not a recompile."""
        self._params = None

    @property
    def stacked_params(self):
        """The live stacked param pytree (None while spilled) — the
        online promotion gate scores the incumbent through this and
        the shadow trainer seeds its working copy from it."""
        return self._params

    def adopt_stacked_params(self, stacked) -> None:
        """The HBM-to-HBM promotion handoff: replace the served params
        with an already-device-resident pytree of the same structure.
        ONE attribute store — a dispatch that already read the old
        tree finishes on it, every later dispatch reads the new one;
        no request ever sees torn params, and the compiled dispatchers
        (keyed on shapes, which are identical) never retrace.  Callers
        go through ResidencyManager.swap_params, which serializes this
        against spill decisions under the residency lock."""
        self._params = stacked

    def notify_next_dispatch(self, callback) -> None:
        """Arm a one-shot hook fired right after the next serving
        dispatch completes (the last-step-to-first-served-request
        clock of ``online.time_to_serve``)."""
        self._on_next_dispatch = callback

    @property
    def busy(self) -> bool:
        """Rows queued or in flight on the serving facade (plain int
        reads — safe from any thread).  The residency manager's spill
        victim selection skips busy engines: a spill mid-dispatch
        would pull the params out from under the flush thread."""
        b = self._batcher
        return b is not None and b.pending_rows > 0

    def restore_params(self, member_params: List[Dict[str, Dict[
            str, Any]]]) -> None:
        """Re-upload spilled member params (the residency manager
        keeps the host copies — model params are immutable while
        serving).  A member-sharded engine restores to the SAME
        sharded placement, so the compiled dispatchers never retrace."""
        if self.member_sharded:
            pad = self._n_stacked - len(member_params)
            member_params = list(member_params) + \
                [member_params[0]] * pad
            self._params = batching.stack_member_params(
                self.forwards, member_params, self.device,
                put=self._put_members)
        else:
            self._params = batching.stack_member_params(
                self.forwards, member_params, self.device)

    @property
    def resident(self) -> bool:
        return self._params is not None

    def release(self) -> None:
        """Drop every device buffer (stacked params + attached split)
        — same hygiene contract as release_device_state above."""
        if self._batcher is not None:
            self._batcher.close()
            self._batcher = None
        self._params = None
        self._dataset = None
        self._labels = None
        self._predict = self._score = None
        self._predict_resident = self._score_resident = None


#: back-compat alias — the stacking helper moved to the shared
#: fixed-shape machinery module (ops/batching.py)
_stack_member_params = batching.stack_member_params


class PopulationTrainEngine:
    """Population-batched GA training: P same-shape-signature genomes
    trained in ONE vmapped fused scan per loader firing.

    The chip-owning GA evaluator (genetics/worker.py --serve) trains
    genomes strictly one at a time, and GA-scale genome nets (Wine /
    MNIST-FC shapes) leave the MXU almost idle per dispatch.  This
    engine applies the EnsembleEvalEngine move to TRAINING: the init
    param pytree (identical across a cohort — same seed, same shapes)
    is stacked P times along a leading member axis, optimizer state
    and the metric accumulator gain the same axis, per-genome
    hyperparameters (learning rates via the ``lr_rates`` contract,
    weight decay via ``update_params(decays=...)``) become per-member
    vectors, and the fused ``train_body`` chain is ``jax.vmap``ed over
    the member axis inside one jitted donated dispatch.  The dataset
    stays UNBATCHED: gather/ingest run before any batched array flows
    in, so vmap broadcasts them and HBM cost is params x P, not
    data x P.

    Parity contract (pinned in tests/test_ga_cohort.py): the engine
    drives the workflow's OWN loader exactly like the fused control
    loop does (same superstep grouping, same shuffle stream, same
    rng_counter advance on every firing) and mirrors DecisionGD's
    min-error / max_epochs / fail_iterations bookkeeping PER MEMBER on
    the host, so each member's fitness equals what the per-genome
    oracle (a full workflow run of that genome) produces, to f32
    tolerance.  Members that complete early stop updating their
    fitness bookkeeping (their params keep training harmlessly until
    the whole cohort is done — vmap has no per-member early exit).

    The workflow must be built+initialized in fused mode on a jax
    device with a device-resident loader; anything else raises
    ValueError and the caller falls back to the per-genome oracle.

    **Member sharding (Lattice)**: handed a ``mesh`` (or built on a
    workflow whose fused runner carries one), the stacked MEMBER axis
    is sharded over the mesh's data axis — P/N members per device, so
    the HBM cohort cap scales with the device count instead of one
    chip's budget.  Members are embarrassingly parallel (no
    cross-member reduction anywhere in the train body), so the
    partitioner moves nothing between devices inside the dispatch and
    per-member math is bit-identical to the unsharded stacking —
    fitness parity vs the unsharded engine is f32-EXACT.  The cohort
    is padded to a whole per-device tile by repeating member 0
    (padded members train harmlessly; their fitness rows are sliced
    off).  The dataset/targets are placed REPLICATED over the mesh
    (GA-scale datasets are small — sharding capacity is the
    row-sharded residency path's job, not this one's).

    **Zoo long tail (Menagerie)**: the step body is composed from the
    shared Keel builders (``build_forward`` / ``build_backward``), so
    any unit the fused trace supports cohorts for free — including
    CD-k RBM pretraining workflows (binarization + rbm layers): the
    CD chain's Bernoulli keys thread through the same (seed,
    rng_counter) contract the per-genome fused run uses, so a CD
    cohort's member params are BITWISE-equal to per-genome runs
    (pinned in tests/test_ga_cohort.py).  The SOM, which has no
    gradient chain, gets its own engine —
    :class:`veles_tpu.ops.kohonen.SOMPopulationEngine` — with the
    same member-axis contract (``_params``, fitness vector, handoff
    adoption).
    """

    def __init__(self, workflow, member_rates: np.ndarray,
                 member_decays: np.ndarray,
                 compute_dtype: Any = None, mesh: Any = None) -> None:
        fused = getattr(workflow, "fused", None)
        if fused is None or fused.loader is None or \
                fused._train_step is None:
            raise ValueError("PopulationTrainEngine needs a workflow "
                             "initialized in fused mode")
        device = fused.device
        if device is None or not getattr(device, "is_jax", False):
            raise ValueError(
                "PopulationTrainEngine needs a jax device (TPU or "
                "XLA:CPU); per-genome evaluation is the numpy path")
        self.workflow = workflow
        self.fused = fused
        self.loader = fused.loader
        self.forwards = list(fused.forwards)
        self.gds = list(fused.gds)
        self.evaluator = fused.evaluator
        self.decision = workflow.decision
        self.lr_adjust = getattr(workflow, "lr_adjust", None)
        self.device = device
        self.compute_dtype = compute_dtype
        #: True = the loader's dataset is not HBM-resident: the cohort
        #: consumes the loader's host-assembled superstep batches
        #: (per-firing uploads through the Keel seam, broadcast over
        #: the member axis) instead of gathering from a resident
        #: store.  This LIFTS the dataset-must-fit constraint — HBM
        #: holds params x P plus two in-flight batches, never the
        #: dataset — with fitness parity exact vs the resident path
        #: (same rows, same order, same trace bodies).
        self.streaming = bool(fused.streaming or not getattr(
            fused.loader, "device_resident", True))
        rates = np.asarray(member_rates, np.float32)
        decays = np.asarray(member_decays, np.float32)
        n_gd = len(self.gds)
        if rates.shape != decays.shape or rates.ndim != 3 or \
                rates.shape[1:] != (n_gd, 2):
            raise ValueError(
                f"member hyperparameters must be (P, {n_gd}, 2) "
                f"[lr, lr_bias] / [wd, wd_bias] arrays; got "
                f"{rates.shape} / {decays.shape}")
        self.n_members = int(rates.shape[0])
        # -- member sharding (Lattice): resolve the mesh + knob ------
        if mesh is None:
            mesh = getattr(fused, "mesh", None)
        self.mesh = mesh if (mesh is not None
                             and int(mesh.devices.size) > 1) else None
        if self.mesh is not None:
            from veles_tpu import knobs
            from veles_tpu.parallel.mesh import shard_mode
            if shard_mode(knobs.get(knobs.MESH_SHARD_MEMBERS)) \
                    == "never":
                self.mesh = None
        self.member_sharded = self.mesh is not None
        from veles_tpu.engine import core as engine_core
        #: the Keel core: all member/replicated placement, donation,
        #: and the cohort-pool arbiter charge route through it
        self._core = engine_core.ExecutionCore(
            device, self.mesh, pool="cohort")
        if self.member_sharded:
            n_dev = int(self.mesh.devices.size)
            (rates, decays), self._n_stacked = batching.pad_members(
                [rates, decays], n_dev)
        else:
            self._n_stacked = self.n_members
        self._rates = rates
        self._wd = self._put_members(decays)
        # P copies of the single init pytree (Vectors hold the host
        # master copy after initialize) stacked on the member axis
        # (padded members are more copies of the same init)
        host = {f.name: {pn: np.asarray(v.map_read(), np.float32)
                         for pn, v in f.param_vectors().items()}
                for f in self.forwards}
        self._params = batching.stack_member_params(
            self.forwards, [host] * self._n_stacked, device,
            put=self._put_members)
        self._opt = {}
        for gd in self.gds:
            if gd is None or not gd.accumulated_grads:
                continue
            self._opt[gd.name] = {
                k: self._zeros_members((self._n_stacked,)
                                       + tuple(v.shape))
                for k, v in gd.accumulated_grads.items()}
        self._acc = self._fresh_cohort_acc()
        self._rng_counter = 0
        self._la_iteration = 0
        self._train_step = None
        self._eval_step = None
        self._build()
        # stacked params + velocities + decays are the cohort's whole
        # HBM footprint (the dataset never stacks, and in streaming
        # mode never even uploads): ledger it in the arbiter's
        # cohort pool so GA pressure is visible next to serving's
        self._core.charge(
            engine_core.tree_nbytes(self._params)
            + engine_core.tree_nbytes(self._opt)
            + engine_core.tree_nbytes(self._wd))

    # -- member-axis placement (Lattice) ------------------------------

    def _put_members(self, array: np.ndarray):
        """Upload a member-axis-leading array: sharded P/N per device
        on a mesh, a plain device put otherwise."""
        return self._core.put_members(array)

    def _put_replicated(self, array: np.ndarray):
        """Replicate a host array over the engine's mesh (dataset,
        targets, superstep indices/masks — multihost-safe placement),
        or hand it through untouched off-mesh (the single-device jit
        consumes host numpy directly, as before)."""
        return self._core.put_replicated(array)

    def _zeros_members(self, shape):
        return self._core.zeros_members(shape)

    def _fresh_cohort_acc(self):
        if not self.member_sharded:
            return np.zeros((self._n_stacked, 3), np.float32)
        return self._zeros_members((self._n_stacked, 3))

    def _fetch_members(self, acc) -> np.ndarray:
        """One (P, 3) metric fetch, REAL members only.  On a mesh the
        member-sharded accumulator is first re-laid-out replicated (a
        fully-replicated global array is host-fetchable from every
        process — the multihost-safe materialization)."""
        acc = self._core.replicate_for_fetch(acc)
        return np.asarray(acc)[:self.n_members]

    # -- trace construction -------------------------------------------

    def _resolved_dtype(self):
        return batching.resolve_compute_dtype(self.compute_dtype,
                                              self.device)

    def _build(self) -> None:
        from veles_tpu.engine import core as engine_core

        seed = prng.get(self.fused.rng_stream).seed
        cd = self._resolved_dtype()
        core = self._core
        # the same shared Keel bodies and the same scan FusedStepRunner
        # jits, as ONE member's — cohort members share the per-genome
        # oracle's seed, so dropout masks match it (and each other)
        # exactly; the backward walk takes the member's decays row.
        # No confusion matrix: the GA consumes n_err only
        ingest = engine_core.build_ingest(
            getattr(self.loader, "dequant", None))
        forward_pass = engine_core.build_forward(self.forwards, seed,
                                                cd)
        backward_update = engine_core.build_backward(self.forwards,
                                                     self.gds, cd)
        train_step, eval_step = engine_core.build_scan_steps(
            ingest, forward_pass, backward_update, cd,
            self.evaluator.metrics_fn,
            gather=None if self.streaming else engine_core.take_rows,
            members=True)
        # member axis on params/opt/acc/lr/wd; the feed (dataset,
        # targets, indices, mask — or the host-assembled batch) and
        # the rng counter broadcast — x stays UNBATCHED through
        # gather+ingest (vmap only batches where member-axis arrays
        # flow in, i.e. from the first matmul on), so the cohort's HBM
        # cost is params x P, not data x P: one copy of the data, or
        # of each streamed batch, serves every member
        feed = (None,) * (3 if self.streaming else 4)
        self._train_step = core.jit(
            core.vmap_members(train_step,
                              in_axes=(0,) * 5 + feed + (None,)),
            donate=(0, 1, 2))
        self._eval_step = core.jit(
            core.vmap_members(eval_step,
                              in_axes=(0,) * 2 + feed + (None,)),
            donate=(1,))

    # -- per-member learning-rate schedule ----------------------------

    def _member_lr(self, k: int) -> np.ndarray:
        """(P, k, n_gd, 2) absolute rates for this train firing —
        the member bases run through the workflow's LR policy with the
        SAME (epoch/iteration) argument logic LearningRateAdjust.run
        uses, so scheduled cohorts track the oracle exactly."""
        P, n_gd = self._rates.shape[:2]
        la = self.lr_adjust
        if la is None:
            return np.ascontiguousarray(np.broadcast_to(
                self._rates[:, None], (P, k, n_gd, 2)))
        ld = self.loader
        e = ld.epoch_number
        ended = bool(ld.epoch_ended)

        def t_of(j: int) -> int:
            if la.by == "epoch":
                return e - 1 if (ended and j < k - 1) else e
            return self._la_iteration + j

        out = np.empty((P, k, n_gd, 2), np.float32)
        for j in range(k):
            t = t_of(j)
            for m in range(P):
                for gi in range(n_gd):
                    out[m, j, gi, 0] = la.policy(
                        float(self._rates[m, gi, 0]), t)
                    out[m, j, gi, 1] = la.policy(
                        float(self._rates[m, gi, 1]), t)
        self._la_iteration += k
        return out

    # -- the run loop --------------------------------------------------

    def run(self) -> np.ndarray:
        """Train the whole cohort; returns the (P,) fitness vector —
        each member's min validation n_err (train n_err for valid-less
        configs), the exact quantity ``workflow_fitness`` reads off a
        per-genome run's DecisionGD."""
        from veles_tpu import faults
        from veles_tpu.loader.base import TRAIN, VALID

        with telemetry.span(events.SPAN_GA_COHORT_TRAIN, journal=True,
                            members=self.n_members):
            telemetry.counter(events.CTR_GA_COHORTS).inc()
            telemetry.counter(
                events.CTR_GA_COHORT_MEMBERS).inc(self.n_members)
            return self._run_inner(faults, TRAIN, VALID)

    def _run_inner(self, faults, TRAIN, VALID) -> np.ndarray:
        if faults.fire("device.oom_on_put", site="cohort",
                       members=self.n_members):
            # surfaces exactly like a real cohort OOM: the serve-mode
            # evaluator's chunk trainer catches it, halves the cohort
            # and retries (genetics/worker.py _evaluate_cohort)
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: fault-injected OOM on the "
                "cohort dispatch")
        ld = self.loader
        dec = self.decision
        P = self.n_members
        max_epochs = dec.max_epochs
        fail_iters = dec.fail_iterations
        has_valid = ld.class_lengths[VALID] > 0
        min_valid = np.full(P, np.inf)
        min_valid_epoch = np.full(P, -1, np.int64)
        min_train = np.full(P, np.inf)
        complete = np.zeros(P, bool)
        streaming = self.streaming
        if streaming:
            # streaming cohort: ZERO dataset residency — the only data
            # on device is each firing's host-assembled superstep
            # batch, broadcast across the member axis by the vmap (one
            # copy serves every member)
            dataset = targets = None
        elif self.member_sharded:
            # the engine owns its data placement on the mesh: the
            # replicated copy lives next to the member-sharded stacks
            # regardless of which single device built the workflow
            dataset = self._put_replicated(ld.original_data.map_read())
            if self.fused._targets_are_rows():
                targets = dataset
            else:
                tvec = ld.original_targets \
                    if self.fused._has_targets() \
                    else ld.original_labels
                targets = self._put_replicated(tvec.map_read())
        else:
            dataset = ld.original_data.unmap()
            targets = self.fused._target_store()
        params, opt, acc = self._params, self._opt, self._acc
        while not complete.all():
            ld.run()
            idxs, mask = ld.superstep_indices, ld.superstep_mask
            k = idxs.shape[0]
            klass = ld.minibatch_class
            if klass == TRAIN or klass == VALID:
                mask_dev = self._put_replicated(mask)
                if streaming:
                    xb = ld.superstep_data
                    if self.fused._targets_are_rows():
                        tb = xb
                    else:
                        tb = ld.superstep_targets \
                            if self.fused._has_targets() \
                            else ld.superstep_labels
                    if xb is None or tb is None:
                        raise RuntimeError(
                            "cohort streaming mode but the loader "
                            "produced no superstep batch "
                            "(superstep_data/targets)")
                    xb_dev = self._put_replicated(xb)
                    tb_dev = self._put_replicated(tb)
                else:
                    idx_dev = self._put_replicated(idxs)
            if klass == TRAIN:
                lr = self._put_members(self._member_lr(k))
                if streaming:
                    params, opt, acc = self._train_step(
                        params, opt, acc, lr, self._wd, xb_dev,
                        tb_dev, mask_dev, self._rng_counter)
                else:
                    params, opt, acc = self._train_step(
                        params, opt, acc, lr, self._wd, dataset,
                        targets, idx_dev, mask_dev, self._rng_counter)
            elif klass == VALID:
                if streaming:
                    acc = self._eval_step(params, acc, xb_dev, tb_dev,
                                          mask_dev, self._rng_counter)
                else:
                    acc = self._eval_step(params, acc, dataset,
                                          targets, idx_dev, mask_dev,
                                          self._rng_counter)
            # TEST firings never feed fitness: skip the dispatch but
            # keep the rng_counter advance so dropout streams stay
            # aligned with the oracle's firing count
            self._rng_counter += k
            if not bool(ld.class_ended):
                continue
            a = self._fetch_members(acc)  # one (P, 3) fetch per class
            acc = self._fresh_cohort_acc()
            err = a[:, 0].astype(np.float64)
            live = ~complete
            if klass == VALID:
                # DecisionGD.on_validation_ended, per member: strict
                # improvement, epoch BEFORE the train class increments
                better = live & (err < min_valid)
                min_valid = np.where(better, err, min_valid)
                min_valid_epoch = np.where(better, ld.epoch_number,
                                           min_valid_epoch)
            if klass == TRAIN:
                if not has_valid:
                    better = live & (err < min_train)
                    min_train = np.where(better, err, min_train)
                # DecisionGD.on_train_ended: epoch_number has already
                # incremented past the ended epoch by now
                epoch = ld.epoch_number
                if max_epochs is not None and epoch >= max_epochs:
                    complete[:] = True
                if has_valid:
                    complete |= (min_valid_epoch >= 0) & \
                        (epoch - min_valid_epoch > fail_iters)
        self._params, self._opt, self._acc = params, opt, acc
        return min_valid if has_valid else min_train

    def release(self) -> None:
        """Drop the stacked device state (params + velocities + wd) —
        same hygiene contract as release_device_state: a serve-mode
        evaluator lives across many cohorts and HBM must not
        accumulate."""
        self._params = None
        self._opt = None
        self._acc = None
        self._wd = None
        self._train_step = self._eval_step = None
        self._core.release()


#: back-compat alias — the chunk/pad helper moved to ops/batching.py
_pad_chunk = batching.pad_chunk
