"""Keel — the ONE execution core under every engine loop.

The repo used to run four engine loops — ``FusedStepRunner``,
``EnsembleEvalEngine``, ``PopulationTrainEngine`` (ops/fused.py) and
the online scavenger's ``ShadowTrainer`` (online/trainer.py) — each
owning its own residency, donation, and dispatch code: four copies of
the forward/backward trace body, four spellings of ``jax.device_put``,
four places a donation bug could hide.  This module collapses the
overlap into one core where the orthogonal execution flags live
TOGETHER instead of being re-derived per loop:

- **member axis**: absent (one model) or a leading stacked axis vmapped
  over P members (:meth:`ExecutionCore.vmap_members`);
- **data residency**: host-streaming (per-batch :func:`put` uploads),
  HBM-resident (in-trace gather), or row-sharded resident (the
  shard_map gather seam in ops/batching.py) — the adapters pick the
  gather, the core owns every upload;
- **mesh placement**: replicated / batch-sharded / member-sharded
  shardings from parallel/mesh.py, resolved once per core;
- **wire format**: the quantized-ingest prologue
  (:func:`build_ingest`) is part of the shared trace, so uint8 wire
  bytes dequantize identically in every loop;
- **donation**: :func:`donating_jit` is THE place a ``donate_argnums``
  is ever spelled (the ``engine-residency-seam`` lint rule forbids it
  anywhere outside this file, serve/residency.py, parallel/mesh.py).

The shared trace builders (:func:`build_forward`,
:func:`build_backward`, :func:`build_member_forward`,
:func:`build_mean_probs`) are the EXACT bodies the four loops traced
before the refactor — adapters compose them into the same jaxprs, so
f32-bitwise parity with the pre-refactor engines holds by construction
(pinned by tests/test_engine_core.py).  The superstep itself — the
``lax.scan`` of a train or eval minibatch body over a firing — is
:func:`build_scan_steps`, spelled once for the fused runner and the
population engine alike.

The core also charges its HBM footprint to the process-wide arbiter
(serve/residency.py ``process_arbiter()``): training, GA cohorts, and
serving draw on ONE ledger with per-pool gauges instead of
per-subsystem budget fictions.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

from veles_tpu import events, telemetry

# -- tracing, lowering and compiles, seen from inside the program -------

#: jax.monitoring's names: the duration of one ``compile_or_get_cached``
#: (a backend compile OR a persistent-cache load), the retrieval time
#: jax reports just before it when the cache held the program, and what
#: the compile cache does NOT save: tracing a jitted function to a jaxpr
#: and lowering the jaxpr to its module (Pallas kernels among it)
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

#: jax's event -> (journal event, counter of all its seconds, counter
#: of the part inside an open ``fused.*`` span)
_DURATIONS = {
    _TRACE_EVENT: (events.EV_XLA_TRACE, events.CTR_XLA_TRACE_SECONDS,
                   events.CTR_FUSED_TRACE_SECONDS),
    _LOWER_EVENT: (events.EV_XLA_LOWER, events.CTR_XLA_LOWER_SECONDS,
                   events.CTR_FUSED_LOWER_SECONDS),
    _COMPILE_EVENT: (events.EV_XLA_COMPILE,
                     events.CTR_XLA_COMPILE_SECONDS,
                     events.CTR_FUSED_COMPILE_SECONDS),
}
#: a trace or a lowering shorter than this is counted and not journaled
#: (every ``jnp`` function a trace calls reports a trace of its own)
_JOURNAL_FROM_SECONDS = 0.01
#: events a thread remembers (below); inner ones go when their outer
#: one comes, so only an unbroken run of this many side by side
#: inside one trace would lose its oldest
_KEPT_EVENTS = 32768
#: an earlier event that ended less than this after an event's start
#: ended before it (two clock reads lie between an end and its report)
_CLOCK_SLACK = 1e-4

_watching_compiles = False
#: per thread: ``.hit`` — the cache held the program now compiling;
#: ``.counted`` — ``(end, seconds counted up to and with this event)``
#: of its events in order of arrival, which is the order of their ends;
#: ``.floor`` — the seconds counted before the oldest one kept
_compiling = threading.local()


def _own_seconds(duration: float) -> float:
    """The part of ``[now - duration, now]`` that no earlier event of
    this thread covered.  jax reports an event at its END, so a jitted
    function called inside a trace (or an eager op compiled under
    ``ensure_compile_time_eval``) reports before the trace it ran in:
    the outer event then counts only what its inner ones left — every
    event that ended after it started lies inside it — and the seconds
    of all kinds add up to the union of their intervals, never to more
    than the wall time they took."""
    now = time.perf_counter()
    state = _compiling.__dict__
    counted = state.setdefault("counted", [])
    total = counted[-1][1] if counted else state.get("floor", 0.0)
    while counted and counted[-1][0] > now - duration + _CLOCK_SLACK:
        counted.pop()
    before = counted[-1][1] if counted else state.get("floor", 0.0)
    own = max(duration - (total - before), 0.0)
    counted.append((now, total + own))
    if len(counted) > _KEPT_EVENTS:
        state["floor"] = counted[_KEPT_EVENTS // 2 - 1][1]
        del counted[:_KEPT_EVENTS // 2]
    return own


def _on_jax_duration(event: str, duration: float, **kw: Any) -> None:
    if event == _CACHE_HIT_EVENT:
        _compiling.hit = True
        return
    names = _DURATIONS.get(event)
    if names is None:
        return
    journal, all_seconds, fused_seconds = names
    own = _own_seconds(duration)
    # the listener runs on the tracing / compiling thread, so its open
    # spans say which step it was and inside what
    during = telemetry.span_stack()
    in_fused = any(name.startswith("fused.") for name in during)
    telemetry.counter(all_seconds).inc(own)
    if in_fused:
        telemetry.counter(fused_seconds).inc(own)
    fields = {}
    if event == _COMPILE_EVENT:
        cached = fields["cached"] = getattr(_compiling, "hit", False)
        _compiling.hit = False
        telemetry.counter(events.CTR_XLA_COMPILES).inc()
        if in_fused and not cached:
            telemetry.counter(events.CTR_FUSED_COLD_COMPILES).inc()
    elif duration < _JOURNAL_FROM_SECONDS:
        return
    telemetry.event(journal, seconds=round(duration, 6),
                    fun=kw.get("fun_name"), during=during, **fields)


def watch_compiles() -> None:
    """Register the process's ONE jax.monitoring listener — tracing,
    lowering, compiles and cache loads all come through it (jax keeps
    a listener for the life of the process, so: once).  Called where
    this module first touches jax."""
    global _watching_compiles
    if _watching_compiles:
        return
    _watching_compiles = True
    import jax

    jax.monitoring.register_event_duration_secs_listener(
        _on_jax_duration)


# -- the two seam primitives -------------------------------------------
# Every host->device placement and every buffer donation in the repo
# goes through these two calls (or through parallel/mesh.py /
# serve/residency.py, the other two allowlisted seam modules).


@contextlib.contextmanager
def not_persisted():
    """Compile what this thread jits inside WITHOUT writing it to the
    persistent compile cache.  For the programs whose OUTPUT lies in a
    device layout of its own (a ``Format``): loaded back from the
    cache, jax 0.9.0 / libtpu 0.0.34 hand out buffers that lie in
    that layout but REPORT the default one, so the next program is
    compiled for the layout reported and refuses the buffer
    (``INVALID_ARGUMENT: expected parameter ... of size ...``; PR 27,
    on the chip).  Compiled fresh, the same program reports the truth;
    a program whose INPUT has such a layout loads back sound.  jax has
    no switch per program, so the cache's write threshold is raised
    for this thread (the context-manager form of a jax option is
    thread-local, and only ``jax._src`` has this one's)."""
    from jax._src import config as jax_config

    with jax_config.persistent_cache_min_compile_time_secs(float("inf")):
        yield


def put(array: Any, where: Any = None):
    """THE ``jax.device_put`` seam: place ``array`` on ``where`` (a
    jax device, a Sharding, or a ``Format`` — a sharding with a device
    layout; the backend's default device when None).  Call sites
    outside the seam modules are lint findings — residency decisions
    must not scatter back across the repo."""
    import jax
    from jax.experimental.layout import Format

    watch_compiles()
    if where is None:
        return jax.device_put(array)
    if isinstance(where, Format):
        # jax re-lays the upload out with a jitted identity
        with not_persisted():
            return jax.device_put(array, where)
    return jax.device_put(array, where)


def donating_jit(fn, donate: Tuple[int, ...] = (),
                 in_shardings: Any = None, out_shardings: Any = None,
                 static_argnums: Any = None,
                 compiler_options: Optional[Dict[str, Any]] = None):
    """THE donation seam: ``jax.jit`` with ``donate_argnums`` spelled
    exactly once in the repo.  ``donate=()`` compiles without donation
    (the eval/predict dispatchers); sharding kwargs pass through only
    when given, so the non-mesh call is byte-identical to a bare
    ``jax.jit(fn, donate_argnums=...)``.  ``compiler_options`` go with
    ONE program (the data-parallel train step: :class:`GradExchange`);
    an option the backend's compiler does not know raises at that
    program's first compile."""
    import jax

    watch_compiles()
    kw: Dict[str, Any] = {}
    if donate:
        kw["donate_argnums"] = tuple(donate)
    if in_shardings is not None:
        kw["in_shardings"] = in_shardings
    if out_shardings is not None:
        kw["out_shardings"] = out_shardings
    if static_argnums is not None:
        kw["static_argnums"] = static_argnums
    if compiler_options:
        kw["compiler_options"] = dict(compiler_options)
    return jax.jit(fn, **kw)


# -- shared trace builders ---------------------------------------------
# The bodies below are the (formerly four-times-copied) fused trace
# pieces.  They are pure closures over static unit lists: composing
# them yields the same jaxpr the pre-Keel loops traced, which is what
# keeps the refactor f32-bitwise.


def build_ingest(dequant: Any):
    """The wire-format prologue: identity for f32/bf16 batches, the
    affine uint8 dequantize (f32 arithmetic, host normalization order)
    for quantized loaders."""
    import jax
    import jax.numpy as jnp

    if dequant is None:
        return lambda x: x
    q_scale = jnp.asarray(dequant.scale, jnp.float32)
    q_bias = jnp.asarray(dequant.bias, jnp.float32)

    def ingest(x):
        with jax.named_scope("ingest"):
            return x.astype(jnp.float32) * q_scale + q_bias

    return ingest


def build_forward(forwards, seed: int, compute_dtype,
                  recompute: bool = False, head_apart: bool = False):
    """One model's forward chain WITH residuals — the train-mode body
    every loop traces.  The rng key chain (``fold_in(fold_in(key(seed),
    rc), i)`` per stochastic layer) is the repo-wide dropout contract:
    cohort members, the online shadow, and the oracle replay all hash
    the same stream.

    The chain's shape is the units' own (:func:`chain_of`): a layer,
    or a **residual** entry ``x + f_k(...f_1(x))`` (the add in f32).
    With no residual entry it is the line, and traces the program it
    always did.  With ``recompute`` a residual
    entry keeps only its INPUT (and the rng counter) as the residual
    of its first layer: :func:`build_backward` re-runs the entry's
    forward inside its backward instead of keeping every layer's
    residuals for the whole chain.  With ``head_apart`` the walk ends
    BEFORE the last layer and returns that layer's input: the head and
    the loss are made together, a block of positions at a time
    (:func:`build_blocked_head`)."""
    import jax

    mixed = _not_f32(compute_dtype)
    chain = chain_of(forwards)
    if head_apart:
        assert chain[-1] == len(forwards) - 1, chain[-1]
        chain = chain[:-1]

    def forward_pass(params, x, rng_counter, train: bool):
        residuals = [None] * len(forwards)
        if mixed and not _is_ids(x):
            with jax.named_scope(events.SCOPE_INGEST):
                x = x.astype(compute_dtype)
        for entry in chain:
            skip = x
            for i in _layers_of(entry):
                f = forwards[i]
                # every device op carries its layer in its metadata
                # (``fwd/<layer>``; the backward walk: ``bwd/``,
                # ``update/``) — metadata only, the program is the same
                with jax.named_scope("fwd/" + f.name):
                    rng = jax.random.fold_in(
                        jax.random.fold_in(jax.random.key(seed),
                                           rng_counter), i) \
                        if f.stochastic else None
                    x, residuals[i] = f.apply_fwd(
                        params[f.name], x, rng=rng, train=train)
            if isinstance(entry, int):
                continue
            x = _skip_add(skip, x)
            if recompute and train:
                for i in entry:
                    residuals[i] = None
                residuals[entry[0]] = {"recompute_from": skip,
                                       "rng_counter": rng_counter}
        return x, residuals

    return forward_pass


def chain_of(forwards):
    """The chain's entries over the indices of ``forwards``: an int for
    a layer of the line, a tuple of ints for a residual entry — the
    run of neighbours that carry the same ``residual_of`` mark
    (``StandardWorkflow`` sets it from the ``layers`` list).  Read from
    the units, so that no engine that walks a list of forwards can
    drop a skip path."""
    chain: list = []
    for i, f in enumerate(forwards):
        mark = getattr(f, "residual_of", None)
        if mark is None:
            chain.append(i)
        elif i and isinstance(chain[-1], tuple) and \
                getattr(forwards[i - 1], "residual_of", None) == mark:
            chain[-1] += (i,)
        else:
            chain.append((i,))
    return chain


def has_residual(forwards) -> bool:
    return any(getattr(f, "residual_of", None) is not None
               for f in forwards)


def _is_ids(x) -> bool:
    """ids stay ids: an integer row is no activation to be cast (bf16
    cannot hold 257)."""
    return np.dtype(x.dtype).kind in "iub"


def _layers_of(entry):
    return (entry,) if isinstance(entry, int) else entry


def _skip_add(skip, x):
    """A residual entry's add (and its backward's: the error splits
    and adds), in f32 whatever the compute dtype."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(events.SCOPE_SKIP):
        return (skip.astype(jnp.float32)
                + x.astype(jnp.float32)).astype(skip.dtype)


def kept_activation_bytes(forwards, compute_dtype, params, x):
    """(kept, kept_recomputing): bytes of the residuals the forward
    chain keeps for its backward when every layer keeps its own, and
    when every residual entry keeps only its input (plus, at any one
    time, the residuals of the ONE entry being re-run).  From shapes
    alone (``jax.eval_shape``; ``params`` / ``x`` may be
    ``ShapeDtypeStruct``); a residual leaf shaped like one of the
    layer's own parameters is the parameter, not an activation.  What
    jax would keep, before XLA fuses or re-makes any of it: an upper
    bound, good for deciding, not a reading of the allocator."""
    import jax

    def residuals(recompute):
        fwd = build_forward(forwards, 0, compute_dtype, recompute)
        return jax.eval_shape(lambda p, xx: fwd(p, xx, 0, True)[1],
                              params, x)

    def nbytes(tree, skip=()):
        return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize
                   for a in jax.tree_util.tree_leaves(tree)
                   if (a.shape, a.dtype) not in skip)

    per_layer = []
    for f, r in zip(forwards, residuals(False)):
        own = {(a.shape, a.dtype)
               for a in jax.tree_util.tree_leaves(params[f.name])}
        per_layer.append(nbytes(r, own))
    inputs = residuals(True)
    recomputing = rerun = 0
    for entry in chain_of(forwards):
        if isinstance(entry, int):
            recomputing += per_layer[entry]
        else:
            recomputing += nbytes(inputs[entry[0]])
            rerun = max(rerun, sum(per_layer[i] for i in entry))
    return sum(per_layer), recomputing + rerun


def _not_f32(compute_dtype) -> bool:
    import jax.numpy as jnp

    return compute_dtype != jnp.float32


#: what the TPU's compiler is told with a data-parallel train step
#: (libtpu 0.0.34 knows all three; an unknown name fails the step's
#: first compile).  Left alone it joins every all-reduce the
#: partitioner emitted into ONE behind the last gradient; with these
#: each gradient's all-reduce stays its own op (the combiner's count
#: threshold), is asynchronous, and is woven into the compute fusion
#: scheduled beside it ("async collective fusion").
TPU_GRAD_EXCHANGE_OPTIONS = {
    "xla_jf_crs_combiner_threshold_count": "1",
    "xla_enable_async_all_reduce": "true",
    "xla_tpu_enable_async_collective_fusion_fuse_all_reduce": "true",
}


class GradExchange:
    """How a data-parallel train step makes every device hold the
    GLOBAL minibatch's gradients — planned from shapes alone, for a
    mesh whose rows ride the batch sharding; there is none without a
    mesh.

    Every device walks back its own rows.  A layer's weight gradient
    is a sum over the rows of the minibatch, so the devices must
    exchange something, and there are two things they can exchange:

    ``reduced``
        each device's partial gradient, summed across devices (the
        partitioner's all-reduce, pinned to the layer that made it):
        ``2 (n-1)/n`` x the gradient's bytes on the wire;
    ``gathered``
        the layer's saved activations and its error, gathered to every
        device, which then makes the whole gradient itself
        (:meth:`gathered_backward`): ``(n-1)/n`` x the activations'
        bytes, no reduction at all, and the sum over the rows happens
        in the matmul's f32 accumulator as on one device.

    A layer is ``gathered`` when that moves fewer bytes than its
    gradient has — a dense layer at a minibatch of a few hundred rows
    (AlexNet's fc6: 17.8 MB of activations against a 75.5 MB
    gradient); a convolution's activations dwarf its kernel, so it
    stays ``reduced``.  The weight gradient of a gathered layer costs
    ``n`` x the matmul work, on a matmul that waits for HBM at such a
    minibatch.  Either way the step is synchronous SGD on the global
    minibatch: every gradient is whole before its update, in the step
    that made it, and every device computes the same update."""

    def __init__(self, mesh, forwards, gds, dtype) -> None:
        from veles_tpu.parallel import mesh as mesh_helpers

        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.devices = n = int(mesh.devices.size)
        self.replicated = mesh_helpers.replicated_sharding(mesh)
        self.dtype = np.dtype(dtype)
        item = self.dtype.itemsize
        #: one entry a layer with parameters, in the order the
        #: backward walk makes its gradients
        self.groups: list = []
        self.leaves = 0
        self._gathered = set()
        for i in reversed(range(len(forwards))):
            f = forwards[i]
            vecs = [v for v in f.param_vectors().values() if v] \
                if gds[i] is not None else []
            if not vecs:
                continue
            self.leaves += len(vecs)
            grad = sum(int(v.size) for v in vecs) * item
            # what a gathered backward needs of every row: the
            # layer's input, its output and the error at its output
            acts = (int(np.prod(f.input.shape))
                    + 2 * int(np.prod(f.output.shape))) * item
            gathered = acts < grad and \
                getattr(f, "residual_of", None) is None
            if gathered:
                self._gathered.add(i)
            self.groups.append({
                "layer": f.name, "bytes": grad,
                "how": "gathered" if gathered else "reduced",
                "wire_bytes": (acts if gathered else 2 * grad)
                * (n - 1) // n})
        self.bytes = sum(g["bytes"] for g in self.groups)
        platform = mesh.devices.flat[0].platform
        self.options: Dict[str, str] = dict(
            TPU_GRAD_EXCHANGE_OPTIONS) if platform == "tpu" else {}

    def describe(self) -> Dict[str, Any]:
        """The ``dp.grad_exchange`` journal event's fields."""
        return {"devices": self.devices, "leaves": self.leaves,
                "bytes": self.bytes, "dtype": self.dtype.name,
                "groups": self.groups, "options": self.options}

    def gathers(self, i: int) -> bool:
        return i in self._gathered

    def reduced(self, grads):
        """Pin a layer's gradients replicated where the walk made
        them, so the partitioner's all-reduce sits at that layer."""
        import jax
        from jax import lax

        return jax.tree_util.tree_map(
            lambda g: lax.with_sharding_constraint(g, self.replicated),
            grads)

    def gathered_backward(self, gd, cparams, saved, err,
                          need_err_input: bool):
        """``gd.backward_from_saved`` with the parameter gradients made
        from the rows of EVERY device: the saved leaves that lead with
        the minibatch axis and the error are all-gathered, the error
        at the layer's input stays this device's rows (its own call on
        the local rows where the unit can skip it, a slice
        otherwise)."""
        import jax
        from jax import lax, shard_map
        from jax.sharding import PartitionSpec

        axis, mb = self.axis, err.shape[0]
        rows, whole = PartitionSpec(axis), PartitionSpec()
        batched = jax.tree_util.tree_map(
            lambda a: bool(getattr(a, "ndim", 0)) and a.shape[0] == mb,
            saved)

        def everyone(a):
            return lax.all_gather(a, axis, axis=0, tiled=True)

        def local(cp, saved, err):
            all_saved = jax.tree_util.tree_map(
                lambda a, b: everyone(a) if b else a, saved, batched)
            if gd.can_skip_err_input:
                _, grads = gd.backward_from_saved(
                    cp, all_saved, everyone(err), need_err_input=False)
                if not need_err_input:
                    return None, grads
                # this device's rows only; its gradients are dead code
                return gd.backward_from_saved(cp, saved, err)[0], grads
            err_in, grads = gd.backward_from_saved(
                cp, all_saved, everyone(err))
            return lax.dynamic_slice_in_dim(
                err_in, lax.axis_index(axis) * err.shape[0],
                err.shape[0]), grads

        return shard_map(
            local, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: whole, cparams),
                      jax.tree_util.tree_map(
                          lambda b: rows if b else whole, batched),
                      rows),
            out_specs=(rows if need_err_input else None, whole),
            check_vma=False)(cparams, saved, err)


def build_backward(forwards, gds, compute_dtype, seed: int = 0,
                   exchange: Optional[GradExchange] = None):
    """The backward + SGD chain: walk the gradient units in reverse,
    skip the chain-head err_input when nothing consumes it, and apply
    ``update_params`` with the per-call (lr, bias-lr) row — plus the
    per-member (wd, bias-wd) row when the caller supplies one (the
    population engine's decays contract; ``decays=None`` omits the
    kwarg entirely, matching the single-model loops exactly).

    The chain is :func:`build_forward`'s.  Behind a residual
    entry the error splits — one copy walks back through the entry's
    layers, the other round them — and the two add at its input.  An
    entry whose forward kept only its input (``recompute``) first
    re-runs its layers' forward here, under ``bwd/<layer>/recompute``,
    behind a barrier that ties the re-run to the error's arrival (so
    the compiler can neither share it with the first forward nor run
    it early); ``seed`` is the forward's, for the same rng keys.

    ``exchange`` (a mesh's :class:`GradExchange`, None without one)
    says how each layer's gradients become the global minibatch's."""
    import jax
    from jax import lax

    chain = chain_of(forwards)
    first_gd = next((i for i, g in enumerate(gds) if g is not None),
                    -1)
    mixed = _not_f32(compute_dtype)

    def backward_update(cparams, params, opt, residuals, err, lr,
                        wd=None):
        if mixed:
            err = err.astype(compute_dtype)
        new_params = dict(params)
        new_opt = dict(opt)
        for entry in reversed(chain):
            skip_err = err
            saved = residuals[_layers_of(entry)[0]]
            if isinstance(saved, dict) and "recompute_from" in saved:
                x, err = lax.optimization_barrier(
                    (saved["recompute_from"], err))
                residuals = list(residuals)
                for i in entry:
                    f = forwards[i]
                    with jax.named_scope("bwd/" + f.name), \
                            jax.named_scope(events.SCOPE_RECOMPUTE):
                        rng = jax.random.fold_in(
                            jax.random.fold_in(jax.random.key(seed),
                                               saved["rng_counter"]),
                            i) if f.stochastic else None
                        x, residuals[i] = f.apply_fwd(
                            cparams[f.name], x, rng=rng, train=True)
            for i in reversed(_layers_of(entry)):
                f, gd = forwards[i], gds[i]
                if gd is None:
                    continue
                with jax.named_scope("bwd/" + f.name):
                    skip = i == first_gd and gd.can_skip_err_input
                    gathered = exchange is not None \
                        and exchange.gathers(i)
                    if isinstance(residuals[i], HeadDone):
                        # the blocked head walked itself back: ``err``
                        # already is the error at its input
                        err_in, grads = err, residuals[i].grads
                    elif gathered:
                        err_in, grads = exchange.gathered_backward(
                            gd, cparams[f.name], residuals[i], err,
                            not skip)
                    elif skip:
                        # nothing consumes the chain-head err_input;
                        # for conv1 this skips the input-dilated
                        # transposed conv (the worst MXU op here)
                        _, grads = gd.backward_from_saved(
                            cparams[f.name], residuals[i], err,
                            need_err_input=False)
                        err_in = None
                    else:
                        err_in, grads = gd.backward_from_saved(
                            cparams[f.name], residuals[i], err)
                    if grads and exchange is not None and not gathered:
                        grads = exchange.reduced(grads)
                if grads:
                    with jax.named_scope("update/" + f.name):
                        if wd is None:
                            p, v = gd.update_params(
                                params[f.name], grads,
                                opt.get(gd.name, {}),
                                rates=(lr[i, 0], lr[i, 1]))
                        else:
                            p, v = gd.update_params(
                                params[f.name], grads,
                                opt.get(gd.name, {}),
                                rates=(lr[i, 0], lr[i, 1]),
                                decays=(wd[i, 0], wd[i, 1]))
                    new_params[f.name] = p
                    if gd.name in opt:
                        new_opt[gd.name] = v
                err = err_in
            if not isinstance(entry, int) and err is not None:
                err = _skip_add(skip_err, err)
        return new_params, new_opt

    return backward_update


class HeadDone(NamedTuple):
    """What stands in the residuals for a head that
    :func:`build_blocked_head` has already walked back: its gradients
    (f32, summed over the blocks)."""
    grads: Any


class BlockedHead(NamedTuple):
    """:func:`build_blocked_head`'s result: the head's ``name`` (the
    key of its parameters), ``train`` and ``evaluate``."""
    name: str
    train: Any
    evaluate: Any


def build_blocked_head(head, evaluator, blocks: int):
    """The last layer and the loss together, a block of positions at a
    time (a :class:`BlockedHead`) — for a head whose whole logits, and
    the loss's arrays at their shape, would not fit beside the state.

    ``head`` acts on each position alone (``[rows, T, width]`` ->
    ``[rows, T, ...]``); ``evaluator.block_metrics(output, target,
    mask, start, n)`` is the loss of the positions ``start ..`` of a
    row under the mean over ``n = evaluator.valid_count(...)``.
    ``train(head_params, x, target, mask)`` returns ``(metrics, the
    head's gradients in f32, the error at x)``; ``evaluate`` the
    metrics alone.  One ``lax.scan`` over the blocks: a block's logits
    are made, scored, walked back and dropped before the next."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def walk(step, carry, x, mask):
        """Scan ``step(carry, block of x, its first position, n) ->
        (carry, the block's metrics, y)`` over the blocks."""
        rows, t = x.shape[:2]
        tb = t // blocks
        n = evaluator.valid_count(head.output_shape_for(x.shape), mask)

        def body(c, xs):
            carry, n_err, loss = c
            xb, i = xs
            with jax.named_scope(events.SCOPE_LOSS_BLOCK):
                carry, m, y = step(carry, xb, i * tb, n)
            return (carry, n_err + m["n_err"], loss + m["loss_sum"]), y

        (carry, n_err, loss), ys = lax.scan(
            body, (carry, jnp.float32(0.0), jnp.float32(0.0)),
            (jnp.moveaxis(x.reshape((rows, blocks, tb) + x.shape[2:]),
                          1, 0), jnp.arange(blocks)))
        return carry, ys, {"n_err": n_err, "loss_sum": loss, "count": n}

    def train(params, x, target, mask):
        def fn(grads, xb, start, n):
            logits, back = jax.vjp(head.forward, params, xb)
            m = evaluator.block_metrics(logits, target, mask, start, n)
            g, dxb = back(m.pop("err_output"))
            grads = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(jnp.float32), grads, g)
            return grads, m, dxb

        zeros = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), params)
        grads, dx, m = walk(fn, zeros, x, mask)
        return m, grads, jnp.moveaxis(dx, 0, 1).reshape(x.shape)

    def evaluate(params, x, target, mask):
        def fn(carry, xb, start, n):
            m = evaluator.block_metrics(head.forward(params, xb), target,
                                        mask, start, n)
            m.pop("err_output")
            return carry, m, None

        return walk(fn, None, x, mask)[2]

    return BlockedHead(head.name, train, evaluate)


def build_probe(forwards, compute_dtype):
    """A forward-only walk of the chain that asks every unit with a
    ``probe(params, x)`` what its input puts on it, and ends at the
    last of them: ``probe(params, x) -> {unit name: its answer}``; None
    where no unit has one.  Never part of a step: set-up's own program
    (``FusedStepRunner.probe_units``)."""
    from veles_tpu.ops import batching

    probed = [i for i, f in enumerate(forwards) if hasattr(f, "probe")]
    if not probed:
        return None
    cast = batching.make_caster(compute_dtype)
    chain = chain_of(forwards)

    def probe(params, x):
        cparams, out = cast(params), {}
        for entry in chain:
            skip = x
            for i in _layers_of(entry):
                f = forwards[i]
                if i in probed:
                    out[f.name] = f.probe(cparams[f.name], x)
                if i == probed[-1]:
                    return out
                x, _ = f.apply_fwd(cparams[f.name], x, train=False)
            if not isinstance(entry, int):
                x = _skip_add(skip, x)
        return out

    return probe


def take_rows(dataset, target_store, indices):
    """The plain gather of a resident feed: one minibatch's rows of
    the data store and of the target store."""
    import jax.numpy as jnp

    return (jnp.take(dataset, indices, axis=0),
            jnp.take(target_store, indices, axis=0))


def build_scan_steps(ingest, forward_pass, backward_update,
                     compute_dtype, metrics_fn, gather=None,
                     n_classes=None, out_shape=None,
                     members: bool = False, blocked_head=None):
    """THE superstep: ``(train_step, eval_step)``, each one
    ``lax.scan`` over the minibatches of a firing, composed from the
    three shared bodies.  Every engine that trains jits these two (the
    population engine under ``vmap_members``); what an engine owns is
    placement — shardings, ``in_axes``, donation.

    **Feed.**  ``gather`` given: the feed is ``(dataset, target_store,
    indices, mask)``, the scanned xs are ``(indices, mask[, lr])`` and
    the body takes its rows with ``gather(dataset, target_store,
    indices)`` (:func:`take_rows`, or an engine's row-sharded one).
    ``gather=None``: the feed is host-assembled rows ``(x, target,
    mask)`` that ride the scan as they are.

    **Member axis.**  ``members=False`` is one model:
    ``train_step(params, opt, acc, conf, *feed, lr, rc)`` returns
    ``(params, opt, acc, conf)`` and ``eval_step(params, acc, conf,
    *feed, rc)`` returns ``(acc, conf, last_output)``.
    ``members=True`` is ONE member of a stack: its own rates and
    decays follow its state, ``train_step(params, opt, acc, lr, wd,
    *feed, rc)`` returns ``(params, opt, acc)`` and
    ``eval_step(params, acc, *feed, rc)`` returns ``acc`` — so the
    member-varying arguments lead and ``in_axes`` is zeros, then
    ``None`` for the feed and the counter.

    ``acc`` is the ``[n_err, loss_sum, count]`` carry; ``conf`` the
    confusion carry, counted when ``n_classes`` is given and carried
    through untouched otherwise (a member has none); the eval carry
    keeps the last minibatch's f32 output when ``out_shape`` is given;
    ``lr`` is ``(k, n_gd, 2)`` absolute rates, one row a minibatch;
    ``wd`` reaches ``backward_update`` only for a member.

    ``blocked_head`` (:func:`build_blocked_head`'s, with a
    ``forward_pass`` built ``head_apart``): the last layer and the loss
    are made together, a block of positions at a time, and the eval
    carry keeps no last output."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from veles_tpu.ops import batching


    cast = batching.make_caster(compute_dtype)
    # ``with`` blocks in place, not wrappers (see Workflow.initialize)
    scope = jax.named_scope
    n_store = 0 if gather is None else 2

    def metrics_of(out, target, mask):
        with scope(events.SCOPE_LOSS):
            m = metrics_fn(out.astype(jnp.float32), target, mask)
            if n_classes is not None:
                conf = jnp.zeros((n_classes, n_classes), jnp.int32)
                m["confusion"] = conf.at[target, m["max_idx"]].add(
                    mask.astype(jnp.int32))
        return m

    def accumulate(acc, conf, m):
        acc = acc + jnp.stack([m["n_err"], m["loss_sum"], m["count"]])
        if n_classes is not None:
            conf = conf + m["confusion"]
        return acc, conf

    def train_step(params, opt, acc, *args):
        if members:
            (lr, wd, *feed, rc), conf = args, None
        else:
            (conf, *feed, lr, rc), wd = args, None
        store = feed[:n_store]

        def body(carry, xs):
            params, opt, acc, conf, rc = carry
            if gather is not None:
                with scope(events.SCOPE_GATHER):
                    xs = gather(*store, xs[0]) + xs[1:]
            # lr: this minibatch's (n_gd, 2) row of absolute (weights,
            # bias) rates — a schedule stays exact inside a superstep
            x, target, mask, lr = xs
            x = ingest(x)
            with scope(events.SCOPE_CAST_PARAMS):
                cparams = cast(params)
            out, residuals = forward_pass(cparams, x, rc, True)
            if blocked_head is None:
                m = metrics_of(out, target, mask)
                err = m.pop("err_output")
            else:
                m, grads, err = blocked_head.train(
                    cparams[blocked_head.name], out, target, mask)
                residuals = residuals[:-1] + [HeadDone(grads)]
            new_params, new_opt = backward_update(
                cparams, params, opt, residuals, err, lr, wd)
            acc, conf = accumulate(acc, conf, m)
            return (new_params, new_opt, acc, conf, rc + 1), None

        (params, opt, acc, conf, _), _ = lax.scan(
            body, (params, opt, acc, conf, rc),
            (*feed[n_store:], lr))
        return (params, opt, acc) if members \
            else (params, opt, acc, conf)

    def eval_step(params, acc, *args):
        if members:
            (*feed, rc), conf = args, None
        else:
            conf, *feed, rc = args
        store = feed[:n_store]
        with scope(events.SCOPE_CAST_PARAMS):
            cparams = cast(params)

        def body(carry, xs):
            acc, conf, _, rc = carry
            if gather is not None:
                with scope(events.SCOPE_GATHER):
                    xs = gather(*store, xs[0]) + xs[1:]
            x, target, mask = xs
            out, _ = forward_pass(cparams, ingest(x), rc, False)
            if blocked_head is None:
                m = metrics_of(out, target, mask)
                m.pop("err_output")
            else:
                m = blocked_head.evaluate(
                    cparams[blocked_head.name], out, target, mask)
            acc, conf = accumulate(acc, conf, m)
            last = None if out_shape is None \
                else out.astype(jnp.float32)
            return (acc, conf, last, rc + 1), None

        last = None if out_shape is None \
            else jnp.zeros(out_shape, jnp.float32)
        (acc, conf, last, _), _ = lax.scan(
            body, (acc, conf, last, rc), tuple(feed[n_store:]))
        return acc if members else (acc, conf, last)

    return train_step, eval_step


def build_member_forward(forwards, compute_dtype):
    """One member's pure inference chain (no rng, f32 output) — the
    body vmapped over a stacked member axis by the ensemble
    dispatchers and the shadow scorer.  The chain and the integer
    rows are :func:`build_forward`'s: a residual entry adds its skip,
    ids stay ids."""
    import jax
    import jax.numpy as jnp

    mixed = _not_f32(compute_dtype)
    chain = chain_of(forwards)

    def layer(f, params, x):
        if getattr(f, "activation_mode", None) == "softmax":
            # a softmax head under the stacked member axis is
            # split at its logits: libtpu 0.0.34's compiler
            # overflows its stack (SIGSEGV, no Python error)
            # fusing the softmax reduction into the
            # member-batched matmul for some member counts —
            # every 3-member head tried, 3 to 1000 classes (PR 21;
            # tests/test_tpu_compile.py compiles them without a
            # chip).  The barrier keeps the two apart; the
            # single-model train path is untouched.
            return f.activation(jax.lax.optimization_barrier(
                f.pre_activation(params[f.name], x)))
        return f.apply_fwd(params[f.name], x, rng=None,
                           train=False)[0]

    def member_forward(params, x):
        if mixed and not _is_ids(x):
            x = x.astype(compute_dtype)
        for entry in chain:
            skip = x
            for i in _layers_of(entry):
                x = layer(forwards[i], params, x)
            if not isinstance(entry, int):
                x = _skip_add(skip, x)
        return x.astype(jnp.float32)

    return member_forward


def build_mean_probs(forwards, n_members: int, compute_dtype,
                     replicated: Any = None):
    """The ensemble's mean member probabilities: vmap the member
    forward over the stacked axis and average with a FIXED
    left-to-right add chain over the REAL members (never mesh-padding
    copies) — XLA may re-associate a ``jnp.mean`` differently between
    sharded and unsharded programs, and serving parity across
    placements is pinned f32-exact.  On a mesh the ``replicated``
    constraint gathers the member axis first (all_gather moves bits,
    bitwise), so both programs run the identical chain."""
    import jax

    from veles_tpu.ops import batching

    cast = batching.make_caster(compute_dtype)
    member_forward = build_member_forward(forwards, compute_dtype)

    def mean_probs(params, x):
        probs = jax.vmap(member_forward, in_axes=(0, None))(
            cast(params), x)
        if replicated is not None:
            probs = jax.lax.with_sharding_constraint(probs, replicated)
        acc = probs[0]
        for i in range(1, n_members):
            acc = acc + probs[i]
        return acc / n_members

    return mean_probs


def build_som_step(coords):
    """One masked Kohonen minibatch update — the body every SOM loop
    (fused epoch scan, eager per-minibatch dispatch, cohort vmap)
    shares, so fused-vs-eager parity is the same-jaxpr argument the
    supervised builders make.  ``coords`` is the (N, 2) host grid;
    the returned closure takes ``(weights, x, alpha, sigma, mask)``
    with ``x`` still carrying the loader's sample shape."""
    import jax.numpy as jnp

    from veles_tpu.ops.kohonen import som_step_masked

    coords = jnp.asarray(np.asarray(coords), jnp.float32)

    def som_update(weights, x, alpha, sigma, mask):
        x = x.reshape(x.shape[0], -1)
        return som_step_masked(weights, x, coords, alpha, sigma, mask)

    return som_update


def build_som_epoch(coords, resident: bool = True, gather=None):
    """A whole SOM superstep group (one epoch at full superstep) as
    ONE donated ``lax.scan``: the prototype matrix is the scan carry
    (donated by the caller's jit), the (alpha, sigma) schedule rides
    the scan xs so the decay is applied PER STEP inside the trace, and
    the per-step quantization-error / sample-count stats accumulate in
    f32 in the carry (sequential adds — the same order the eager loop's
    per-minibatch accumulator produces).

    ``resident=True`` returns ``epoch(weights, alphas, sigmas,
    dataset, indices, mask)`` gathering rows in-trace (``gather`` is
    the row-sharded shard_map gather on a mesh, ``jnp.take``
    otherwise); ``resident=False`` returns ``epoch(weights, alphas,
    sigmas, xb, mask)`` consuming host-assembled (k, mb, ...) batches.
    Argument order keeps weights at 0 (the donation slot) and the
    member-varying arrays (weights, alphas, sigmas) leading, so the
    cohort engine vmaps with in_axes=(0, 0, 0, None, ...)."""
    import jax.numpy as jnp
    from jax import lax

    som_update = build_som_step(coords)

    def _take(dataset, idx):
        if gather is not None:
            return gather(idx, dataset)
        return jnp.take(dataset, idx, axis=0)

    if resident:
        def epoch(weights, alphas, sigmas, dataset, indices, mask):
            def body(carry, xs):
                w, qe, cnt = carry
                idx, msk, a, s = xs
                w, _, qe_b, n_b = som_update(w, _take(dataset, idx),
                                             a, s, msk)
                return (w, qe + qe_b, cnt + n_b), None

            (weights, qe, cnt), _ = lax.scan(
                body, (weights, jnp.float32(0.0), jnp.float32(0.0)),
                (indices, mask, alphas, sigmas))
            return weights, jnp.stack([qe, cnt])

        return epoch

    def epoch(weights, alphas, sigmas, xb, mask):
        def body(carry, xs):
            w, qe, cnt = carry
            x, msk, a, s = xs
            w, _, qe_b, n_b = som_update(w, x, a, s, msk)
            return (w, qe + qe_b, cnt + n_b), None

        (weights, qe, cnt), _ = lax.scan(
            body, (weights, jnp.float32(0.0), jnp.float32(0.0)),
            (xb, mask, alphas, sigmas))
        return weights, jnp.stack([qe, cnt])

    return epoch


def build_som_eval(coords, resident: bool = True, gather=None):
    """The evaluation-class twin of :func:`build_som_epoch`: same scan
    skeleton, weights untouched (no donation), quantization error and
    sample count accumulated.  ``resident=True`` returns
    ``evaluate(weights, dataset, indices, mask)``; streaming returns
    ``evaluate(weights, xb, mask)`` (``coords`` is accepted for
    signature symmetry; evaluation needs distances only)."""
    import jax.numpy as jnp
    from jax import lax

    from veles_tpu.ops.kohonen import som_qe_masked

    del coords

    def _take(dataset, idx):
        if gather is not None:
            return gather(idx, dataset)
        return jnp.take(dataset, idx, axis=0)

    def _step(w, x, msk):
        return som_qe_masked(w, x.reshape(x.shape[0], -1), msk)

    if resident:
        def evaluate(weights, dataset, indices, mask):
            def body(carry, xs):
                qe, cnt = carry
                idx, msk = xs
                qe_b, n_b = _step(weights, _take(dataset, idx), msk)
                return (qe + qe_b, cnt + n_b), None

            (qe, cnt), _ = lax.scan(
                body, (jnp.float32(0.0), jnp.float32(0.0)),
                (indices, mask))
            return jnp.stack([qe, cnt])

        return evaluate

    def evaluate(weights, xb, mask):
        def body(carry, xs):
            qe, cnt = carry
            x, msk = xs
            qe_b, n_b = _step(weights, x, msk)
            return (qe + qe_b, cnt + n_b), None

        (qe, cnt), _ = lax.scan(
            body, (jnp.float32(0.0), jnp.float32(0.0)), (xb, mask))
        return jnp.stack([qe, cnt])

    return evaluate


# -- the core ----------------------------------------------------------


class ExecutionCore:
    """One engine loop's placement + compile + budget surface.

    Flags are orthogonal and resolved ONCE at construction:

    ``device``
        the framework device (backends.JaxDevice / MeshJaxDevice);
    ``mesh``
        a ``jax.sharding.Mesh`` (or None off-mesh) — placement
        properties (:attr:`replicated`, :attr:`batch_sharding`,
        :attr:`row_sharding`, :attr:`member_axis_sharding`) resolve
        against it;
    ``donate``
        whether :meth:`jit` actually donates (False pins buffers for
        debugging without touching adapter code);
    ``pool``
        which arbiter ledger pool this core's HBM footprint charges
        (``train`` / ``cohort`` / ``serve`` / ``scratch``).
    """

    def __init__(self, device: Any = None, mesh: Any = None, *,
                 donate: bool = True, pool: str = "train",
                 name: Optional[str] = None) -> None:
        self.device = device
        self.mesh = mesh if (mesh is not None
                             and int(mesh.devices.size) > 1) else None
        self.donate = bool(donate)
        self.pool = str(pool)
        self.name = name
        self._shardings: Dict[str, Any] = {}
        self._zeros_cache: Dict[Tuple[int, ...], Any] = {}
        self._replicate_fn = None
        self._charge_key: Optional[str] = None

    # -- placement -----------------------------------------------------

    @property
    def on_mesh(self) -> bool:
        return self.mesh is not None

    @property
    def jax_device(self):
        return getattr(self.device, "jax_device", None)

    def _sharding(self, kind: str):
        s = self._shardings.get(kind)
        if s is None and self.on_mesh:
            from veles_tpu.parallel import mesh as mesh_helpers
            if kind == "replicated":
                s = mesh_helpers.replicated_sharding(self.mesh)
            elif kind == "batch":
                # superstep batches are (k, mb, ...): shard the
                # MINIBATCH axis
                import jax.sharding as shd
                s = shd.NamedSharding(
                    self.mesh,
                    shd.PartitionSpec(None, self.mesh.axis_names[0]))
            elif kind == "rows":
                s = mesh_helpers.row_sharding(self.mesh)
            elif kind == "members":
                s = mesh_helpers.member_sharding(self.mesh)
            self._shardings[kind] = s
        return s

    @property
    def replicated(self):
        """Params/scalars placement on the mesh (None off-mesh)."""
        return self._sharding("replicated")

    @property
    def batch_sharding(self):
        """(k, mb, ...) superstep batches: minibatch axis over the
        data axis (None off-mesh — the single-device jit consumes
        host numpy directly)."""
        return self._sharding("batch")

    @property
    def row_sharding(self):
        """Resident dataset rows 1/N per device (None off-mesh)."""
        return self._sharding("rows")

    @property
    def member_axis_sharding(self):
        """Stacked member axis P/N per device (None off-mesh)."""
        return self._sharding("members")

    def put(self, array: Any, where: Any = None):
        """Place ``array``: on ``where`` when given, else on the
        core's default device."""
        if where is None:
            where = self.jax_device
        return put(array, where)

    def put_members(self, array: np.ndarray):
        """Upload a member-axis-leading array: sharded P/N per device
        on a mesh (multihost-safe ``make_array_from_callback``
        placement, H2D bytes charged to the device accounting), a
        plain device put otherwise."""
        if not self.on_mesh:
            return self.device.put(array)
        from veles_tpu.parallel import mesh as mesh_helpers
        buf = mesh_helpers.put_member_sharded(self.mesh,
                                              np.asarray(array))
        self.device.h2d_bytes += int(buf.nbytes)
        return buf

    def put_replicated(self, array: np.ndarray):
        """Replicate a host array over the mesh (dataset, targets,
        superstep indices/masks), or hand it through untouched
        off-mesh — the single-device jit consumes host numpy
        directly, as before."""
        if not self.on_mesh:
            return array
        import jax.sharding as shd

        from veles_tpu.parallel import mesh as mesh_helpers
        return mesh_helpers.put_along(self.mesh, np.asarray(array),
                                      shd.PartitionSpec())

    def zeros_members(self, shape) -> Any:
        """A member-axis-leading zeros buffer under the member
        sharding — cached per shape (a fresh jit per accumulator
        reset would retrace every class end)."""
        if not self.on_mesh:
            return self.device.zeros(shape, np.float32)
        key = tuple(int(s) for s in shape)
        fn = self._zeros_cache.get(key)
        if fn is None:
            import jax.numpy as jnp
            fn = donating_jit(
                lambda: jnp.zeros(key, jnp.float32),
                out_shardings=self.member_axis_sharding)
            self._zeros_cache[key] = fn
        return fn()

    def replicate_for_fetch(self, array: Any) -> Any:
        """Re-lay a (member-)sharded array out replicated so every
        process can host-fetch it (the multihost-safe
        materialization); identity off-mesh."""
        if not self.on_mesh:
            return array
        if self._replicate_fn is None:
            self._replicate_fn = donating_jit(
                lambda a: a, out_shardings=self.replicated)
        return self._replicate_fn(array)

    # -- compile -------------------------------------------------------

    def jit(self, fn, donate: Tuple[int, ...] = (),
            in_shardings: Any = None, out_shardings: Any = None,
            compiler_options: Optional[Dict[str, Any]] = None):
        """Compile through the donation seam; ``donate`` is dropped
        when the core was built with ``donate=False``."""
        return donating_jit(
            fn, donate=donate if self.donate else (),
            in_shardings=in_shardings, out_shardings=out_shardings,
            compiler_options=compiler_options)

    @staticmethod
    def vmap_members(fn, in_axes):
        """Lift ``fn`` over the leading stacked member axis (axis 0
        where ``in_axes`` says so, broadcast where None)."""
        import jax

        return jax.vmap(fn, in_axes=in_axes)

    # -- the process HBM arbiter ---------------------------------------

    def charge(self, nbytes: int, label: Optional[str] = None) -> None:
        """Charge this core's HBM footprint to the process-wide
        arbiter's ledger under :attr:`pool` (re-charging under the
        same key replaces, so a growing footprint stays one entry)."""
        from veles_tpu.serve import residency

        if self._charge_key is None:
            self._charge_key = (f"{self.pool}:"
                                f"{label or self.name or hex(id(self))}")
        residency.process_arbiter(self.device).reserve(
            self._charge_key, int(nbytes), pool=self.pool)

    def discharge(self) -> None:
        """Release this core's ledger entry (engine release path)."""
        if self._charge_key is None:
            return
        from veles_tpu.serve import residency

        residency.process_arbiter(self.device).release(
            self._charge_key)
        self._charge_key = None

    def release(self) -> None:
        """Drop cached dispatchers and the ledger charge."""
        self._zeros_cache.clear()
        self._replicate_fn = None
        self.discharge()


def tree_nbytes(tree: Any) -> int:
    """Total leaf bytes of a (possibly nested dict) param/opt pytree —
    the arbiter-charge accounting (works on host numpy and device
    arrays alike)."""
    import jax

    return sum(int(getattr(leaf, "nbytes", 0))
               for leaf in jax.tree_util.tree_leaves(tree))
