"""The central registry of Sightline telemetry names.

Every journal event, counter, gauge, histogram, and span name this
framework emits is declared HERE as an importable constant.  Call
sites use the constants (``telemetry.event(events.EV_SNAPSHOT_SAVE,
...)``), and veleslint's event-registry rule flags any ad-hoc string
literal passed to ``telemetry.event / counter / gauge / histogram /
span / recent_events`` — the typo class chaos_drill's journal
assertions could previously only catch at runtime (an emitter and an
asserter disagreeing on a name means the drill reads an event that
never fires) is now a parse-time finding.

A few hot-path names are *families* keyed by the fused step kind or by
a unit's name and are necessarily built dynamically
(``fused.<kind>_submit`` spans, ``fused.first_<kind>_submit_seconds``
gauges, ``fused.<kind>_images`` counters, ``<unit>.run`` /
``init.<unit>`` spans); the lint rule checks literals only, and the
families are documented here (``DYNAMIC_FAMILIES``) so the registry
stays the one place a name is looked up.
"""

from __future__ import annotations

from typing import Set

EVENTS: Set[str] = set()
COUNTERS: Set[str] = set()
GAUGES: Set[str] = set()
HISTOGRAMS: Set[str] = set()
SPANS: Set[str] = set()


def _ev(name: str) -> str:
    EVENTS.add(name)
    return name


def _ctr(name: str) -> str:
    COUNTERS.add(name)
    return name


def _gauge(name: str) -> str:
    GAUGES.add(name)
    return name


def _hist(name: str) -> str:
    HISTOGRAMS.add(name)
    return name


def _span(name: str) -> str:
    # a journaled span emits an event AND feeds the histogram of the
    # same name — it lives in every namespace it touches
    SPANS.add(name)
    EVENTS.add(name)
    HISTOGRAMS.add(name)
    return name


def _timed(name: str) -> str:
    # a span that never journals: the histogram of its name, and the
    # ``veles:<name>`` annotation in a profiler trace
    SPANS.add(name)
    HISTOGRAMS.add(name)
    return name


# -- journal events ----------------------------------------------------

#: the first call of a step kind: where and what (device, static
#: shapes), its ``seconds`` and of them ``trace_seconds``,
#: ``lower_seconds``, ``compile_seconds`` (compile or cache load) and
#: ``cold`` — compiles the persistent cache did not serve
EV_FUSED_FIRST_DISPATCH = _ev("fused.first_dispatch")
EV_FUSED_SUMMARY = _ev("fused.summary")
EV_FUSED_RECOMPUTE = _ev("fused.recompute")
#: which form of EVA attention a unit runs (``ops/sequence.py``
#: ``eva_path``): ``unit``, ``path`` (``fused`` / ``xla``), ``reason``
#: where it is ``xla`` (``platform`` / ``batched`` / ``head_size`` /
#: ``window``), the kernels' ``tiles`` where it is ``fused``; once a
#: unit at ``initialize``, again only where a later trace must differ
EV_EVA_PATH = _ev("eva.path")
#: which form of the gated delta rule a unit runs (``ops/deltanet.py``
#: ``rule_path``): ``unit``, ``form`` (``chunked`` / ``recurrent``),
#: ``reason`` where it is ``recurrent`` (``ragged``: the row is not
#: whole chunks), ``chunk``; and, where it is ``chunked``, what makes
#: the chunks' ``[C, C]`` products (``products_path``): ``products``
#: (``fused``: the Pallas kernels of ``ops/deltanet_pallas.py`` /
#: ``xla``), ``reason`` where they are ``xla`` (``platform`` /
#: ``batched`` / ``head_size`` / ``chunk``), the kernels' ``tiles``
#: where they are ``fused``; once a unit at ``initialize``, again only
#: where a later trace must differ
EV_GDN_PATH = _ev("gdn.path")
#: which form of the causal attention core a unit runs
#: (``ops/attention.py`` ``attention_path``): ``unit``, ``form``
#: (``splash``: the Pallas kernel that ships with jax / ``xla``: a block
#: of queries at a time), ``reason`` where it is ``xla`` (``platform`` /
#: ``batched`` / ``head_size`` / ``row``), ``tiles``, ``window`` (keys
#: a query reads; None: every key up to itself), ``rope`` (``default``
#: / ``yarn``) and, under the kernel form, ``kv_blocks``: the most key
#: blocks a query block visits; once a unit at ``initialize``, again
#: only where a later trace must differ
EV_ATTN_PATH = _ev("attn.path")
#: the share of a mixture of experts a unit holds, once at
#: ``initialize`` (``ops/moe.py``): ``unit``, ``experts_total``,
#: ``experts_held``, ``first_held``, ``top_k``, ``rows`` the dispatch
#: buffers of one block of tokens are sized for (every token of the
#: block may choose ``top_k`` held experts: nothing is ever dropped),
#: ``blocks`` of tokens a row is cut into, ``capacity`` (rows of the
#: compact buffers a block goes through, once where its held pairs
#: fit them: the pairs it expects x ``DISPATCH_HEADROOM``, from shapes
#: alone; None: the ``rows``-row buffers), ``form`` of the grouped
#: products (``gmm``: the Pallas grouped matmul that ships with jax /
#: ``ragged_dot``) and its ``reason`` / ``tiles``, ``shared`` (whether
#: the layer has a shared expert)
EV_MOE_SHARE = _ev("moe.share")
#: what the routing of the FIRST firing's first minibatch put on the
#: held experts, a layer — read by a forward-only probe at set-up,
#: never inside a timed step (``FusedStepRunner._report_loads``):
#: ``unit``, ``local_assignments``, ``max_expert_rows``,
#: ``min_expert_rows``, ``dropped`` (must read 0),
#: ``over_capacity_blocks`` of the minibatch's ``blocks`` (they go
#: through the compact buffers more than once)
EV_MOE_LOAD = _ev("moe.load")
#: whether the head's product, the loss and the head's error are made
#: a block of positions at a time (``FusedStepRunner._decide_loss_
#: blocks``, from shapes against free memory): ``blocks`` (0: whole),
#: ``bytes_whole``, ``bytes_block``, ``reason`` (``no_limit`` / ``fits``
#: / ``whole_exceeds_free`` / ``not_blockable``)
EV_LOSS_BLOCKED = _ev("loss.blocked")
#: how a data-parallel train step exchanges its gradients, once at
#: ``FusedStepRunner._build_steps`` on a mesh and never without one
#: (``engine/core.py`` ``GradExchange``): ``devices``, ``leaves``,
#: ``bytes`` and ``dtype`` of the gradients, ``groups`` in the order
#: the backward walk makes them, ``options`` handed to the compiler
EV_DP_GRAD_EXCHANGE = _ev("dp.grad_exchange")

#: one per backend compile OR persistent-cache load of a program
#: (jax.monitoring reports both under one name): ``seconds``, the
#: jitted function's name, ``cached``, and ``during`` = the spans open
#: on the compiling thread — which step compiled, and inside what
EV_XLA_COMPILE = _ev("xla.compile")
#: the same shape (``seconds``, ``fun``, ``during``; no ``cached``) for
#: what the compile cache does not save, from 10 ms up: a jitted
#: function traced to a jaxpr, a jaxpr lowered to its module (a Pallas
#: kernel's Mosaic lowering is in the second).  A function traced
#: inside a trace reports before the outer one, whose ``seconds`` hold
#: it; the counters below count every interval once
EV_XLA_TRACE = _ev("xla.trace")
EV_XLA_LOWER = _ev("xla.lower")

EV_DEVICE_OOM_RETRY = _ev("device.oom_retry")
EV_DEVICE_OOM_DEGRADED = _ev("device.oom_degraded")

EV_SNAPSHOT_SAVE = _ev("snapshot.save")
EV_SNAPSHOT_FALLBACK = _ev("snapshot.fallback")
EV_SNAPSHOT_UNRECOVERABLE = _ev("snapshot.unrecoverable")

EV_LOADER_EPOCH = _ev("loader.epoch")
EV_LOADER_SHARD_RESIDENT = _ev("loader.shard_resident")
EV_LOADER_CORRUPT_FILE = _ev("loader.corrupt_file")
EV_LOADER_CORRUPT_OVER_TOLERANCE = _ev("loader.corrupt_over_tolerance")

EV_GA_GENERATION = _ev("ga.generation")
EV_GA_GENERATION_EVALUATED = _ev("ga.generation_evaluated")
EV_GA_HANG_DETECTED = _ev("ga.hang_detected")
EV_GA_EVALUATOR_RESTART = _ev("ga.evaluator_restart")
EV_GA_GENOME_LOST = _ev("ga.genome_lost")
EV_GA_GENOME_RETRY = _ev("ga.genome_retry")
EV_GA_CHECKPOINT_FALLBACK = _ev("ga.checkpoint_fallback")
EV_GA_CHECKPOINT_UNRECOVERABLE = _ev("ga.checkpoint_unrecoverable")
EV_GA_RESUMED = _ev("ga.resumed")
EV_GA_HANDOFF = _ev("ga.handoff")

EV_DBN_STAGE_HANDOFF = _ev("dbn.stage_handoff")

EV_PREEMPT_REQUESTED = _ev("preempt.requested")
EV_PREEMPT_DEADLINE_EXCEEDED = _ev("preempt.deadline_exceeded")
EV_PREEMPT_FINAL_SNAPSHOT = _ev("preempt.final_snapshot")
EV_PREEMPT_PEER_BROADCAST = _ev("preempt.peer_broadcast")
EV_PREEMPT_GA_STOP = _ev("preempt.ga_stop")
EV_PREEMPT_GA_EXIT = _ev("preempt.ga_exit")

EV_MULTIHOST_EMERGENCY_SNAPSHOT = _ev("multihost.emergency_snapshot")
EV_MULTIHOST_COLLECTIVE_FAILED = _ev("multihost.collective_failed")
EV_MULTIHOST_PEER_DEATH = _ev("multihost.peer_death")
EV_MULTIHOST_INIT_REFUSED = _ev("multihost.init_refused")

EV_SERVE_READY = _ev("serve.ready")
EV_SERVE_MODEL_LOADED = _ev("serve.model_loaded")
EV_SERVE_MODEL_SPILLED = _ev("serve.model_spilled")
EV_SERVE_MODEL_RESTORED = _ev("serve.model_restored")
EV_SERVE_MODEL_SHARDED = _ev("serve.model_sharded_resident")
EV_SERVE_FIRST_DISPATCH = _ev("serve.first_dispatch")
EV_SERVE_DRAIN = _ev("serve.drain")
EV_SERVE_SHUTDOWN = _ev("serve.shutdown")

EV_FLEET_READY = _ev("fleet.ready")
EV_FLEET_PLACEMENT = _ev("fleet.placement")
EV_FLEET_REPLICA_SPAWNED = _ev("fleet.replica_spawned")
EV_FLEET_REPLICA_DIED = _ev("fleet.replica_died")
EV_FLEET_REPLICA_RESPAWNED = _ev("fleet.replica_respawned")
EV_FLEET_DRAIN = _ev("fleet.drain")
EV_FLEET_SHUTDOWN = _ev("fleet.shutdown")
EV_FLEET_REPLICA_EJECTED = _ev("fleet.eject.replica")
EV_FLEET_REPLICA_REINSTATED = _ev("fleet.eject.reinstated")
EV_FLEET_PROBE_RESULT = _ev("fleet.probe.result")
EV_FLEET_SCALE_UP = _ev("fleet.scale.up")
EV_FLEET_SCALE_DOWN = _ev("fleet.scale.down")
EV_FLEET_REPLICA_RETIRED = _ev("fleet.replica_retired")
EV_FLEET_DEGRADE_ENGAGE = _ev("fleet.degrade.engage")
EV_FLEET_DEGRADE_RELEASE = _ev("fleet.degrade.release")

EV_TRAFFIC_TRACE = _ev("traffic.trace")
EV_TRAFFIC_DONE = _ev("traffic.done")

EV_ONLINE_ARMED = _ev("online.armed")
EV_ONLINE_GATE = _ev("online.gate")
EV_ONLINE_PROMOTED = _ev("online.promoted")
EV_ONLINE_ROLLBACK = _ev("online.rollback")

EV_TRACE_REQUEST = _ev("trace.request")
EV_TRACE_LEG = _ev("trace.leg")
EV_TRACE_SERVE = _ev("trace.serve")
EV_TRACE_BATCH = _ev("trace.batch")
EV_FLIGHTREC_DUMP = _ev("flightrec.dump")
EV_LOG_RECORD = _ev("log.record")

EV_SUPERVISOR_RESTART = _ev("supervisor.restart")
EV_SUPERVISOR_RESUMED = _ev("supervisor.resumed")
EV_SUPERVISOR_SHUTDOWN = _ev("supervisor.shutdown")
EV_SUPERVISOR_DONE = _ev("supervisor.done")
EV_SUPERVISOR_GIVEUP = _ev("supervisor.giveup")

# -- counters ----------------------------------------------------------

CTR_FUSED_DISPATCHES = _ctr("fused.dispatches")
CTR_FUSED_TRAIN_TOKENS = _ctr("fused.train_tokens")
CTR_FUSED_STREAM_TRANSFER_BYTES = _ctr("fused.stream_transfer_bytes")
CTR_FUSED_STREAM_TRANSFER_SECONDS = _ctr(
    "fused.stream_transfer_seconds")
CTR_FUSED_STREAM_OOM_RETRIES = _ctr("fused.stream_oom_retries")

#: backend compiles + cache loads of this process, their seconds, and
#: the part of those seconds spent inside a ``fused.*`` span
CTR_XLA_COMPILES = _ctr("xla.compiles")
CTR_XLA_COMPILE_SECONDS = _ctr("xla.compile_seconds")
CTR_FUSED_COMPILE_SECONDS = _ctr("fused.compile_seconds")
#: tracing and lowering the same way: all of it, and the part inside a
#: ``fused.*`` span.  Seconds are the UNION of the reported intervals a
#: thread (an inner trace, or an eager op compiled under a trace, is
#: taken off the event it ran in), so trace + lower + compile never
#: exceed the wall time of the span they fell in
CTR_XLA_TRACE_SECONDS = _ctr("xla.trace_seconds")
CTR_XLA_LOWER_SECONDS = _ctr("xla.lower_seconds")
CTR_FUSED_TRACE_SECONDS = _ctr("fused.trace_seconds")
CTR_FUSED_LOWER_SECONDS = _ctr("fused.lower_seconds")
#: backend compiles inside a ``fused.*`` span that the persistent cache
#: did NOT serve: 0 in a warm run
CTR_FUSED_COLD_COMPILES = _ctr("fused.cold_compiles")

CTR_ENSEMBLE_CHUNKS = _ctr("ensemble.chunks")
CTR_ENSEMBLE_SECONDS = _ctr("ensemble.seconds")
CTR_ENSEMBLE_IMAGES = _ctr("ensemble.images")
CTR_ENSEMBLE_MEMBER_IMAGES = _ctr("ensemble.member_images")

CTR_GA_COHORTS = _ctr("ga.cohorts")
CTR_GA_COHORT_MEMBERS = _ctr("ga.cohort_members")
CTR_GA_EVALUATIONS = _ctr("ga.evaluations")
CTR_GA_EVAL_SECONDS = _ctr("ga.eval_seconds")
CTR_GA_HANGS_DETECTED = _ctr("ga.hangs_detected")
CTR_GA_EVALUATOR_RESTARTS = _ctr("ga.evaluator_restarts")
CTR_GA_GENOMES_LOST = _ctr("ga.genomes_lost")
CTR_GA_GENOME_RETRIES = _ctr("ga.genome_retries")
CTR_GA_CHECKPOINT_FALLBACKS = _ctr("ga.checkpoint_fallbacks")

CTR_SERVE_REQUESTS = _ctr("serve.requests")
CTR_SERVE_REQUEST_ERRORS = _ctr("serve.request_errors")
CTR_SERVE_ROWS = _ctr("serve.rows")
CTR_SERVE_MEMBER_ROWS = _ctr("serve.member_rows")
CTR_SERVE_BATCHES = _ctr("serve.batches")
CTR_SERVE_BATCH_SLOTS = _ctr("serve.batch_slots")
CTR_SERVE_COMPILES = _ctr("serve.compiles")
CTR_SERVE_SPILLS = _ctr("serve.spills")
CTR_SERVE_DEADLINE_DROPPED = _ctr("serve.deadline_dropped")
CTR_SERVE_WAIT_COLLAPSED = _ctr("serve.wait_collapsed")
CTR_SERVE_WAIT_STRETCHED = _ctr("serve.wait_stretched")

CTR_FLEET_REQUESTS = _ctr("fleet.requests")
CTR_FLEET_REQUEST_ERRORS = _ctr("fleet.request_errors")
CTR_FLEET_SHED = _ctr("fleet.shed")
CTR_FLEET_RETRIES = _ctr("fleet.retries")
CTR_FLEET_MIRRORED = _ctr("fleet.mirrored")
CTR_FLEET_REPLICA_DEATHS = _ctr("fleet.replica_deaths")
CTR_FLEET_REPLICA_RESPAWNS = _ctr("fleet.replica_respawns")
CTR_FLEET_HEDGES = _ctr("fleet.hedge.issued")
CTR_FLEET_HEDGE_WINS = _ctr("fleet.hedge.wins")
CTR_FLEET_HEDGE_DENIED = _ctr("fleet.hedge.denied")
CTR_FLEET_STALE_RESPONSES = _ctr("fleet.stale_response")
CTR_FLEET_DEADLINE_MISSES = _ctr("fleet.deadline_misses")
CTR_FLEET_INTEGRITY_STRIKES = _ctr("fleet.integrity_strikes")
CTR_FLEET_EJECTIONS = _ctr("fleet.eject.total")
CTR_FLEET_REINSTATEMENTS = _ctr("fleet.eject.reinstated_total")
CTR_FLEET_PROBES = _ctr("fleet.probe.sent")
CTR_FLEET_PROBES_OK = _ctr("fleet.probe.ok")
CTR_FLEET_PROBES_FAILED = _ctr("fleet.probe.fail")
CTR_FLEET_SCALE_UPS = _ctr("fleet.scale.ups")
CTR_FLEET_SCALE_DOWNS = _ctr("fleet.scale.downs")
CTR_FLEET_RETIRED = _ctr("fleet.replicas_retired")
CTR_TRAFFIC_SENT = _ctr("traffic.sent")
CTR_TRAFFIC_LATE = _ctr("traffic.late")

CTR_ONLINE_TAPPED_ROWS = _ctr("online.tapped_rows")
CTR_ONLINE_LABELED_ROWS = _ctr("online.labeled_rows")
CTR_ONLINE_LABEL_ORPHANS = _ctr("online.label_orphans")
CTR_ONLINE_STEPS = _ctr("online.steps")
CTR_ONLINE_STEP_ROWS = _ctr("online.step_rows")
CTR_ONLINE_STEP_SECONDS = _ctr("online.step_seconds")
CTR_ONLINE_STEPS_SKIPPED_BUSY = _ctr("online.steps_skipped_busy")
CTR_ONLINE_PROMOTIONS = _ctr("online.promotions")
CTR_ONLINE_ROLLBACKS = _ctr("online.rollbacks")

CTR_SOM_FUSED_DISPATCHES = _ctr("som.fused_dispatches")
CTR_SOM_FUSED_IMAGES = _ctr("som.fused_images")
CTR_SOM_COHORTS = _ctr("som.cohorts")
CTR_SOM_COHORT_MEMBERS = _ctr("som.cohort_members")

CTR_EVALUATOR_JOBS = _ctr("evaluator.jobs")
CTR_EVALUATOR_JOB_ERRORS = _ctr("evaluator.job_errors")

CTR_LOADER_EPOCHS = _ctr("loader.epochs")
CTR_LOADER_IMAGES_DECODED = _ctr("loader.images_decoded")
CTR_LOADER_CORRUPT_SKIPPED = _ctr("loader.corrupt_skipped")
#: whole-store passes that put the resident dataset into the form the
#: step reads (``FullBatchLoader.reside_as``): 1 a run; a value that
#: grows with the firings is the per-superstep re-cast come back
CTR_LOADER_RESIDENT_CASTS = _ctr("loader.resident_casts")

CTR_SNAPSHOT_SAVES = _ctr("snapshot.saves")
CTR_SNAPSHOT_FALLBACKS = _ctr("snapshot.fallbacks")

CTR_DEVICE_OOM_DEGRADED = _ctr("device.oom_degraded")
CTR_MULTIHOST_EMERGENCY_SNAPSHOTS = _ctr(
    "multihost.emergency_snapshots")
CTR_PREEMPT_FINAL_SNAPSHOTS = _ctr("preempt.final_snapshots")
CTR_SUPERVISOR_RESTARTS = _ctr("supervisor.restarts")

# -- gauges ------------------------------------------------------------

GAUGE_FUSED_MFU = _gauge("fused.mfu")
GAUGE_FUSED_KEPT_ACTIVATION_BYTES = _gauge("fused.kept_activation_bytes")
#: layers whose gradients the traced data-parallel step exchanges
#: (never set without a mesh)
GAUGE_DP_GRAD_EXCHANGE_GROUPS = _gauge("dp.grad_exchange_groups")
GAUGE_EVA_WINDOW = _gauge("eva.window")
GAUGE_EVA_CHUNK = _gauge("eva.chunk")
GAUGE_EVA_SUMMARIES_PER_ROW = _gauge("eva.summaries_per_row")
#: ``eva_attention`` units of the workflow on the fused kernels
GAUGE_EVA_FUSED_LAYERS = _gauge("eva.fused_layers")
#: ``gated_delta_net`` units of the workflow whose chunk products the
#: fused kernels make
GAUGE_GDN_FUSED_LAYERS = _gauge("gdn.fused_layers")
#: attention units of the workflow whose core reads a window of keys
GAUGE_ATTN_WINDOW_LAYERS = _gauge("attn.window_layers")
#: rows the held experts could not take in the probed minibatch, summed
#: over the layers (static buffers are sized for the worst routing: 0)
GAUGE_MOE_DROPPED_ROWS = _gauge("moe.dropped_rows")
#: dispatch blocks of the probed minibatch whose held pairs passed the
#: compact buffers' ``capacity`` and went through them a piece at a
#: time, summed over the layers (of ``moe.load``'s ``blocks`` a layer)
GAUGE_MOE_OVER_CAPACITY_BLOCKS = _gauge("moe.over_capacity_blocks")
GAUGE_SERVE_QUEUE_DEPTH = _gauge("serve.queue_depth")
GAUGE_SERVE_MODELS_RESIDENT = _gauge("serve.models_resident")
GAUGE_SERVE_RESIDENT_BYTES = _gauge("serve.resident_bytes")
GAUGE_SERVE_RESIDENT_BYTES_PER_DEVICE = _gauge(
    "serve.resident_bytes_per_device")
GAUGE_SERVE_MESH_DEVICES = _gauge("serve.mesh_devices")
GAUGE_ARBITER_BUDGET_BYTES = _gauge("arbiter.budget_bytes")
GAUGE_ARBITER_RESIDENT_BYTES = _gauge("arbiter.resident_bytes")
GAUGE_SERVE_EFFECTIVE_WAIT_MS = _gauge("serve.effective_wait_ms")
GAUGE_SERVE_FIRST_DISPATCH_SECONDS = _gauge(
    "serve.first_dispatch_seconds")

GAUGE_FLEET_REPLICAS_HEALTHY = _gauge("fleet.replicas_healthy")
GAUGE_FLEET_INFLIGHT = _gauge("fleet.inflight")
GAUGE_FLEET_EST_WAIT_MS = _gauge("fleet.est_wait_ms")
GAUGE_FLEET_DISPATCH_EMA_MS = _gauge("fleet.dispatch_ema_ms")
GAUGE_FLEET_HEDGE_THRESHOLD_MS = _gauge("fleet.hedge.threshold_ms")
GAUGE_FLEET_REPLICAS_EJECTED = _gauge("fleet.eject.current")
GAUGE_FLEET_REPLICAS_TOTAL = _gauge("fleet.replicas_total")
GAUGE_FLEET_DEGRADE_RUNGS = _gauge("fleet.degrade.rungs")
GAUGE_FLEET_SCALE_PRESSURE_MS = _gauge("fleet.scale.pressure_ms")
GAUGE_TRAFFIC_RATE_RPS = _gauge("traffic.rate_rps")

GAUGE_ONLINE_BUFFER_ROWS = _gauge("online.buffer_rows")
GAUGE_ONLINE_BUFFER_BYTES = _gauge("online.buffer_bytes")
GAUGE_ONLINE_TIME_TO_SERVE = _gauge("online.time_to_serve")

GAUGE_LOCKSTEP_EDGES = _gauge("lockstep.edges_observed")
GAUGE_LOCKSTEP_ACQUIRES = _gauge("lockstep.acquires")

#: device bytes of the resident data store once its dtype is decided
#: (0 = streaming)
GAUGE_LOADER_RESIDENT_BYTES = _gauge("loader.resident_bytes")
GAUGE_GA_LAST_HANG_WAIT = _gauge("ga.last_hang_wait")
GAUGE_PREEMPT_SNAPSHOT_SECONDS = _gauge("preempt.snapshot_seconds")
GAUGE_MULTIHOST_PEER_HEARTBEAT_AGE = _gauge(
    "multihost.peer_heartbeat_age")

# -- histograms --------------------------------------------------------

HIST_SNAPSHOT_SAVE_SECONDS = _hist("snapshot.save_seconds")
HIST_SNAPSHOT_LOAD_SECONDS = _hist("snapshot.load_seconds")
HIST_GA_GENOME_SECONDS = _hist("ga.genome_seconds")
HIST_GA_GENERATION_SECONDS = _hist("ga.generation_seconds")
HIST_LOADER_DECODE_SECONDS = _hist("loader.decode_seconds")
HIST_LOADER_EPOCH_SECONDS = _hist("loader.epoch_seconds")
HIST_ENSEMBLE_DISPATCH_SECONDS = _hist("ensemble.dispatch_seconds")
HIST_ENSEMBLE_SCORE_SECONDS = _hist("ensemble.score_seconds")
HIST_SUPERVISOR_DOWNTIME_SECONDS = _hist(
    "supervisor.downtime_seconds")
HIST_FLEET_REQUEST_SECONDS = _hist("fleet.request_seconds")
HIST_SERVE_REQUEST_SECONDS = _hist("serve.request_seconds")
HIST_SERVE_DISPATCH_SECONDS = _hist("serve.dispatch_seconds")
HIST_SERVE_BATCH_ROWS = _hist("serve.batch_rows")
HIST_SERVE_WAIT_SECONDS = _hist("serve.wait_seconds")
HIST_ONLINE_STEP_DISPATCH_SECONDS = _hist(
    "online.step_dispatch_seconds")
HIST_ONLINE_GATE_SECONDS = _hist("online.gate_seconds")
#: class-end metric fetch returned -> next superstep submitted: the
#: host time the device idles on at each class end (crosses
#: decision.run, the workflow loop, loader.run, the head of fused.run)
HIST_LOOP_TURNAROUND = _hist("loop.turnaround")

# -- journaled spans (event + histogram of the same name) --------------

SPAN_GA_COHORT_TRAIN = _span("ga.cohort_train")
SPAN_SOM_COHORT_TRAIN = _span("som.cohort_train")
SPAN_EVALUATOR_JOB_SECONDS = _span("evaluator.job_seconds")
#: the decision about the resident store's dtype, once per initialize:
#: ``from``, ``to``, ``bytes_before``, ``bytes_after``, ``seconds`` and,
#: where the store was left as it is, ``reason`` (``streaming`` /
#: ``dequant`` / ``same_dtype`` / ``targets_alias`` / ``oom``)
SPAN_LOADER_RESIDENT_DTYPE = _span("loader.resident_dtype")

# -- spans of the training path (histogram + profiler annotation) ------

SPAN_WORKFLOW_INITIALIZE = _timed("workflow.initialize")
SPAN_WORKFLOW_RUN = _timed("workflow.run")
SPAN_FUSED_BUILD_STEPS = _timed("fused.build_steps")
#: inside it, the decisions made from shapes alone before anything is
#: jitted: what the chain keeps or re-runs (two ``eval_shape`` walks of
#: every layer: all but milliseconds of it, on the chip), the loss's
#: blocks, on a mesh the gradient exchange
SPAN_FUSED_PLAN = _timed("fused.plan")
#: ``probe_units``: the forward-only program built, compiled or loaded,
#: run on one minibatch and fetched (set-up, once after the first
#: train firing where a unit has a ``probe``)
SPAN_FUSED_PROBE = _timed("fused.probe")
#: the splash kernel's mask tables, made on the host once a shape by
#: whatever trace asks first (``ops/attention.py`` ``_splash_kernel``)
SPAN_ATTN_MASK_TABLES = _timed("attn.mask_tables")
SPAN_FUSED_ENSURE_PARAMS = _timed("fused.ensure_params")
SPAN_FUSED_PUT_CARRY = _timed("fused.put_carry")
#: the host's wait for every queued superstep of the class: the
#: barrier of the training loop
SPAN_FUSED_FETCH_METRICS = _timed("fused.fetch_metrics")

#: dynamic name families (built with f-strings at the call site; the
#: lint rule checks literals only), where <kind> is the fused step
#: kind (train/eval) and <unit> a unit's name:
#: ``fused.<kind>_submit`` spans round the jitted call alone (host
#: SUBMIT time of one superstep — the device works on after it);
#: the first call of a kind (trace + compile or cache load + upload)
#: is the span ``fused.first_<kind>_submit`` and the gauge
#: ``fused.first_<kind>_submit_seconds`` instead;
#: ``fused.<kind>_images`` counters over ``fused.<kind>_wall_seconds``
#: (first submit of a class -> its metric fetch returned, summed) are
#: the delivered rate; ``<unit>.run`` spans for every firing
#: (Unit.fire) and ``init.<unit>`` spans under ``workflow.initialize``
#: ...plus the fleet router's per-model traffic split (the canary A/B
#: read): ``fleet.model.<name>.requests`` / ``.errors`` / ``.shed`` /
#: ``.mirrored`` counters and a ``fleet.model.<name>.request_seconds``
#: histogram, where <name> is the served model's registered name
#: ...plus the sentinel's per-replica health split (the fleet_rows
#: health column): a ``fleet.replica.<i>.health_score`` gauge and a
#: ``fleet.replica.<i>.hedge_wins`` counter, where <i> is the replica
#: index
# -- device scopes ------------------------------------------------------
# ``jax.named_scope`` names: metadata of the device ops the step
# program traces (the ``tf_op`` stat of a profiler trace's events), not
# registry entries.  Per layer: ``fwd/<layer>``, ``bwd/<layer>``,
# ``update/<layer>`` (engine/core.py); a recomputed forward runs under
# ``bwd/<layer>/recompute``.

SCOPES: Set[str] = set()


def _scope(name: str) -> str:
    SCOPES.add(name)
    return name


SCOPE_GATHER = _scope("gather")
SCOPE_INGEST = _scope("ingest")
SCOPE_CAST_PARAMS = _scope("cast_params")
SCOPE_LOSS = _scope("loss")
SCOPE_SKIP = _scope("skip")
SCOPE_RECOMPUTE = _scope("recompute")
SCOPE_EVA_SUMMARIES = _scope("eva/summaries")
SCOPE_EVA_LOCAL = _scope("eva/local")
SCOPE_EVA_REMOTE = _scope("eva/remote")
#: Gated DeltaNet: the causal depthwise convolution; the delta rule
#: (normalisation of q and k, gates, chunk products, the state's
#: scan); the gated norm on the way out
SCOPE_GDN_CONV = _scope("gdn/conv")
SCOPE_GDN_RULE = _scope("gdn/rule")
SCOPE_GDN_GATE_NORM = _scope("gdn/gate_norm")
#: causal attention: scores, softmax, weighted sums (no projection)
SCOPE_ATTN_CORE = _scope("attn/core")
#: the same of a layer that has a window of keys
SCOPE_ATTN_WINDOW = _scope("attn/window")
#: mixture of experts: router (product, softmax, top-k); dispatch
#: (sort, gather, combine); the grouped products over the held
#: experts; the shared expert
SCOPE_MOE_ROUTER = _scope("moe/router")
SCOPE_MOE_DISPATCH = _scope("moe/dispatch")
SCOPE_MOE_EXPERTS = _scope("moe/experts")
SCOPE_MOE_SHARED = _scope("moe/shared")
#: the head's product, loss and error of ONE block of positions
SCOPE_LOSS_BLOCK = _scope("loss/block")


DYNAMIC_FAMILIES = (
    "fused.<kind>_submit",
    "fused.first_<kind>_submit",
    "fused.first_<kind>_submit_seconds",
    "fused.<kind>_wall_seconds",
    "fused.<kind>_images",
    "<unit>.run",
    "init.<unit>",
    "fleet.model.<name>.requests",
    "fleet.model.<name>.errors",
    "fleet.model.<name>.shed",
    "fleet.model.<name>.mirrored",
    "fleet.model.<name>.request_seconds",
    "fleet.replica.<i>.health_score",
    "fleet.replica.<i>.hedge_wins",
    "online.model.<name>.buffer_rows",
    "online.model.<name>.steps",
    "online.model.<name>.gate_state",
    "arbiter.pool.<pool>.resident_bytes",
)


def known(name: str) -> bool:
    """Is ``name`` declared in any telemetry namespace?"""
    return name in EVENTS or name in COUNTERS or name in GAUGES \
        or name in HISTOGRAMS or name in SPANS or name in SCOPES


def all_names() -> frozenset:
    return frozenset(EVENTS | COUNTERS | GAUGES | HISTOGRAMS | SPANS)
