"""The central registry of ``VELES_*`` environment knobs.

Every environment variable this framework reads is declared HERE —
name, default, parser, and a one-line doc — and nowhere else.  The
declarations serve three consumers:

- **veleslint's env-registry rule** (veles_tpu/analysis): any
  ``os.environ`` read of a ``VELES_*`` name that is not declared here
  is a lint finding, so a typo'd knob (read forever, set never) can't
  ship;
- **docs/guide.md**: the knob table in the guide is GENERATED from
  this module (``python scripts/veleslint.py --sync-docs``) and the
  same lint rule fails when the table drifts out of sync;
- **call sites**, which may read through ``get(name)`` for parsed
  values but are equally free to keep their existing
  ``os.environ.get(...)`` reads — declaration, not routing, is the
  contract.

Parsers: ``flag`` knobs are armed by any non-empty value except
``"0"`` (matching the scattered ``== "1"`` / truthiness idioms the
call sites actually use); the rest parse with the declared type and
fall back to the default on a malformed value rather than raising —
an env typo must degrade, not take down a run.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional


def flag(raw: str) -> bool:
    """The repo's env-flag convention: set-and-not-"0" means on."""
    return bool(raw) and raw != "0"


class Knob:
    """One declared environment knob."""

    __slots__ = ("name", "default", "parser", "doc")

    def __init__(self, name: str, default: Any,
                 parser: Callable[[str], Any], doc: str) -> None:
        self.name = name
        self.default = default
        self.parser = parser
        self.doc = doc

    @property
    def type_name(self) -> str:
        return self.parser.__name__

    def read(self, environ: Optional[Dict[str, str]] = None) -> Any:
        env = os.environ if environ is None else environ
        raw = env.get(self.name)
        if raw is None or raw == "":
            return self.default
        try:
            return self.parser(raw)
        except (TypeError, ValueError):
            return self.default

    def __repr__(self) -> str:
        return f"Knob({self.name}={self.default!r})"


#: every declared knob, by name — the single source of truth
KNOBS: Dict[str, Knob] = {}


def _knob(name: str, default: Any, parser: Callable[[str], Any],
          doc: str) -> str:
    assert name.startswith("VELES_"), name
    assert name not in KNOBS, f"duplicate knob {name}"
    KNOBS[name] = Knob(name, default, parser, doc)
    return name


# -- robustness / supervision (Faultline, Phoenix) ---------------------

FAULTS = _knob(
    "VELES_FAULTS", "", str,
    "Arm Faultline injection points: `point[@qual=v[&qual=v...]]`, "
    "comma-separated; inherited by child processes (faults.py).")
FAULTS_SEED = _knob(
    "VELES_FAULTS_SEED", 0, int,
    "Seed for the deterministic garbage/rng of injected faults.")
PREEMPT_GRACE = _knob(
    "VELES_PREEMPT_GRACE", 25.0, float,
    "Seconds a graceful stop may take before the watchdog "
    "hard-snapshots and exits 14.")
PREEMPT_DISABLE = _knob(
    "VELES_PREEMPT_DISABLE", False, flag,
    "Opt this process out of SIGTERM/SIGINT graceful-stop handlers "
    "(set for GA evaluator children).")
SUPERVISE_ATTEMPT = _knob(
    "VELES_SUPERVISE_ATTEMPT", 0, int,
    "Exported by the supervisor to each child: 0 first launch, "
    "incrementing per restart (fault qualifiers target one attempt).")
SUPERVISE_MAX_CRASHES = _knob(
    "VELES_SUPERVISE_MAX_CRASHES", 5, int,
    "Genuine crashes inside the crash window before --supervise "
    "gives up loudly.")
SUPERVISE_CRASH_WINDOW = _knob(
    "VELES_SUPERVISE_CRASH_WINDOW", 300.0, float,
    "Seconds of sliding window the supervisor counts crashes in.")
RESUME_MANIFEST = _knob(
    "VELES_RESUME_MANIFEST", "", str,
    "Extra path every snapshot/checkpoint writer merge-updates the "
    "resume manifest at (the supervisor exports it).")

# -- multihost ---------------------------------------------------------

MULTIHOST_HEARTBEAT = _knob(
    "VELES_MULTIHOST_HEARTBEAT", 2.0, float,
    "Seconds between KV-store liveness heartbeats of a --multihost "
    "peer.")
MULTIHOST_DEADLINE = _knob(
    "VELES_MULTIHOST_DEADLINE", 15.0, float,
    "Seconds without a peer heartbeat before the watchdog declares "
    "peer death (final snapshot + exit 13).")
MULTIHOST_ALLOW_SOLO = _knob(
    "VELES_MULTIHOST_ALLOW_SOLO", False, flag,
    "Accept single-process semantics when "
    "jax.distributed.initialize() refuses a --multihost launch.")

# -- genetic search ----------------------------------------------------

GA_GENERATION = _knob(
    "VELES_GA_GENERATION", 0, int,
    "Exported by the GA parent so evaluator jobs and fault "
    "qualifiers (`@gen=N`) can target one generation.")
HEARTBEAT_EVERY = _knob(
    "VELES_HEARTBEAT_EVERY", 5.0, float,
    "Seconds between serve-mode evaluator heartbeat lines "
    "(0 disables).")
TPU_GA_HBM_BUDGET = _knob(
    "VELES_TPU_GA_HBM_BUDGET", 8 << 30, int,
    "LEGACY fallback (superseded by $VELES_HBM_BUDGET): HBM byte "
    "budget for population-batched cohort sizing when the device "
    "reports no bytes_limit.")

# -- online serving (Hive) ---------------------------------------------

SERVE_MAX_WAIT_MS = _knob(
    "VELES_SERVE_MAX_WAIT_MS", 5.0, float,
    "Longest a queued serving request may wait for co-batchable "
    "traffic before its micro-batch dispatches anyway (the "
    "latency/throughput tradeoff knob of veles_tpu/serve).")
SERVE_MAX_BATCH = _knob(
    "VELES_SERVE_MAX_BATCH", 64, int,
    "Rows per serving micro-batch: the batcher flushes as soon as "
    "this many rows coalesce (also the ONE fixed dispatch shape — "
    "zero steady-state recompiles).")
HBM_BUDGET = _knob(
    "VELES_HBM_BUDGET", 0, int,
    "Unified PER-DEVICE HBM byte budget of the process-wide arbiter "
    "(engine/core.py charges training, GA cohorts, and serving "
    "against ONE ledger): non-zero overrides the device's probed "
    "bytes_limit and the legacy per-subsystem fallbacks "
    "($VELES_SERVE_HBM_BUDGET, $VELES_TPU_GA_HBM_BUDGET); 0 keeps "
    "probe-then-fallback.")
SERVE_HBM_BUDGET = _knob(
    "VELES_SERVE_HBM_BUDGET", 8 << 30, int,
    "LEGACY fallback (superseded by $VELES_HBM_BUDGET): HBM byte "
    "budget for resident serving models when the device reports no "
    "bytes_limit; over budget the LRU model spills to host.")
SERVE_MESH = _knob(
    "VELES_SERVE_MESH", 0, int,
    "Devices a hive replica owns (the Prism arm of --serve-models): "
    ">1 binds an N-device mesh instead of a single device, so the "
    "fleet topology becomes replicas x mesh and residency budgets "
    "are charged per device (0/1 keeps the single-device replica).")
SERVE_MESH_SHARD = _knob(
    "VELES_SERVE_MESH_SHARD", "auto", str,
    "Shard the stacked member axis of a served ensemble over the "
    "replica's mesh (P/N members per device, replicated request "
    "rows): `auto` shards only when the model exceeds ONE device's "
    "residency budget but fits sharded — the over-budget placement "
    "becomes member-sharded-RESIDENT instead of LRU spill — "
    "`always` shards every model on a mesh replica, `never`/`0` "
    "keeps the replicated placement.")
SERVE_ADAPTIVE_WAIT = _knob(
    "VELES_SERVE_ADAPTIVE_WAIT", True, flag,
    "Let the serving micro-batcher track the windowed arrival rate "
    "(the Sentinel delta-quantile estimator) and adapt its flush "
    "wait: stretch past the static deadline only while the cadence "
    "predicts the batch fills, collapse a stalled stretch back to "
    "it.  Strictly additive — no window flushes before the static "
    "$VELES_SERVE_MAX_WAIT_MS deadline; off disables stretching.")
SERVE_WAIT_STRETCH = _knob(
    "VELES_SERVE_WAIT_STRETCH", 2.0, float,
    "Upper bound of the adaptive batching wait as a multiple of "
    "$VELES_SERVE_MAX_WAIT_MS: the oldest queued request never "
    "waits longer than stretch x the static window even when "
    "arrivals keep trickling in.  2x keeps the stretched tail "
    "inside ~1.1x the static p99 on a busy box; raise it when "
    "batch fill matters more than tail latency.")

# -- fleet serving (Swarm) ---------------------------------------------

FLEET_SLO_P99_MS = _knob(
    "VELES_FLEET_SLO_P99_MS", 0.0, float,
    "Fleet admission-control SLO target: when a request's estimated "
    "completion (queue depth x observed per-dispatch time + batching "
    "window) would exceed this many milliseconds on EVERY candidate "
    "replica, the router sheds it with an explicit `overloaded` "
    "response instead of letting p99 run away (0 disables shedding).")
FLEET_MAX_INFLIGHT = _knob(
    "VELES_FLEET_MAX_INFLIGHT", 64, int,
    "Hard per-replica bound on router-side in-flight requests (the "
    "bounded router queue); a request that finds every candidate "
    "replica at the bound is shed `overloaded`.")
FLEET_HEARTBEAT_DEADLINE = _knob(
    "VELES_FLEET_HEARTBEAT_DEADLINE", 30.0, float,
    "Seconds of replica stdout silence (no heartbeat, no response) "
    "before the fleet monitor declares the replica hung, kills it, "
    "and respawns (0 disables).")
FLEET_CANARY_FRACTION = _knob(
    "VELES_FLEET_CANARY_FRACTION", 0.1, float,
    "Default traffic fraction mirrored to a `canary-of:NAME` model "
    "when its registration does not carry an explicit split.")
FLEET_RESPAWN_BACKOFF = _knob(
    "VELES_FLEET_RESPAWN_BACKOFF", 0.5, float,
    "Initial seconds the fleet monitor backs off before respawning a "
    "dead replica (doubles per consecutive death, capped at 30s).")

# -- elastic fleet (Gauntlet) ------------------------------------------

FLEET_SCALE_MIN = _knob(
    "VELES_FLEET_SCALE_MIN", 1, int,
    "Floor of the elastic fleet's replica count: the scale "
    "controller never retires below this many replicas.")
FLEET_SCALE_MAX = _knob(
    "VELES_FLEET_SCALE_MAX", 4, int,
    "Ceiling of the elastic fleet's replica count: once the fleet is "
    "at the ceiling, sustained pressure engages the graceful-"
    "degradation ladder instead of spawning.")
FLEET_SCALE_UP_MS = _knob(
    "VELES_FLEET_SCALE_UP_MS", 200.0, float,
    "Scale-up pressure threshold: when the BEST candidate replica's "
    "estimated completion (queue depth x observed dispatch cadence) "
    "stays above this many milliseconds for "
    "$VELES_FLEET_SCALE_UP_SUSTAIN seconds, the controller spawns a "
    "replica into a warm install dir.")
FLEET_SCALE_DOWN_MS = _knob(
    "VELES_FLEET_SCALE_DOWN_MS", 25.0, float,
    "Scale-down idle threshold: when fleet pressure stays below this "
    "many milliseconds for $VELES_FLEET_SCALE_DOWN_SUSTAIN seconds, "
    "the controller retires the youngest replica (drain its router "
    "queue, re-place its exclusive tail models, then SIGTERM).")
FLEET_SCALE_UP_SUSTAIN = _knob(
    "VELES_FLEET_SCALE_UP_SUSTAIN", 1.0, float,
    "Seconds the scale-up pressure must be SUSTAINED before the "
    "controller acts (the hysteresis half that keeps one burst from "
    "spawning a replica).")
FLEET_SCALE_DOWN_SUSTAIN = _knob(
    "VELES_FLEET_SCALE_DOWN_SUSTAIN", 3.0, float,
    "Seconds the fleet must stay idle below the scale-down threshold "
    "before the controller retires a replica (longer than the up "
    "sustain on purpose: spawning is slow, flapping is worse).")
FLEET_SCALE_COOLDOWN = _knob(
    "VELES_FLEET_SCALE_COOLDOWN", 5.0, float,
    "Seconds between ANY two scale/degradation actions — the "
    "controller's refractory period, which also keeps a respawn-"
    "backoff storm (fleet.replica_flap) from compounding into a "
    "spawn hot-loop.")
FLEET_SCALE_INTERVAL = _knob(
    "VELES_FLEET_SCALE_INTERVAL", 0.25, float,
    "Seconds between autoscaler signal polls (the controller "
    "observes fleet pressure on this cadence).")

# -- traffic replay (Gauntlet) -----------------------------------------

TRAFFIC_SEED = _knob(
    "VELES_TRAFFIC_SEED", 0, int,
    "Seed of the open-loop traffic generator: the whole arrival "
    "schedule (times, model mix, burst placement) is a pure function "
    "of the spec + this seed, so a logged trace replays bit-"
    "identically.")
TRAFFIC_DURATION_S = _knob(
    "VELES_TRAFFIC_DURATION_S", 60.0, float,
    "Length of the generated production day in seconds.")
TRAFFIC_PEAK_RPS = _knob(
    "VELES_TRAFFIC_PEAK_RPS", 60.0, float,
    "Arrival rate at the top of the diurnal sine (requests/second); "
    "the trough is peak / $VELES_TRAFFIC_SWING.")
TRAFFIC_SWING = _knob(
    "VELES_TRAFFIC_SWING", 10.0, float,
    "Peak-to-trough ratio of the diurnal arrival curve (>= 10x is "
    "the production-day acceptance bar).")
TRAFFIC_BURST_MULT = _knob(
    "VELES_TRAFFIC_BURST_MULT", 2.0, float,
    "Rate multiplier inside a Poisson-placed burst window (bursts "
    "ride ON TOP of the diurnal curve).")
TRAFFIC_ZIPF_S = _knob(
    "VELES_TRAFFIC_ZIPF_S", 1.1, float,
    "Zipf exponent of the multi-model popularity skew: model rank k "
    "draws traffic proportional to 1/k^s — the long tail that makes "
    "shed-tail-before-hot-prefix degradation mean something.")

# -- online learning (Evergreen) ---------------------------------------

ONLINE = _knob(
    "VELES_ONLINE", False, flag,
    "Arm the Evergreen online-learning tier inside a hive "
    "(--serve-models): tapped live traffic fills a replay buffer, a "
    "scavenger trainer fine-tunes shadow params in serving idle gaps, "
    "and the promotion gate hot-swaps them HBM-to-HBM when the "
    "held-out slice improves past $VELES_ONLINE_PROMOTE_MARGIN.")
ONLINE_TAP_FRAC = _knob(
    "VELES_ONLINE_TAP_FRAC", 1.0, float,
    "Deterministic fraction of admitted hive requests the online tap "
    "mirrors into the replay buffer (an error-diffusion accumulator, "
    "not a coin flip — the tapped subsequence is reproducible).")
ONLINE_BUFFER_ROWS = _knob(
    "VELES_ONLINE_BUFFER_ROWS", 4096, int,
    "Replay-buffer capacity in sample rows per learning model "
    "(reservoir-sampled once full); rows store uint8-quantized when "
    "the model's ingest codec round-trips them, stacking the PR 2 4x "
    "on the buffer's residency charge.")
ONLINE_HOLDOUT_EVERY = _knob(
    "VELES_ONLINE_HOLDOUT_EVERY", 8, int,
    "Every Nth labeled tapped request lands in the held-out slice "
    "the promotion gate scores (never trained on).")
ONLINE_MICRO_BATCH = _knob(
    "VELES_ONLINE_MICRO_BATCH", 32, int,
    "Rows per scavenged fine-tune micro-step — the ONE fixed train "
    "dispatch shape (compiles once, like the serving micro-batch).")
ONLINE_MIN_STEPS = _knob(
    "VELES_ONLINE_MIN_STEPS", 8, int,
    "Fine-tune steps between promotion-gate evaluations (and before "
    "the first one).")
ONLINE_PROMOTE_MARGIN = _knob(
    "VELES_ONLINE_PROMOTE_MARGIN", 1.0, float,
    "Held-out error-pct margin the shadow must beat the incumbent by "
    "before the gate promotes it (the anti-noise hysteresis); a "
    "shadow WORSE by this margin after a full gate round rolls back "
    "to the incumbent's params and journals.")
ONLINE_IDLE_MS = _knob(
    "VELES_ONLINE_IDLE_MS", 2.0, float,
    "Milliseconds every serving batcher must have been idle (empty "
    "queue, nothing in flight) before the scavenger fires a "
    "fine-tune step — serving latency owns the chip, learning eats "
    "the gaps.")
ONLINE_SLO_P99_MS = _knob(
    "VELES_ONLINE_SLO_P99_MS", 0.0, float,
    "SLO headroom gate for the scavenger (the PR 11 admission-"
    "estimator move applied to learning): when the EMA fine-tune "
    "step cost exceeds this many milliseconds the step is skipped "
    "even on an idle chip — a step that long would blow the p99 of "
    "a request arriving under it (0 disables the check).")
ONLINE_LR_SCALE = _knob(
    "VELES_ONLINE_LR_SCALE", 0.1, float,
    "Fine-tune learning-rate scale applied to each gradient unit's "
    "packaged training rate (online steps nudge a converged model; "
    "full training rates overshoot).")
ONLINE_DUTY = _knob(
    "VELES_ONLINE_DUTY", 0.5, float,
    "Ceiling on the scavenger's duty cycle (fraction of wall it may "
    "spend stepping, 0..1): after each step it rests at least "
    "cost*(1-duty)/duty, so even an all-idle chip keeps host cores "
    "and GIL mostly free for the serving threads — the lever behind "
    "the <=1.2x learner-on p99 bar.")

# -- gray-failure defense (Sentinel) -----------------------------------

FLEET_DEADLINE_MS = _knob(
    "VELES_FLEET_DEADLINE_MS", 10000.0, float,
    "Default per-request deadline the fleet router stamps onto every "
    "request; it rides the JSONL protocol end-to-end so a hive "
    "batcher drops already-expired rows before dispatch and a waiter "
    "never burns more than this against a wedged replica.")
FLEET_HEDGE_MIN_MS = _knob(
    "VELES_FLEET_HEDGE_MIN_MS", 25.0, float,
    "Floor of the adaptive hedge threshold: a request older than "
    "max(this, the model's measured p95 latency) is hedged on a "
    "second replica and the first answer wins.")
FLEET_HEDGE_BUDGET = _knob(
    "VELES_FLEET_HEDGE_BUDGET", 0.05, float,
    "Cap on hedged requests as a fraction of admitted fleet traffic "
    "(0 disables hedging) — hedges fight tail latency, the budget "
    "keeps them from melting an already-overloaded fleet.")
FLEET_EJECT_THRESHOLD = _knob(
    "VELES_FLEET_EJECT_THRESHOLD", 3.0, float,
    "Health-score level (decaying weighted strikes: deadline misses, "
    "deaths, integrity failures, hedge losses, latency outliers) at "
    "which the sentinel ejects a replica from routing; ejection is "
    "capped at N-1 replicas so the fleet degrades, never "
    "self-destructs.")
FLEET_PROBE_OK = _knob(
    "VELES_FLEET_PROBE_OK", 3, int,
    "Consecutive clean synthetic probes an ejected replica must "
    "answer before the sentinel reinstates it into routing.")
FLEET_PROBE_INTERVAL = _knob(
    "VELES_FLEET_PROBE_INTERVAL", 0.5, float,
    "Initial seconds between synthetic canary probes of an ejected "
    "replica (a failed probe doubles it, capped at 10s; a clean one "
    "resets it).")

# -- observability -----------------------------------------------------

LOCK_WITNESS = _knob(
    "VELES_LOCK_WITNESS", False, flag,
    "Arm the Lockstep lock-order witness: locks created through "
    "analysis/witness.py record (holder -> acquired) pairs, flushed "
    "as lockwitness-<pid>.json next to the Sightline snapshot; a "
    "tier-1 test asserts every observed edge is declared in "
    "analysis/lock_order.json.  Off (the default) the factories "
    "return bare threading primitives — zero overhead.")
METRICS_DIR = _knob(
    "VELES_METRICS_DIR", "", str,
    "Arm Sightline persistence: journal-<pid>.jsonl + atomic "
    "metrics-<pid>.json snapshots under this directory; inherited by "
    "children.")
PLOTS_DIR = _knob(
    "VELES_PLOTS_DIR", "plots", str,
    "Output directory of the graphics server's rendered plot "
    "artifacts.")
TRACE_SAMPLE = _knob(
    "VELES_TRACE_SAMPLE", 1.0, float,
    "Flightline head-based trace sampling rate in [0, 1]: the "
    "fraction of fleet requests minted with the sampled bit set "
    "(error diffusion, so the rate is exact, not a coin flip).  A "
    "sampled request carries trace/span/parent wire keys on every "
    "hop and journals trace.* events for cross-process assembly; 0 "
    "disables causal tracing.")
FLIGHTREC_CAP = _knob(
    "VELES_FLIGHTREC_CAP", 512, int,
    "Entries the per-process flight-recorder ring retains (recent "
    "spans/events, in memory, always armed).  The ring dumps to "
    "flightrec-<pid>-<n>-<reason>.json in the metrics dir on "
    "SIGTERM, injected SIGKILL, sentinel ejection, and promotion-"
    "gate verdicts, so every ejection/rollback ships with the trace "
    "tail that explains it.")

# -- mesh execution (Lattice) ------------------------------------------

MESH_SHARD_DATA = _knob(
    "VELES_MESH_SHARD_DATA", "auto", str,
    "Row-shard the HBM-resident dataset over the device mesh (each "
    "device holds 1/N of the rows): `auto` shards only when the "
    "dataset exceeds ONE device's residency budget but fits sharded "
    "(so a dataset N x one chip's budget goes resident instead of "
    "degrading to host streaming), `always` shards any mesh-resident "
    "dataset, `never`/`0` keeps the replicated placement.")
MESH_SHARD_MEMBERS = _knob(
    "VELES_MESH_SHARD_MEMBERS", "auto", str,
    "Shard the stacked member axis of population-batched GA cohorts "
    "over the mesh (P/N members per device, raising the HBM cohort "
    "cap by the device count): `auto`/`always` shard whenever the "
    "engine is handed a mesh, `never`/`0` keeps single-device "
    "stacking.")

# -- device / kernel tuning --------------------------------------------

MAX_RESIDENT_BYTES = _knob(
    "VELES_MAX_RESIDENT_BYTES", 8 << 30, int,
    "PER-DEVICE HBM byte budget for device-resident datasets; over "
    "budget degrades to host streaming (on a mesh with "
    "$VELES_MESH_SHARD_DATA, a dataset over one device's budget "
    "first tries the row-sharded placement at total/N per device).")
SOM_FUSED = _knob(
    "VELES_SOM_FUSED", True, flag,
    "Train Kohonen SOM workflows as fused donated epoch scans on jax "
    "devices (ONE dispatch per superstep group, schedule applied per "
    "step inside the trace); `0` falls back to the eager "
    "per-minibatch dispatch loop.")
SOM_SUPERSTEP = _knob(
    "VELES_SOM_SUPERSTEP", 0, int,
    "Minibatches per fused SOM dispatch group (the SOM loader's "
    "superstep); 0 groups the WHOLE class per firing — one dispatch "
    "per epoch.")
TPU_SYNTH_CACHE = _knob(
    "VELES_TPU_SYNTH_CACHE", False, flag,
    "Cache large synthetic datasets in-process across loader "
    "constructions (ablation runs).")


def names() -> frozenset:
    """Every declared knob name (the env-registry rule's whitelist)."""
    return frozenset(KNOBS)


def get(name: str, environ: Optional[Dict[str, str]] = None) -> Any:
    """The parsed value of a declared knob (default when unset or
    malformed).  Raises KeyError on an undeclared name — reading an
    unregistered knob is exactly the bug the registry exists to
    catch."""
    return KNOBS[name].read(environ)


def render_table() -> str:
    """The guide's knob table, generated (markdown, sorted by name).
    ``scripts/veleslint.py --sync-docs`` writes it between the
    ``veleslint:knobs`` markers in docs/guide.md and the env-registry
    rule fails when the checked-in copy drifts."""
    rows = ["| Knob | Default | Type | Meaning |",
            "| --- | --- | --- | --- |"]
    for name in sorted(KNOBS):
        k = KNOBS[name]
        default = ("on" if k.default else "off") \
            if k.parser is flag else \
            ("(unset)" if k.default == "" else repr(k.default))
        rows.append(f"| `{name}` | {default} | {k.type_name} | "
                    f"{k.doc} |")
    return "\n".join(rows) + "\n"
