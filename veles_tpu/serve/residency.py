"""Multi-model HBM residency: budget accounting + LRU spill-to-host.

Serving keeps every model's stacked f32 member params resident so a
request costs one dispatch and zero uploads — but HBM is finite and
the fleet of models is not.  This manager extends the residency-budget
idea of the uint8 ingest work (loader/quantize.py shrank DATASET
residency 4x against ``$VELES_MAX_RESIDENT_BYTES``) to MODELS: each
model's device cost is known exactly before upload
(``batching.stacked_param_bytes``), the budget is the device's
reported ``bytes_limit`` or ``$VELES_SERVE_HBM_BUDGET``, and when
admitting a model would overflow it, the least-recently-USED resident
model spills — its engine keeps the compiled dispatchers and only
drops the stacked params (the manager holds the immutable host
copies), so a later restore pays one H2D upload, NOT a recompile.

Every transition journals (``serve.model_loaded`` /
``serve.model_spilled`` / ``serve.model_restored`` /
``serve.model_sharded_resident``) and the ``serve.models_resident`` /
``serve.resident_bytes`` / ``serve.resident_bytes_per_device`` gauges
track the live set.

On a mesh replica (``--serve-models --mesh N``, the Prism arm) the
budget stays PER DEVICE and a model's charge depends on its placement:
replicated params cost ``param_bytes`` on every device, while a
member-sharded model (``$VELES_SERVE_MESH_SHARD``) costs
``padded/N`` per device — so a model over ONE device's budget but
under ``total/N`` goes member-sharded-RESIDENT instead of LRU
spilling, and capacity scales with the mesh instead of replicating it
(the Lattice move applied to serving).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

from veles_tpu import events, knobs, telemetry
from veles_tpu.analysis import witness
from veles_tpu.logger import Logger

#: the arbiter's ledger pools — every byte resident on the device is
#: charged to exactly one: served model stacks (``serve``), training /
#: online-shadow state (``train``), GA cohort stacks (``cohort``), and
#: everything else (replay buffers, probes: ``scratch``)
POOLS = ("serve", "train", "cohort", "scratch")


class HostedModel:
    """One servable model: the pure forward chain, the immutable host
    member params, and (when resident) the vmapped engine."""

    def __init__(self, name: str, forwards: List[Any],
                 member_params: List[Dict[str, Dict[str, Any]]],
                 meta: Optional[Dict[str, Any]] = None,
                 sample_shape=None) -> None:
        from veles_tpu.ops import batching
        self.name = name
        self.forwards = list(forwards)
        self.member_params = member_params
        self.meta = dict(meta or {})
        #: per-sample input shape (from the template loader) — lets
        #: the batcher bounce mis-shaped requests at submit time
        self.sample_shape = tuple(sample_shape) if sample_shape \
            else None
        self.engine = None   # EnsembleEvalEngine once first admitted
        self.param_bytes = batching.stacked_param_bytes(member_params)
        self.last_used = 0.0

    @property
    def resident(self) -> bool:
        return self.engine is not None and self.engine.resident


class ResidencyManager(Logger):
    """Admit models under the HBM budget; spill the LRU one over it."""

    def __init__(self, device: Any,
                 budget_bytes: Optional[int] = None,
                 max_batch: int = 64,
                 max_wait_s: float = 0.005) -> None:
        self.device = device
        self.budget_bytes = int(budget_bytes) if budget_bytes \
            else self._device_budget(device)
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self.models: Dict[str, HostedModel] = {}
        #: serializes registry state, spill victim selection, and the
        #: online promotion's param swap (PR 14): the swap and the
        #: spill decision racing unlocked is exactly how a dispatch
        #: could lose its params mid-flight.  Blocking work (engine
        #: drain, compile, H2D upload) stays OUTSIDE this lock.
        self._lock = witness.lock("residency.state")
        #: side charges against the budget that are not stacked model
        #: params — name -> (bytes, pool): the online tier's shadow
        #: params + replay buffers, and (PR 18) every ExecutionCore's
        #: footprint, so training, GA cohorts, and serving draw on ONE
        #: ledger instead of per-subsystem budget fictions
        self.reserved: Dict[str, Tuple[int, str]] = {}
        #: devices the replica's device owns (1 off-mesh): budgets are
        #: per device, so a member-sharded model charges padded/N here
        self.n_devices = int(getattr(device, "n_devices", 1))

    @staticmethod
    def _device_budget(device: Any) -> int:
        """The device's reported HBM limit, else the declared knob
        default — the same accounting the GA cohort sizing uses.
        PER DEVICE by construction: on a mesh device the probed
        ``jax_device`` is one chip, and a replicated model costs its
        full ``param_bytes`` on EVERY chip, so charging one device's
        budget against one copy's bytes stays honest (the Lattice
        convention — capacity multiplies only for SHARDED placements,
        and served model params replicate)."""
        unified = int(knobs.get(knobs.HBM_BUDGET))
        if unified:
            # the set-wins unified arbiter budget: one number for
            # training, GA cohorts, and serving alike
            return unified
        jdev = getattr(device, "jax_device", None)
        if jdev is not None:
            from veles_tpu.backends import device_bytes_limit
            limit = device_bytes_limit(jdev)
            if limit:
                # half held back for activations + micro-batches
                return limit // 2
        return int(knobs.get(knobs.SERVE_HBM_BUDGET))

    # -- registry ------------------------------------------------------

    def register(self, model: HostedModel) -> None:
        with self._lock:
            if model.name in self.models:
                raise ValueError(
                    f"duplicate model name {model.name!r}")
            self.models[model.name] = model

    def reserve(self, name: str, nbytes: int,
                pool: str = "scratch") -> None:
        """Charge (or re-charge) a named side allocation against the
        budget, tagged with its ledger ``pool`` — the online tier's
        shadow params and replay-buffer bytes stack on the model
        residency cost exactly like the uint8 ingest charge stacks on
        the dataset budget; since PR 18 every ExecutionCore charges
        its params/opt footprint here too, making this THE process
        HBM ledger."""
        if pool not in POOLS:
            raise ValueError(f"unknown arbiter pool {pool!r} "
                             f"(declared: {POOLS})")
        with self._lock:
            self.reserved[name] = (int(nbytes), pool)
        self._update_gauges()

    def release(self, name: str) -> None:
        """Drop a named charge (engine/core release path); unknown
        names are a no-op — a release must never fail a teardown."""
        with self._lock:
            self.reserved.pop(name, None)
        self._update_gauges()

    def ledger(self) -> Dict[str, int]:
        """Per-pool resident bytes — the arbiter's public read: the
        ``serve`` pool carries the resident stacked models plus any
        serve-tagged reserves; the other pools are pure reserve
        sums.  Rendered by /api/metrics and the obs fleet view so an
        over-budget reserve is visible BEFORE it OOMs."""
        with self._lock:
            reserved = list(self.reserved.values())
            model_bytes = sum(self._charge(m)
                              for m in self.models.values()
                              if m.resident)
        out = {pool: 0 for pool in POOLS}
        out["serve"] = model_bytes
        for nbytes, pool in reserved:
            out[pool] += nbytes
        return out

    # -- placement / charging ------------------------------------------

    def _shard_members(self, m: HostedModel) -> bool:
        """The Prism placement decision for ``m``'s stacked member
        axis, mirroring ``$VELES_MESH_SHARD_DATA``'s surface: `always`
        shards every model on a mesh replica, `never` keeps the
        replicated placement, and `auto` shards exactly the models
        that overflow ONE device's budget — the case that was an LRU
        spill (or a loud over-budget admit) before the mesh existed.
        An already-built engine's placement is fixed (restore lands on
        the same sharding, so dispatchers never retrace)."""
        live = getattr(m.engine, "member_sharded", None)
        if live is not None:
            return bool(live)
        mesh = getattr(self.device, "mesh", None)
        if mesh is None or self.n_devices < 2:
            return False
        from veles_tpu.parallel.mesh import shard_mode
        mode = shard_mode(knobs.get(knobs.SERVE_MESH_SHARD))
        if mode == "never":
            return False
        if mode == "always":
            return True
        return m.param_bytes > self.budget_bytes

    def _charge(self, m: HostedModel) -> int:
        """``m``'s residency cost PER DEVICE under its (decided or
        live) placement: the full stack when replicated, padded/N
        when member-sharded."""
        live = getattr(m.engine, "param_bytes_per_device", None)
        if live is not None:
            return int(live)
        if self._shard_members(m):
            p = len(m.member_params)
            p_pad = -(-p // self.n_devices) * self.n_devices
            return (m.param_bytes // p * p_pad) // self.n_devices
        return m.param_bytes

    def resident_bytes(self) -> int:
        # PER-DEVICE charge (identical to the total off-mesh).
        # snapshot the dicts first: gauges read this from the main
        # loop while the scavenger re-charges its buffer reservation
        return sum(self._charge(m) for m in list(self.models.values())
                   if m.resident) + sum(
            v[0] for v in tuple(self.reserved.values()))

    def resident_count(self) -> int:
        return sum(1 for m in list(self.models.values())
                   if m.resident)

    def _update_gauges(self) -> None:
        led = self.ledger()
        telemetry.gauge(events.GAUGE_ARBITER_BUDGET_BYTES).set(
            self.budget_bytes)
        telemetry.gauge(events.GAUGE_ARBITER_RESIDENT_BYTES).set(
            sum(led.values()))
        for pool, nbytes in led.items():
            telemetry.gauge(
                f"arbiter.pool.{pool}.resident_bytes").set(nbytes)
        if not self.models:
            # a training-only process arbiter: publishing serve.*
            # gauges from it would pollute the obs fleet rows with a
            # phantom zero-model replica
            return
        telemetry.gauge(events.GAUGE_SERVE_MODELS_RESIDENT).set(
            self.resident_count())
        telemetry.gauge(events.GAUGE_SERVE_RESIDENT_BYTES).set(
            self.resident_bytes())
        telemetry.gauge(
            events.GAUGE_SERVE_RESIDENT_BYTES_PER_DEVICE).set(
            self.resident_bytes())
        telemetry.gauge(events.GAUGE_SERVE_MESH_DEVICES).set(
            self.n_devices)

    # -- admission -----------------------------------------------------

    def ensure(self, name: str):
        """The serving entry point: return ``name``'s ready engine,
        admitting (or restoring) it under the budget first.  Raises
        KeyError for an unregistered name.

        Victim SELECTION happens under the residency lock (so it can
        never race a promotion swap), but the spill itself — which
        drains the victim's in-flight dispatches — and the engine
        build/restore — compile + H2D — run outside it: blocking work
        under the registry lock is the batcher-stall class Lockstep
        exists to forbid."""
        wait_deadline = None
        while True:
            with self._lock:
                m = self.models[name]
                m.last_used = time.monotonic()
                if m.resident:
                    return m.engine
                victim, blocked = self._pick_victim(m)
            if victim is not None:
                self._spill(victim)
                continue
            if not blocked:
                break
            # over budget but every candidate is mid-flight: WAIT for
            # one to go quiet rather than admit over budget — the
            # busy window is normally microseconds (a request that
            # just resolved), and the budget invariant the LRU test
            # pins must survive it.  Under genuinely continuous
            # traffic the wait caps out and we admit over budget
            # (with the loud warning) instead of starving.
            now = time.monotonic()
            if wait_deadline is None:
                wait_deadline = now + 2.0
            if now >= wait_deadline:
                break
            time.sleep(0.002)
        if m.engine is None:
            from veles_tpu.ops.fused import EnsembleEvalEngine
            t0 = time.perf_counter()
            shard = self._shard_members(m)
            engine = EnsembleEvalEngine(m.forwards, m.member_params,
                                        self.device,
                                        shard_members=shard)
            engine.attach_batcher(self.max_batch, self.max_wait_s,
                                  label=name,
                                  sample_shape=m.sample_shape)
            with self._lock:
                if m.engine is None:
                    m.engine = engine
            telemetry.event(events.EV_SERVE_MODEL_LOADED, model=name,
                            members=m.engine.n_members,
                            param_bytes=m.param_bytes,
                            seconds=round(time.perf_counter() - t0, 4))
            if m.engine.member_sharded:
                telemetry.event(
                    events.EV_SERVE_MODEL_SHARDED, model=name,
                    devices=self.n_devices,
                    param_bytes=m.param_bytes,
                    per_device=m.engine.param_bytes_per_device)
                self.info(
                    "model %r member-sharded over %d devices: %.2f "
                    "MiB total, %.2f MiB/device — resident where a "
                    "single device would spill", name, self.n_devices,
                    m.param_bytes / (1 << 20),
                    m.engine.param_bytes_per_device / (1 << 20))
            self.info("model %r loaded: %d members, %.2f MiB stacked",
                      name, m.engine.n_members,
                      m.param_bytes / (1 << 20))
        elif not m.resident:
            t0 = time.perf_counter()
            m.engine.restore_params(m.member_params)
            telemetry.event(events.EV_SERVE_MODEL_RESTORED, model=name,
                            param_bytes=m.param_bytes,
                            seconds=round(time.perf_counter() - t0, 4))
            self.info("model %r restored from host spill (%.2f MiB)",
                      name, m.param_bytes / (1 << 20))
        self._update_gauges()
        return m.engine

    def _pick_victim(self, incoming: HostedModel) \
            -> tuple:
        """Called under the lock: ``(victim, blocked)`` — the least-
        recently-used resident model to spill for ``incoming`` (None
        when it already fits, or when nothing is safely evictable;
        ``blocked`` is True in the latter over-budget case).  BUSY
        engines — rows queued or a dispatch in flight — are never
        victims: spilling one would pull the stacked params out from
        under its flush thread mid-request (the PR 14 promotion/LRU
        race, pinned by tests/test_online.py).  A model that alone
        exceeds the budget is admitted anyway (with a loud warning) —
        refusing it would make the budget knob a denial-of-service on
        itself.  On a mesh replica the charge is taken AFTER the
        placement decision: a member-sharded model needs padded/N per
        device, so the over-one-device's-budget case stops being a
        spill (or a warning) and becomes a resident placement."""
        need = self._charge(incoming)
        if need > self.budget_bytes:
            self.warning(
                "model %r needs %d bytes/device, over the residency "
                "budget (%d) — admitting alone; consider raising "
                "$VELES_SERVE_HBM_BUDGET%s", incoming.name, need,
                self.budget_bytes,
                "" if self.n_devices > 1 else
                " or serving on a mesh (--mesh N)")
        if self.resident_bytes() + need <= self.budget_bytes:
            return None, False
        candidates = [m for m in self.models.values()
                      if m.resident and m is not incoming]
        victims = [m for m in candidates if not m.engine.busy]
        if not victims:
            return None, bool(candidates)
        return min(victims, key=lambda m: m.last_used), False

    def swap_params(self, name: str, stacked_params: Any):
        """The online promotion's atomic dispatcher swap: hand an
        already-device-resident stacked param pytree to ``name``'s
        serving engine, under the SAME lock spill decisions take — a
        concurrent ensure() either sees the model resident (and
        leaves it alone) or picks its victim after the swap landed.
        Returns the engine.  Raises RuntimeError when the model is
        not resident (a spilled model has nothing to swap into; the
        gate retries after the next restore)."""
        with self._lock:
            m = self.models[name]
            if m.engine is None or not m.resident:
                raise RuntimeError(
                    f"model {name!r} is not resident; cannot swap "
                    f"promoted params into a spilled engine")
            m.engine.adopt_stacked_params(stacked_params)
            m.last_used = time.monotonic()
            return m.engine

    def refresh_host_params(self, name: str,
                            member_params: List[Dict[str, Dict[
                                str, Any]]]) -> None:
        """Adopt new host member copies after a promotion (the
        spill/restore source of truth) — called OFF the swap path."""
        with self._lock:
            m = self.models[name]
            m.member_params = member_params

    def _spill(self, m: HostedModel) -> None:
        # outstanding requests first: the engine's queued micro-batches
        # must dispatch while the params are still on device
        m.engine.drain()
        m.engine.spill_params()
        telemetry.counter(events.CTR_SERVE_SPILLS).inc()
        telemetry.event(events.EV_SERVE_MODEL_SPILLED, model=m.name,
                        param_bytes=m.param_bytes)
        self.info("model %r spilled to host (LRU, freeing %.2f MiB)",
                  m.name, m.param_bytes / (1 << 20))
        self._update_gauges()

    def drain_all(self, timeout: float = 30.0) -> bool:
        """Drain every model's batcher (the SIGTERM path)."""
        ok = True
        for m in self.models.values():
            if m.engine is not None:
                ok = m.engine.drain(timeout) and ok
        return ok

    def close(self) -> None:
        for m in self.models.values():
            if m.engine is not None:
                m.engine.release()
                m.engine = None


# -- the process-wide arbiter ------------------------------------------
# ONE ResidencyManager per process is THE HBM arbiter: a hive installs
# its (model-hosting) manager at startup, while a training/GA process
# lazily gets a model-less one the first time an ExecutionCore charges
# its footprint.  Either way the ledger() pools and the arbiter.*
# gauges read the same single source of truth.

_process_arbiter: Optional[ResidencyManager] = None
_arbiter_lock = witness.lock("residency.arbiter")


def install_process_arbiter(manager: ResidencyManager) \
        -> ResidencyManager:
    """Make ``manager`` THE process arbiter (the hive calls this with
    its model-hosting manager before serving starts, so training
    charges land on the ledger the LRU spill reads)."""
    global _process_arbiter
    with _arbiter_lock:
        _process_arbiter = manager
    return manager


def process_arbiter(device: Any = None) -> ResidencyManager:
    """The process-wide HBM arbiter, created on first use when no
    hive installed one — a model-less manager whose reserve ledger
    still budgets and gauges every ExecutionCore's footprint."""
    global _process_arbiter
    with _arbiter_lock:
        if _process_arbiter is None:
            _process_arbiter = ResidencyManager(device)
        return _process_arbiter
