"""Fleet replica lifecycle: spawn, supervise, respawn.

One **replica** is one ``--serve-models`` Hive subprocess owned
through :class:`~veles_tpu.serve.client.HiveClient` — the proven
topology (hello line, JSONL, heartbeats) unchanged; what is new is
that N of them run side by side and a monitor thread keeps the set
healthy:

- each replica gets a **per-replica metrics child dir**
  (``<metrics_dir>/replica-<i>``) so its Sightline snapshots merge
  into one fleet view (``veles_tpu/obs.py fleet_rows``);
- **death detection** is the pool discipline: reader-thread EOF or a
  heartbeat deadline (any stdout line is proof of life).  A dead
  replica's pending waiters fail immediately with ``ReplicaDied``
  (the router retries them on a peer); the monitor respawns the
  replica with exponential backoff, reusing its install dir so the
  package unpack — and on a real chip the persistent XLA compile
  cache — is warm (the same warm-resume property the exit-14 /
  ``--supervise`` contract gives a single supervised hive);
- **per-dispatch time** is polled from each replica's live stats into
  an EMA — the signal the router's SLO admission control multiplies
  by queue depth.

:class:`PlacementPolicy` decides which models a replica should serve
*preferentially*: every replica is spawned with the full model set on
its command line (so any replica can LRU-load any model as a
fallback), and the placement controls routing affinity — hot models
(the declaration-order prefix that fits every replica's residency
budget, or an explicit set) are replicated across all replicas, the
long tail is partitioned greedily onto the least-filled replica.
"""

from __future__ import annotations

import os
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Set

from veles_tpu import events, knobs, telemetry
from veles_tpu.analysis import witness
from veles_tpu.logger import Logger
from veles_tpu.serve.client import HiveClient

#: a replica must SURVIVE this long past its hello for a death to
#: reset the crash streak — a flapping member (clean hello, dead
#: seconds later, forever) keeps escalating its respawn backoff
#: toward the 30s cap instead of spawn-storming at the base rate
STABLE_UPTIME_S = 10.0


class PlacementPolicy:
    """Model -> preferred replica set, under a per-replica budget.

    Declaration order is the hotness order (the operator lists the
    traffic-heavy models first, exactly like ``--serve-models`` admits
    eagerly in CLI order): models are replicated on ALL replicas while
    the running total fits every replica's residency budget; the first
    model that would overflow ends the replicated prefix and starts
    the partitioned long tail (greedy least-filled bin).  An explicit
    ``hot`` set overrides the prefix rule.

    Replicas are HETEROGENEOUS since the Prism arm: a ``--mesh N``
    replica advertises ``devices x device_budget`` in its hello, so
    ``assign`` takes an optional per-replica ``capacities`` list and
    places against REAL capacity — the replicated prefix must fit the
    SMALLEST replica, and the greedy tail lands on the replica with
    the most free bytes (not the least absolute fill, which would
    starve a big mesh replica next to an empty small one).
    """

    def __init__(self, budget_bytes: Optional[int] = None,
                 hot: Optional[Set[str]] = None) -> None:
        self.budget_bytes = int(budget_bytes) if budget_bytes \
            else int(knobs.get(knobs.SERVE_HBM_BUDGET))
        self.hot = set(hot) if hot is not None else None

    def assign(self, model_bytes: Dict[str, int],
               n_replicas: int,
               capacities: Optional[List[Optional[int]]] = None
               ) -> Dict[str, List[int]]:
        """{model: [replica indices]} — insertion order of
        ``model_bytes`` is the declaration order.  ``capacities`` is
        the per-replica byte capacity (hello ``devices x
        device_budget``); None entries (or no list) fall back to the
        policy's uniform ``budget_bytes``."""
        n = max(1, int(n_replicas))
        caps = [self.budget_bytes] * n
        if capacities:
            for i, c in enumerate(capacities[:n]):
                if c:
                    caps[i] = int(c)
        fill = [0] * n
        placement: Dict[str, List[int]] = {}
        replicating = True
        for name, nbytes in model_bytes.items():
            nbytes = int(nbytes)
            if self.hot is not None:
                is_hot = name in self.hot
            else:
                is_hot = replicating and all(
                    f + nbytes <= c for f, c in zip(fill, caps))
                if not is_hot:
                    replicating = False
            if is_hot:
                placement[name] = list(range(n))
                fill = [f + nbytes for f in fill]
            else:
                r = max(range(n),
                        key=lambda i: (caps[i] - fill[i], -i))
                placement[name] = [r]
                fill[r] += nbytes
        return placement


class Replica(Logger):
    """One Hive subprocess slot: spawn/respawn + load accounting."""

    def __init__(self, idx: int, models: Dict[str, str],
                 backend: str = "cpu",
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 hbm_budget: Optional[int] = None,
                 heartbeat_every: Optional[float] = None,
                 metrics_dir: Optional[str] = None,
                 cwd: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 mesh: int = 0,
                 start_timeout: float = 180.0,
                 install_dir: Optional[str] = None) -> None:
        self.idx = idx
        self.models = dict(models)
        self.backend = backend
        #: devices this replica's hive owns (--mesh N); 0/1 = one chip
        self.mesh = int(mesh)
        #: capacity advertised by the replica's OWN hello (devices x
        #: per-device budget) — the heterogeneous-placement input;
        #: None until the first spawn
        self.devices = 1
        self.capacity_bytes: Optional[int] = None
        #: where the replica runs, from its OWN hello (None until the
        #: first spawn; preset by the router for an elastic member) —
        #: a later spawn may not change it
        self.platform: Optional[str] = None
        self.device_kind: Optional[str] = None
        self.max_batch = max_batch
        #: rows one dispatch can drain — the admission estimate's
        #: queue divisor (capacity, NOT the recent fill: dividing by
        #: the fill EMA is procyclical — shedding empties batches,
        #: which inflates the estimate, which sheds more)
        self.batch_capacity = int(max_batch) if max_batch \
            else int(knobs.get(knobs.SERVE_MAX_BATCH))
        self.max_wait_ms = max_wait_ms if max_wait_ms is not None \
            else float(knobs.get(knobs.SERVE_MAX_WAIT_MS))
        self.hbm_budget = hbm_budget
        self.heartbeat_every = heartbeat_every
        self.metrics_dir = os.path.join(metrics_dir,
                                        f"replica-{idx}") \
            if metrics_dir else None
        self.cwd = cwd
        self.env = env
        self.start_timeout = start_timeout
        #: reused across respawns: the package unpack stays warm.  An
        #: elastic fleet passes a pooled dir from a RETIRED replica so
        #: a scale-up inherits the unpack (and on a real chip the
        #: persistent compile cache) instead of paying a cold install
        self.install_dir = install_dir or tempfile.mkdtemp(
            prefix=f"fleet_replica{idx}_")
        self.client: Optional[HiveClient] = None
        self.healthy = False
        #: set by the router's scale-down path BEFORE the drain: a
        #: retiring replica takes no new work, and the monitor neither
        #: respawns it nor counts its orderly exit as a death
        self.retiring = False
        self.deaths = 0
        #: set by mark_dead on the healthy->dead transition; the
        #: monitor consumes it exactly once (death accounting +
        #: backoff scheduling), whoever noticed first
        self.death_kind: Optional[str] = None
        self._consecutive_deaths = 0
        #: monotonic stamp of the last successful hello — a death
        #: within STABLE_UPTIME_S of it continues the crash streak
        #: (the backoff escalates), a longer stint resets it
        self._ready_at: Optional[float] = None
        self.next_respawn_at = 0.0
        self._lock = witness.lock("fleet.replica")
        #: router-side in-flight requests (the bounded router queue)
        self.inflight = 0
        #: EMAs polled from the replica's live stats by the monitor
        self.ema_dispatch_s: Optional[float] = None
        self.ema_batch_rows: Optional[float] = None
        #: observed per-dispatch CYCLE: wall time between dispatches
        #: (batches-counter delta over the poll interval) — window +
        #: compute + CPU contention in one measured number, the
        #: admission estimate's multiplier
        self.ema_cycle_s: Optional[float] = None
        self._dispatch_base = (0, 0.0)   # (count, sum) last poll
        self._rows_base = (0, 0.0)
        self._batches_base: Optional[tuple] = None  # (count, t)

    # -- lifecycle -----------------------------------------------------

    def spawn(self) -> Dict[str, Any]:
        """Start (or restart) the subprocess; returns its hello."""
        self.client = HiveClient(
            self.models, backend=self.backend,
            max_batch=self.max_batch, max_wait_ms=self.max_wait_ms,
            hbm_budget=self.hbm_budget,
            heartbeat_every=self.heartbeat_every,
            metrics_dir=self.metrics_dir,
            install_dir=self.install_dir,
            env=self.env, cwd=self.cwd, mesh=self.mesh,
            start_timeout=self.start_timeout)
        hello = self.client.hello or {}
        platform = hello.get("platform")
        if self.platform is not None and platform != self.platform:
            # the chip may still be held by the corpse for a moment: a
            # (re)spawn that came up elsewhere is a FAILED spawn (the
            # monitor retries with backoff), never a CPU stand-in
            # answering under the same replica index
            self.client.close(kill=True)
            raise RuntimeError(
                f"replica {self.idx} came up on {platform!r}; its "
                f"fleet serves on {self.platform!r}")
        with self._lock:
            self.platform = platform
            self.device_kind = hello.get("device_kind")
            # capacity comes from the replica's OWN hello — the probed
            # per-device budget on its real device, not a router-side
            # assumption (a mixed fleet's whole point)
            self.devices = int(hello.get("devices") or 1)
            budget = hello.get("device_budget")
            self.capacity_bytes = int(budget) * self.devices \
                if budget else None
            self.healthy = True
            self.death_kind = None
            # NOT a streak reset: a flapping replica (crash shortly
            # after a clean hello, respawn, crash again) must keep
            # escalating its backoff — only surviving STABLE_UPTIME_S
            # clears the streak (judged at death time in _on_death)
            self._ready_at = time.monotonic()
            self.inflight = 0
            self._dispatch_base = (0, 0.0)
            self._rows_base = (0, 0.0)
            self._batches_base = None
        return self.client.hello

    def mark_dead(self, kind: str = "eof") -> bool:
        """Flip healthy off; True only on the transition (the first
        noticer — router request path or fleet monitor — wins, and
        the monitor consumes ``death_kind`` for the accounting)."""
        with self._lock:
            if not self.healthy:
                return False
            self.healthy = False
            self.death_kind = kind
            return True

    @property
    def alive(self) -> bool:
        return self.client is not None and not self.client.dead

    @property
    def pid(self) -> Optional[int]:
        return self.client.pid if self.client is not None else None

    # -- load accounting -----------------------------------------------

    def acquire(self) -> None:
        with self._lock:
            self.inflight += 1

    def release(self) -> None:
        with self._lock:
            self.inflight = max(0, self.inflight - 1)

    def update_from_stats(self, st: Dict[str, Any]) -> None:
        """Fold one live stats snapshot into the dispatch-time,
        batch-fill, and dispatch-cadence EMAs (delta vs the previous
        poll)."""
        hists = st.get("histograms") or {}
        now = time.monotonic()

        def delta(name, base):
            h = hists.get(name) or {}
            c, s = int(h.get("count", 0)), float(h.get("sum", 0.0))
            dc, ds = c - base[0], s - base[1]
            return (c, s), (dc, ds)

        with self._lock:
            self._dispatch_base, (dc, ds) = delta(
                "serve.dispatch_seconds", self._dispatch_base)
            if dc > 0:
                mean = ds / dc
                self.ema_dispatch_s = mean \
                    if self.ema_dispatch_s is None \
                    else 0.5 * self.ema_dispatch_s + 0.5 * mean
            self._rows_base, (rc, rs) = delta(
                "serve.batch_rows", self._rows_base)
            if rc > 0:
                mean = rs / rc
                self.ema_batch_rows = mean \
                    if self.ema_batch_rows is None \
                    else 0.5 * self.ema_batch_rows + 0.5 * mean
            batches = int((st.get("counters") or {})
                          .get("serve.batches", 0))
            if self._batches_base is not None:
                db = batches - self._batches_base[0]
                dt = now - self._batches_base[1]
                if db > 0 and dt > 0:
                    cycle = dt / db
                    self.ema_cycle_s = cycle \
                        if self.ema_cycle_s is None \
                        else 0.5 * self.ema_cycle_s + 0.5 * cycle
            self._batches_base = (batches, now)
        if self.ema_dispatch_s is not None:
            telemetry.gauge(events.GAUGE_FLEET_DISPATCH_EMA_MS).set(
                round(1000.0 * self.ema_dispatch_s, 3))

    def _cycle_s(self) -> float:
        """The observed per-dispatch cycle: measured cadence when the
        monitor has polled one, else the batching window + a small
        dispatch (the idle-replica floor)."""
        cycle = self.ema_cycle_s
        floor = self.max_wait_ms / 1000.0 + 0.002
        return max(cycle, floor) if cycle is not None else floor

    def estimated_wait_ms(self) -> float:
        """Queue depth x observed per-dispatch time: how long a new
        request would queue behind this replica's in-flight work (the
        admission-control estimate).  One dispatch drains up to
        ``batch_capacity`` rows, and the per-dispatch time is the
        MEASURED cadence — window + compute + contention — so CPU
        saturation raises the estimate (negative feedback) while a
        busier, fuller batch does not."""
        with self._lock:
            inflight = self.inflight
        pending_dispatches = inflight / max(1, self.batch_capacity)
        return 1000.0 * pending_dispatches * self._cycle_s()

    def estimated_total_ms(self) -> float:
        """The admission estimate a request's completion would see:
        queued wait + its own dispatch cycle."""
        return self.estimated_wait_ms() + 1000.0 * self._cycle_s()

    def close(self, kill: bool = False) -> None:
        self.mark_dead()
        if self.client is not None:
            self.client.close(kill=kill)


class ReplicaSet(Logger):
    """Spawn N replicas concurrently and keep the set healthy."""

    def __init__(self, replicas: List[Replica],
                 heartbeat_deadline: Optional[float] = None,
                 respawn_backoff: Optional[float] = None,
                 stats_every: float = 0.5) -> None:
        self.replicas = replicas
        self.heartbeat_deadline = float(heartbeat_deadline) \
            if heartbeat_deadline is not None \
            else float(knobs.get(knobs.FLEET_HEARTBEAT_DEADLINE))
        self.respawn_backoff = float(respawn_backoff) \
            if respawn_backoff is not None \
            else float(knobs.get(knobs.FLEET_RESPAWN_BACKOFF))
        self.stats_every = stats_every
        self._closing = False
        self._monitor_thread: Optional[threading.Thread] = None
        self._last_stats_poll = 0.0

    def start(self) -> List[Dict[str, Any]]:
        """Spawn every replica CONCURRENTLY (jax import + package
        install dominate startup; N x serial would multiply it);
        returns their hellos in replica order.  Any spawn failure
        tears the whole set down and raises."""
        from concurrent.futures import ThreadPoolExecutor
        try:
            with ThreadPoolExecutor(len(self.replicas)) as tp:
                hellos = list(tp.map(lambda r: r.spawn(),
                                     self.replicas))
        except BaseException:
            for r in self.replicas:
                try:
                    r.close(kill=True)
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            raise
        for r, hello in zip(self.replicas, hellos):
            telemetry.event(events.EV_FLEET_REPLICA_SPAWNED,
                            replica=r.idx, pid=hello.get("pid"),
                            models=sorted(r.models))
        self._update_health_gauge()
        self._monitor_thread = threading.Thread(
            target=self._monitor, daemon=True, name="fleet-monitor")
        self._monitor_thread.start()
        return hellos

    def healthy(self) -> List[Replica]:
        return [r for r in self.replicas if r.healthy]

    # -- elastic membership (the autoscaler's two verbs) ---------------

    def add(self, r: Replica) -> None:
        """Adopt an ALREADY-SPAWNED replica into supervision.  The
        caller spawns first (slow: jax import + install) so the
        monitor never sees a half-started member."""
        self.replicas.append(r)
        self._update_health_gauge()

    def remove(self, r: Replica) -> None:
        """Drop a replica from supervision (scale-down: the router
        drains and terminates it AFTER removal, so the monitor cannot
        mistake the orderly SIGTERM exit for a death to respawn)."""
        if r in self.replicas:
            self.replicas.remove(r)
        self._update_health_gauge()

    def _update_health_gauge(self) -> None:
        telemetry.gauge(events.GAUGE_FLEET_REPLICAS_HEALTHY).set(
            len(self.healthy()))

    # -- monitor -------------------------------------------------------

    def _monitor(self) -> None:
        while not self._closing:
            time.sleep(0.25)
            if self._closing:
                return
            now = time.monotonic()
            poll_stats = now - self._last_stats_poll \
                >= self.stats_every
            if poll_stats:
                self._last_stats_poll = now
            # snapshot: the autoscaler adds/removes members while the
            # monitor iterates
            for r in list(self.replicas):
                if self._closing:
                    return
                if r.retiring:
                    # the router's scale-down path owns this replica's
                    # remaining lifecycle (drain -> SIGTERM -> remove)
                    continue
                if r.healthy and not r.alive:
                    r.mark_dead("eof")
                    self._on_death(r)
                elif r.healthy and self.heartbeat_deadline > 0 \
                        and r.client is not None \
                        and now - r.client.last_line_ts \
                        > self.heartbeat_deadline:
                    # silent too long: declare it hung, kill it — the
                    # reader's EOF then fails its pending waiters
                    r.client.kill()
                    r.mark_dead("heartbeat_deadline")
                    self._on_death(r)
                elif not r.healthy and r.death_kind is not None:
                    # the router's request path noticed first (its
                    # waiter got ReplicaDied); account the death here
                    self._on_death(r)
                elif not r.healthy \
                        and now >= r.next_respawn_at:
                    self._respawn(r)
                elif r.healthy and poll_stats:
                    try:
                        r.update_from_stats(r.client.stats(timeout=5))
                    except Exception:  # noqa: BLE001 — a stats miss
                        pass           # must never kill supervision

    def _on_death(self, r: Replica) -> None:
        kind = r.death_kind or "eof"
        r.death_kind = None
        r.deaths += 1
        # the streak is judged by UPTIME, not by hello success: a
        # replica that flaps (clean hello, dead again within
        # STABLE_UPTIME_S) keeps escalating its backoff toward the
        # 30s cap; one that served a stable stint starts over
        uptime = (time.monotonic() - r._ready_at) \
            if r._ready_at is not None else None
        if uptime is not None and uptime >= STABLE_UPTIME_S:
            r._consecutive_deaths = 0
        r._consecutive_deaths += 1
        backoff = min(30.0, self.respawn_backoff
                      * (2 ** (r._consecutive_deaths - 1)))
        r.next_respawn_at = time.monotonic() + backoff
        telemetry.counter(events.CTR_FLEET_REPLICA_DEATHS).inc()
        telemetry.event(events.EV_FLEET_REPLICA_DIED,
                        replica=r.idx, pid=r.pid, kind=kind,
                        rc=getattr(r.client, "exit_rc", None),
                        backoff=round(backoff, 3))
        self._update_health_gauge()
        self.warning("replica %d (pid %s) died (%s); respawn in "
                     "%.2fs", r.idx, r.pid, kind, backoff)
        try:
            r.close(kill=True)   # reap the corpse
        except Exception:  # noqa: BLE001 — already dead
            pass

    def _respawn(self, r: Replica) -> None:
        consecutive = r._consecutive_deaths
        try:
            hello = r.spawn()
        except Exception as e:  # noqa: BLE001 — retry with backoff
            r._consecutive_deaths = max(consecutive, 1) + 1
            backoff = min(30.0, self.respawn_backoff
                          * (2 ** (r._consecutive_deaths - 1)))
            r.next_respawn_at = time.monotonic() + backoff
            self.warning("replica %d respawn failed (%s); next try "
                         "in %.2fs", r.idx, e, backoff)
            return
        telemetry.counter(events.CTR_FLEET_REPLICA_RESPAWNS).inc()
        telemetry.event(events.EV_FLEET_REPLICA_RESPAWNED,
                        replica=r.idx, pid=hello.get("pid"),
                        deaths=r.deaths)
        self._update_health_gauge()
        self.info("replica %d respawned (pid %s, warm install dir)",
                  r.idx, hello.get("pid"))

    # -- teardown ------------------------------------------------------

    def close(self, kill: bool = False) -> None:
        self._closing = True
        if self._monitor_thread is not None:
            self._monitor_thread.join(timeout=5.0)
        from concurrent.futures import ThreadPoolExecutor
        replicas = list(self.replicas)
        with ThreadPoolExecutor(max(1, len(replicas))) as tp:
            list(tp.map(lambda r: r.close(kill=kill), replicas))
        self._update_health_gauge()
