"""Swarm: the fleet router — N Hive replicas behind one front end.

``python -m veles_tpu --serve-fleet N NAME=PKG.vpkg [NAME=PKG ...]``

PR 10's Hive is ONE chip-owning process, so serving throughput is
capped by one process no matter how many cores/chips the box has.
:class:`FleetRouter` owns N Hive replicas
(:mod:`veles_tpu.serve.fleet` spawns and supervises them) and fans
concurrent requests out across them:

- **placement-aware routing**: a :class:`~veles_tpu.serve.fleet.
  PlacementPolicy` replicates hot models on every replica and
  partitions the long tail; a request goes to the LEAST-LOADED healthy
  replica holding the model (router-side in-flight queue depth per
  replica), falling back to any healthy replica (which LRU-loads the
  model under its own residency budget);
- **failover**: a replica death (reader EOF or heartbeat deadline)
  fails its in-flight requests with ``ReplicaDied`` *immediately*;
  the router retries each exactly once on a healthy peer (inference
  is idempotent) while the fleet monitor respawns the replica with a
  warm install dir — pending waiters NEVER hang;
- **admission control**: a bounded per-replica router queue plus an
  SLO target (``$VELES_FLEET_SLO_P99_MS``): when the estimated
  completion (queue depth x observed per-dispatch time + batching
  window) would blow the target on even the least-loaded candidate,
  the request is shed with an explicit ``overloaded`` response
  instead of letting p99 run away;
- **canary / shadow**: a model registered as ``canary-of:NAME``
  receives a sampled fraction of NAME's traffic as asynchronous
  mirrors; per-model QPS/latency/error telemetry is split
  (``fleet.model.<name>.*``) so the A/B reads directly from
  ``obs_report --fleet``;
- **gray-failure defense** (:mod:`veles_tpu.serve.sentinel`): every
  request carries an absolute ``deadline_ms`` end-to-end (the hive
  batcher drops expired rows before dispatch), a request older than
  the adaptive hedge threshold is reissued on a second replica under
  the ``$VELES_FLEET_HEDGE_BUDGET`` cap (first answer wins, the loser
  is cancelled by wire id), responses are integrity-verified against
  their row-count/crc echo, and a replica accumulating strikes —
  deadline misses, deaths, integrity failures, hedge losses, latency
  outliers — is EJECTED from routing, probed with synthetic canaries,
  and reinstated after ``$VELES_FLEET_PROBE_OK`` clean probes.

The CLI front end speaks the same JSONL protocol as a single hive
(hello line, heartbeats, ``{"id", "model", "rows"}`` in /
``{"id", "pred", "probs"}`` out), so every Hive client — including
another router — can point at a fleet unchanged.  Shed responses are
``{"id", "error": "overloaded", "overloaded": true}``.
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import signal
import sys
import threading
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from veles_tpu import events, knobs, telemetry, trace
from veles_tpu.analysis import witness
from veles_tpu.logger import Logger
from veles_tpu.serve.client import ReplicaDied
from veles_tpu.serve.fleet import PlacementPolicy, Replica, ReplicaSet
from veles_tpu.serve.sentinel import Sentinel
from veles_tpu.supervisor import EXIT_PREEMPTED


class FleetRouter(Logger):
    """Own N Hive replicas; route, shed, mirror, and fail over."""

    def __init__(self, models: Dict[str, str], n_replicas: int,
                 backend: str = "cpu",
                 max_batch: Optional[int] = None,
                 max_wait_ms: Optional[float] = None,
                 hbm_budget: Optional[int] = None,
                 heartbeat_every: Optional[float] = None,
                 metrics_dir: Optional[str] = None,
                 cwd: Optional[str] = None,
                 env: Optional[Dict[str, str]] = None,
                 canaries: Optional[Dict[str,
                                         Tuple[str,
                                               Optional[float]]]] = None,
                 placement: Optional[PlacementPolicy] = None,
                 slo_p99_ms: Optional[float] = None,
                 max_inflight: Optional[int] = None,
                 heartbeat_deadline: Optional[float] = None,
                 respawn_backoff: Optional[float] = None,
                 start_timeout: float = 300.0,
                 deadline_ms: Optional[float] = None,
                 hedge_min_ms: Optional[float] = None,
                 hedge_budget: Optional[float] = None,
                 eject_threshold: Optional[float] = None,
                 probe_ok: Optional[int] = None,
                 probe_interval: Optional[float] = None,
                 probe_backoff_cap: Optional[float] = None,
                 env_overrides: Optional[Dict[int, Dict[str, str]]]
                 = None,
                 mesh: Optional[Any] = None) -> None:
        if n_replicas < 1:
            raise ValueError(f"a fleet needs >= 1 replica, got "
                             f"{n_replicas}")
        if not models:
            raise ValueError("a fleet needs at least one model")
        self.models = dict(models)
        self.n_replicas = int(n_replicas)
        #: {canary_name: (primary_name, fraction)} — validated here
        self.canaries: Dict[str, Tuple[str, float]] = {}
        default_frac = float(knobs.get(knobs.FLEET_CANARY_FRACTION))
        for cname, (primary, frac) in (canaries or {}).items():
            if cname not in self.models:
                raise ValueError(f"canary {cname!r} is not a "
                                 f"registered model")
            if primary not in self.models:
                raise ValueError(f"canary {cname!r} mirrors unknown "
                                 f"model {primary!r}")
            if cname == primary:
                raise ValueError(f"{cname!r} cannot canary itself")
            f = default_frac if frac is None else float(frac)
            if not 0.0 <= f <= 1.0:
                raise ValueError(f"canary fraction must be in [0, 1], "
                                 f"got {f} for {cname!r}")
            self.canaries[cname] = (primary, f)
        self._mirrors_by_primary: Dict[str, List[Tuple[str, float]]] \
            = {}
        for cname, (primary, f) in self.canaries.items():
            self._mirrors_by_primary.setdefault(primary, []).append(
                (cname, f))
        #: admission knobs — plain mutable attributes so an operator
        #: embedding the router (or a test) can retune a live fleet
        self.slo_p99_ms = float(slo_p99_ms) if slo_p99_ms is not None \
            else float(knobs.get(knobs.FLEET_SLO_P99_MS))
        self.max_inflight = int(max_inflight) \
            if max_inflight is not None \
            else int(knobs.get(knobs.FLEET_MAX_INFLIGHT))
        #: default per-request deadline budget (ms); a request's
        #: absolute deadline_ms = now + min(this, caller timeout)
        self.deadline_ms = float(deadline_ms) \
            if deadline_ms is not None \
            else float(knobs.get(knobs.FLEET_DEADLINE_MS))
        if metrics_dir:
            telemetry.configure(metrics_dir)
        self.metrics_dir = metrics_dir

        def _replica_env(i: int) -> Optional[Dict[str, str]]:
            # per-replica overrides (gray-failure drills arm ONE
            # replica's VELES_FAULTS without touching its peers)
            over = (env_overrides or {}).get(i)
            if not over:
                return env
            merged = dict(env or {})
            merged.update(over)
            return merged

        def _replica_mesh(i: int) -> int:
            # the Prism topology knob: an int meshes every replica,
            # a {replica_index: devices} dict mixes 1-device and
            # N-device replicas in one fleet
            if mesh is None:
                return 0
            if isinstance(mesh, dict):
                return int(mesh.get(i, 0))
            return int(mesh)

        def _make_replica(i: int,
                          install_dir: Optional[str] = None) -> Replica:
            return Replica(i, self.models, backend=backend,
                           max_batch=max_batch,
                           max_wait_ms=max_wait_ms,
                           hbm_budget=hbm_budget,
                           heartbeat_every=heartbeat_every,
                           metrics_dir=metrics_dir, cwd=cwd,
                           env=_replica_env(i), mesh=_replica_mesh(i),
                           start_timeout=start_timeout,
                           install_dir=install_dir)

        #: the scale-up path re-uses the ctor's replica recipe (same
        #: models/env/backend), so an elastic member is
        #: indistinguishable from a founding one
        self._make_replica = _make_replica
        self.replicas = [_make_replica(i)
                         for i in range(self.n_replicas)]
        self.fleet = ReplicaSet(
            self.replicas, heartbeat_deadline=heartbeat_deadline,
            respawn_backoff=respawn_backoff)
        hellos = self.fleet.start()
        platforms = {r.platform for r in self.replicas}
        if len(platforms) > 1:
            # e.g. `-b auto` with more replicas than chips: the first
            # claims the TPU and the rest land on XLA:CPU.  One fleet
            # answers from one platform; say so instead of serving a
            # mixed one under the same model names
            self.fleet.close(kill=True)
            raise RuntimeError(
                f"replicas came up on different platforms "
                f"{sorted(map(str, platforms))} — one process per "
                f"chip; a fleet of "
                f"{self.n_replicas} needs {self.n_replicas} devices")
        #: the one platform / device kind every replica reported
        self.platform = platforms.pop()
        self.device_kind = self.replicas[0].device_kind
        self.hello_models = hellos[0].get("models", {})

        #: routing affinity: hot models on all replicas, long tail
        #: partitioned (any healthy replica remains a fallback).
        #: Capacities come from each replica's OWN hello — a --mesh N
        #: replica advertises devices x per-device budget, so the
        #: split places against real, heterogeneous capacity
        policy = placement or PlacementPolicy(budget_bytes=hbm_budget)
        self.placement = policy.assign(
            {name: self.hello_models.get(name, {})
             .get("param_bytes", 0) for name in self.models},
            self.n_replicas,
            capacities=[r.capacity_bytes for r in self.replicas])
        self._lock = witness.lock("router.state")
        self._routed = [0] * self.n_replicas
        self._mirror_acc: Dict[str, float] = {}
        self._closed = False
        # -- elastic-fleet state (Gauntlet) ---------------------------
        #: next replica index to mint: indices are NEVER reused, so
        #: ``_routed``/telemetry rows stay unambiguous across the day
        self._next_idx = self.n_replicas
        #: install dirs of retired replicas — a scale-up pops one so
        #: the package unpack (and compile cache) stays warm
        self._warm_dirs: List[str] = []
        #: the replicated hot prefix at placement time: a scale-up
        #: joins these; everything else is the sheddable long tail
        self._hot_models = {
            m for m, placed in self.placement.items()
            if len(placed) == self.n_replicas}
        #: degradation-ladder levers (the autoscaler flips these via
        #: ``apply_degradation``; mutable for tests/operators too)
        self.hedging_enabled = True
        self.shed_tail = False
        #: gray-failure defense: health scoring, hedging governor,
        #: ejection + probe/reinstate lifecycle
        sentinel_kw = {}
        if probe_backoff_cap is not None:
            sentinel_kw["probe_backoff_cap"] = probe_backoff_cap
        self.sentinel = Sentinel(
            self.replicas, probe_fn=self._probe_replica,
            hedge_min_ms=hedge_min_ms, hedge_budget=hedge_budget,
            eject_threshold=eject_threshold, probe_ok=probe_ok,
            probe_interval=probe_interval, **sentinel_kw)
        telemetry.event(events.EV_FLEET_PLACEMENT,
                        placement=self.placement)
        telemetry.event(
            events.EV_FLEET_READY, replicas=self.n_replicas,
            pids=[h.get("pid") for h in hellos],
            models=sorted(self.models),
            canaries={c: {"of": p, "fraction": f}
                      for c, (p, f) in self.canaries.items()},
            slo_p99_ms=self.slo_p99_ms,
            max_inflight=self.max_inflight)
        self.info("fleet up: %d replicas (pids %s), %d models, "
                  "placement %s", self.n_replicas,
                  [h.get("pid") for h in hellos], len(self.models),
                  self.placement)

    # -- routing -------------------------------------------------------

    def _pick(self, model: str,
              exclude: Tuple[Replica, ...] = ()) -> Optional[Replica]:
        """The least-loaded healthy, non-ejected replica holding
        ``model``; any eligible replica when none of the placed set is
        (the fallback LRU-loads the model on arrival).  A
        sentinel-ejected replica sheds route around it — only probes
        reach it until it is reinstated."""
        placed = set(self.placement.get(model, ()))
        healthy = [r for r in self.fleet.healthy()
                   if r not in exclude and not r.retiring
                   and self.sentinel.eligible(r)]
        candidates = [r for r in healthy if r.idx in placed] \
            or healthy
        if not candidates:
            return None
        return min(candidates, key=lambda r: (r.inflight, r.idx))

    def _shed(self, r: Replica) -> Optional[float]:
        """Admission control on the picked (least-loaded) candidate:
        returns the estimated completion in ms when the request must
        be shed, None when it is admitted.  Checking only the pick is
        sound because _pick minimizes queue depth — if the best
        replica sheds, every other candidate is deeper."""
        if r.inflight >= self.max_inflight:
            return float(r.estimated_total_ms())
        if self.slo_p99_ms > 0:
            est = r.estimated_total_ms()
            telemetry.gauge(events.GAUGE_FLEET_EST_WAIT_MS).set(
                round(est, 3))
            if est > self.slo_p99_ms:
                return float(est)
        return None

    def request(self, model: str, rows: Any,
                timeout: float = 60.0,
                deadline_ms: Optional[float] = None) -> Dict[str, Any]:
        """One routed round trip; returns the replica's response dict
        ({"pred", "probs"}), an {"error": ...} dict, or the shed
        response {"error": "overloaded", "overloaded": True}.  Never
        raises for replica death, overload, or a blown deadline — the
        protocol carries all three.

        ``deadline_ms`` is the request's ABSOLUTE unix-epoch deadline;
        when None it is stamped as now + min($VELES_FLEET_DEADLINE_MS,
        ``timeout``*1000) and rides the wire end-to-end."""
        telemetry.counter(events.CTR_FLEET_REQUESTS).inc()
        telemetry.counter(f"fleet.model.{model}.requests").inc()
        rows = np.asarray(rows, np.float32)
        self.sentinel.note_request(model, rows)
        if deadline_ms is None:
            budget = self.deadline_ms if self.deadline_ms > 0 \
                else 1000.0 * timeout
            if timeout:
                budget = min(budget, 1000.0 * timeout)
            deadline_ms = time.time() * 1000.0 + budget
        # the trace ROOT is minted here, at the fleet's admission
        # edge: every leg (hedge copies, failover retries, canary
        # mirrors) derives a child span from it, so one request
        # assembles into ONE cross-process tree however it was routed
        ctx = trace.mint()
        t0 = time.perf_counter()
        with trace.use(ctx):
            resp = self._dispatch(model, rows, float(deadline_ms))
            if resp.get("overloaded"):
                telemetry.counter(events.CTR_FLEET_SHED).inc()
                telemetry.counter(f"fleet.model.{model}.shed").inc()
            elif "error" in resp:
                telemetry.counter(
                    events.CTR_FLEET_REQUEST_ERRORS).inc()
                telemetry.counter(f"fleet.model.{model}.errors").inc()
            else:
                dt = time.perf_counter() - t0
                telemetry.histogram(
                    events.HIST_FLEET_REQUEST_SECONDS).record(
                    dt, exemplar=ctx.trace_id if ctx.sampled else None)
                telemetry.histogram(
                    f"fleet.model.{model}.request_seconds").record(dt)
                self._maybe_mirror(model, rows, timeout)
            if ctx.sampled:
                outcome = ("shed" if resp.get("overloaded")
                           else "timeout" if resp.get("timeout")
                           else "error" if "error" in resp else "ok")
                telemetry.event(
                    events.EV_TRACE_REQUEST, trace=ctx.trace_id,
                    span=ctx.span_id, model=model,
                    rows=int(len(rows)), outcome=outcome,
                    seconds=round(time.perf_counter() - t0, 6))
                trace.record("fleet.request", ctx=ctx, model=model,
                             outcome=outcome)
        return resp

    def _dispatch(self, model: str, rows: Any,
                  deadline_ms: float) -> Dict[str, Any]:
        if self.shed_tail and model not in self._hot_models:
            # the ladder's last rung: the long tail is explicitly shed
            # so the hot prefix keeps its p99 — an honest overloaded
            # response, never a timeout and never a 404
            return {"error": "overloaded", "overloaded": True,
                    "degraded": True, "model": model}
        r = self._pick(model)
        if r is None:
            return {"error": "no healthy replica", "model": model}
        est = self._shed(r)
        if est is not None:
            return {"error": "overloaded", "overloaded": True,
                    "model": model, "est_ms": round(est, 2)}
        tried: Tuple[Replica, ...] = ()
        cur = r
        resp: Dict[str, Any] = {"error": "unroutable", "model": model}
        for attempt in (0, 1):
            resp, verdict = self._routed_round(model, rows, cur,
                                               deadline_ms, tried)
            if verdict in ("ok", "timeout"):
                # a blown deadline is FINAL: the budget is spent, a
                # retry would only answer after nobody is waiting
                return resp
            # verdict died/integrity: retry ONCE on a healthy peer
            # (idempotent inference) — the admission gate is not
            # re-run, the request was already accepted
            tried = tried + (cur,)
            if attempt == 0:
                telemetry.counter(events.CTR_FLEET_RETRIES).inc()
                peer = self._pick(model, exclude=tried)
                if peer is None:
                    return {"error": f"replica failed ({verdict}) "
                                     f"and no healthy peer",
                            "model": model}
                cur = peer
        return resp

    def _routed_round(self, model: str, rows: np.ndarray,
                      primary: Replica, deadline_ms: float,
                      tried: Tuple[Replica, ...]
                      ) -> Tuple[Dict[str, Any], str]:
        """One routed attempt with hedging: submit to ``primary``;
        once the request's age crosses the adaptive hedge threshold
        (and the hedge budget allows), issue a second copy on a
        different replica, take the FIRST clean answer, and cancel the
        loser by wire id.  Returns (response, verdict) with verdict
        one of ``ok`` (also replica-side request errors — they are
        deterministic, not gray), ``timeout`` (deadline blown),
        ``died``, ``integrity``.

        Structured in two phases so the HOT path (the answer beats the
        hedge threshold, i.e. almost always) costs exactly what the
        pre-sentinel router did — one submit + one blocking wait; the
        per-request fan-in queue exists only for the rare request that
        actually hedges."""
        n_rows = int(len(rows))
        t_start = time.perf_counter()
        # the round runs on the thread that minted the root (or a pool
        # worker under ``trace.use``); each LEG — primary attempt,
        # hedge copy, failover retry — gets its own child span, so the
        # assembled trace shows every replica the request touched
        rctx = trace.current()

        def leg_ctx() -> Optional[trace.TraceContext]:
            return rctx.child() \
                if rctx is not None and rctx.sampled else None

        def leg_event(rep: Replica,
                      lctx: Optional[trace.TraceContext],
                      verdict: str, t0_leg: float,
                      hedge: bool = False,
                      winner: bool = False) -> None:
            if lctx is None:
                return
            telemetry.event(
                events.EV_TRACE_LEG, trace=lctx.trace_id,
                span=lctx.span_id, parent=lctx.parent_id,
                replica=rep.idx, verdict=verdict,
                seconds=round(time.perf_counter() - t0_leg, 6),
                hedge=bool(hedge), winner=bool(winner))

        def timeout_resp() -> Tuple[Dict[str, Any], str]:
            return ({"error": "deadline exceeded", "model": model,
                     "timeout": True,
                     "deadline_ms": round(deadline_ms, 1)}, "timeout")

        def evaluate(rep: Replica, msg: Dict[str, Any]) \
                -> Tuple[Dict[str, Any], str]:
            """Judge one ANSWERED leg (the caller already released)."""
            if "error" in msg:
                if msg.get("expired"):
                    # the hive's own batcher dropped it past deadline:
                    # that replica's queue blew the budget
                    self.sentinel.record_timeout(rep)
                    return timeout_resp()
                # a deterministic request error (bad shape, unknown
                # model): return it as-is — no strike, no retry
                return msg, "ok"
            if not self._verify_integrity(msg, n_rows):
                self.sentinel.record_integrity(rep)
                return ({"error": "response failed integrity check",
                         "model": model}, "integrity")
            self.sentinel.record_ok(
                rep, model, time.perf_counter() - t_start)
            return msg, "ok"

        remain_s = (deadline_ms - time.time() * 1000.0) / 1000.0
        if remain_s <= 0:
            return timeout_resp()
        with self._lock:
            self._routed[primary.idx] += 1
        primary.acquire()
        telemetry.gauge(events.GAUGE_FLEET_INFLIGHT).set(
            self.inflight_total())
        pctx = leg_ctx()
        t_leg0 = time.perf_counter()
        try:
            jid = primary.client.submit(model, rows,
                                        deadline_ms=deadline_ms,
                                        ctx=pctx)
        except ReplicaDied:
            primary.release()
            primary.mark_dead()
            self.sentinel.record_died(primary)
            leg_event(primary, pctx, "died", t_leg0)
            return {"error": "replica died", "model": model}, "died"
        # -- phase 1: plain wait until the hedge threshold ------------
        hedge_thr_s = self.sentinel.hedge_threshold_ms(model) / 1000.0
        try:
            msg = primary.client.wait_for(
                jid, timeout=max(0.001, min(hedge_thr_s, remain_s)))
            primary.release()
            out = evaluate(primary, msg)
            leg_event(primary, pctx, out[1], t_leg0,
                      winner=out[1] == "ok")
            return out
        except TimeoutError:
            pass   # outlived the hedge threshold: fall through
        except ReplicaDied:
            # the reader already failed every waiter, but the wire id
            # must still be retired — uniform waiter discipline, and
            # a respawned client can never collide with it
            primary.client.cancel(jid)
            primary.release()
            primary.mark_dead()
            self.sentinel.record_died(primary)
            leg_event(primary, pctx, "died", t_leg0)
            return {"error": "replica died", "model": model}, "died"
        # -- the request outlived the hedge threshold -----------------
        remain_s = (deadline_ms - time.time() * 1000.0) / 1000.0
        if remain_s <= 0:
            primary.client.cancel(jid)
            primary.release()
            self.sentinel.record_timeout(primary)
            leg_event(primary, pctx, "timeout", t_leg0)
            return timeout_resp()
        peer: Optional[Replica] = None
        if self.hedging_enabled and self.sentinel.hedge_budget > 0:
            cand = self._pick(model, exclude=tried + (primary,))
            # a hedge duplicates load: it must pass the SAME admission
            # gate a fresh request would — hedging fights tail
            # latency, never overload (an overloaded peer would only
            # queue the copy)
            if cand is not None and self._shed(cand) is None \
                    and self.sentinel.allow_hedge():
                peer = cand
            elif cand is not None:
                telemetry.counter(events.CTR_FLEET_HEDGE_DENIED).inc()
        if peer is None:
            # no hedge possible: wait the primary out to the deadline
            try:
                msg = primary.client.wait_for(
                    jid, timeout=max(0.001, remain_s))
            except TimeoutError:
                primary.client.cancel(jid)
                primary.release()
                self.sentinel.record_timeout(primary)
                leg_event(primary, pctx, "timeout", t_leg0)
                return timeout_resp()
            except ReplicaDied:
                primary.client.cancel(jid)
                primary.release()
                primary.mark_dead()
                self.sentinel.record_died(primary)
                leg_event(primary, pctx, "died", t_leg0)
                return ({"error": "replica died", "model": model},
                        "died")
            primary.release()
            out = evaluate(primary, msg)
            leg_event(primary, pctx, out[1], t_leg0,
                      winner=out[1] == "ok")
            return out
        # -- phase 2: the hedged fan-in (the rare, already-slow case) -
        telemetry.counter(events.CTR_FLEET_HEDGES).inc()
        trace.record("fleet.hedge", ctx=rctx, model=model,
                     primary=primary.idx, peer=peer.idx)
        results: "queue.SimpleQueue[Tuple[Replica, int, Any, Any]]" \
            = queue.SimpleQueue()
        outstanding: Dict[Tuple[int, int], Replica] = {}
        # per-leg trace span + submit time, keyed like ``outstanding``
        # — BOTH hedge legs are recorded and the winner attributed
        legmeta: Dict[Tuple[int, int],
                      Tuple[Optional[trace.TraceContext],
                            float, bool]] = {}
        outstanding[(primary.idx, jid)] = primary
        legmeta[(primary.idx, jid)] = (pctx, t_leg0, False)
        primary.client.collect_async(
            jid, lambda m, e, rep=primary, j=jid:
            results.put((rep, j, m, e)))
        with self._lock:
            self._routed[peer.idx] += 1
        peer.acquire()
        hctx = leg_ctx()
        t_hleg0 = time.perf_counter()
        try:
            hjid = peer.client.submit(model, rows,
                                      deadline_ms=deadline_ms,
                                      ctx=hctx)
        except ReplicaDied:
            peer.release()
            peer.mark_dead()
            self.sentinel.record_died(peer)
            leg_event(peer, hctx, "died", t_hleg0, hedge=True)
        else:
            # registered ONLY after the submit succeeded: the except
            # arm above covers exactly the risky call, so a hedge id
            # can never be created and then forgotten
            outstanding[(peer.idx, hjid)] = peer
            legmeta[(peer.idx, hjid)] = (hctx, t_hleg0, True)
            peer.client.collect_async(
                hjid, lambda m, e, rep=peer, j=hjid:
                results.put((rep, j, m, e)))

        def drop_outstanding(score_timeout: bool) -> None:
            for (idx, ojid), rep in list(outstanding.items()):
                rep.client.cancel(ojid)
                rep.release()
                if score_timeout:
                    self.sentinel.record_timeout(rep)
                lctx, t0l, hedged = legmeta.get(
                    (idx, ojid), (None, t_start, False))
                leg_event(rep, lctx,
                          "timeout" if score_timeout else "cancelled",
                          t0l, hedge=hedged)
            outstanding.clear()

        fail: Optional[Tuple[Dict[str, Any], str]] = None
        while outstanding:
            remain_s = (deadline_ms - time.time() * 1000.0) / 1000.0
            if remain_s <= 0:
                drop_outstanding(score_timeout=True)
                return timeout_resp()
            try:
                rep, rjid, msg, err = results.get(
                    timeout=max(0.001, remain_s))
            except queue.Empty:
                continue
            if (rep.idx, rjid) not in outstanding:
                continue   # already cancelled
            outstanding.pop((rep.idx, rjid))
            rep.release()
            lctx, t0l, hedged = legmeta.get(
                (rep.idx, rjid), (None, t_start, False))
            if err is not None:
                rep.mark_dead()
                self.sentinel.record_died(rep)
                leg_event(rep, lctx, "died", t0l, hedge=hedged)
                fail = ({"error": "replica died", "model": model},
                        "died")
                continue   # the other leg may still answer
            out = evaluate(rep, msg)
            leg_event(rep, lctx, out[1], t0l, hedge=hedged,
                      winner=out[1] == "ok")
            if out[1] == "ok":
                if rep is peer and "probs" in out[0]:
                    self.sentinel.record_hedge_win(rep, primary)
                drop_outstanding(score_timeout=False)
                return out
            fail = out   # expired / integrity: other leg may save it
        return fail if fail is not None \
            else ({"error": "replica died", "model": model}, "died")

    @staticmethod
    def _verify_integrity(msg: Dict[str, Any], n_rows: int) -> bool:
        """The response-integrity echo: the probability payload must
        carry exactly the requested row count and match the crc32 the
        hive computed over its clean float32 payload (float32
        round-trips JSON exactly, so any wire/compute corruption
        breaks the checksum).  Responses from pre-echo hives (no crc
        field) pass — the row-count check still applies."""
        if "probs" not in msg:
            return True
        try:
            probs = np.asarray(msg["probs"], np.float32)
        except (TypeError, ValueError):
            return False
        if probs.ndim < 1 or len(probs) != n_rows:
            return False
        rows_n = msg.get("rows_n")
        if rows_n is not None and int(rows_n) != len(probs):
            return False
        crc = msg.get("crc")
        if crc is not None \
                and zlib.crc32(probs.tobytes()) != int(crc):
            return False
        return True

    def _probe_replica(self, r: Replica, model: str,
                       rows: np.ndarray) -> Tuple[bool, str]:
        """One synthetic canary request aimed STRAIGHT at replica
        ``r`` (bypassing routing — it is ejected) — the sentinel's
        reinstatement evidence.  Clean = answered inside the probe
        deadline AND integrity-verified."""
        timeout_s = max(1.0,
                        4.0 * self.sentinel.hedge_threshold_ms(model)
                        / 1000.0)
        if not r.healthy or r.client is None:
            return False, "replica process down"
        deadline_ms = time.time() * 1000.0 + 1000.0 * timeout_s
        try:
            jid = r.client.submit(model, rows,
                                  deadline_ms=deadline_ms)
        except ReplicaDied as e:
            return False, f"died at submit: {e}"
        try:
            msg = r.client.wait_for(jid, timeout=timeout_s)
        except TimeoutError:
            r.client.cancel(jid)
            return False, f"no answer in {timeout_s:.1f}s"
        except ReplicaDied as e:
            r.client.cancel(jid)
            return False, f"died: {e}"
        if "error" in msg:
            return False, f"error: {msg['error']}"
        if not self._verify_integrity(msg, int(len(rows))):
            return False, "integrity mismatch"
        return True, "clean"

    def _maybe_mirror(self, primary: str, rows: Any,
                      timeout: float) -> None:
        """Mirror a deterministic sampled fraction of ``primary``'s
        admitted traffic to each of its canaries, asynchronously (the
        caller's latency never carries the mirror; the reader thread
        records the canary-side telemetry)."""
        pairs = self._mirrors_by_primary.get(primary)
        if not pairs:
            return
        for cname, frac in pairs:
            with self._lock:
                acc = self._mirror_acc.get(cname, 0.0) + frac
                fire = acc >= 1.0
                self._mirror_acc[cname] = acc - 1.0 if fire else acc
            if not fire:
                continue
            r = self._pick(cname)
            if r is None:
                continue
            telemetry.counter(events.CTR_FLEET_MIRRORED).inc()
            telemetry.counter(f"fleet.model.{cname}.requests").inc()
            telemetry.counter(f"fleet.model.{cname}.mirrored").inc()
            t0 = time.perf_counter()
            # the mirror leg joins the primary request's trace (a
            # child of the root minted in request()) so a canary
            # regression can be tied back to the traffic that hit it
            c = trace.current()
            mctx = c.child() if c is not None and c.sampled else None
            r.acquire()
            try:
                jid = r.client.submit(
                    cname, rows,
                    deadline_ms=time.time() * 1000.0
                    + self.deadline_ms if self.deadline_ms > 0
                    else None,
                    ctx=mctx)
            except ReplicaDied:
                r.release()
                r.mark_dead()
                telemetry.counter(
                    f"fleet.model.{cname}.errors").inc()
                continue

            def _collect(msg, err, r=r, cname=cname, t0=t0):
                r.release()
                if err is not None or (msg and "error" in msg):
                    telemetry.counter(
                        f"fleet.model.{cname}.errors").inc()
                else:
                    telemetry.histogram(
                        f"fleet.model.{cname}.request_seconds"
                    ).record(time.perf_counter() - t0)

            r.client.collect_async(jid, _collect)

    # -- elastic scaling (Gauntlet) ------------------------------------

    def add_replica(self, cause: str = "manual",
                    **info: Any) -> Optional[Replica]:
        """Scale up by one replica: spawn (into a warm install dir
        from a retired peer when one is pooled), join it into the hot
        placement, and hand it to the monitor + sentinel.  The spawn
        runs on the caller's thread (the autoscaler's loop) — routing
        never sees the replica until its hello arrived.  Returns the
        new Replica, or None when the spawn failed (the controller's
        cooldown spaces the retry)."""
        with self._lock:
            idx = self._next_idx
            self._next_idx += 1
            warm = self._warm_dirs.pop() if self._warm_dirs else None
        r = self._make_replica(idx, install_dir=warm)
        # an elastic member joins the fleet's ONE platform or its
        # spawn fails (Replica.spawn checks the hello against this)
        r.platform = self.platform
        try:
            hello = r.spawn()
        except Exception as e:  # noqa: BLE001 — a failed scale-up
            self.error("scale-up replica %d failed to spawn: %s: %s",
                       idx, type(e).__name__, e)
            try:
                r.close(kill=True)
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass
            return None
        with self._lock:
            while len(self._routed) <= idx:
                self._routed.append(0)
            # the new member serves the replicated hot prefix; the
            # partitioned tail keeps its existing owners
            for m in self._hot_models:
                placed = self.placement.get(m)
                if placed is not None and idx not in placed:
                    placed.append(idx)
        # health record BEFORE routing can see it (fleet.add puts it
        # in the shared replicas list every picker iterates)
        self.sentinel.add_replica(r)
        self.fleet.add(r)
        telemetry.counter(events.CTR_FLEET_SCALE_UPS).inc()
        telemetry.event(events.EV_FLEET_REPLICA_SPAWNED,
                        replica=idx, pid=hello.get("pid"),
                        models=sorted(r.models))
        telemetry.event(events.EV_FLEET_SCALE_UP, replica=idx,
                        pid=hello.get("pid"), cause=cause,
                        warm_dir=warm is not None,
                        n_replicas=len(self.replicas), **info)
        telemetry.event(events.EV_FLEET_PLACEMENT,
                        placement=self.placement)
        self.info("scale-up: replica %d (pid %s, %s install dir) — "
                  "fleet now %d", idx, hello.get("pid"),
                  "warm" if warm else "cold", len(self.replicas))
        return r

    def retire_replica(self, cause: str = "manual",
                       drain_timeout: float = 30.0,
                       **info: Any) -> Optional[int]:
        """Scale down by one replica, in the only safe order:

        1. mark it ``retiring`` — routing and the hedge/mirror picks
           exclude it immediately, the monitor stops supervising it;
        2. re-place its EXCLUSIVE models onto a survivor (every
           replica spawns with the full model set, so the survivor
           LRU-loads on first request — a shrunk fleet can never 404
           a tail model);
        3. drain its router-side in-flight queue (the requests it
           already accepted finish normally);
        4. only THEN remove it from supervision and SIGTERM it — the
           hive's graceful-stop path drains its batcher, dumps the
           flight recorder, and exits 14;
        5. pool its install dir for the next scale-up.

        Returns the retired replica's idx, or None when the fleet has
        no retirable member (a lone or all-unhealthy fleet)."""
        with self._lock:
            # the youngest healthy member retires: founding replicas
            # carry the longest stats history (and any CLI pins)
            cands = [r for r in self.replicas
                     if not r.retiring and r.healthy]
            if len(cands) < 2:
                return None
            victim = max(cands, key=lambda r: r.idx)
            victim.retiring = True
        survivors = [r for r in self.replicas
                     if r is not victim and not r.retiring]
        with self._lock:
            replaced = []
            for m, placed in self.placement.items():
                kept = [i for i in placed if i != victim.idx]
                if not kept:
                    # exclusive model: hand it to the least-loaded
                    # live survivor BEFORE any traffic can miss it
                    tgt = min(
                        (r for r in survivors if r.healthy),
                        key=lambda r: (r.inflight, r.idx),
                        default=survivors[0])
                    kept = [tgt.idx]
                    replaced.append((m, tgt.idx))
                self.placement[m] = kept
        if replaced:
            telemetry.event(events.EV_FLEET_PLACEMENT,
                            placement=self.placement)
            self.info("retire: re-placed exclusive models %s off "
                      "replica %d", replaced, victim.idx)
        # drain what it already accepted — new work stopped at step 1
        deadline = time.monotonic() + drain_timeout
        while victim.inflight > 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        drained = victim.inflight == 0
        self.fleet.remove(victim)
        self.sentinel.remove_replica(victim)
        rc = None
        if victim.client is not None and victim.alive:
            victim.client.sigterm()
            try:
                rc = victim.client.wait(timeout=30.0)
            except Exception:  # noqa: BLE001 — stuck in its drain
                pass
        victim.close(kill=True)   # idempotent reap
        with self._lock:
            self._warm_dirs.append(victim.install_dir)
        telemetry.counter(events.CTR_FLEET_SCALE_DOWNS).inc()
        telemetry.counter(events.CTR_FLEET_RETIRED).inc()
        telemetry.event(events.EV_FLEET_REPLICA_RETIRED,
                        replica=victim.idx, rc=rc, drained=drained,
                        replaced=[m for m, _ in replaced])
        telemetry.event(events.EV_FLEET_SCALE_DOWN,
                        replica=victim.idx, cause=cause, rc=rc,
                        n_replicas=len(self.replicas), **info)
        self.info("scale-down: replica %d retired (drained=%s, "
                  "rc=%s) — fleet now %d", victim.idx, drained, rc,
                  len(self.replicas))
        return victim.idx

    def apply_degradation(self, rung: str, engage: bool,
                          cause: str = "manual",
                          **info: Any) -> None:
        """Flip one ladder rung's lever.  ``learner`` fans the
        suspend/resume op to every live replica; ``hedge`` gates the
        hedged-request path; ``shed_tail`` sheds non-hot models with
        an explicit degraded/overloaded response."""
        if rung == "learner":
            for r in list(self.replicas):
                if not r.healthy or r.client is None or r.retiring:
                    continue
                try:
                    r.client.learner_ctl(engage, timeout=10.0)
                except Exception as e:  # noqa: BLE001 — a replica
                    # mid-respawn just misses the rung; the monitor's
                    # respawn spawns with the learner in default state
                    self.warning("learner_ctl(%s) failed on replica "
                                 "%d: %s", engage, r.idx, e)
        elif rung == "hedge":
            self.hedging_enabled = not engage
        elif rung == "shed_tail":
            self.shed_tail = engage
        else:
            raise ValueError(f"unknown degradation rung {rung!r}")
        telemetry.event(
            events.EV_FLEET_DEGRADE_ENGAGE if engage
            else events.EV_FLEET_DEGRADE_RELEASE,
            rung=rung, cause=cause, **info)

    # -- introspection -------------------------------------------------

    def routed_counts(self) -> List[int]:
        """Requests routed per replica index (request spreading)."""
        with self._lock:
            return list(self._routed)

    def inflight_total(self) -> int:
        return sum(r.inflight for r in list(self.replicas))

    def replica_stats(self, timeout: float = 30.0) \
            -> List[Optional[Dict[str, Any]]]:
        """Each healthy replica's live telemetry snapshot (None for a
        dead slot) — the bench's per-replica recompile audit."""
        out: List[Optional[Dict[str, Any]]] = []
        for r in list(self.replicas):
            if r.healthy and r.client is not None:
                try:
                    out.append(r.client.stats(timeout=timeout))
                    continue
                except (ReplicaDied, TimeoutError):
                    pass
            out.append(None)
        return out

    def fleet_status(self) -> Dict[str, Any]:
        """One JSON-ready view of the fleet (the CLI's op=fleet),
        including each replica's sentinel health row — the operator's
        answer to "why is replica i out of rotation"."""
        return {
            "replicas": [
                {"replica": r.idx, "pid": r.pid,
                 "healthy": r.healthy, "inflight": r.inflight,
                 "routed": self.routed_counts()[r.idx],
                 "deaths": r.deaths,
                 "platform": r.platform,
                 "devices": r.devices,
                 "device_budget": (r.capacity_bytes // r.devices
                                   if r.capacity_bytes else None),
                 "ema_dispatch_ms": round(
                     1000 * r.ema_dispatch_s, 3)
                 if r.ema_dispatch_s else None,
                 "retiring": r.retiring,
                 "sentinel": self.sentinel.status(r)}
                for r in list(self.replicas)],
            "n_replicas": len(self.replicas),
            "hedging_enabled": self.hedging_enabled,
            "shed_tail": self.shed_tail,
            "warm_dirs": len(self._warm_dirs),
            "placement": self.placement,
            "canaries": {c: {"of": p, "fraction": f}
                         for c, (p, f) in self.canaries.items()},
            "slo_p99_ms": self.slo_p99_ms,
            "max_inflight": self.max_inflight,
            "deadline_ms": self.deadline_ms,
            "hedge_rate": round(self.sentinel.hedge_rate(), 4),
        }

    # -- teardown ------------------------------------------------------

    def drain(self, timeout: float = 30.0) -> bool:
        """Wait for every in-flight request to resolve."""
        deadline = time.monotonic() + timeout
        while self.inflight_total() > 0:
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.02)
        return True

    def close(self, kill: bool = False, reason: Optional[str] = None,
              code: int = 0) -> None:
        if self._closed:
            return
        self._closed = True
        self.sentinel.close()
        self.fleet.close(kill=kill)
        telemetry.event(events.EV_FLEET_SHUTDOWN,
                        routed=self.routed_counts(), reason=reason,
                        code=code)
        telemetry.flush()

    def __enter__(self) -> "FleetRouter":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -- the CLI front end -------------------------------------------------

def parse_mesh(specs: List[str]) -> Any:
    """``--mesh`` specs -> the FleetRouter ``mesh`` argument: a bare
    ``N`` meshes every replica with N devices; ``I=N`` (repeatable)
    meshes only replica I — the mixed 1-device / N-device fleet."""
    if not specs:
        return None
    per: Dict[int, int] = {}
    uniform: Optional[int] = None
    for s in specs:
        idx, eq, n = s.partition("=")
        if eq:
            per[int(idx)] = int(n)
        else:
            uniform = int(s)
    if per and uniform is not None:
        raise ValueError(
            "mix of bare N and I=N --mesh specs; use one form")
    return per if per else uniform


def parse_canary(spec: str) -> Tuple[str, str, Optional[float]]:
    """``CNAME=PRIMARY[:FRACTION]`` -> (cname, primary, fraction)."""
    cname, _, rest = spec.partition("=")
    primary, _, frac_s = rest.partition(":")
    if not cname or not primary:
        raise ValueError(
            f"bad --canary spec {spec!r} (want "
            f"CANARY=PRIMARY[:FRACTION])")
    frac = None
    if frac_s:
        frac = float(frac_s)
    return cname, primary, frac


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="veles_tpu --serve-fleet",
        description="Swarm: SLO-aware fleet router over N Hive "
                    "replicas")
    p.add_argument("replicas", type=int,
                   help="replica count (each is one --serve-models "
                        "subprocess)")
    p.add_argument("models", nargs="+", metavar="NAME=PKG",
                   help="model name = Forge ensemble package path; "
                        "DECLARATION ORDER is the placement hotness "
                        "order")
    p.add_argument("-b", "--backend", default="auto")
    p.add_argument("--mesh", action="append", default=[],
                   metavar="N|I=N",
                   help="devices per replica: a bare N meshes EVERY "
                        "replica, I=N (repeatable) meshes only "
                        "replica I — the fleet topology becomes "
                        "replicas x mesh and placement follows each "
                        "replica's advertised capacity "
                        "($VELES_SERVE_MESH)")
    p.add_argument("--canary", action="append", default=[],
                   metavar="CNAME=PRIMARY[:FRACTION]",
                   help="register model CNAME as canary-of:PRIMARY, "
                        "mirroring FRACTION of PRIMARY's traffic "
                        "(default $VELES_FLEET_CANARY_FRACTION)")
    p.add_argument("--hot", action="append", default=None,
                   metavar="NAME",
                   help="override the placement hot set (repeatable); "
                        "hot models replicate on every replica")
    p.add_argument("--max-batch", type=int,
                   default=int(knobs.get(knobs.SERVE_MAX_BATCH)))
    p.add_argument("--max-wait-ms", type=float,
                   default=float(knobs.get(knobs.SERVE_MAX_WAIT_MS)))
    p.add_argument("--hbm-budget", type=int, default=0,
                   help="per-replica residency budget override")
    p.add_argument("--slo-p99-ms", type=float,
                   default=float(knobs.get(knobs.FLEET_SLO_P99_MS)),
                   help="admission-control SLO target "
                        "($VELES_FLEET_SLO_P99_MS; 0 disables "
                        "shedding)")
    p.add_argument("--max-inflight", type=int,
                   default=int(knobs.get(knobs.FLEET_MAX_INFLIGHT)),
                   help="per-replica in-flight bound "
                        "($VELES_FLEET_MAX_INFLIGHT)")
    p.add_argument("--deadline-ms", type=float,
                   default=float(knobs.get(knobs.FLEET_DEADLINE_MS)),
                   help="default per-request deadline budget "
                        "($VELES_FLEET_DEADLINE_MS); a request's "
                        "own deadline_ms field overrides it")
    p.add_argument("--heartbeat-every", type=float,
                   default=float(knobs.get(knobs.HEARTBEAT_EVERY)))
    p.add_argument("--metrics-dir", default=None,
                   help="fleet Sightline dir; each replica writes "
                        "into replica-<i>/ under it")
    p.add_argument("-v", "--verbose", action="store_true")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    from concurrent.futures import ThreadPoolExecutor

    from veles_tpu.logger import setup_logging

    args = build_parser().parse_args(argv)
    setup_logging(10 if args.verbose else 20)
    if args.replicas < 1:
        print(f"--serve-fleet: replica count must be >= 1 "
              f"(got {args.replicas})", file=sys.stderr)
        return 2
    specs: Dict[str, str] = {}
    for spec in args.models:
        name, _, path = spec.partition("=")
        if not name or not path:
            print(f"--serve-fleet: bad model spec {spec!r} "
                  f"(want NAME=PACKAGE.vpkg)", file=sys.stderr)
            return 2
        if not os.path.isfile(path):
            print(f"--serve-fleet: no such package {path!r}",
                  file=sys.stderr)
            return 2
        specs[name] = path
    canaries: Dict[str, Tuple[str, Optional[float]]] = {}
    try:
        for cspec in args.canary:
            cname, primary, frac = parse_canary(cspec)
            canaries[cname] = (primary, frac)
        router = FleetRouter(
            specs, args.replicas, backend=args.backend,
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            hbm_budget=args.hbm_budget or None,
            heartbeat_every=args.heartbeat_every,
            metrics_dir=args.metrics_dir,
            canaries=canaries,
            mesh=parse_mesh(args.mesh),
            placement=PlacementPolicy(
                budget_bytes=args.hbm_budget or None,
                hot=set(args.hot) if args.hot else None),
            slo_p99_ms=args.slo_p99_ms,
            max_inflight=args.max_inflight,
            deadline_ms=args.deadline_ms)
    except (ValueError, RuntimeError) as e:
        print(f"--serve-fleet: {e}", file=sys.stderr)
        return 2

    emit_lock = witness.lock("router.emit")

    def emit(obj: Dict[str, Any]) -> None:
        with emit_lock:
            print(json.dumps(obj), flush=True)

    emit({"ready": True, "pid": os.getpid(),
          # where the REPLICAS run (this parent never loads jax)
          "platform": router.platform,
          "device_kind": router.device_kind,
          "fleet": args.replicas,
          "replica_pids": [r.pid for r in router.replicas],
          "models": router.hello_models,
          "placement": router.placement,
          "canaries": {c: {"of": p, "fraction": f}
                       for c, (p, f) in router.canaries.items()},
          "slo_p99_ms": router.slo_p99_ms,
          "max_inflight": router.max_inflight})
    telemetry.flush()

    stop = {"signal": None}
    stop_event = threading.Event()

    def _on_term(signum, frame) -> None:
        if stop["signal"] is not None:
            os.write(2, b"fleet: second signal - hard exit\n")
            os._exit(EXIT_PREEMPTED)
        stop["signal"] = signum
        stop_event.set()

    try:
        signal.signal(signal.SIGTERM, _on_term)
        signal.signal(signal.SIGINT, _on_term)
    except (ValueError, OSError):   # embedded / non-main thread
        pass

    hb_stop = threading.Event()

    def _hb_loop() -> None:
        n = 0
        while not hb_stop.wait(args.heartbeat_every):
            emit({"hb": n, "pid": os.getpid()})
            telemetry.maybe_flush()
            n += 1

    if args.heartbeat_every > 0:
        threading.Thread(target=_hb_loop, daemon=True,
                         name="fleet-heartbeat").start()

    jobs: "queue.Queue[Optional[str]]" = queue.Queue()

    def _read_stdin() -> None:
        for line in sys.stdin:
            jobs.put(line)
        jobs.put(None)   # EOF

    threading.Thread(target=_read_stdin, daemon=True,
                     name="fleet-stdin").start()

    pool = ThreadPoolExecutor(
        max_workers=min(64, 8 * args.replicas),
        thread_name_prefix="fleet-route")

    def handle(line: str) -> bool:
        """One request line; returns False when the loop should end."""
        line = line.strip()
        if not line:
            return True
        try:
            job = json.loads(line)
        except ValueError:
            emit({"error": f"bad request line: {line[:120]!r}"})
            return True
        op = job.get("op")
        if op == "shutdown":
            return False
        if op == "stats":
            emit({"id": job.get("id"), "stats": telemetry.snapshot()})
            return True
        if op == "fleet":
            emit({"id": job.get("id"),
                  "fleet": router.fleet_status()})
            return True
        jid = job.get("id")
        try:
            model = job["model"]
            rows = np.asarray(job["rows"], np.float32)
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as e:  # noqa: BLE001 — a bad request
            emit({"id": jid, "error": f"{type(e).__name__}: {e}"})
            return True

        def _route(jid=jid, model=model, rows=rows,
                   dl=job.get("deadline_ms")) -> None:
            # a client-supplied deadline_ms rides through unchanged —
            # the fleet front end is deadline-transparent
            resp = router.request(model, rows, deadline_ms=dl)
            resp = dict(resp)
            resp["id"] = jid
            emit(resp)

        def _route_done(f, jid=jid) -> None:
            # a routing thread that died past router.request (broken
            # stdout, encode error) must not vanish into the
            # executor: count it, so "answers stopped" has a signal
            err = f.exception()
            if err is not None:
                telemetry.counter(
                    events.CTR_FLEET_REQUEST_ERRORS).inc()
                print(f"fleet: request {jid} route thread died: "
                      f"{type(err).__name__}: {err}",
                      file=sys.stderr)

        pool.submit(_route).add_done_callback(_route_done)
        return True

    rc = 0
    while not stop_event.is_set():
        try:
            line = jobs.get(timeout=0.2)
        except queue.Empty:
            continue
        if line is None:      # stdin closed: the parent went away
            break
        if not handle(line):
            break

    # -- drain: accept what is already on the wire, then let every
    # in-flight routed request resolve before the replicas go down
    if stop_event.is_set():
        time.sleep(0.3)
    n_late = 0
    while True:
        try:
            line = jobs.get_nowait()
        except queue.Empty:
            break
        if line is None:
            continue
        n_late += 1
        handle(line)
    pool.shutdown(wait=True)
    drained = router.drain()
    telemetry.event(events.EV_FLEET_DRAIN, late_requests=n_late,
                    complete=bool(drained))
    reason = None
    if stop["signal"] is not None:
        try:
            reason = signal.Signals(stop["signal"]).name
        except ValueError:
            reason = f"sig{stop['signal']}"
        rc = EXIT_PREEMPTED
        # flight-recorder SIGTERM hook: the router's recent legs and
        # hedges reach disk before the replicas are torn down
        trace.dump("sigterm")
    router.close(reason=reason, code=rc)
    hb_stop.set()
    telemetry.flush()
    if rc:
        # the Phoenix preemption contract: a supervised fleet resumes
        # with warm replica install dirs
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(rc)
    return rc


if __name__ == "__main__":
    sys.exit(main())
