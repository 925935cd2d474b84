"""Swarm fleet serving (ISSUE 11 tentpole): N Hive replicas behind
one SLO-aware router — placement, least-loaded routing, canary traffic
mirroring, admission-control shedding, and SIGKILL failover with zero
lost requests.

The subprocess suites spawn REAL 2-replica fleets (each replica is a
full ``--serve-models`` child) and drive them with concurrent client
threads, asserting (a) responses match the host member-loop oracle,
(b) requests spread over both replicas, (c) a canary registered as
``canary-of:alpha`` receives its traffic split within tolerance,
(d) overload sheds with an explicit ``overloaded`` response (never a
timeout), and (e) killing one replica mid-load loses ZERO in-flight
requests — they are retried once on the healthy peer while the
monitor respawns the corpse.
"""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

WF_TEXT = textwrap.dedent("""
    from veles_tpu import prng
    from veles_tpu.datasets import synthetic_classification
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.ops.standard_workflow import StandardWorkflow

    def create_workflow(launcher):
        prng.seed_all(4242)
        train, valid, _ = synthetic_classification(
            64, 16, (6, 6, 1), n_classes=3, seed=5)
        return StandardWorkflow(
            loader_factory=lambda w: ArrayLoader(
                w, train=train, valid=valid, minibatch_size=16,
                name="loader"),
            layers=[
                {"type": "all2all_tanh",
                 "->": {"output_sample_shape": 12},
                 "<-": {"learning_rate": 0.1}},
                {"type": "softmax", "->": {"output_sample_shape": 3},
                 "<-": {"learning_rate": 0.1}},
            ],
            decision_config={"max_epochs": 2}, name="fleet_wf")
""")


def _build_package(d, name, seed, n_members=3):
    """One Forge ensemble package + its host oracle ingredients
    (the test_serve recipe)."""
    from veles_tpu import prng
    from veles_tpu.backends import NumpyDevice
    from veles_tpu.ensemble.packaging import pack_ensemble
    from veles_tpu.launcher import load_workflow_module

    wf_path = os.path.join(d, f"wf_{name}.py")
    with open(wf_path, "w") as f:
        f.write(WF_TEXT)
    mod = load_workflow_module(wf_path)

    class FL:
        workflow = None

    prng.seed_all(seed)
    w = mod.create_workflow(FL())
    w.initialize(device=NumpyDevice())
    base = {fw.name: {k: np.asarray(v) for k, v in
                      fw.gather_params().items()}
            for fw in w.forwards}
    rng = np.random.default_rng(seed)
    members = []
    for _ in range(n_members):
        params = {fn: {pn: (a + 0.05 * rng.standard_normal(a.shape)
                            .astype(np.float32))
                       for pn, a in p.items()}
                  for fn, p in base.items()}
        members.append({"params": params, "valid_error": 0.0,
                        "seed": seed,
                        "forward_names": [fw.name
                                          for fw in w.forwards],
                        "values": None})
    pkg = os.path.join(d, f"{name}.vpkg")
    pack_ensemble(pkg, name, members, wf_path)
    return {"pkg": pkg, "members": members, "workflow": w}


def _host_oracle(model, x):
    acc = None
    for m in model["members"]:
        out = np.asarray(x, np.float32)
        for fw in model["workflow"].forwards:
            p = {k: np.asarray(v)
                 for k, v in m["params"][fw.name].items()}
            out, _ = fw.apply_fwd(p, out, rng=None, train=False)
        out = np.asarray(out)
        acc = out if acc is None else acc + out
    return acc / len(model["members"])


@pytest.fixture(scope="module")
def packages(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("fleet_pkgs"))
    return {"alpha": _build_package(d, "alpha", 11),
            "beta": _build_package(d, "beta", 22)}


class TestPlacementPolicy:
    """Pure placement math: hot prefix replicated, tail partitioned."""

    def _policy(self, **kw):
        from veles_tpu.serve.fleet import PlacementPolicy
        return PlacementPolicy(**kw)

    def test_hot_prefix_replicates_until_budget(self):
        pl = self._policy(budget_bytes=100).assign(
            {"a": 40, "b": 40, "c": 40, "d": 10}, 2)
        assert pl["a"] == [0, 1] and pl["b"] == [0, 1]
        # c would overflow 100 on every replica: the hot prefix ends
        # and the tail partitions onto least-filled bins
        assert len(pl["c"]) == 1 and len(pl["d"]) == 1
        assert pl["c"] != pl["d"]

    def test_explicit_hot_set_overrides_prefix(self):
        pl = self._policy(budget_bytes=100, hot={"c"}).assign(
            {"a": 40, "b": 40, "c": 40}, 3)
        assert pl["c"] == [0, 1, 2]
        assert len(pl["a"]) == 1 and len(pl["b"]) == 1

    def test_everything_fits_everything_replicates(self):
        pl = self._policy(budget_bytes=1 << 30).assign(
            {"a": 10, "b": 10}, 4)
        assert pl == {"a": [0, 1, 2, 3], "b": [0, 1, 2, 3]}

    def test_single_replica_degenerates_to_hive(self):
        pl = self._policy(budget_bytes=50).assign(
            {"a": 40, "b": 40}, 1)
        assert pl == {"a": [0], "b": [0]}


class TestFleetRoundTrip:
    """(a)-(d) against one real 2-replica fleet: oracle parity under
    concurrent clients, request spreading, the canary split, and
    shed-on-overload semantics."""

    @pytest.fixture(scope="class")
    def router(self, packages, tmp_path_factory):
        from veles_tpu.serve.router import FleetRouter
        mdir = str(tmp_path_factory.mktemp("fleet_metrics"))
        r = FleetRouter(
            {"alpha": packages["alpha"]["pkg"],
             "beta": packages["beta"]["pkg"]},
            n_replicas=2, backend="cpu", max_batch=16, max_wait_ms=5,
            canaries={"beta": ("alpha", 0.25)},
            metrics_dir=mdir, cwd=REPO)
        r.metrics_dir_path = mdir
        yield r
        r.close()

    def test_fleet_comes_up_with_placement(self, router):
        assert len(router.replicas) == 2
        assert all(r.healthy for r in router.replicas)
        # both tiny models fit every replica's budget: replicated
        assert router.placement == {"alpha": [0, 1], "beta": [0, 1]}
        assert router.canaries == {"beta": ("alpha", 0.25)}

    def test_concurrent_responses_match_host_oracle(self, router,
                                                    packages):
        errs = []

        def worker(i):
            try:
                rng = np.random.default_rng(100 + i)
                name = "alpha" if i % 2 == 0 else "beta"
                for _ in range(4):
                    x = rng.standard_normal((2, 6, 6, 1)) \
                        .astype(np.float32)
                    r = router.request(name, x, timeout=60)
                    assert "probs" in r, r
                    got = np.asarray(r["probs"], np.float32)
                    want = _host_oracle(packages[name], x)
                    np.testing.assert_allclose(got, want, atol=1e-4)
            except Exception as e:  # noqa: BLE001 — collected below
                errs.append(e)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs, errs

    def test_requests_spread_over_both_replicas(self, router):
        # enough sequential traffic that least-loaded routing must
        # alternate (an idle peer is always less loaded)
        x = np.ones((1, 6, 6, 1), np.float32)
        for _ in range(8):
            assert "probs" in router.request("alpha", x)
        counts = router.routed_counts()
        assert len(counts) == 2 and all(c > 0 for c in counts), counts

    def test_canary_receives_its_traffic_split(self, router):
        from veles_tpu import telemetry
        x = np.ones((1, 6, 6, 1), np.float32)
        req0 = telemetry.counter("fleet.model.alpha.requests").value
        mir0 = telemetry.counter("fleet.model.beta.mirrored").value
        n = 40
        for _ in range(n):
            assert "probs" in router.request("alpha", x)
        d_req = telemetry.counter(
            "fleet.model.alpha.requests").value - req0
        d_mir = telemetry.counter(
            "fleet.model.beta.mirrored").value - mir0
        assert d_req == n
        # deterministic stride sampling: 0.25 of 40 = 10 mirrors
        # (+-1 for the accumulator's starting phase)
        assert abs(d_mir / n - 0.25) <= 0.05, (d_mir, n)
        # the mirrors resolve asynchronously and land in the canary's
        # own latency/error split
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            done = telemetry.histogram(
                "fleet.model.beta.request_seconds").count \
                + telemetry.counter("fleet.model.beta.errors").value
            if done >= d_mir:
                break
            time.sleep(0.1)
        assert telemetry.histogram(
            "fleet.model.beta.request_seconds").count > 0
        assert telemetry.counter("fleet.model.beta.errors").value == 0

    def test_overload_sheds_explicitly_not_by_timeout(self, router):
        from veles_tpu import telemetry
        x = np.ones((1, 6, 6, 1), np.float32)
        shed0 = telemetry.counter("fleet.shed").value
        saved_slo, saved_inflight = router.slo_p99_ms, \
            router.max_inflight
        try:
            # (1) the SLO estimate path: an impossible 0.5ms target
            # means even an idle replica's batching window blows it
            router.slo_p99_ms = 0.5
            t0 = time.perf_counter()
            r = router.request("alpha", x, timeout=60)
            dt = time.perf_counter() - t0
            assert r.get("overloaded") is True, r
            assert r["error"] == "overloaded"
            assert "est_ms" in r
            assert dt < 5.0   # a shed answers immediately, never by
            #                   waiting out the request timeout
            # (2) the bounded-queue path
            router.slo_p99_ms = 0.0
            router.max_inflight = 0
            r = router.request("alpha", x, timeout=60)
            assert r.get("overloaded") is True, r
        finally:
            router.slo_p99_ms, router.max_inflight = saved_slo, \
                saved_inflight
        assert telemetry.counter("fleet.shed").value - shed0 == 2
        assert telemetry.counter(
            "fleet.model.alpha.shed").value >= 2
        # admission restored: the fleet serves again
        assert "probs" in router.request("alpha", x)

    def test_per_replica_metrics_dirs_written(self, router):
        from veles_tpu import telemetry
        telemetry.flush()
        for i in (0, 1):
            d = os.path.join(router.metrics_dir_path, f"replica-{i}")
            assert os.path.isdir(d), d
            # each replica flushed at least its hello-time snapshot
            files = os.listdir(d)
            assert any(fn.startswith("journal-") for fn in files), \
                files

    def test_obs_fleet_view_reads_real_replica_dirs(self, router):
        from veles_tpu.obs import fleet_rows, render_fleet
        rows = fleet_rows(router.metrics_dir_path)
        assert [r["replica"] for r in rows] == [0, 1]
        live_pids = {r.pid for r in router.replicas}
        assert {r["pid"] for r in rows} == live_pids
        out = render_fleet(router.metrics_dir_path)
        assert "fleet replicas" in out


class TestFleetFailover:
    """(e) SIGKILL one replica mid-load: zero lost requests (retried
    once on the healthy peer), and the monitor respawns the corpse
    with its warm install dir."""

    def test_sigkill_mid_load_loses_nothing(self, packages,
                                            tmp_path_factory):
        from veles_tpu import telemetry
        from veles_tpu.serve.router import FleetRouter
        mdir = str(tmp_path_factory.mktemp("fleet_kill"))
        router = FleetRouter(
            {"alpha": packages["alpha"]["pkg"]},
            n_replicas=2, backend="cpu", max_batch=16, max_wait_ms=5,
            metrics_dir=mdir, cwd=REPO, respawn_backoff=0.25)
        try:
            x = np.ones((2, 6, 6, 1), np.float32)
            want = _host_oracle(packages["alpha"], x)
            assert "probs" in router.request("alpha", x)   # warm
            results = []
            errs = []
            per_worker = 15

            def worker(i):
                try:
                    for k in range(per_worker):
                        if i == 0 and k == 3:
                            # SIGKILL mid-load, synchronously: the
                            # other five closed-loop workers have
                            # requests in flight on both replicas
                            router.replicas[0].client.proc.kill()
                        r = router.request("alpha", x, timeout=60)
                        results.append(r)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

            threads = [threading.Thread(target=worker, args=(i,))
                       for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs
            # ZERO lost: every request answered with real
            # probabilities (no errors, no timeouts), oracle-exact
            assert len(results) == 6 * per_worker
            for r in results:
                assert "probs" in r, r
                np.testing.assert_allclose(
                    np.asarray(r["probs"], np.float32), want,
                    atol=1e-4)
            # at least one in-flight request was retried on the peer
            assert telemetry.counter("fleet.retries").value >= 1
            # the monitor (0.25s tick) observes the death
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline \
                    and router.replicas[0].deaths < 1:
                time.sleep(0.1)
            assert router.replicas[0].deaths >= 1
            # the monitor respawns the replica (warm install dir)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if router.replicas[0].healthy:
                    break
                time.sleep(0.25)
            assert router.replicas[0].healthy, \
                "replica 0 was not respawned"
            assert "probs" in router.request("alpha", x)
            assert telemetry.counter(
                "fleet.replica_respawns").value >= 1
        finally:
            router.close(kill=True)


class TestFleetGrayFailures:
    """ISSUE 12 (Sentinel): gray failures — a replica that is slow,
    wedged, or corrupt while remaining process-alive and heartbeating.
    One replica of a REAL 2-replica fleet is armed via a per-replica
    VELES_FAULTS override; the router's deadline/hedge/integrity
    machinery must keep every client answer clean and bounded, eject
    the sick replica from routing, and (once the fault budget
    exhausts) reinstate it after consecutive clean probes."""

    def _gray_router(self, packages, tmp_path_factory, fault, name,
                     **kw):
        from veles_tpu.serve.router import FleetRouter
        mdir = str(tmp_path_factory.mktemp(name))
        defaults = dict(
            n_replicas=2, backend="cpu", max_batch=16, max_wait_ms=5,
            metrics_dir=mdir, cwd=REPO,
            env_overrides={0: {"VELES_FAULTS": fault}})
        defaults.update(kw)
        r = FleetRouter({"alpha": packages["alpha"]["pkg"]},
                        **defaults)
        r.metrics_dir_path = mdir
        return r

    @staticmethod
    def _ctr(name):
        from veles_tpu import telemetry
        return telemetry.counter(name).value

    def test_slow_replica_hedged_ejected_then_reinstated(
            self, packages, tmp_path_factory):
        # replica 0's every dispatch stalls 1.5s for the first 6
        # firings (requests AND probes consume the budget), then the
        # fault exhausts and the replica is genuinely healthy again
        router = self._gray_router(
            packages, tmp_path_factory,
            "hive.slow_dispatch@label=alpha&times=6&seconds=1.5",
            "fleet_gray_slow",
            deadline_ms=8000, hedge_min_ms=60, hedge_budget=1.0,
            probe_interval=0.2, probe_ok=2, probe_backoff_cap=0.4)
        try:
            hedges0 = self._ctr("fleet.hedge.issued")
            wins0 = self._ctr("fleet.hedge.wins")
            eject0 = self._ctr("fleet.eject.total")
            x = np.ones((1, 6, 6, 1), np.float32)
            want = _host_oracle(packages["alpha"], x)
            lats = []
            for _ in range(30):
                t0 = time.perf_counter()
                r = router.request("alpha", x, timeout=30)
                lats.append(time.perf_counter() - t0)
                # EVERY answer is clean despite the slow replica: the
                # hedge (or post-ejection routing) covered it
                assert "probs" in r, r
                np.testing.assert_allclose(
                    np.asarray(r["probs"], np.float32), want,
                    atol=1e-4)
                if self._ctr("fleet.eject.total") > eject0:
                    break
            assert self._ctr("fleet.hedge.issued") > hedges0
            assert self._ctr("fleet.hedge.wins") > wins0
            assert self._ctr("fleet.eject.total") == eject0 + 1
            st = router.sentinel.status(router.replicas[0])
            assert st["state"] in ("ejected", "probing"), st
            assert st["strikes"].get("hedge_loss", 0) >= 1, st
            # post-ejection traffic routes around the sick replica and
            # p99 stays bounded: nothing waits out the 1.5s stall
            post = []
            for _ in range(10):
                t0 = time.perf_counter()
                r = router.request("alpha", x, timeout=30)
                assert "probs" in r, r
                post.append(time.perf_counter() - t0)
            assert max(post) < 1.0, post
            # the fault budget exhausts under probing; PROBE_OK=2
            # consecutive clean probes reinstate the replica
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                st = router.sentinel.status(router.replicas[0])
                if st["state"] == "healthy" \
                        and st["reinstatements"] >= 1:
                    break
                time.sleep(0.2)
            assert st["state"] == "healthy", st
            assert st["reinstatements"] >= 1, st
            assert self._ctr("fleet.eject.reinstated_total") >= 1
            # reinstated means ROUTABLE again: the fleet serves fine
            assert "probs" in router.request("alpha", x, timeout=30)
            # the hedge losers' late answers were dropped as stale,
            # never leaked into other waiters (all answers were clean)
            assert self._ctr("fleet.stale_response") >= 1
        finally:
            router.close(kill=True)

    def test_wedged_replica_detected_without_heartbeat_loss(
            self, packages, tmp_path_factory):
        # replica 0 swallows EVERY model request forever while its
        # heartbeats and stats keep flowing — invisible to the
        # heartbeat-deadline monitor, caught only by the sentinel
        router = self._gray_router(
            packages, tmp_path_factory, "hive.wedge@times=*",
            "fleet_gray_wedge",
            deadline_ms=5000, hedge_min_ms=60, hedge_budget=1.0,
            probe_interval=0.25, probe_ok=2, heartbeat_every=0.2)
        try:
            eject0 = self._ctr("fleet.eject.total")
            probe_fail0 = self._ctr("fleet.probe.fail")
            x = np.ones((1, 6, 6, 1), np.float32)
            for _ in range(25):
                # every request still answers (hedged onto the peer)
                assert "probs" in router.request("alpha", x,
                                                 timeout=30)
                if self._ctr("fleet.eject.total") > eject0:
                    break
            assert self._ctr("fleet.eject.total") == eject0 + 1
            st = router.sentinel.status(router.replicas[0])
            assert st["state"] in ("ejected", "probing"), st
            # DETECTION WITHOUT HEARTBEAT LOSS: the monitor never saw
            # a death (no EOF, no silence) — the process is alive and
            # chatting the whole time
            assert router.replicas[0].deaths == 0
            assert router.replicas[0].healthy
            assert router.replicas[0].client.heartbeats > 0
            # probes are swallowed too: the wedged replica can NEVER
            # pass its canary, so it stays out of rotation
            deadline = time.monotonic() + 15
            while time.monotonic() < deadline \
                    and self._ctr("fleet.probe.fail") <= probe_fail0:
                time.sleep(0.1)
            assert self._ctr("fleet.probe.fail") > probe_fail0
            st = router.sentinel.status(router.replicas[0])
            assert st["state"] in ("ejected", "probing"), st
        finally:
            router.close(kill=True)

    def test_garbage_response_never_reaches_a_client(
            self, packages, tmp_path_factory):
        # replica 0 corrupts every probability payload AFTER the crc
        # echo was computed from the clean one: the router's integrity
        # check must strike + retry on the peer so oracle parity holds
        router = self._gray_router(
            packages, tmp_path_factory,
            "hive.garbage_response@times=*", "fleet_gray_garbage",
            deadline_ms=8000, hedge_budget=0.0,
            probe_interval=0.25, probe_ok=2)
        try:
            strikes0 = self._ctr("fleet.integrity_strikes")
            retries0 = self._ctr("fleet.retries")
            eject0 = self._ctr("fleet.eject.total")
            x = np.ones((2, 6, 6, 1), np.float32)
            want = _host_oracle(packages["alpha"], x)
            for _ in range(20):
                r = router.request("alpha", x, timeout=30)
                # ZERO corrupt answers reach a client — every response
                # is oracle-exact (the corrupt ones were caught by the
                # checksum echo and retried on the healthy peer)
                assert "probs" in r, r
                np.testing.assert_allclose(
                    np.asarray(r["probs"], np.float32), want,
                    atol=1e-4)
            assert self._ctr("fleet.integrity_strikes") > strikes0
            assert self._ctr("fleet.retries") > retries0
            assert self._ctr("fleet.eject.total") == eject0 + 1
            st = router.sentinel.status(router.replicas[0])
            assert st["state"] in ("ejected", "probing"), st
            assert st["strikes"].get("integrity", 0) >= 2, st
            # probes read garbage too: reinstatement is impossible
            # while the fault is armed
            assert st["reinstatements"] == 0, st
            # the sentinel overlay reaches the operator surfaces
            fs = router.fleet_status()
            assert fs["replicas"][0]["sentinel"]["state"] in (
                "ejected", "probing")
            from veles_tpu import telemetry
            telemetry.flush()
            from veles_tpu.obs import fleet_rows
            rows = fleet_rows(router.metrics_dir_path)
            assert rows[0]["state"] in ("ejected", "probing"), rows
            assert rows[0]["health_score"] is not None
            assert rows[1]["state"] == "healthy", rows
        finally:
            router.close(kill=True)


class TestOnePlatformPerFleet:
    """PR 21: replicas say where they run in their own hello, and a
    fleet answers from ONE platform — `-b auto` with more replicas
    than chips (first claims the TPU, the rest land on XLA:CPU) is
    refused, and a (re)spawn that comes up elsewhere is a failed
    spawn, never a CPU stand-in under the same replica index."""

    def test_mixed_platform_fleet_is_refused(self, monkeypatch):
        from veles_tpu.serve import fleet, router

        def spawn(self):
            self.platform = "tpu" if self.idx == 0 else "cpu"
            return {"pid": 1000 + self.idx, "platform": self.platform}

        monkeypatch.setattr(fleet.Replica, "spawn", spawn)
        with pytest.raises(RuntimeError, match="different platforms"):
            router.FleetRouter({"m": "/no/such.vpkg"}, 2,
                               backend="auto")

    def test_respawn_on_another_platform_fails_the_spawn(
            self, monkeypatch):
        from veles_tpu.serve import fleet
        platforms = iter(["tpu", "cpu"])

        class Client:
            def __init__(self, *a, **k):
                self.hello = {"pid": 1, "platform": next(platforms),
                              "device_kind": "fake"}
                self.closed = False

            def close(self, kill=False):
                self.closed = True

        monkeypatch.setattr(fleet, "HiveClient", Client)
        r = fleet.Replica(0, {"m": "/no/such.vpkg"}, backend="auto")
        assert r.spawn()["platform"] == "tpu" and r.healthy
        with pytest.raises(RuntimeError, match="came up on 'cpu'"):
            r.spawn()
        assert r.client.closed and r.platform == "tpu"


class TestFleetCliProtocol:
    """The real ``python -m veles_tpu --serve-fleet N`` front end: the
    hello line carries fleet/placement/canary state, requests answer
    over the same JSONL protocol as a single hive, op=fleet reports
    per-replica health, and shutdown drains cleanly."""

    def test_cli_round_trip(self, packages):
        proc = subprocess.Popen(
            [sys.executable, "-m", "veles_tpu", "--serve-fleet", "2",
             f"alpha={packages['alpha']['pkg']}",
             f"beta={packages['beta']['pkg']}",
             "--canary", "beta=alpha:0.5",
             "-b", "cpu", "--max-batch", "8", "--max-wait-ms", "5"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            text=True, bufsize=1, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        try:
            def read_msg(timeout=180):
                deadline = time.monotonic() + timeout
                while time.monotonic() < deadline:
                    line = proc.stdout.readline()
                    if not line:
                        raise AssertionError(
                            f"fleet died rc={proc.poll()}")
                    line = line.strip()
                    if not line:
                        continue
                    try:
                        msg = json.loads(line)
                    except ValueError:
                        continue
                    if "hb" in msg:
                        continue
                    return msg
                raise AssertionError("no message in time")

            hello = read_msg()
            assert hello["ready"] and hello["fleet"] == 2
            # where the REPLICAS run, relayed by a parent that never
            # loads jax itself
            assert hello["platform"] == "cpu" and hello["device_kind"]
            assert set(hello["models"]) == {"alpha", "beta"}
            assert hello["canaries"]["beta"]["of"] == "alpha"
            assert len(hello["replica_pids"]) == 2

            x = np.ones((1, 6, 6, 1), np.float32)
            proc.stdin.write(json.dumps(
                {"id": 1, "model": "alpha",
                 "rows": x.tolist()}) + "\n")
            proc.stdin.flush()
            resp = read_msg()
            assert resp["id"] == 1 and "probs" in resp, resp

            proc.stdin.write(json.dumps(
                {"op": "fleet", "id": 2}) + "\n")
            proc.stdin.flush()
            st = read_msg()
            assert st["id"] == 2
            assert len(st["fleet"]["replicas"]) == 2
            assert all(r["healthy"] and r["platform"] == "cpu"
                       for r in st["fleet"]["replicas"])

            proc.stdin.write(json.dumps({"op": "shutdown"}) + "\n")
            proc.stdin.flush()
            assert proc.wait(timeout=60) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=10)
