"""Gauntlet: one accountable production day for the elastic fleet.

Usage::

    JAX_PLATFORMS=cpu python scripts/gauntlet.py [--json]
        [--duration S] [--trace FILE]

The drill every serving PR rehearsed one organ at a time, run as a
whole body instead: a seeded OPEN-LOOP day of traffic (diurnal swing,
Poisson bursts, a Zipf model mix — veles_tpu/serve/traffic.py) is
fired at a FleetRouter whose replica count is owned by the
FleetAutoscaler (veles_tpu/serve/autoscale.py), with Evergreen armed
on every replica and chaos injected mid-day: a gray slow-dispatch
blip on the founding replica and a coordinated SIGTERM preemption in
the middle of a traffic burst.  The fleet must track the load curve
(scale up under the morning ramp, scale down through the evening
trough), hold its p99 in the non-degraded windows, and lose ZERO
answers.

Then the books are balanced.  The post-run ACCOUNTABILITY CHECK
replays the day from the outcome ledger plus the merged Sightline
journals (router process + every ``replica-*/`` subdir) and demands:

- every arrival in the trace has exactly one recorded outcome, and
  none of them is an error (sheds are honest, errors are lost answers);
- every ``probs`` payload's crc32 matches its echo, and a random
  sample of answers matches the host ensemble oracle bit-close;
- every scale-up/scale-down/degradation/retirement/ejection/
  promotion/rollback event in the journals carries its recorded
  cause — an unexplained fleet mutation fails the day.

The last stdout line is one JSON record.  Sizing knobs are
``GAUNTLET_*`` env vars; the CI day is ~3 minutes, the ``-m slow``
pytest wrapper raises GAUNTLET_DURATION to an hours-long soak.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import textwrap
import threading
import time
import zlib

# the gauntlet is a CPU rehearsal: pin BEFORE any jax import so it
# can run next to (not on) a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def log(msg: str) -> None:
    print(f"[gauntlet] {msg}", file=sys.stderr, flush=True)


def _env_f(name: str, default: float) -> float:
    return float(os.environ.get(name, default))


def _env_i(name: str, default: int) -> int:
    return int(os.environ.get(name, default))


# -- the workload ------------------------------------------------------

#: fixed rows per request: one dispatch shape, one compile per replica
ROWS_PER_REQUEST = 8
INPUT_SHAPE = (6, 6, 1)

WF_TEXT = textwrap.dedent(f"""
    from veles_tpu import prng
    from veles_tpu.datasets import synthetic_classification
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.ops.standard_workflow import StandardWorkflow

    def create_workflow(launcher):
        prng.seed_all(2020)
        train, valid, _ = synthetic_classification(
            64, 16, {INPUT_SHAPE}, n_classes=3, seed=9)
        return StandardWorkflow(
            loader_factory=lambda w: ArrayLoader(
                w, train=train, valid=valid, minibatch_size=16,
                name="loader"),
            layers=[
                {{"type": "all2all_tanh",
                  "->": {{"output_sample_shape": 64}},
                  "<-": {{"learning_rate": 0.1}}}},
                {{"type": "softmax", "->": {{"output_sample_shape": 3}},
                  "<-": {{"learning_rate": 0.1}}}},
            ],
            decision_config={{"max_epochs": 2}}, name="gauntlet_wf")
""")


def _build_package(d: str, members: int = 2):
    """One Forge ensemble package + the host-oracle ingredients (the
    test_serve recipe); registered under all three Zipf model names."""
    from veles_tpu import prng
    from veles_tpu.backends import NumpyDevice
    from veles_tpu.ensemble.packaging import pack_ensemble
    from veles_tpu.launcher import load_workflow_module

    wf_path = os.path.join(d, "wf_gauntlet.py")
    with open(wf_path, "w") as f:
        f.write(WF_TEXT)
    mod = load_workflow_module(wf_path)

    class _FL:
        workflow = None

    prng.seed_all(33)
    w = mod.create_workflow(_FL())
    w.initialize(device=NumpyDevice())
    base = {fw.name: {k: np.asarray(v) for k, v in
                      fw.gather_params().items()}
            for fw in w.forwards}
    rng = np.random.default_rng(33)
    ms = []
    for _ in range(members):
        params = {fn: {pn: (a + 0.05 * rng.standard_normal(a.shape)
                            .astype(np.float32))
                       for pn, a in p.items()}
                  for fn, p in base.items()}
        ms.append({"params": params, "valid_error": 0.0, "seed": 33,
                   "forward_names": [fw.name for fw in w.forwards],
                   "values": None})
    pkg = os.path.join(d, "gauntlet.vpkg")
    pack_ensemble(pkg, "gauntlet", ms, wf_path)
    return {"pkg": pkg, "members": ms, "workflow": w}


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


def _row_for(arrival) -> np.ndarray:
    """The arrival's input rows, regenerated from its trace seed —
    what makes every oracle spot check replayable after the fact."""
    rng = np.random.default_rng(arrival.row_seed)
    return rng.standard_normal(
        (ROWS_PER_REQUEST,) + INPUT_SHAPE).astype(np.float32)


# -- the journals ------------------------------------------------------

def _journal_events(mdir: str, name: str = None) -> list:
    """Events from every ``journal-*.jsonl`` under ``mdir`` —
    INCLUDING the per-replica subdirs (``replica-<i>/``), so the
    accountability check sees what the whole process tree reported."""
    evs = []
    pats = [os.path.join(mdir, "journal-*.jsonl"),
            os.path.join(mdir, "*", "journal-*.jsonl")]
    for pat in pats:
        for jf in glob.glob(pat):
            with open(jf) as f:
                for line in f:
                    try:
                        evs.append(json.loads(line))
                    except ValueError:
                        pass
    if name is not None:
        evs = [e for e in evs if e.get("event") == name]
    return sorted(evs, key=lambda e: e.get("ts", 0))


def accountability_check(mdir: str, preemptions: list) -> dict:
    """Balance the day's books: every fleet mutation in the merged
    journals must carry its recorded cause.  Returns the verdict
    record; ``unexplained`` non-empty fails the gauntlet."""
    from veles_tpu import events

    unexplained = []
    explained = 0

    #: events whose contract is an explicit ``cause`` field
    caused = [events.EV_FLEET_SCALE_UP, events.EV_FLEET_SCALE_DOWN,
              events.EV_FLEET_DEGRADE_ENGAGE,
              events.EV_FLEET_DEGRADE_RELEASE]
    for name in caused:
        for e in _journal_events(mdir, name):
            if e.get("cause"):
                explained += 1
            else:
                unexplained.append({"event": name, "record": e})

    # a retirement must tie back to a scale-down of the same replica
    downs = {e.get("replica")
             for e in _journal_events(mdir, events.EV_FLEET_SCALE_DOWN)}
    for e in _journal_events(mdir, events.EV_FLEET_REPLICA_RETIRED):
        if e.get("replica") in downs:
            explained += 1
        else:
            unexplained.append({"event": "orphan retirement",
                                "record": e})

    # an ejection's cause is its recorded score + strike count; a
    # reinstatement's is its clean-probe streak
    for e in _journal_events(mdir, events.EV_FLEET_REPLICA_EJECTED):
        if e.get("score") is not None and e.get("strikes") is not None:
            explained += 1
        else:
            unexplained.append({"event": "uncaused ejection",
                                "record": e})
    for e in _journal_events(mdir,
                             events.EV_FLEET_REPLICA_REINSTATED):
        if e.get("probes_ok"):
            explained += 1
        else:
            unexplained.append({"event": "uncaused reinstatement",
                                "record": e})

    # a promotion/rollback must carry the gate's measured standings
    for name in (events.EV_ONLINE_PROMOTED, events.EV_ONLINE_ROLLBACK):
        for e in _journal_events(mdir, name):
            if e.get("shadow_error_pct") is not None:
                explained += 1
            else:
                unexplained.append({"event": name, "record": e})

    # every replica death must be explained: a retirement (SIGTERM
    # drain), a coordinated preemption we injected, or — failing
    # those — a monitor respawn of the same slot AFTER the death
    # (crash + recovery, the journal pair the operator reads)
    retired = {e.get("replica") for e in _journal_events(
        mdir, events.EV_FLEET_REPLICA_RETIRED)}
    preempted = {p["replica"] for p in preemptions}
    spawns = _journal_events(mdir, events.EV_FLEET_REPLICA_SPAWNED)
    for e in _journal_events(mdir, events.EV_FLEET_REPLICA_DIED):
        idx = e.get("replica")
        if idx in retired or idx in preempted:
            explained += 1
        elif any(s.get("replica") == idx
                 and s.get("ts", 0) >= e.get("ts", 0)
                 for s in spawns):
            explained += 1
        else:
            unexplained.append({"event": "unexplained death",
                                "record": e})

    return {"explained": explained,
            "unexplained": unexplained,
            "accounted": not unexplained}


# -- the day -----------------------------------------------------------

def _spec():
    """The CI-sized day (every figure GAUNTLET_*-overridable): a
    >=10x diurnal swing with 2.5x bursts over ~3 minutes."""
    from veles_tpu.serve.traffic import TrafficSpec
    duration = _env_f("GAUNTLET_DURATION", 150.0)
    return TrafficSpec(
        seed=_env_i("GAUNTLET_SEED", 20),
        duration_s=duration,
        peak_rps=_env_f("GAUNTLET_PEAK_RPS", 30.0),
        swing=_env_f("GAUNTLET_SWING", 12.0),
        period_s=duration,
        burst_every_s=_env_f("GAUNTLET_BURST_EVERY", 25.0),
        burst_len_s=_env_f("GAUNTLET_BURST_LEN", 5.0),
        burst_mult=_env_f("GAUNTLET_BURST_MULT", 2.5),
        models=["hot", "warm", "tail"],
        zipf_s=_env_f("GAUNTLET_ZIPF_S", 1.1))


def _determinism_pin(spec, d: str) -> bool:
    """The replay contract, pinned on every run: the same seeded spec
    writes a byte-identical trace file twice."""
    import filecmp

    from veles_tpu.serve.traffic import generate, write_trace
    p1, p2 = os.path.join(d, "day_a.jsonl"), os.path.join(
        d, "day_b.jsonl")
    write_trace(p1, spec, generate(spec))
    write_trace(p2, spec, generate(spec))
    return filecmp.cmp(p1, p2, shallow=False)


def run_gauntlet(trace_path: str = None) -> dict:
    from veles_tpu import events, telemetry
    from veles_tpu.serve.autoscale import (FleetAutoscaler,
                                           ScaleController)
    from veles_tpu.serve.router import FleetRouter
    from veles_tpu.serve.traffic import (OpenLoopDriver,
                                         _burst_windows, generate,
                                         read_trace, write_trace)

    t_start = time.perf_counter()
    d = tempfile.mkdtemp(prefix="gauntlet_")
    mdir = os.path.join(d, "metrics")

    spec = _spec()
    log(f"day: {spec.duration_s:.0f}s, peak {spec.peak_rps:.0f} rps, "
        f"swing {spec.swing:.0f}x, bursts {spec.burst_mult:.1f}x")
    deterministic = _determinism_pin(spec, d)
    log(f"determinism pin: trace bitwise-equal={deterministic}")

    if trace_path:
        spec, arrivals = read_trace(trace_path)
        log(f"replaying {trace_path}: {len(arrivals)} arrivals")
    else:
        arrivals = generate(spec)
        trace_path = os.path.join(d, "day.jsonl")
        write_trace(trace_path, spec, arrivals)
        log(f"generated {len(arrivals)} arrivals -> {trace_path}")

    log("packing the ensemble (one package, three Zipf names)")
    model = _build_package(d,
                           members=_env_i("GAUNTLET_MEMBERS", 2))
    specs = {name: model["pkg"] for name in spec.models}

    max_batch = _env_i("GAUNTLET_MAX_BATCH", 16)
    max_wait_ms = _env_f("GAUNTLET_MAX_WAIT_MS", 40.0)
    # chaos, leg 1 (the gray blip): the founding replica dispatches
    # slow a few times mid-morning — strikes, hedges, maybe an
    # ejection; the sentinel's N-1 cap keeps the fleet routable
    # (label=warm: the warm-up loop drives "hot", so the blip spends
    # its firings mid-day on live traffic, not on the compile pass)
    gray = os.environ.get(
        "GAUNTLET_GRAY_FAULTS",
        "hive.slow_dispatch@label=warm&times=3&seconds=0.6")
    router = FleetRouter(
        specs, n_replicas=1, backend="cpu", max_batch=max_batch,
        max_wait_ms=max_wait_ms, metrics_dir=mdir, cwd=REPO,
        env={"VELES_ONLINE": "1"},        # Evergreen armed fleet-wide
        env_overrides={0: {"VELES_FAULTS": gray}} if gray else None,
        deadline_ms=60000.0)
    controller = ScaleController(
        min_replicas=_env_i("GAUNTLET_SCALE_MIN", 1),
        max_replicas=_env_i("GAUNTLET_SCALE_MAX", 3),
        up_ms=_env_f("GAUNTLET_UP_MS", 150.0),
        down_ms=_env_f("GAUNTLET_DOWN_MS", 60.0),
        up_sustain_s=_env_f("GAUNTLET_UP_SUSTAIN", 2.0),
        down_sustain_s=_env_f("GAUNTLET_DOWN_SUSTAIN", 4.0),
        cooldown_s=_env_f("GAUNTLET_COOLDOWN", 10.0))
    scaler = FleetAutoscaler(router, controller=controller,
                             interval_s=0.25)

    preemptions = []
    record = {}
    try:
        log("warming the founding replica (compile + baselines)")
        warm_lat = []
        row = _row_for(arrivals[0])
        for i in range(12):
            t0 = time.perf_counter()
            resp = router.request("hot", row, timeout=180)
            if "probs" in resp:
                warm_lat.append(time.perf_counter() - t0)
        assert warm_lat, "warm-up never produced an answer"
        warm_p50 = 1000 * float(np.percentile(warm_lat, 50))
        oracle_diff = float(np.abs(
            np.asarray(resp["probs"])
            - _host_oracle(model, row)).max())
        assert oracle_diff < 1e-3, oracle_diff
        log(f"warm p50 {warm_p50:.1f}ms, oracle diff {oracle_diff:.2e}")

        # chaos, leg 2 (coordinated preemption): a SIGTERM lands on
        # the youngest replica in the middle of a traffic burst —
        # exactly when losing its queue would hurt most.  Drain +
        # monitor respawn + the router's retry-on-peer must make it
        # invisible in the outcome ledger.
        day_wall0 = [None]
        stop_chaos = threading.Event()
        windows = _burst_windows(spec,
                                 np.random.default_rng(spec.seed))

        def _preempt_loop():
            fired = 0
            want = _env_i("GAUNTLET_PREEMPTIONS", 1)
            while not stop_chaos.is_set() and fired < want:
                if day_wall0[0] is None:
                    time.sleep(0.1)
                    continue
                t = time.monotonic() - day_wall0[0]
                mid_burst = any(a + 0.5 <= t < b for a, b in windows)
                live = [r for r in list(router.replicas)
                        if r.healthy and not r.retiring]
                if mid_burst and len(live) >= 2:
                    victim = max(live, key=lambda r: r.idx)
                    log(f"chaos: SIGTERM replica {victim.idx} "
                        f"(pid {victim.pid}) at t={t:.1f}s mid-burst")
                    preemptions.append(
                        {"replica": victim.idx, "t": round(t, 1),
                         "pid": victim.pid})
                    try:
                        victim.client.sigterm()
                    except OSError:
                        pass
                    fired += 1
                stop_chaos.wait(0.25)

        chaos_thread = threading.Thread(
            target=_preempt_loop, name="gauntlet-chaos", daemon=True)
        chaos_thread.start()

        def request_fn(a):
            return router.request(a.model, _row_for(a), timeout=120)

        scaler.start()
        log("the day begins")
        driver = OpenLoopDriver(
            request_fn, workers=_env_i("GAUNTLET_WORKERS", 64))
        wall0_unix = time.time()
        day_wall0[0] = time.monotonic()
        results = driver.run(arrivals)
        day_sec = time.monotonic() - day_wall0[0]
        stop_chaos.set()
        chaos_thread.join(timeout=5)
        log(f"the day ends: {len(results)} outcomes in {day_sec:.0f}s")
        # the epilogue: traffic is over, but the day isn't done until
        # the fleet walks back down to its floor — the scaler keeps
        # running against idle pressure so every spawned replica is
        # RETIRED (journaled, drained, install dir pooled), exactly
        # like the quiet hours after a real peak
        epilogue = _env_f("GAUNTLET_EPILOGUE", 60.0)
        ep_deadline = time.monotonic() + epilogue
        while time.monotonic() < ep_deadline:
            live = [r for r in router.replicas if not r.retiring]
            if len(live) <= scaler.controller.min_replicas \
                    and not scaler.ladder.engaged:
                break
            time.sleep(0.25)
        log(f"epilogue: fleet at "
            f"{len([r for r in router.replicas if not r.retiring])} "
            f"after {epilogue - max(0, ep_deadline - time.monotonic()):.0f}s")
    finally:
        scaler.close()
        router.close(kill=True)
        telemetry.flush()

    # -- the books -----------------------------------------------------
    by_status = {}
    for r in results:
        by_status[r["status"]] = by_status.get(r["status"], 0) + 1
    lost = len(arrivals) - len(results)
    errors = by_status.get("error", 0)

    corrupt = 0
    checked_crc = 0
    oks = [r for r in results if r["status"] == "ok"]
    for r in oks:
        resp = r["response"]
        if resp.get("crc") is not None and "probs" in resp:
            checked_crc += 1
            probs = np.asarray(resp["probs"], np.float32)
            if zlib.crc32(probs.tobytes()) != int(resp["crc"]):
                corrupt += 1

    # oracle spot checks: replay a sample of answered arrivals from
    # their trace seeds and demand bit-closeness to the host ensemble
    arr_by_i = {a.i: a for a in arrivals}
    sample = oks[:: max(1, len(oks) // 24)][:24]
    oracle_max = 0.0
    for r in sample:
        want = _host_oracle(model, _row_for(arr_by_i[r["i"]]))
        got = np.asarray(r["response"]["probs"], np.float32)
        oracle_max = max(oracle_max,
                         float(np.abs(got - want).max()))
    oracle_ok = bool(sample) and oracle_max < 1e-3

    # p99 in the non-degraded windows (outside engage..release spans)
    def _spans(env_name, rel_name):
        opens = _journal_events(mdir, env_name)
        closes = _journal_events(mdir, rel_name)
        spans, open_ts = [], None
        for e in sorted(opens + closes, key=lambda e: e.get("ts", 0)):
            if e.get("event") == env_name and open_ts is None:
                open_ts = e["ts"]
            elif e.get("event") == rel_name and open_ts is not None:
                spans.append((open_ts, e["ts"]))
                open_ts = None
        if open_ts is not None:
            spans.append((open_ts, wall0_unix + spec.duration_s))
        return spans

    degraded_spans = _spans(events.EV_FLEET_DEGRADE_ENGAGE,
                            events.EV_FLEET_DEGRADE_RELEASE)

    def _degraded(r):
        w = wall0_unix + r["t"]
        return any(a <= w <= b for a, b in degraded_spans)

    lat_clear = [r["latency_s"] for r in oks if not _degraded(r)]
    lat_all = [r["latency_s"] for r in oks]
    p99_clear_ms = 1000 * float(np.percentile(lat_clear, 99)) \
        if lat_clear else None
    p99_bar_ms = _env_f("GAUNTLET_P99_BAR_MS", 5000.0)

    ups = _journal_events(mdir, events.EV_FLEET_SCALE_UP)
    dns = _journal_events(mdir, events.EV_FLEET_SCALE_DOWN)
    acct = accountability_check(mdir, preemptions)

    swing_x = spec.peak_rps / spec.trough_rps
    ok = (deterministic and lost == 0 and errors == 0
          and corrupt == 0 and oracle_ok
          and len(ups) >= 2 and len(dns) >= 2
          and (p99_clear_ms is None or p99_clear_ms <= p99_bar_ms)
          and acct["accounted"])
    record = {
        "gauntlet_ok": ok,
        "gauntlet_sec": round(time.perf_counter() - t_start, 1),
        "day_sec": round(spec.duration_s, 1),
        "arrivals": len(arrivals),
        "answered": by_status.get("ok", 0),
        "shed": by_status.get("shed", 0),
        "errors": errors,
        "lost": lost,
        "corrupt": corrupt,
        "crc_checked": checked_crc,
        "oracle_spot_checks": len(sample),
        "oracle_max_abs_diff": oracle_max,
        "diurnal_swing_x": round(swing_x, 1),
        "burst_swing_x": round(swing_x * spec.burst_mult, 1),
        "trace_deterministic": deterministic,
        "scale_ups": len(ups),
        "scale_downs": len(dns),
        "scale_causes": sorted({e.get("cause") for e in ups + dns}),
        "degraded_spans": len(degraded_spans),
        "degraded_sec": round(sum(b - a
                                  for a, b in degraded_spans), 1),
        "preemptions": preemptions,
        "warm_p50_ms": round(warm_p50, 1),
        "p99_nondegraded_ms": p99_clear_ms
        and round(p99_clear_ms, 1),
        "p99_all_ms": lat_all
        and round(1000 * float(np.percentile(lat_all, 99)), 1),
        "p99_bar_ms": p99_bar_ms,
        "late_sends": telemetry.counter(
            events.CTR_TRAFFIC_LATE).value,
        "accountability": {
            "explained": acct["explained"],
            "unexplained": acct["unexplained"][:8],
            "accounted": acct["accounted"]},
    }
    log(f"verdict: ok={ok} answered={record['answered']} "
        f"shed={record['shed']} lost={lost} errors={errors} "
        f"corrupt={corrupt} ups={len(ups)} downs={len(dns)} "
        f"p99_clear={p99_clear_ms and round(p99_clear_ms)}ms "
        f"accounted={acct['accounted']}")
    return record


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", action="store_true",
                    help="print only the final JSON record on stdout")
    ap.add_argument("--duration", type=float, default=None,
                    help="day length in seconds (GAUNTLET_DURATION)")
    ap.add_argument("--trace", default=None,
                    help="replay THIS trace file instead of "
                         "generating the day")
    args = ap.parse_args()
    if args.duration:
        os.environ["GAUNTLET_DURATION"] = str(args.duration)
    record = run_gauntlet(trace_path=args.trace)
    print(json.dumps(record), flush=True)
    return 0 if record.get("gauntlet_ok") else 1


if __name__ == "__main__":
    sys.exit(main())
