"""Evergreen online learning (ISSUE 14): the replay buffer's
determinism + uint8 codec, the online-vs-offline training oracle
(f32-exact replay), residency/swap atomicity (a busy model is never a
spill victim; promotion swaps under the residency lock are never
torn), and the REAL ``--serve-models --online`` hive: a drifted label
stream is learned and gated-promoted HBM-to-HBM while serving stays
correct, and a poisoned training stream never promotes.
"""

import os
import threading
import time

import numpy as np
import pytest

from tests.test_serve import (_build_package, _host_oracle,
                              _journal_events)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _drifted(label, n_classes=3):
    """The drift the online tests serve: the truth generator's labels
    rotate one class — a served model frozen at package time is
    suddenly (and consistently) wrong."""
    return (int(label) + 1) % n_classes


class TestReplayBuffer:
    def test_reservoir_bounded_and_deterministic(self):
        from veles_tpu.online.buffer import ReplayBuffer
        rng = np.random.default_rng(3)
        rows = rng.standard_normal((300, 4)).astype(np.float32)
        labels = rng.integers(0, 3, 300)

        def fill():
            b = ReplayBuffer(capacity=32, seed=9, holdout_every=10)
            for i in range(300):
                b.add(rows[i][None], labels[i])
            return b

        b1, b2 = fill(), fill()
        assert b1.train_rows == 32
        assert 0 < b1.holdout_rows <= b1.holdout_cap
        # same seed + same tap order -> identical retained sets (the
        # property the offline training oracle replays against)
        x1, l1 = b1.sample(16, np.random.default_rng(5))
        x2, l2 = b2.sample(16, np.random.default_rng(5))
        assert np.array_equal(x1, x2) and np.array_equal(l1, l2)
        assert b1.version == b2.version

    def test_uint8_codec_roundtrips_and_shrinks(self):
        from veles_tpu.loader.quantize import AffineDequant
        from veles_tpu.online.buffer import ReplayBuffer
        dq = AffineDequant(1.0 / 255.0, 0.0)
        src = np.random.default_rng(0).integers(
            0, 256, (40, 8), dtype=np.uint8)
        rows = dq.apply_host(src)   # what a client would send: f32
        bq = ReplayBuffer(64, seed=1, holdout_every=0, dequant=dq)
        bf = ReplayBuffer(64, seed=1, holdout_every=0, dequant=None)
        for i in range(40):
            bq.add(rows[i][None], 0)
            bf.add(rows[i][None], 0)
        assert bq.quantized and not bf.quantized
        # 4x against the residency charge, value-exact on decode
        assert bq.nbytes * 4 == bf.nbytes
        xq, _ = bq.sample(16, np.random.default_rng(2))
        xf, _ = bf.sample(16, np.random.default_rng(2))
        assert np.array_equal(xq, xf)

    def test_non_byte_ranged_rows_stay_float(self):
        from veles_tpu.loader.quantize import AffineDequant
        from veles_tpu.online.buffer import ReplayBuffer
        dq = AffineDequant(1.0 / 255.0, 0.0)
        b = ReplayBuffer(16, seed=1, holdout_every=0, dequant=dq)
        rows = np.random.default_rng(1).standard_normal(
            (4, 8)).astype(np.float32)
        b.add(rows, np.zeros(4))
        assert not b.quantized   # lossless or nothing
        x, _ = b.sample(4, np.random.default_rng(0))
        assert x.dtype == np.float32


def _tiny_served_model(seed=11, n_members=3):
    """A resident HostedModel + manager on XLA:CPU (in-process)."""
    from veles_tpu import prng
    from veles_tpu.backends import JaxDevice
    from veles_tpu.datasets import synthetic_classification
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.ops.standard_workflow import StandardWorkflow
    from veles_tpu.serve.residency import HostedModel, ResidencyManager

    prng.seed_all(4242)
    train, valid, _ = synthetic_classification(
        64, 16, (6, 6, 1), n_classes=3, seed=5)
    w = StandardWorkflow(
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
        decision_config={"max_epochs": 2}, name="online_wf")
    device = JaxDevice(platform="cpu")
    w.initialize(device=device)
    base = {fw.name: {k: np.asarray(v) for k, v in
                      fw.gather_params().items()}
            for fw in w.forwards}
    rng = np.random.default_rng(seed)
    members = [{fn: {pn: a + 0.05 * rng.standard_normal(a.shape)
                     .astype(np.float32) for pn, a in p.items()}
                for fn, p in base.items()} for _ in range(n_members)]
    m = HostedModel("alpha", w.forwards, members,
                    meta={"workflow": w, "seed": seed},
                    sample_shape=(6, 6, 1))
    res = ResidencyManager(device, budget_bytes=1 << 30, max_batch=8,
                           max_wait_s=0.002)
    res.register(m)
    res.ensure("alpha")
    return res, m, w, train


class TestOnlineOfflineOracle:
    """The determinism contract: replaying the SAME tapped rows
    through the recorded (step, buffer version) history reproduces
    the online param trajectory f32-exactly — online learning is a
    pure function of the tap order."""

    def test_offline_replay_is_f32_exact(self):
        from veles_tpu.online.buffer import ReplayBuffer
        from veles_tpu.online.trainer import ShadowTrainer
        from veles_tpu.ops import batching
        res, m, w, (xs, ys) = _tiny_served_model()
        device = res.device
        B = 8
        adds = [(xs[i % len(xs)][None],
                 _drifted(ys[i % len(ys)])) for i in range(120)]

        def make(seed=77):
            buf = ReplayBuffer(64, seed=seed, holdout_every=8)
            tr = ShadowTrainer(
                m.forwards, w.gds, w.evaluator, device,
                batching.stack_member_params(m.forwards,
                                             m.member_params, device),
                seed=seed, lr_scale=1.0, micro_batch=B)
            return buf, tr

        # ONLINE: adds and steps interleaved (the live hive shape)
        buf1, t1 = make()
        k = 0
        for i, (rows, lab) in enumerate(adds):
            buf1.add(rows, lab)
            if buf1.train_rows >= B and i % 7 == 3:
                x, lb = buf1.sample(B, t1.sample_rng())
                t1.step(x, lb, buf1.version)
                k += 1
        assert k >= 10 and t1.history

        # OFFLINE: same tapped rows, steps replayed at the recorded
        # buffer versions
        buf2, t2 = make()
        it = iter(adds)
        for step, version in t1.history:
            while buf2.version < version:
                rows, lab = next(it)
                buf2.add(rows, lab)
            x, lb = buf2.sample(B, t2.sample_rng(step))
            t2.step(x, lb, version)

        for fn, d in t1._params.items():
            for pn, a in d.items():
                assert np.array_equal(np.asarray(a),
                                      np.asarray(t2._params[fn][pn])), \
                    f"param {fn}.{pn} diverged between online and " \
                    f"offline replay"


class _FakeEngine:
    def __init__(self, busy=False):
        self.busy = busy
        self.resident = True
        self.drained = 0
        self.spilled = 0

    def drain(self, timeout=30.0):
        self.drained += 1
        return True

    def spill_params(self):
        self.spilled += 1
        self.resident = False


class TestResidencySwapAtomicity:
    """ISSUE 14 satellite: a promotion-triggered (or any) LRU spill
    can never evict the model a dispatch is mid-flight on, and the
    promotion swap happens under the declared residency lock."""

    def _manager(self, budget):
        from veles_tpu.backends import JaxDevice
        from veles_tpu.serve.residency import ResidencyManager
        return ResidencyManager(JaxDevice(platform="cpu"),
                                budget_bytes=budget)

    def _hosted(self, name, nbytes, busy):
        from veles_tpu.serve.residency import HostedModel
        m = HostedModel.__new__(HostedModel)
        m.name = name
        m.forwards = []
        m.member_params = []
        m.meta = {}
        m.sample_shape = None
        m.engine = _FakeEngine(busy=busy)
        m.param_bytes = nbytes
        m.last_used = 0.0
        return m

    def test_busy_model_is_never_the_spill_victim(self):
        res = self._manager(budget=1000)
        a = self._hosted("a", 600, busy=True)    # LRU and mid-flight
        b = self._hosted("b", 600, busy=False)
        a.last_used, b.last_used = 1.0, 2.0
        res.models["a"] = a
        res.models["b"] = b
        incoming = self._hosted("c", 600, busy=False)
        incoming.engine = None
        res.models["c"] = incoming
        with res._lock:
            victim, blocked = res._pick_victim(incoming)
        # the idle model spills; the busy LRU one is untouchable
        assert victim is b and not blocked
        assert a.engine.spilled == 0
        # with ONLY busy candidates, nothing spills (the caller waits
        # for a quiet window rather than tearing params out from
        # under a dispatch)
        b.engine.busy = True
        with res._lock:
            victim, blocked = res._pick_victim(incoming)
        assert victim is None and blocked

    def test_swap_params_requires_residency(self):
        res = self._manager(budget=1 << 30)
        m = self._hosted("a", 100, busy=False)
        m.engine.adopted = None
        m.engine.adopt_stacked_params = \
            lambda p: setattr(m.engine, "adopted", p)
        res.models["a"] = m
        token = {"new": "params"}
        assert res.swap_params("a", token) is m.engine
        assert m.engine.adopted is token
        m.engine.resident = False
        with pytest.raises(RuntimeError):
            res.swap_params("a", token)

    def test_swap_mid_request_never_tears_answers(self):
        """``online.swap_mid_request``: promotion races live
        dispatches; every answer equals the OLD oracle or the NEW one
        — never a mix of the two param sets."""
        from veles_tpu import faults
        from veles_tpu.online.promote import PromotionGate
        from veles_tpu.online.trainer import ShadowTrainer
        from veles_tpu.ops import batching
        res, m, w, (xs, ys) = _tiny_served_model(seed=21)
        engine = m.engine
        x = xs[:4]
        old = np.asarray(engine.submit(x).result(timeout=30))
        tr = ShadowTrainer(
            m.forwards, w.gds, w.evaluator, res.device,
            batching.stack_member_params(m.forwards, m.member_params,
                                         res.device),
            seed=3, lr_scale=1.0, micro_batch=8)
        # make the shadow measurably different: a few real steps
        for k in range(6):
            rng = tr.sample_rng()
            idx = rng.integers(0, len(xs), 8)
            tr.step(xs[idx],
                    [(int(ys[i]) + 1) % 3 for i in idx], k)
        answers = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                answers.append(
                    np.asarray(engine.submit(x).result(timeout=30)))

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        time.sleep(0.1)
        gate = PromotionGate("alpha", res, margin=0.0, min_steps=1)
        gate.last_step_ts = time.monotonic()
        faults.arm("online.swap_mid_request@model=alpha&seconds=0.3")
        try:
            gate.promote(tr.take_params(), tr.steps)
        finally:
            faults.arm("")
        time.sleep(0.2)
        stop.set()
        t.join(timeout=10)
        new = np.asarray(engine.submit(x).result(timeout=30))
        assert not np.allclose(old, new)   # the swap really landed
        assert len(answers) >= 2
        for a in answers:
            ok_old = np.allclose(a, old, atol=1e-6)
            ok_new = np.allclose(a, new, atol=1e-6)
            assert ok_old or ok_new, "torn answer: matches neither " \
                "the pre- nor the post-promotion oracle"


@pytest.fixture(scope="module")
def online_pkg(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("online_pkgs"))
    return _build_package(d, "alpha", 11)


def _learn_env(**extra):
    env = {
        "VELES_ONLINE_MICRO_BATCH": "8",
        "VELES_ONLINE_MIN_STEPS": "4",
        "VELES_ONLINE_LR_SCALE": "1.0",
        "VELES_ONLINE_PROMOTE_MARGIN": "5.0",
        "VELES_ONLINE_HOLDOUT_EVERY": "6",
        "VELES_ONLINE_IDLE_MS": "1",
        "VELES_FAULTS": "",
    }
    env.update(extra)
    return env


class TestHiveOnline:
    """The real ``--serve-models --online`` subprocess: drift is
    learned, the gate promotes HBM-to-HBM, serving stays correct and
    recompile-free, and time_to_serve is recorded."""

    @pytest.fixture(scope="class")
    def served(self, online_pkg, tmp_path_factory):
        from veles_tpu.serve.client import HiveClient
        mdir = str(tmp_path_factory.mktemp("online_metrics"))
        c = HiveClient({"alpha": online_pkg["pkg"]}, backend="cpu",
                       max_batch=8, max_wait_ms=2, online=True,
                       metrics_dir=mdir, env=_learn_env(), cwd=REPO)
        c.metrics_dir = mdir
        yield c
        c.close()

    def _payloads(self, pkg, n=96):
        """Labeled drifted traffic: rows from the packaged training
        distribution, labels = the live truth AFTER drift (what the
        frozen model is now consistently wrong about)."""
        w = pkg["workflow"]
        xs = np.asarray(w.loader.original_data.mem, np.float32)
        ys = np.asarray(w.loader.original_labels.mem)
        out = []
        for i in range(n):
            j = i % len(xs)
            out.append((xs[j][None], [_drifted(ys[j])]))
        return out

    def test_drift_is_learned_and_promoted(self, served, online_pkg):
        assert served.hello.get("online") is True
        payloads = self._payloads(online_pkg)
        deadline = time.monotonic() + 180
        i = 0
        row = None
        first_promote_row = None
        while time.monotonic() < deadline:
            for _ in range(8):
                x, lab = payloads[i % len(payloads)]
                i += 1
                jid = served.submit("alpha", x, label=lab)
                r = served.wait_for(jid, timeout=60)
                assert "error" not in r, r
            row = served.learn().get("alpha")
            if row and row["promotions"] >= 1:
                if first_promote_row is None:
                    first_promote_row = row
                # keep learning until the SERVING model (gate rounds
                # re-score it as the incumbent) is genuinely good on
                # the drifted truth, so the served-accuracy check
                # below is not judging a barely-over-the-margin
                # first promotion
                if row["incumbent_error_pct"] is not None and \
                        row["incumbent_error_pct"] <= 40.0:
                    break
            time.sleep(0.05)
        assert row, "no learner row from op=learn"
        assert row["promotions"] >= 1, row
        # the gated win was real: the journal's promotion record
        # carries the scores of the round that fired it — the
        # shadow's held-out error beat the then-incumbent by the
        # margin (the live op=learn row may already show a LATER
        # round's scores)
        promos = []
        wait_until = time.monotonic() + 30
        while time.monotonic() < wait_until and not promos:
            promos = _journal_events(served.metrics_dir,
                                     "online.promoted")
            if not promos:
                time.sleep(0.5)
        assert promos, "no online.promoted journal event"
        ev = promos[0]
        assert ev["shadow_error_pct"] \
            < ev["incumbent_error_pct"] - 4.9, ev
        # and the promoted model now answers the DRIFTED truth better
        # than the frozen oracle did
        right = wrong_frozen = 0
        for x, lab in payloads[:24]:
            r = served.request("alpha", x, timeout=60)
            assert "pred" in r, r
            frozen_pred = int(np.argmax(
                _host_oracle(online_pkg, x), axis=-1)[0])
            if r["pred"][0] == lab[0]:
                right += 1
            if frozen_pred != lab[0]:
                wrong_frozen += 1
        assert right > 24 - wrong_frozen, (right, wrong_frozen)
        # time_to_serve: last step -> first served request, recorded
        row = served.learn()["alpha"]
        assert row["time_to_serve_ms"] is not None
        assert row["time_to_serve_ms"] >= 0.0

    def test_zero_post_warmup_recompiles_with_learner(self, served):
        st0 = served.stats()
        before = st0["counters"].get("serve.compiles", 0)
        x = np.ones((2, 6, 6, 1), np.float32)
        for _ in range(6):
            assert "probs" in served.request("alpha", x, timeout=60)
        after = served.stats()["counters"].get("serve.compiles", 0)
        assert after == before, "the learner caused serving recompiles"

    def test_learner_journals_and_gauges(self, served):
        st = served.stats()
        assert st["counters"].get("online.steps", 0) > 0
        assert st["counters"].get("online.tapped_rows", 0) > 0
        gs = st["gauges"]
        assert gs.get("online.model.alpha.steps", 0) > 0
        served.stats()   # flush-adjacent poke
        evs = _journal_events(served.metrics_dir, "online.promoted")
        # the journal file may lag one flush; the op=learn row is the
        # live truth and was asserted above — only check consistency
        for ev in evs:
            assert ev["model"] == "alpha"


class TestHiveOnlinePoison:
    """``online.poison_batch``: a corrupted training label stream —
    with CLEAN traffic that matches the packaged model — must never
    promote."""

    def test_poisoned_stream_never_promotes(self, online_pkg,
                                            tmp_path_factory):
        from veles_tpu.serve.client import HiveClient
        mdir = str(tmp_path_factory.mktemp("online_poison"))
        env = _learn_env(
            VELES_FAULTS="online.poison_batch@slot=train&times=*")
        c = HiveClient({"alpha": online_pkg["pkg"]}, backend="cpu",
                       max_batch=8, max_wait_ms=2, online=True,
                       metrics_dir=mdir, env=env, cwd=REPO)
        try:
            w = online_pkg["workflow"]
            xs = np.asarray(w.loader.original_data.mem, np.float32)
            # CLEAN labels: what the packaged ensemble actually
            # predicts (so the un-poisoned incumbent is near-perfect
            # on the held-out slice and garbage cannot beat it)
            deadline = time.monotonic() + 60
            i = 0
            row = None
            while time.monotonic() < deadline:
                for _ in range(8):
                    j = i % len(xs)
                    i += 1
                    x = xs[j][None]
                    lab = [int(np.argmax(_host_oracle(online_pkg, x),
                                         axis=-1)[0])]
                    r = c.wait_for(
                        c.submit("alpha", x, label=lab), timeout=60)
                    assert "error" not in r, r
                row = c.learn().get("alpha")
                if row and row["steps"] >= 12 and \
                        row["shadow_error_pct"] is not None:
                    break
                time.sleep(0.05)
            assert row and row["steps"] >= 12, row
            assert row["shadow_error_pct"] is not None, row
            assert row["promotions"] == 0, \
                f"poisoned labels were promoted: {row}"
        finally:
            c.close()


def _hist_window(after, before):
    """The latency distribution of ONE measurement window from two
    cumulative histogram snapshots as ``stats`` carries them over the
    wire (bucket-wise subtraction; min/max are the cumulative ones,
    which only widens the clamp range the quantile interpolation
    uses)."""
    from veles_tpu.telemetry import Histogram
    a, b = dict(after or {}), dict(before or {})
    h = Histogram("window")
    h.count = int(a.get("count", 0)) - int(b.get("count", 0))
    h.sum = float(a.get("sum", 0.0)) - float(b.get("sum", 0.0))
    if a.get("min") is not None:
        h.min = float(a["min"])
    if a.get("max") is not None:
        h.max = float(a["max"])
    bb = b.get("buckets") or {}
    for i, c in (a.get("buckets") or {}).items():
        d = int(c) - int(bb.get(i, 0))
        if d > 0:
            h.buckets[int(i)] += d
    return h


class TestHiveOnlineLatency:
    """The scavenger must not own the chip: serving p99 with the
    learner active stays bounded vs learner-off on the same box (the
    strict 1.2x bar is the BENCH_r09 acceptance; the tier-1 bound is
    loose enough to survive a noisy CI box)."""

    @pytest.mark.slow
    def test_p99_bounded_vs_learner_off(self, online_pkg,
                                        tmp_path_factory):
        from veles_tpu.serve.client import HiveClient
        w = online_pkg["workflow"]
        xs = np.asarray(w.loader.original_data.mem, np.float32)
        ys = np.asarray(w.loader.original_labels.mem)

        def window(online):
            mdir = str(tmp_path_factory.mktemp(
                f"online_lat_{int(online)}"))
            c = HiveClient({"alpha": online_pkg["pkg"]},
                           backend="cpu", max_batch=8, max_wait_ms=2,
                           online=online, metrics_dir=mdir,
                           env=_learn_env(), cwd=REPO)
            try:
                x = xs[:1]
                for _ in range(8):   # warm the serving dispatch
                    c.request("alpha", x, timeout=60)
                if online:
                    # warm the LEARNER too: feed labeled traffic and
                    # wait for the first scavenged step, so the timed
                    # window never pays the one-time step compile
                    deadline = time.monotonic() + 60
                    i = 0
                    while time.monotonic() < deadline:
                        j = i % len(xs)
                        i += 1
                        c.wait_for(c.submit("alpha", xs[j][None],
                                            label=[_drifted(ys[j])]),
                                   timeout=60)
                        if i % 8 == 0:
                            if c.stats()["counters"].get(
                                    "online.steps", 0) > 0:
                                break
                            time.sleep(0.05)
                st0 = c.stats()
                steps0 = st0["counters"].get("online.steps", 0)
                # bursty closed loop: live traffic has gaps — that is
                # exactly the resource the scavenger exists to steal
                t_end = time.perf_counter() + 3.0
                i = 0
                while time.perf_counter() < t_end:
                    for _ in range(5):
                        j = i % len(xs)
                        i += 1
                        r = c.wait_for(c.submit(
                            "alpha", xs[j][None],
                            label=[_drifted(ys[j])] if online
                            else None), timeout=60)
                        assert "error" not in r, r
                    time.sleep(0.01)
                st1 = c.stats()
                steps = st1["counters"].get("online.steps", 0) - steps0
            finally:
                c.close()
            lat = _hist_window(
                st1["histograms"].get("serve.request_seconds"),
                st0["histograms"].get("serve.request_seconds"))
            return (lat.quantile(0.99) or 0.0), steps

        p99_off, _ = window(False)
        p99_on, steps_on = window(True)
        assert steps_on > 0, "the learner never scavenged a step " \
                             "under bursty load"
        assert p99_on <= max(8.0 * p99_off, p99_off + 0.25), \
            (p99_on, p99_off)
