"""ISSUE 37: set-up seen from inside.

- the process's ONE ``jax.monitoring`` listener (``engine/core.py``)
  also counts tracing and lowering: the union of the reported
  intervals a thread, so a jitted function traced inside a trace is
  counted once and trace + lower + compile never exceed the span they
  fell in; compiles the persistent cache did not serve are counted
  apart (``fused.cold_compiles``); the first call of a step kind
  journals its own split;
- ``telemetry`` keeps set-up's spans as a timeline (name, parent,
  start, end, thread) until the training loop seals it at the first
  train class end;
- ``fused.plan`` and ``fused.probe`` name what ran under no span;
- the three names nobody read are gone;
- ``scripts/obs_report.py`` prints the set-up section.
"""

import json
import os
import sys
import threading

import numpy as np
import pytest

from veles_tpu import events, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tests"))

from test_telemetry import _run_tiny  # noqa: E402

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.fixture
def raw_events():
    """Every duration event jax reports while the test runs, as a
    plain listener sees them: [(event, seconds)]."""
    import jax
    from jax._src import monitoring
    from veles_tpu.engine import core as engine_core
    engine_core.watch_compiles()
    seen = []

    def listener(event, duration, **kw):
        seen.append((event, duration))

    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listener)


def _fresh_nested_jit():
    """A jitted function that calls two jitted functions, none of
    them traced or compiled before."""
    import jax
    import jax.numpy as jnp
    salt = float(np.random.default_rng().integers(1, 1 << 30))

    @jax.jit
    def inner(v):
        for i in range(100):        # long enough to trace to be told
            v = jnp.tanh(v * salt + i)      # from a clock read
        return v.sum()

    @jax.jit
    def inner2(v):
        for i in range(100):
            v = jnp.where(v > salt, v, i * 1.0)
        return v.sum()

    @jax.jit
    def outer(v):
        return inner(v) + inner2(v) + inner(v * 2.0)

    return outer


# -- the listener --------------------------------------------------------

def test_a_jit_inside_a_trace_is_counted_once(raw_events):
    import jax.numpy as jnp
    outer = _fresh_nested_jit()
    x = jnp.arange(8.0)
    x.block_until_ready()
    n0 = len(raw_events)
    lowered0 = telemetry.counter("xla.lower_seconds").value
    with telemetry.span("fused.t_nested") as sp:
        outer(x).block_until_ready()
    mine = raw_events[n0:]
    traces = [d for e, d in mine if e == TRACE]
    # the inner functions reported traces of their own, before the
    # outer one, inside its interval
    assert len(traces) >= 3 and traces[-1] == max(traces)
    traced = telemetry.counter("fused.trace_seconds").value
    assert 0 < traced <= sp.seconds
    assert traced < sum(traces)
    # the union is the outermost interval's length (give or take the
    # clock reads between an event's end and the listener's call),
    # where the plain sum counts the inner functions twice
    assert traced == pytest.approx(max(traces), rel=0.2)
    assert sum(traces) > 1.5 * max(traces)
    # one module was lowered and one program compiled: counted once
    lowers = [d for e, d in mine if e == LOWER]
    assert len(lowers) == 1
    assert telemetry.counter("fused.lower_seconds").value == \
        pytest.approx(lowers[0], abs=1e-3)
    assert telemetry.counter("xla.lower_seconds").value - lowered0 == \
        pytest.approx(telemetry.counter("fused.lower_seconds").value)
    # the parts add up to no more than the span they fell in
    parts = sum(telemetry.counter(n).value for n in (
        "fused.trace_seconds", "fused.lower_seconds",
        "fused.compile_seconds"))
    assert 0 < parts <= sp.seconds


def test_outside_a_fused_span_only_the_xla_counters_grow():
    import jax.numpy as jnp
    from veles_tpu.engine import core as engine_core
    engine_core.watch_compiles()
    outer = _fresh_nested_jit()
    with telemetry.span("t.not_the_step"):
        outer(jnp.arange(8.0)).block_until_ready()
    c = telemetry.snapshot()["counters"]
    assert c["xla.trace_seconds"] > 0 and c["xla.lower_seconds"] > 0
    assert "fused.trace_seconds" not in c
    assert "fused.lower_seconds" not in c
    assert "fused.cold_compiles" not in c


@pytest.mark.parametrize("arrivals,want", [
    # (now, duration) in order of arrival -> seconds each one counts
    # an inner trace and an eager compile inside an outer trace
    ([(3.0, 1.0), (5.0, 1.0), (7.0, 6.0)], [1.0, 1.0, 4.0]),
    # events one after the other share nothing
    ([(1.0, 1.0), (2.5, 1.0), (4.0, 1.5)], [1.0, 1.0, 1.5]),
    # two levels of nesting
    ([(2.0, 1.0), (4.0, 3.5), (6.0, 1.0), (9.0, 9.0)],
     [1.0, 2.5, 1.0, 4.5]),
    # an earlier event that ended a clock read after this one's start
    # ended before it
    ([(2.0, 1.0), (3.0, 1.00005)], [1.0, 1.00005]),
    # thousands of inner events side by side, as in a step's trace
    ([(2.0 * i, 1.0) for i in range(1, 3001)] + [(6001.0, 6001.0)],
     [1.0] * 3000 + [3001.0]),
])
def test_an_event_counts_what_no_earlier_one_covered(
        monkeypatch, arrivals, want):
    assert _own_seconds_of(monkeypatch, arrivals) == pytest.approx(want)


def _own_seconds_of(monkeypatch, arrivals):
    """What ``_own_seconds`` counts for events that arrive at the
    given ``(now, duration)``, on a thread of its own (no history)."""
    from veles_tpu.engine import core as engine_core
    clock = iter(now for now, _ in arrivals)
    monkeypatch.setattr(engine_core.time, "perf_counter",
                        lambda: next(clock))
    got = []

    def run():
        got.extend(engine_core._own_seconds(d) for _, d in arrivals)
        got.append(len(engine_core._compiling.counted))

    t = threading.Thread(target=run)
    t.start()
    t.join(30)
    assert not t.is_alive()
    kept = got.pop()
    assert kept <= engine_core._KEPT_EVENTS
    return got


def test_what_a_thread_remembers_is_bounded(monkeypatch):
    """Past the cap the oldest events go, their seconds kept as a
    floor: an outer event over the newest ones still counts right."""
    from veles_tpu.engine import core as engine_core
    monkeypatch.setattr(engine_core, "_KEPT_EVENTS", 8)
    arrivals = [(2.0 * i, 1.0) for i in range(1, 31)]     # ends 2 .. 60
    arrivals.append((61.0, 6.5))        # over the last three: 56, 58, 60
    got = _own_seconds_of(monkeypatch, arrivals)
    assert got[:30] == [1.0] * 30 and got[30] == pytest.approx(3.5)


@pytest.fixture
def cache_dir(tmp_path):
    """jax's persistent compile cache in a directory of the test's."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = [getattr(jax.config, n) for n in names]
    compilation_cache.reset_cache()
    for n, v in zip(names, (str(tmp_path), 0.0, -1)):
        jax.config.update(n, v)
    try:
        yield tmp_path
    finally:
        for n, v in zip(names, saved):
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_cold_compiles_count_what_the_cache_did_not_serve(cache_dir):
    import jax
    import jax.numpy as jnp
    from veles_tpu.engine import core as engine_core
    engine_core.watch_compiles()
    salt = float(np.random.default_rng().integers(1, 1 << 30))

    def f(v):
        return v * 3.0 + salt

    x = jnp.arange(8.0)
    x.block_until_ready()
    cold = telemetry.counter("fused.cold_compiles")
    with telemetry.span("fused.t_cold"):
        jax.jit(f)(x).block_until_ready()
    assert cold.value == 1
    assert os.listdir(cache_dir)
    jax.clear_caches()          # the next call traces and LOADS
    with telemetry.span("fused.t_cold"):
        jax.jit(f)(x).block_until_ready()
    assert cold.value == 1
    flags = [e["cached"] for e in telemetry.recent_events("xla.compile")
             if e["fun"] == "jit(f)"]
    assert flags == [False, True]
    # a load is still seconds of ``fused.compile_seconds``, and what
    # the cache does not save was paid twice
    assert telemetry.counter("xla.compiles").value >= 2
    assert telemetry.counter("fused.trace_seconds").value > 0


def test_first_dispatch_journals_the_split_of_its_own_call():
    _run_tiny(max_epochs=2)
    evs = {e["kind"]: e
           for e in telemetry.recent_events("fused.first_dispatch")}
    assert set(evs) == {"train", "eval"}
    for kind, e in evs.items():
        parts = e["trace_seconds"] + e["lower_seconds"] \
            + e["compile_seconds"]
        assert e["trace_seconds"] > 0 and e["lower_seconds"] > 0
        assert e["compile_seconds"] > 0
        # XLA:CPU engines keep no persistent cache: compiled cold
        assert e["cold"] == 1
        first = telemetry.gauge(
            f"fused.first_{kind}_submit_seconds").value
        assert parts <= first
    c = telemetry.snapshot()["counters"]
    total = c["fused.trace_seconds"] + c["fused.lower_seconds"] \
        + c["fused.compile_seconds"]
    assert total <= sum(
        telemetry.gauge(f"fused.first_{k}_submit_seconds").value
        for k in evs) + telemetry.histogram("fused.build_steps").sum
    assert c["fused.cold_compiles"] == 2
    # the step's own trace and lowering are long enough to journal
    assert any(e["fun"] == "train_step" and "fused.first_train_submit"
               in e["during"] for e in telemetry.recent_events(
                   "xla.trace"))


# -- the timeline --------------------------------------------------------

def test_timeline_records_nest_as_the_stack_nests():
    with telemetry.span("t.a"):
        with telemetry.span("t.b"):
            pass
        with telemetry.span("t.c"):
            pass
    tl = telemetry.setup_timeline()
    recs = {r[0]: r for r in tl["records"]}
    assert [r[0] for r in tl["records"]] == ["t.b", "t.c", "t.a"]
    assert [recs[n][1] for n in ("t.a", "t.b", "t.c")] == \
        [None, "t.a", "t.a"]
    a, b, c = recs["t.a"], recs["t.b"], recs["t.c"]
    assert a[2] <= b[2] <= b[3] <= c[2] <= c[3] <= a[3]
    assert {r[4] for r in tl["records"]} == {threading.get_ident()}
    assert tl["sealed_at"] is None and tl["dropped"] == 0
    # the histogram's clock: a record's length is the span's seconds
    assert telemetry.histogram("t.a").sum == pytest.approx(a[3] - a[2])


def test_timeline_seals_once_and_keeps_the_open_spans():
    with telemetry.span("t.loop"):
        with telemetry.span("t.before"):
            pass
        with telemetry.span("t.sealing"):
            telemetry.seal_setup()
        at = telemetry.setup_timeline()["sealed_at"]
        with telemetry.span("t.after"):
            telemetry.seal_setup()
    tl = telemetry.setup_timeline()
    assert tl["sealed_at"] == at and \
        tl["sealed_thread"] == threading.get_ident()
    names = [r[0] for r in tl["records"]]
    # the spans open at the seal end AT the seal; nothing later
    assert names == ["t.before", "t.sealing", "t.loop"]
    assert all(r[3] <= at for r in tl["records"])
    assert [r[3] for r in tl["records"][1:]] == [at, at]
    # the histograms go on as ever
    assert telemetry.histogram("t.after").count == 1


def test_timeline_drops_and_counts_past_the_cap(monkeypatch):
    monkeypatch.setattr(telemetry, "TIMELINE_CAP", 3)
    for i in range(5):
        with telemetry.span(f"t.s{i}"):
            pass
    tl = telemetry.setup_timeline()
    assert [r[0] for r in tl["records"]] == ["t.s0", "t.s1", "t.s2"]
    assert tl["dropped"] == 2


def test_timeline_records_nothing_when_disabled():
    telemetry.set_enabled(False)
    try:
        with telemetry.span("t.off"):
            pass
    finally:
        telemetry.set_enabled(True)
    assert telemetry.setup_timeline()["records"] == []
    assert "setup_timeline" not in telemetry.snapshot()


def test_timeline_reopens_on_reset():
    with telemetry.span("t.one"):
        telemetry.seal_setup()
    assert telemetry.setup_timeline()["sealed_at"] is not None
    telemetry.reset()
    assert telemetry.setup_timeline() == {
        "records": [], "sealed_at": None, "sealed_thread": None,
        "dropped": 0}
    with telemetry.span("t.two"):
        pass
    assert [r[0] for r in telemetry.setup_timeline()["records"]] == \
        ["t.two"]


def test_a_two_epoch_run_keeps_the_first_epoch_alone(tmp_path):
    w, calls = _run_tiny(tmp_path, max_epochs=2)
    tl = telemetry.setup_timeline()
    assert tl["sealed_at"] is not None and tl["dropped"] == 0
    names = [r[0] for r in tl["records"]]
    for name in ("workflow.initialize", "init.loader", "init.fused",
                 "fused.build_steps", "fused.plan",
                 "fused.first_train_submit", "fused.fetch_metrics",
                 "workflow.run"):
        assert name in names, name
    assert all(r[2] <= tl["sealed_at"] and r[3] <= tl["sealed_at"]
               for r in tl["records"])
    # sealed when the first TRAIN class's fetch returned: one fetch a
    # class of the first epoch (validation, then train) and not the
    # second epoch's; every train submit of the run but the first
    # epoch's is missing
    assert names.count("fused.fetch_metrics") == 2 < calls["fetch"]
    in_timeline = names.count("fused.first_train_submit") \
        + names.count("fused.train_submit")
    assert 0 < in_timeline < calls["train"]
    assert telemetry.histogram("fused.train_submit").count + 1 == \
        calls["train"]
    # ``fused.plan`` lies inside ``fused.build_steps`` inside
    # ``init.fused``
    by = {r[0]: r for r in tl["records"]}
    plan, build = by["fused.plan"], by["fused.build_steps"]
    assert plan[1] == "fused.build_steps" and build[1] == "init.fused"
    assert build[2] <= plan[2] <= plan[3] <= build[3]
    # the flushed snapshot carries the timeline as it stands
    with open(telemetry.flush()) as f:
        flushed = json.load(f)["setup_timeline"]
    assert flushed == json.loads(json.dumps(tl))


def test_obs_report_prints_the_setup_section(tmp_path, capsys):
    _run_tiny(tmp_path, max_epochs=2)
    telemetry.flush()
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    assert obs_report.main([str(tmp_path)]) == 0
    text = capsys.readouterr().out
    section = text[text.index("-- set-up ["):]
    assert "sealed at the first train class end" in section
    for name in ("init.loader", "fused.plan", "fused.fetch_metrics",
                 "fused.first_train_submit", "(no span)"):
        assert name in section, name
    assert "first train submit" in section and "trace" in section \
        and "lowering" in section and "compile" in section
    # the section's arithmetic is the benchmark's: self times add up
    # to the interval
    from benchmarks.lib import timeline
    b = timeline.breakdown(telemetry.setup_timeline())
    assert sum(b["self_s"].values()) == pytest.approx(b["interval_s"])
    assert 0 < b["unspanned_s"] < 0.2 * b["interval_s"]


# -- spans where the unnamed seconds were --------------------------------

def test_probe_has_a_span_and_its_compile_is_the_fused_steps():
    import jax
    from veles_tpu import prng
    from veles_tpu.backends import make_device
    from veles_tpu.loader import ArrayLoader
    from veles_tpu.models.qwen3next import TINY, qwen3next_layers
    from veles_tpu.ops.standard_workflow import StandardWorkflow
    layers = qwen3next_layers(**TINY)
    layers = [layers[0], layers[2], layers[-2], layers[-1]]
    assert [e["type"] for e in layers[1]["layers"]] == ["rmsnorm", "moe"]
    rows = np.asarray(jax.random.randint(
        jax.random.key(5), (4, 32), 0, TINY["vocab_held"]), np.int32)
    prng.seed_all(11)
    w = StandardWorkflow(
        loader_factory=lambda wf: ArrayLoader(
            wf, train=(rows,), minibatch_size=2, name="loader"),
        layers=layers, loss_function="next_byte",
        decision_config={"max_epochs": 1}, superstep=2, name="moe_tiny")
    w.initialize(device=make_device("cpu"))
    w.run()
    w.stop()
    assert telemetry.recent_events(events.EV_MOE_LOAD)
    probe = telemetry.histogram("fused.probe")
    assert probe.count == 1 and probe.sum > 0
    compiled = [e for e in telemetry.recent_events("xla.compile")
                if "fused.probe" in e["during"]]
    assert compiled and all(
        e["during"][:2] == ["workflow.run", "fused.run"]
        for e in compiled)
    c = telemetry.snapshot()["counters"]
    assert c["fused.compile_seconds"] >= \
        sum(e["seconds"] for e in compiled) \
        + telemetry.recent_events("fused.first_dispatch")[0][
            "compile_seconds"] - 1e-6
    # the probe's span is in the timeline, before the seal
    tl = telemetry.setup_timeline()
    assert "fused.probe" in [r[0] for r in tl["records"]]
    # a chain with a residual entry: the plan walked it, and jax
    # reported the walks' traces inside it
    assert telemetry.histogram("fused.plan").sum > 0
    assert any("fused.plan" in e["during"]
               for e in telemetry.recent_events("xla.trace"))


def test_the_splash_kernels_mask_tables_have_a_span():
    """Made on the host once a shape (nothing runs on a device)."""
    from veles_tpu.ops import attention
    attention._splash_kernel.cache_clear()
    made = telemetry.histogram("attn.mask_tables")
    try:
        attention._splash_kernel(256, 2, 128)
        assert made.count == 1 and made.sum > 0
        attention._splash_kernel(256, 2, 128)       # kept: no new span
        assert made.count == 1
        attention._splash_kernel(256, 2, 128, 128)  # a window: new tables
        assert made.count == 2
    finally:
        attention._splash_kernel.cache_clear()


# -- what went -----------------------------------------------------------

@pytest.mark.parametrize("name", [
    "fused.minibatches", "fused.train_gflops_per_image",
    "fused.train_images_per_sec_wall"])
def test_names_nobody_read_are_gone(name):
    assert not events.known(name)
    _run_tiny(max_epochs=1)
    snap = telemetry.snapshot()
    assert name not in snap["counters"] and name not in snap["gauges"]
    # the summary event still says the delivered rate
    assert telemetry.recent_events("fused.summary")[0][
        "images_per_sec_wall"] > 0


@pytest.mark.parametrize("name", [
    "fused.trace_seconds", "fused.lower_seconds", "fused.cold_compiles",
    "xla.trace_seconds", "xla.lower_seconds", "xla.trace", "xla.lower",
    "fused.plan", "fused.probe", "attn.mask_tables"])
def test_new_names_are_in_the_registry(name):
    assert events.known(name)
