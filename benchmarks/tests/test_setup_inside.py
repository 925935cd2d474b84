"""Tests of the six readers of set-up seen from inside (PR 37), run by
hand with the rest of ``benchmarks/tests`` (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_setup_inside.py -q

The readers on a tiny CPU mix through ``run_cell``, on a registry that
lacks their names (a parent commit), and the timeline's arithmetic
(``lib/timeline.py``) on hand-made records.
"""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run                # noqa: E402
from benchmarks.lib import timeline       # noqa: E402
from benchmarks.tests import tiny_lm      # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}
SIX = ["fused.trace_ms", "fused.lower_ms", "fused.cold_compiles",
       "fused.plan_ms", "fused.probe_ms", "setup.unspanned_ms"]
ACCEPTED = ["workflow.initialize_ms", "fused.first_submit_ms",
            "fused.compile_ms"]


def test_the_manifest_lists_the_six_where_they_have_something_to_read():
    bench = run.manifest()
    cells = [w["name"] for w in bench["workloads"]]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-6:] == SIX
    for name in SIX:
        m = entries[name]
        assert m["moves"] == "setup_s" and m["better"] == "lower"
        want = ["qwen3next.train_packed32k", "mellum2.train_packed8k"] \
            if name == "fused.probe_ms" else cells
        assert sorted(m["workloads"]) == sorted(want), name
        assert callable(run.metric_reader(name))


@pytest.fixture(scope="module")
def traced():
    """One traced run of the tiny language-model mix on XLA:CPU, the
    six readers as its per-layer list."""
    from veles_tpu import telemetry
    from veles_tpu.backends import make_device
    telemetry.reset()
    mix = dict(tiny_lm.MIX, per_layer=ACCEPTED + SIX)
    r = run.run_cell(mix, tiny_lm.CFG, 7, 0.3, 1,
                     device=make_device("cpu"), t_start=time.time(),
                     device_info=CPU_INFO)
    assert r["correct"], r["compared"]
    return r, telemetry.setup_timeline(), telemetry.snapshot()


@pytest.mark.parametrize("name", SIX)
def test_each_reader_returns_a_number(traced, name):
    value = traced[0]["metrics"][name]["value"]
    assert isinstance(value, float), traced[0]["metrics"]
    if name == "fused.cold_compiles":
        # XLA:CPU engines keep no persistent cache: the train step and
        # the probe compiled cold
        assert value >= 2.0
    else:
        assert value > 0.0


def test_the_parts_add_up(traced):
    v = {k: m["value"] for k, m in traced[0]["metrics"].items()}
    snap = traced[2]
    # every second jax reported inside a fused.* span lies inside one
    # of the outermost of them (the harness probes every resident row
    # itself, outside the loop; the program's own probe lies inside
    # ``fused.run`` and is counted twice here)
    outer = sum(snap["histograms"][n]["sum"] for n in (
        "fused.build_steps", "fused.run", "fused.probe"))
    assert v["fused.trace_ms"] + v["fused.lower_ms"] \
        + v["fused.compile_ms"] <= 1e3 * outer
    # a step's trace holds thousands of inner traces side by side
    # (more than the listener remembers apart): still counted once
    from veles_tpu import telemetry
    first = telemetry.recent_events("fused.first_dispatch")[0]
    assert first["trace_seconds"] + first["lower_seconds"] \
        + first["compile_seconds"] <= first["seconds"]
    # the probe's compile counts in ``fused.compile_ms`` now
    assert v["fused.compile_ms"] <= v["fused.first_submit_ms"] \
        + v["fused.probe_ms"]
    assert v["fused.plan_ms"] < v["workflow.initialize_ms"]


def test_the_timeline_of_a_run_is_sealed_and_adds_up(traced):
    r, tl, _ = traced
    assert tl["sealed_at"] is not None and tl["dropped"] == 0
    assert all(rec[2] <= tl["sealed_at"] for rec in tl["records"])
    b = timeline.breakdown(tl)
    named = sum(s for n, s in b["self_s"].items()
                if n not in ("",) + timeline.UNNAMED)
    assert named + b["unspanned_s"] == pytest.approx(b["interval_s"])
    assert 1e3 * b["unspanned_s"] == pytest.approx(
        r["metrics"]["setup.unspanned_ms"]["value"])
    # the harness binds its weights between ``initialize`` and ``run``:
    # under no span
    assert b["self_s"][""] > 0
    for name in ("fused.plan", "fused.probe",
                 "fused.first_train_submit", "fused.fetch_metrics"):
        assert b["self_s"][name] > 0, name


@pytest.mark.parametrize("name", SIX)
def test_a_program_without_the_name_reads_none(monkeypatch, name):
    """What a parent commit's registry looks like to a reader."""
    from veles_tpu import events, telemetry
    telemetry.reset()
    telemetry.counter("xla.compiles").inc()      # it counts compiles
    monkeypatch.setattr(events, "COUNTERS", {"xla.compiles"})
    monkeypatch.delattr(telemetry, "setup_timeline")
    assert run.metric_reader(name)({}) is None


# -- the arithmetic, on hand-made records --------------------------------

T = 7       # the loop's thread


def rec(name, start, end, parent=None, thread=T):
    return [name, parent, float(start), float(end), thread]


def tl_of(records, sealed_at=None, thread=T, dropped=0):
    return {"records": records, "sealed_at": sealed_at,
            "sealed_thread": thread if sealed_at is not None else None,
            "dropped": dropped}


def test_nested_spans_keep_their_own_time():
    # 0 [init 1..5 [a 2..3] [b 3..4.5 [c 3.5..4]]] gap [run 6..10 open]
    tl = tl_of([rec("a", 2, 3, "workflow.initialize"),
                rec("c", 3.5, 4, "b"),
                rec("b", 3, 4.5, "workflow.initialize"),
                rec("workflow.initialize", 1, 5),
                rec("fused.run", 6.5, 8, "workflow.run"),
                rec("workflow.run", 6, 9)], sealed_at=9.0)
    b = timeline.breakdown(tl)
    assert b["interval_s"] == 8.0
    assert b["self_s"] == pytest.approx({
        "workflow.initialize": 1.5, "a": 1.0, "b": 1.0, "c": 0.5,
        "": 1.0, "workflow.run": 1.5, "fused.run": 1.5})
    assert b["unspanned_s"] == pytest.approx(4.0)
    assert sum(b["self_s"].values()) == pytest.approx(b["interval_s"])


def test_overlapping_spans_are_counted_once():
    # two spans that cross (hand-made: ``with`` blocks cannot): every
    # instant goes to the one that started last
    tl = tl_of([rec("x", 0, 4), rec("y", 2, 6)], sealed_at=7.0)
    b = timeline.breakdown(tl)
    assert b["self_s"] == pytest.approx({"x": 2.0, "y": 4.0, "": 1.0})
    assert b["unspanned_s"] == pytest.approx(1.0)


def test_a_span_on_another_thread_covers_none_of_the_loops_time():
    tl = tl_of([rec("workflow.run", 0, 10),
                rec("loader.fill", 1, 9, thread=8),
                rec("fused.run", 2, 3, "workflow.run")], sealed_at=10.0)
    b = timeline.breakdown(tl)
    assert b["self_s"] == pytest.approx({"workflow.run": 9.0,
                                         "fused.run": 1.0})
    assert b["unspanned_s"] == pytest.approx(9.0) and b["others"] == 1


def test_the_seal_cuts_what_ends_after_it_and_an_open_timeline_ends_at_its_last_end():
    records = [rec("workflow.run", 0, 6), rec("late", 5, 8)]
    assert timeline.breakdown(tl_of(records, sealed_at=6.0))["self_s"] \
        == pytest.approx({"workflow.run": 5.0, "late": 1.0})
    b = timeline.breakdown(tl_of(records))
    assert b["interval_s"] == 8.0 and b["self_s"]["late"] == 3.0
