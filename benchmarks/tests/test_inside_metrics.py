"""Tests of what reads the program's OWN spans and scopes, run by hand
with the rest of ``benchmarks/tests`` (not tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

The six inside readers on the tiny CPU run, and the hand tool's
reduction (``scopes_chip.py``) on a small trace recorded on the chip
and on a CPU trace of the tiny run.
"""

import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmarks import run                      # noqa: E402
from benchmarks.tests import scopes_chip, tiny  # noqa: E402

CPU_INFO = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced tiny run on XLA:CPU with its trace kept, and what
    the inside readers gave right after it."""
    from veles_tpu import telemetry
    from veles_tpu.backends import make_device
    telemetry.reset()
    keep = str(tmp_path_factory.mktemp("trace"))
    r = run.run_cell(tiny.MIX, tiny.CFG, 5, 0.3, 1,
                     device=make_device("cpu"), t_start=time.time(),
                     device_info=CPU_INFO, keep_trace=keep)
    assert r["correct"], r["compared"]
    inside = scopes_chip.inside_metrics()
    path = os.path.join(keep, sorted(os.listdir(keep))[-1])
    return r, inside, path


@pytest.mark.parametrize("name", scopes_chip.INSIDE)
def test_inside_reader_finds_the_programs_own_reading(traced, name):
    _, inside, _ = traced
    assert isinstance(inside[name], float), inside
    if name == "fused.compile_ms":
        assert inside[name] >= 0.0
    else:
        assert inside[name] > 0.0


def test_inside_and_outside_time_the_same_calls(traced):
    r, inside, _ = traced
    outside = {k: v["value"] for k, v in r["metrics"].items()}
    # the submit span lies inside the wrapper round fused.run, the
    # fetch inside the one round decision.run (bucket error: 7.5 %)
    assert inside["fused.submit_ms"] <= 1.1 * outside["fused.dispatch_ms"]
    assert inside["fused.fetch_wait_ms"] <= \
        1.1 * outside["decision.epoch_end_ms"]
    assert inside["fused.first_submit_ms"] >= inside["fused.compile_ms"]


def test_readers_return_nothing_where_the_program_has_no_such_span():
    from veles_tpu import telemetry
    telemetry.reset()
    assert scopes_chip.inside_metrics() == dict.fromkeys(
        scopes_chip.INSIDE)


def test_host_spans_of_the_program_nest_in_the_harnesss(traced):
    # (``veles:workflow.run`` opened before the harness's trace began
    # and is not in it; tier-1 holds that nesting on a trace of its own)
    _, _, path = traced
    red = scopes_chip.read(path)
    for pair in ("veles:fused.train_submit in bench:fused.run",
                 "veles:fused.train_submit in veles:fused.run",
                 "veles:fused.fetch_metrics in veles:decision.run",
                 "bench:fused.run in veles:fused.run",
                 "bench:decision.run in veles:decision.run"):
        ok, total = red["nesting"][pair]
        # the harness stops its trace inside the last decision.run,
        # whose span the trace then lacks
        cut = 1 if pair.endswith("veles:decision.run") else 0
        assert total > 0 and total - cut <= ok <= total, \
            (pair, ok, total)
    assert red["host_span_counts"]["bench:traced_window"] == 1


def test_scopes_of_the_sample_recorded_on_the_chip():
    """A scan of matmul + tanh under ``fwd/l1``, an elementwise pass
    under ``update/l1`` (which XLA fused into the matmul's fusion: one
    scope a fusion) and a sum under ``loss``, traced on a v5e."""
    sample = os.path.join(HERE, "scopes_sample.xplane.pb")
    dev = next(p for p in scopes_chip.planes(sample)
               if p["name"] == "/device:TPU:0")
    ops = dev["lines"]["XLA Ops"]
    paths = {st.get("tf_op") for _, _, _, st in ops}
    assert "jit(step)/while/body/closed_call/fwd/l1/dot_general:" in paths
    assert scopes_chip.scope_of("jit(step)/loss/reduce_sum:") == "loss"
    assert scopes_chip.scope_of("jit(f)/while/body/bwd/fwd3_conv/x:") \
        == "bwd/fwd3_conv"
    assert scopes_chip.scope_of("jit(step)/while:") is None
    red = scopes_chip.read(sample)["devices"]["/device:TPU:0"]
    top = red["by_scope"][0]
    assert top[0] == "fwd/l1" and top[2] > 80.0
    # 4 matmuls of 1024^3 MACs each, as the device counted them
    assert top[3] == pytest.approx(4 * 2 * 1024 ** 3, rel=0.01)
    assert {r[0] for r in red["by_scope"]} >= {"fwd/l1", "loss"}
