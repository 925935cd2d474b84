"""The gradient exchange of ``--dp 4`` as the chip's compiler made it
(ISSUE 31).  Needs four chips:

    chiprun --chips 4 -- python -m pytest tests_tpu/test_dp_exchange.py -q

On the parent the compiled AlexNet step held ONE synchronous
``all-reduce`` over a tuple of all sixteen gradient leaves (124.8 MB of
bf16) behind the last gradient.  Now ``GradExchange`` (engine/core.py)
plans the exchange from shapes: the three dense layers gather their
activations and make the whole gradient on every chip, the five
convolutions keep an all-reduce each.  This file reads the compiled
text of the very step the adapter runs and holds it to that, then runs
one firing and compares the four chips' copies of the state.
"""

import re

import numpy as np
import pytest

from veles_tpu import events, prng, telemetry
from veles_tpu.engine import core as engine_core
from veles_tpu.loader.synthetic import DeviceSyntheticLoader
from veles_tpu.models.alexnet import alexnet_layers
from veles_tpu.ops.standard_workflow import StandardWorkflow

CHIPS, MB = 4, 512
DENSE = ("9216,4096", "4096,4096", "4096,1000")
#: ``%name = <result> opcode(`` of one instruction of the compiled text
INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([\w\-]+)\(")
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "all-to-all", "collective-permute")


def body_schedule(hlo_text):
    """[(name, result, opcode, line)] of the scheduled computation
    that holds the step's collectives: the scan's body."""
    best = []
    for comp in hlo_text.split("\n\n"):
        rows = [m.groups() + (line,) for line in comp.splitlines()
                for m in [INSTRUCTION.match(line)] if m]
        if sum("async-collective-start" in r[0] for r in rows) > \
                sum("async-collective-start" in r[0] for r in best):
            best = rows
    return best


def collectives_of(hlo_text):
    """[(opcode, result)] of every collective of the module, the ones
    the compiler wove into a fusion included."""
    found = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m and m.group(3) in COLLECTIVES:
            found.append((m.group(3), m.group(2)))
    return found


@pytest.fixture(scope="module")
def four_chips(tpu_device):
    import jax
    devs = jax.devices("tpu")
    if len(devs) < CHIPS:
        pytest.skip(f"needs {CHIPS} chips, this machine has {len(devs)} "
                    f"(chiprun --chips 4)")
    return devs[:CHIPS]


def test_alexnet_dp4_exchange_as_compiled(four_chips):
    from veles_tpu.parallel import DataParallel

    prng.seed_all(1234)
    w = StandardWorkflow(
        loader_factory=lambda wf: DeviceSyntheticLoader(
            wf, name="loader", minibatch_size=MB, n_train=8 * MB,
            n_valid=0, shape=(227, 227, 3), n_classes=1000, seed=7),
        layers=alexnet_layers(1000), decision_config={"max_epochs": 1},
        superstep=8, name="TpuDpExchange")
    w.evaluator.compute_confusion = False
    w.initialize(device=DataParallel(w, CHIPS,
                                     devices=four_chips).install())

    (ev,) = telemetry.recent_events(events.EV_DP_GRAD_EXCHANGE)
    assert (ev["devices"], ev["leaves"], ev["dtype"]) == \
        (CHIPS, 16, "bfloat16")
    assert ev["bytes"] == 62378344 * 2
    assert [g["how"] for g in ev["groups"]] == \
        ["gathered"] * 3 + ["reduced"] * 5
    assert ev["options"] == engine_core.TPU_GRAD_EXCHANGE_OPTIONS

    texts = []
    step = w.fused._train_step

    def train_step(*args):
        texts.append(step.lower(*args).compile().as_text())
        return step(*args)

    w.fused._train_step = train_step
    w.loader.run()
    w.fused.run()
    w.fused._train_step = step
    hlo = texts[0]

    found = collectives_of(hlo)
    # the exchange keeps the parent's operand dtype: the compute
    # dtype's, bf16 (the 12-byte metric carry is the f32 one)
    for opcode, result in found:
        assert "bf16[" in result or "f32[]" in result \
            or "f32[1]" in result, (opcode, result)
    # no dense layer's gradient crosses the wire any more ...
    for opcode, result in found:
        assert not any(f"[{d}]" in result for d in DENSE), \
            (opcode, result)
    # ... their activations do: whole global minibatches of bf16
    gathers = [r for op, r in found if op == "all-gather"]
    assert sum(f"bf16[{MB}," in r for r in gathers) >= 6, gathers
    # the convolutions' kernels keep an all-reduce EACH, not one of all
    reduces = [r for op, r in found if op == "all-reduce"]
    # (one the compiler wove into a fusion shows in each of its parts)
    kernels = {m.group(0) for r in reduces for m in
               [re.match(r"bf16\[\d+,\d+,\d+,\d+\]", r)] if m}
    assert len(kernels) == 5, reduces
    assert not any(r.count("bf16[") > 2 for r in reduces), reduces

    # some of them run as start/done pairs with a convolution's
    # backward between the two
    rows = body_schedule(hlo)
    names = [r[0] for r in rows]
    starts = [n for n in names if n.startswith("async-collective-start")]
    assert len(starts) >= 3, names
    beside = 0
    for s in starts:
        done = s.replace("start", "done")
        between = rows[names.index(s) + 1:names.index(done)]
        beside += any("bwd/" in line and "conv_general_dilated" in line
                      for *_, line in between)
    assert beside >= 1, starts

    # one firing later the four chips hold the same state
    import jax
    leaves = jax.tree_util.tree_leaves(
        {"params": w.fused._params, "opt": w.fused._opt})
    assert len(leaves) == 32
    for leaf in leaves:
        first, *others = [np.asarray(s.data)
                          for s in leaf.addressable_shards]
        assert len(others) == CHIPS - 1
        for other in others:
            np.testing.assert_array_equal(first, other)
    acc = np.asarray(w.fused._acc)
    assert acc[2] == 8 * MB and np.isfinite(acc[1])
    w.stop()
