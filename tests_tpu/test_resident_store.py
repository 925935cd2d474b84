"""The resident dataset as the step reads it, on the chip (ISSUE 27).

PR 26's trace found the whole resident store converted f32 -> bf16 AND
re-laid out on every superstep (``copy.24``, 14.7 % of AlexNet's
device time): the TPU keeps the ROWS of a rank-4 array minor-most by
default, a gather of whole rows needs them major-most, and XLA hoists
both out of the scan into the step program.  ``FullBatchLoader.
reside_as`` now does both once, at set-up.  The first test is the
check that would have caught it: no instruction of the step's
optimised HLO may PRODUCE an array of the store's full shape.
"""

import re

import numpy as np

from veles_tpu import events, prng, telemetry
from veles_tpu.loader.synthetic import (DeviceSyntheticLoader,
                                        SyntheticClassificationLoader)
from veles_tpu.models.alexnet import alexnet_layers
from veles_tpu.ops.standard_workflow import StandardWorkflow

INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = (\w+)\[([\d,]*)\](?:\{[^}]*\})? "
    r"([\w-]+)\(")


def makers_of(hlo_text, shape):
    """(name, opcode) of every instruction whose result is ONE array
    of ``shape`` and that is neither a parameter nor a tuple element
    handed through (the scan's carry)."""
    want = ",".join(str(n) for n in shape)
    found = []
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        if m and m.group(3) == want and m.group(4) not in (
                "parameter", "get-tuple-element"):
            found.append((m.group(1), m.group(4)))
    return found


def workflow(loader_class, layers, shape, n_train, mb, n_classes,
             superstep=8):
    prng.seed_all(1234)
    return StandardWorkflow(
        loader_factory=lambda wf: loader_class(
            wf, name="loader", minibatch_size=mb, n_train=n_train,
            n_valid=0, shape=shape, n_classes=n_classes, seed=7),
        layers=layers, decision_config={"max_epochs": 1},
        superstep=superstep, name="TpuResidentStore")


def hlo_of_first_train_step(w):
    """Optimised HLO of the train step, lowered for the very arguments
    the adapter hands it (their dtypes AND device layouts), and then
    the call itself."""
    texts = []
    step = w.fused._train_step

    def train_step(*args):
        texts.append(step.lower(*args).compile().as_text())
        return step(*args)

    w.fused._train_step = train_step
    w.loader.run()
    w.fused.run()
    w.fused._train_step = step
    return texts[0]


class TestResidentStore:
    def test_alexnet_step_makes_no_array_of_the_stores_shape(
            self, tpu_device):
        shape, n_train = (227, 227, 3), 2048
        w = workflow(DeviceSyntheticLoader, alexnet_layers(1000), shape,
                     n_train, 128, 1000)
        w.evaluator.compute_confusion = False
        w.initialize(device=tpu_device)
        store = w.loader.original_data.devmem
        assert str(store.dtype) == "bfloat16"
        # whole rows contiguous: axis 0 major-most
        assert store.format.layout.major_to_minor[0] == 0, store.format
        (ev,) = telemetry.recent_events(events.SPAN_LOADER_RESIDENT_DTYPE)
        assert (ev["from"], ev["to"]) == ("float32", "bfloat16")
        assert ev["bytes_after"] * 2 == ev["bytes_before"]
        hlo = hlo_of_first_train_step(w)
        # the parser sees the step: the gathered minibatches are made
        assert makers_of(hlo, (128,) + shape), "no gather in the HLO?"
        assert makers_of(hlo, (n_train,) + shape) == []
        acc = np.asarray(w.fused._acc)
        assert acc[2] == 8 * 128 and np.isfinite(acc[1])
        assert telemetry.counter(
            events.CTR_LOADER_RESIDENT_CASTS).value == 1
        w.stop()

    def test_reupload_after_host_write_keeps_form_and_program(
            self, tpu_device):
        """A stale store comes back in the mirror's dtype AND layout,
        so the step that was compiled for them is not compiled again."""
        gd = {"learning_rate": 0.02, "gradient_moment": 0.9}
        w = workflow(
            SyntheticClassificationLoader,
            [{"type": "conv_relu", "<-": gd,
              "->": {"n_kernels": 8, "kx": 3, "ky": 3}},
             {"type": "softmax", "->": {"output_sample_shape": 4},
              "<-": gd}],
            (14, 14, 3), 512, 32, 4, superstep=2)
        w.initialize(device=tpu_device)
        store = w.loader.original_data
        assert store.mem.dtype == np.float32
        before = store.devmem.format
        assert before.layout.major_to_minor[0] == 0, before
        w.loader.run()
        w.fused.run()
        store.map_write()[0] = 0.5
        fresh = store.unmap()
        assert fresh.format == before
        assert store.mem.dtype == np.float32
        compiles = telemetry.counter(events.CTR_XLA_COMPILES).value
        w.loader.run()
        w.fused.run()
        assert np.isfinite(np.asarray(w.fused._acc)[1])
        assert telemetry.counter(events.CTR_XLA_COMPILES).value \
            == compiles
        w.stop()
