"""Compile for the TPU without a TPU (PR 21).

libtpu is installed here, and ``jax.experimental.topologies`` hands out
a compile-only v5e topology, so the programs the chip will be asked to
run can be pushed through the real TPU compiler (and Mosaic, for the
Pallas kernels) on a CPU-only box.  Nothing executes; a timing is not
to be had this way.  Each case is its own subprocess because the
failure this file exists for is a SIGSEGV inside the compiler, not a
Python exception.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRELUDE = """
import sys
import numpy as np
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                    platform="tpu")
on_chip = SingleDeviceSharding(topo.devices[0])


def spec(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=on_chip)
"""

MEMBER_STACKED = PRELUDE + """
from veles_tpu import prng
from veles_tpu.backends import NumpyDevice
from veles_tpu.datasets import synthetic_classification
from veles_tpu.engine import core as engine_core
from veles_tpu.loader import ArrayLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow

members, hidden, classes = (int(a) for a in sys.argv[1:4])
prng.seed_all(4242)
train, valid, _ = synthetic_classification(
    64, 16, (6, 6, 1), n_classes=classes, seed=5)
w = StandardWorkflow(
    loader_factory=lambda w: ArrayLoader(
        w, train=train, valid=valid, minibatch_size=16, name="loader"),
    layers=[{"type": "all2all_tanh",
             "->": {"output_sample_shape": hidden},
             "<-": {"learning_rate": 0.1}},
            {"type": "softmax", "->": {"output_sample_shape": classes},
             "<-": {"learning_rate": 0.1}}],
    decision_config={"max_epochs": 2}, name="wf")
w.initialize(device=NumpyDevice())
params = {f.name: {k: spec((members,) + np.asarray(v).shape, jnp.float32)
                   for k, v in f.gather_params().items()}
          for f in w.forwards}
fn = engine_core.build_mean_probs(w.forwards, members, jnp.bfloat16)
jax.jit(fn).lower(params, spec((8, 6, 6, 1), jnp.float32)).compile()
print("COMPILED")
"""

PALLAS_EVA = PRELUDE + """
from veles_tpu.ops import eva_pallas

t, nh, d, win, chunk = (int(a) for a in sys.argv[1:6])
tiles = eva_pallas.tiles_for(d, win, chunk, t)
assert tiles is not None
x = spec((1, t, nh, d), jnp.bfloat16)
s = spec((1, t // chunk, nh, d), jnp.bfloat16)


def attend(q, k, v, ks, vs):
    return eva_pallas.eva_fused(q, k, v, ks, vs, win, chunk, tiles)


def both(q, k, v, ks, vs, do):
    return jax.vjp(attend, q, k, v, ks, vs)[1](do)


hlo = jax.jit(both).lower(x, x, x, s, s, x).compile().as_text()
assert hlo.count("tpu_custom_call") >= 2, hlo[-2000:]
print("COMPILED")
"""

DP_STEP = PRELUDE + """
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from veles_tpu import events, prng, telemetry
from veles_tpu.backends import make_device
from veles_tpu.datasets import synthetic_classification
from veles_tpu.engine import core as engine_core
from veles_tpu.loader import ArrayLoader
from veles_tpu.ops.standard_workflow import StandardWorkflow

mb, hidden, k = (int(a) for a in sys.argv[1:4])
prng.seed_all(4242)
train, _, _ = synthetic_classification(
    2 * mb, 0, (12, 12, 1), n_classes=10, seed=5)
gd = {"learning_rate": 0.1, "gradient_moment": 0.9}
w = StandardWorkflow(
    loader_factory=lambda w: ArrayLoader(
        w, train=train, minibatch_size=mb, name="loader"),
    layers=[{"type": "all2all_tanh",
             "->": {"output_sample_shape": hidden}, "<-": gd},
            {"type": "dropout", "->": {"dropout_ratio": 0.5}, "<-": {}},
            {"type": "softmax", "->": {"output_sample_shape": 10},
             "<-": gd}],
    decision_config={"max_epochs": 1}, superstep=k, name="wf")
w.initialize(device=make_device("cpu"))
# the runner's own mesh step, built for the four described chips
fused = w.fused
mesh = Mesh(np.array(topo.devices), ("data",))
fused.mesh, fused.compute_dtype, fused._train_step = \
    mesh, jnp.bfloat16, None
fused._build_steps()
ev = telemetry.recent_events(events.EV_DP_GRAD_EXCHANGE)[-1]
assert [g["how"] for g in ev["groups"]] == ["reduced", "gathered"], ev
assert ev["options"] == engine_core.TPU_GRAD_EXCHANGE_OPTIONS, ev
repl, batch = NamedSharding(mesh, P()), NamedSharding(mesh, P(None, "data"))


def shapes(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=repl), tree)


S = jax.ShapeDtypeStruct
args = (shapes(fused._collect_params()), shapes(fused._collect_opt()),
        S((3,), jnp.float32, sharding=repl),
        S(fused._conf_shape(), jnp.int32, sharding=repl),
        S((2 * mb, 12, 12, 1), jnp.bfloat16, sharding=repl),
        S((2 * mb,), jnp.int32, sharding=repl),
        S((k, mb), jnp.int32, sharding=batch),
        S((k, mb), w.loader.minibatch_mask.mem.dtype, sharding=batch),
        S((k, len(fused.gds), 2), jnp.float32, sharding=repl),
        S((), jnp.int32, sharding=repl))
# an option libtpu does not know would raise here
hlo = fused._train_step.lower(*args).compile().as_text()
wide = "[144,%d]" % hidden
for line in hlo.splitlines():
    if " all-reduce(" in line or " reduce-scatter(" in line:
        assert wide not in line.split(" all-re")[0], line[:200]
assert "all-gather" in hlo or "all_gather" in hlo
assert "bf16[%d,144]" % mb in hlo or "bf16[%d,12,12,1]" % mb in hlo
print("COMPILED")
"""

HYBRID_UNITS = PRELUDE + """
from veles_tpu.models.qwen3next import qwen3next_layers
from veles_tpu.ops import deltanet
from veles_tpu.ops.registry import forward_registry
from veles_tpu.ops.sequence import SequenceUnit

kind, t = sys.argv[1], int(sys.argv[2])
SequenceUnit.platform = lambda self: "tpu"      # no chip to observe
flat = [c for e in qwen3next_layers() for c in e.get("layers", [e])]
fw = dict(next(c for c in flat if c["type"] == kind)["->"])
fw.pop("weights_stddev")
unit = forward_registry[kind][0](None, name="u", **fw)
x = spec((1, t, 2048), jnp.bfloat16)
params = {n: spec(s, jnp.bfloat16)
          for n, s in unit.param_shapes(x.shape).items()}


def both(params, x, err):
    out, back = jax.vjp(unit.forward, params, x)
    return (out,) + back(err)


err = spec(unit.output_shape_for(x.shape), jnp.bfloat16)
hlo = jax.jit(both).lower(params, x, err).compile().as_text()
want = {"gated_attention": ("splash", 3), "moe": ("gmm", 9),
        "gated_delta_net": ("chunked", 2)}[kind]
form = {"gated_attention": lambda: unit.path, "moe": lambda: unit.share,
        "gated_delta_net": lambda: unit.path}[kind]()
assert form["form"] == want[0], form
assert hlo.count("tpu_custom_call") >= want[1], hlo.count("tpu_custom_call")
if kind == "moe":
    # the compact buffers alone (nine grouped products + the combine's
    # two): no array of the whole buffers' 40 960 rows is left
    assert (form["rows"], form["capacity"]) == (10 * t, t), form
    assert hlo.count("tpu_custom_call") >= 11
    assert "bf16[%d,2048]" % (10 * t) not in hlo
if kind == "gated_delta_net":
    assert form == dict(deltanet.rule_path(t, unit.chunk_size),
                        products="fused", tiles=(8, 2), scan="fused",
                        scan_tiles=(16, 2)), form
    for kernel in ("gdn_products_fwd", "gdn_products_bwd",
                   "gdn_scan_fwd", "gdn_scan_bwd"):
        assert kernel in hlo, kernel
    # every [C, C] matrix lives in the kernels: none is an HLO value
    assert "f32[%d,1,16,2,64,64]" % (t // 64) not in hlo
    # the state lives in the scan kernels: no loop carries it
    for line in hlo.splitlines():
        assert not (" while(" in line and "f32[1,16,2,128,128]" in line), \
            line[:200]
print("COMPILED")
"""

MELLUM2_UNITS = PRELUDE + """
from veles_tpu.models.mellum2 import CUT, mellum2_layers
from veles_tpu.ops.registry import forward_registry
from veles_tpu.ops.sequence import SequenceUnit

which = sys.argv[1]
SequenceUnit.platform = lambda self: "tpu"      # no chip to observe
flat = [c for e in mellum2_layers() for c in e.get("layers", [e])]
fw = dict(flat[{"window": 2, "full": 17, "moe": 5}[which]])
unit = forward_registry[fw["type"]][0](None, name="u", **{
    k: v for k, v in fw["->"].items() if k != "weights_stddev"})
x = spec((CUT["minibatch"], CUT["seq_len"], 2304), jnp.bfloat16)
params = {n: spec(s, jnp.bfloat16)
          for n, s in unit.param_shapes(x.shape).items()}


def both(params, x, err):
    out, back = jax.vjp(unit.forward, params, x)
    return (out,) + back(err)


err = spec(unit.output_shape_for(x.shape), jnp.bfloat16)
hlo = jax.jit(both).lower(params, x, err).compile().as_text()
if which == "moe":
    assert (unit.share["form"], unit.share["rows"], unit.share["blocks"],
            unit.share["capacity"], unit.share["shared"]) == (
        "gmm", 32768, 8, 12288, False), unit.share
    assert unit.share["tiles"]["in"] == {
        "forward": (512, 1152, 896), "input_grad": (512, 896, 1152),
        "weight_grad": (512, 1152, 896)}, unit.share
    assert unit.share["padding"]["fold"] == 1.0, unit.share
    assert hlo.count("tpu_custom_call") >= 11
    assert "bf16[32768,896]" not in hlo     # no whole-buffer product
else:
    assert (unit.path["form"], unit.path["window"],
            unit.path.get("visited", unit.path.get("kv_blocks"))) == (
        ("band", 1024, 1.25) if which == "window"
        else ("splash", None, 16)), unit.path
    assert hlo.count("tpu_custom_call") >= 3
print("COMPILED")
"""

BAND_CORE = PRELUDE + """
import re
from veles_tpu.ops import attention, attention_pallas

nkv, group, window = (int(a) for a in sys.argv[1:4])
rows, t, d = 4, 8192, 128
path = attention.attention_path("tpu", d, t, window=window, group=group)
assert path["form"] == "band", path
tiles = attention_pallas.BandTiles(**path["tiles"])


def core(q, k, v):
    # the projections' layout in and out, as the attention layer has it
    o = attention_pallas.band_attention(
        q.reshape(rows, t, nkv, group, d), k.reshape(rows, t, nkv, d),
        v.reshape(rows, t, nkv, d), window, tiles)
    return o.reshape(q.shape)


def both(q, k, v, err):
    out, back = jax.vjp(core, q, k, v)
    return (out,) + back(err)


q = spec((rows, t, nkv * group * d), jnp.bfloat16)
k = spec((rows, t, nkv * d), jnp.bfloat16)
hlo = jax.jit(both).lower(q, k, k, q).compile().as_text()
for name in ("band_fwd", "band_bwd_dq", "band_bwd_dkv"):
    assert name in hlo, name
# nothing is laid out anew round the kernels
moved = [line for line in hlo.splitlines()
         if re.search(r"= \\S+ (copy|transpose)\\(", line)
         and f"[{rows},{t}," in line]
assert not moved, moved[:3]
print("COMPILED")
"""

EMBED_GATHER = PRELUDE + """
from veles_tpu.ops import sequence

vocab, hidden = (int(a) for a in sys.argv[1:3])
rows, t = 4, 8192
path = sequence.embed_path(vocab, hidden, rows * t, "tpu")
assert path == {"form": "gather", "backward": "segment_sum"}, path


def step(table, ids, err):
    # the f32 table looked up in bf16, its gradient back in f32 and the
    # SGD update, as the train step has them
    out, back = jax.vjp(lambda w: sequence.gather_embed(
        w, ids, path["backward"]), table.astype(jnp.bfloat16))
    (dw,) = back(err)
    return out, table - 0.01 * dw.astype(jnp.float32)


compiled = jax.jit(step, donate_argnums=0).lower(
    spec((vocab, hidden), jnp.float32), spec((rows, t), jnp.int32),
    spec((rows, t, hidden), jnp.bfloat16)).compile()
hlo = compiled.as_text()
assert "embed_segment_sum" in hlo
# no one-hot product and no f32 copy of the error rows
assert " convolution(" not in hlo and " dot(" not in hlo
assert "f32[%d,%d]" % (rows * t, hidden) not in hlo
print("COMPILED")
"""


def _compile(src, *argv):
    res = subprocess.run(
        [sys.executable, "-c", src, *map(str, argv)], cwd=REPO,
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0 and "COMPILED" in res.stdout, \
        f"rc={res.returncode}\n{res.stderr[-1500:]}"


@pytest.mark.parametrize("members,hidden,classes", [
    (3, 12, 3),        # chip_smoke's served package: the first sighting
    (3, 128, 1000),    # a 3-member ensemble over AlexNet's head width
    (3, 16, 4),
])
def test_member_stacked_softmax_head_compiles(members, hidden, classes):
    """The served ensemble forward — softmax head under a stacked
    member axis — at shapes for which libtpu 0.0.34's compiler
    overflowed its stack fusing the softmax into the member-batched
    matmul (every 3-member case tried), killing the Hive replica on
    its first request.  engine/core.py build_member_forward now
    splits the head at its logits."""
    _compile(MEMBER_STACKED, members, hidden, classes)


@pytest.mark.parametrize("t,window", [(32768, 2048), (8192, 2048),
                                      (1024, 512)])
def test_pallas_eva_attention_compiles_at_the_published_widths(
        t, window):
    """The fused EVA attention kernels (ISSUE 29), forward and fused
    backward, through Mosaic in bf16 at EvaByte's 32 heads x 128 and
    chunk 16: the cell's row (a whole window a grid step, 64 MiB of
    scoped VMEM), the chip test's row, and a small window whose query
    block is one key tile.  Interpret mode (tests/test_sequence.py)
    cannot see an unaligned slice or a VMEM overrun; this can.
    tests_tpu/test_eva_kernel.py runs them on the chip."""
    _compile(PALLAS_EVA, t, 32, 128, window, 4 if window == 512 else 16)


def test_data_parallel_step_compiles_with_its_exchange_options():
    """The mesh train step as ``FusedStepRunner._build_steps`` jits it
    (ISSUE 31) through the v5e's compiler for a 2x2 host: the three
    options ``GradExchange`` hands over are names libtpu knows (an
    unknown one fails the compile), the wide dense layer's gradient is
    made from gathered activations — no all-reduce of its shape in the
    compiled text, the global minibatch's rows gathered instead — and
    the ``shard_map`` inside the scanned backward partitions.
    tests_tpu/test_dp_exchange.py runs AlexNet's on four chips."""
    _compile(DP_STEP, 64, 2048, 2)


@pytest.mark.parametrize("kind,t", [("gated_delta_net", 4096),
                                    ("gated_delta_net", 32768),
                                    ("gated_attention", 4096),
                                    ("moe", 4096)])
def test_hybrid_layer_types_compile_at_the_published_widths(kind, t):
    """The three layer types of ISSUE 32, forward and backward, through
    the v5e's compiler in bf16 at Qwen3-Next's published widths on a
    row of 4096: the chunked delta rule with its scan over chunks and
    its chunk products in the two fused kernels of ISSUE 33 (also on
    the cell's row of 32 768: Mosaic through the v5e's compiler sees
    the unaligned slice or VMEM overrun that interpret mode cannot;
    the compiled text names both kernels and holds no ``[C, C]`` f32
    array of the chunks), the causal attention core on the flash kernel
    that ships with jax (16 query heads x 256 over 2 key heads: its
    multi-query kernel mapped over the key heads, forward + dq + dkv),
    and the expert share's
    three grouped products on the shipped grouped-matmul kernel
    (forward + two backward each) through the compact buffers of
    ISSUE 35 (4 096 rows of the worst routing's 40 960, the combine a
    grouped product too, a loop over a block's pieces round them).
    tests_tpu/test_hybrid_layers.py runs them on the chip."""
    _compile(HYBRID_UNITS, kind, t)



@pytest.mark.parametrize("which", ["window", "full", "moe"])
def test_mellum2_layer_types_compile_at_the_cells_sizes(which):
    """ISSUE 34's layer types, forward and backward, through the v5e's
    compiler in bf16 at Mellum2's published widths on the cell's four
    rows of 8 192: the attention core on the shipped flash kernel under
    a window of 1 024 keys (its mask tables leave a query block 3 key
    blocks of 512) and without one (16), 32 query heads x 128 over 4
    key heads; the share of 16 of 64 experts of width 896 on the
    shipped grouped matmul at the compact 12 288-row buffers (ISSUE 35;
    32 768 rows would take the worst routing), 8 blocks, no shared expert.
    tests_tpu/test_mellum2_layers.py runs them on the chip."""
    _compile(MELLUM2_UNITS, which)


@pytest.mark.parametrize("nkv,group,window", [(8, 9, 512), (4, 8, 1024)],
                         ids=["laguna", "mellum2"])
def test_band_kernels_compile_at_the_cells_shapes(nkv, group, window):
    """The window core's three band kernels, forward and
    backward, through Mosaic in bf16 at the two window cells' shapes
    (four rows of 8 192, head size 128) and at the block
    ``attention_path`` chooses: the laguna window of 512 keys at group
    9, the mellum2 window of 1024 at group 8; no copy or transpose of
    a row-sized array is left round them.  Interpret mode
    (tests/test_attention_pallas.py) cannot see an unaligned slice or
    a VMEM overrun; this can.  tests_tpu/test_band_attention.py runs
    them on the chip."""
    _compile(BAND_CORE, nkv, group, window)


@pytest.mark.parametrize("vocab,hidden", [(24576, 2304), (12544, 3072)],
                         ids=["mellum2", "laguna"])
def test_embedding_gather_compiles_at_the_cells_shapes(vocab, hidden):
    """The embedding's gather form (``ops/sequence.py``
    ``gather_embed``) at the two cells' held tables and four rows of
    8 192 ids: the lookup, the table's gradient by the segment sum
    kernel of ``ops/embed_pallas.py`` (through Mosaic: interpret mode,
    tests/test_embedding.py, cannot see an unaligned slice or a VMEM
    overrun) and the f32 table's SGD update, with no product left and
    no f32 copy of the error rows.  tests_tpu/test_mellum2_layers.py
    runs it on the chip."""
    _compile(EMBED_GATHER, vocab, hidden)
