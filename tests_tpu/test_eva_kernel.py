"""The fused EVA attention kernels on the chip (ISSUE 29), at the
published widths — 32 heads x 128, window 2048, chunk 16 — on a row of
8192 (four windows: no summaries, then 128, 256, 384 of them), bf16:
forward and every gradient against the XLA form (``eva_window``, the
path every other platform runs), each within the gap that the XLA form
itself keeps from an f32 run of the same mathematics (the bf16
witness); and the cell's own sizes put 4 of 4 layers on the kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np

from veles_tpu import events, prng, telemetry
from veles_tpu.loader.synthetic import PackedBytesLoader
from veles_tpu.models.evabyte import PUBLISHED, evabyte_layers
from veles_tpu.ops import eva_pallas
from veles_tpu.ops import sequence as seq
from veles_tpu.ops.standard_workflow import StandardWorkflow

NH, D = PUBLISHED["n_heads"], PUBLISHED["head_size"]
WIN, CHUNK, T = PUBLISHED["window_size"], PUBLISHED["chunk_size"], 8192


def _xla(q, k, v, phi, mu):
    ks, vs = seq.eva_summaries(k, v, phi, mu, CHUNK)
    return seq.eva_rows(q, k, v, ks, vs, WIN, CHUNK)


def _fused(q, k, v, phi, mu):
    ks, vs = seq.eva_summaries(k, v, phi, mu, CHUNK)
    return eva_pallas.eva_fused(q, k, v, ks, vs, WIN, CHUNK,
                                eva_pallas.tiles_for(D, WIN, CHUNK, T))


def _gap(got, want):
    """Largest difference over the reference's own scale."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / np.abs(want).max())


def test_fused_matches_the_xla_form_at_the_published_widths(tpu_device):
    keys = jax.random.split(jax.random.key(2929), 6)
    q, k, v, err = (jax.random.normal(keys[i], (1, T, NH, D),
                                      jnp.float32) for i in range(4))
    phi, mu = (0.5 * jax.random.normal(keys[i], (NH, D), jnp.float32)
               for i in (4, 5))

    def run(fn, dtype):
        @jax.jit
        def both(err, *args):
            out, back = jax.vjp(fn, *(a.astype(dtype) for a in args))
            return (out,) + back(err.astype(dtype))
        return both(err, q, k, v, phi, mu)

    names = ("o", "dq", "dk", "dv", "dphi", "dmu")
    with jax.default_matmul_precision("highest"):
        exact = run(_xla, jnp.float32)
    xla, fused = run(_xla, jnp.bfloat16), run(_fused, jnp.bfloat16)
    for name, e, x, f in zip(names, exact, xla, fused):
        assert f.dtype == x.dtype and f.shape == x.shape, name
        assert np.isfinite(np.asarray(f, np.float32)).all(), name
        witness, ours = _gap(x, e), _gap(f, e)
        # as close to the f32 answer as bf16 XLA ops come (with room
        # for a different order of the same roundings)
        assert ours <= 1.5 * witness + 1e-3, (name, ours, witness)
        assert _gap(f, x) <= 2.5 * witness + 1e-3, (name, _gap(f, x))


def test_every_layer_of_the_cell_takes_the_fused_path(tpu_device):
    """The cell's attention sizes (heads, window, chunk, a row of
    32 768) through ``StandardWorkflow.initialize``; the widths the
    choice does not read are cut so that nothing large is filled."""
    telemetry.reset()
    prng.seed_all(29)
    w = StandardWorkflow(
        loader_factory=lambda wf: PackedBytesLoader(
            wf, name="loader", n_train=2, minibatch_size=1,
            seq_len=PUBLISHED["seq_len"]),
        layers=evabyte_layers(4, hidden_size=128, intermediate_size=128),
        loss_function="next_byte", decision_config={"max_epochs": 1},
        superstep=1, name="EvaPaths")
    w.initialize(device=tpu_device)
    seen = telemetry.recent_events(events.EV_EVA_PATH)
    assert [(e["path"], e["reason"]) for e in seen] == [("fused", None)] * 4
    assert seen[0]["tiles"] == {"q": 2048, "k": 512, "r": 512, "rs": 128}
    assert telemetry.gauge(events.GAUGE_EVA_FUSED_LAYERS).value == 4
    w.stop()
