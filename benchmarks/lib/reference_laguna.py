"""The plain reference of configuration ``laguna``: a decoder whose
period of four layers is one full causal attention layer and three
sliding-window attention layers — grouped-query with head counts of
their own, a sigmoid gate a head, each kind with a RoPE law of its own
(YaRN on the first half of each head of the full layers) — the first
layer followed by a dense SwiGLU, every other one by a mixture of
experts of which this chip holds a share, its routed part scaled,
beside one shared expert; next-token cross-entropy and SGD with
momentum — plain ``jax.numpy`` float32 at matmul precision "highest",
an interpreter of the configuration's ``layers`` list.  It imports
nothing of the program.  What is the same mathematics as in
``reference_qwen3next.py`` and ``reference_mellum2.py`` is imported
from there: the norm, the router, a SwiGLU, the routed experts' masked
loop, the loss, the SGD step, the seeded leaves, the rounding of a
control's operands, the RoPE laws and the rotation, the window mask
written out from its definition.  So is the walk — ``follow``, its
``_Walker``, ``rates_of``, ``init_params``, ``entry_forward``,
``model_loss`` — which reads this module's layer functions
(``lib/rebind.py``); what this architecture adds is written out here.

What it computes, per row of ``T`` token ids (``layers`` names the
sizes), beyond ``reference_mellum2.py``'s embedding, norm, ``dense``
and head:

- ``attention``: ``q = x Wq [T, nh, d]``, ``k, v [T, nkv, d]``;
  rotate-half RoPE on the first ``r = d * partial_rotary_factor``
  elements of each head (the whole head where the specification gives
  no factor), ``x[:r] <- a (x[:r] cos(n f) + rot(x[:r]) sin(n f))``,
  the other ``d - r`` elements untouched and unscaled, ``(f, a)`` of
  the layer's ``rope`` made over the r rotated elements (``default``:
  ``f_j = theta^(-2j/r)``, ``a = 1``; ``yarn``: ``e_j =
  theta^(-2j/r)``, ``p_j = e_j / factor``, ``dim(b) = r ln(original /
  (2 pi b)) / (2 ln theta)``, ``low = max(floor(dim(beta_fast)), 0)``,
  ``high = min(ceil(dim(beta_slow)), r - 1)``, ``ramp_j = clip((j -
  low) / (high - low), 0, 1)``, ``f_j = p_j ramp_j + e_j (1 -
  ramp_j)``, ``a = attention_factor``); ``o_n = softmax_m(q_n . k_m /
  sqrt(d)) v_m`` over ``m <= n``, and with ``window`` W also ``m > n -
  W``, query head h reading key head ``h // (nh / nkv)``; with
  ``gate``: ``y_h = sigmoid(x W_g)_h o_h``, ``W_g [H, nh]``;
- ``swiglu``: ``silu(x W_gate) * (x W_up)`` (the ``dense`` after it is
  the down projection);
- ``moe``: ``p = softmax(x W_r)`` over ALL ``experts_total``; the
  ``top_k`` largest, ``w_e = s p_e / sum_topk p`` with ``s`` the
  ``routed_scale``; ``y = sum over e in topk AND held of w_e W_d,e(
  silu(W_g,e x) * W_u,e x) + sigmoid(x . w_s) Shared(x)`` — the held
  experts' masked loop of ``reference_qwen3next.py``, its sum times
  ``s`` (the sum is linear in the weights), then the shared expert.

``precision`` selects what stands in the program's place for the
control: "f32" is the reference; "bf16" / "fp8" round the operands of
every matmul to bfloat16 / float8_e4m3 as plain casts.  ``fault``
plants a fault into the same arithmetic: "no_gate" (the gate left
out), "no_routed_scale" (the routed scale left out), "yarn_scale_whole"
(YaRN's scale on the unrotated elements too), "rope_whole" (RoPE over
the whole head on the full layers, its law made over the whole head),
"no_window" (the window left out of the window layers), "no_shared"
(the shared expert left out), "half_batch" (the second half of every
minibatch's rows left out, the mean taken over the rest),
"state_unchanged" (the state returned as given).

``follow`` walks the top-level entries back one at a time with
``jax.vjp`` (entries whose layers act on each position alone, and the
head with its loss, in blocks of ``seq_block`` positions), keeps the
weights, the momentum and the entries' inputs on the host — only the
entry being walked is on the device — and applies an entry's update as
soon as its gradient exists.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np  # noqa: F401  (the walk reads it)

from . import reference_mellum2 as _m2
from . import reference_qwen3next as _q3
from .rebind import rebound, rebound_class
from .reference_mellum2 import masked_attention, rope_law, rotate
from .reference_qwen3next import (  # noqa: F401  (the walk reads them)
    _ein, _norm, _sgd, entries, flatten, init_leaf, loss_sum,
    moe_routing, swiglu_mlp, targets_of, valid_count)

#: parameters that take the weights' rate and decay; gains the bias's
MATRICES = dict(_q3.MATRICES, attention=("wq", "wk", "wv", "wg"),
                swiglu=("w_gate", "w_up"))
#: layer types that act on each position alone
POINTWISE = ("rmsnorm", "dense", "moe", "swiglu")
FAULTS = ("no_gate", "no_routed_scale", "yarn_scale_whole", "rope_whole",
          "no_window", "no_shared", "half_batch")

#: queries of the attention scored at once (72 heads: a block's f32
#: scores over a row of 8 192 keys are 1.2 GB at four rows)
QUERY_BLOCK = 128


# -- the layers list --------------------------------------------------

def param_shapes(layers) -> List[Dict[str, Tuple[int, ...]]]:
    """Parameter shapes of every flat layer (empty where it has none)."""
    out, width = [], None
    for cfg in flatten(layers):
        kind, fw = cfg["type"], cfg.get("->", {})
        if kind == "embedding":
            width = int(fw["hidden_size"])
            p = {"weights": (int(fw["vocab_size"]), width)}
        elif kind == "rmsnorm":
            p = {"gain": (width,)}
        elif kind == "dense":
            p = {"weights": (width, int(fw["output_size"]))}
            width = int(fw["output_size"])
        elif kind == "swiglu":
            n = int(fw["intermediate_size"])
            p = {"w_gate": (width, n), "w_up": (width, n)}
            width = n
        elif kind == "attention":
            nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
            d = int(fw["head_size"])
            p = {"wq": (width, nh * d), "wk": (width, nkv * d),
                 "wv": (width, nkv * d)}
            if fw.get("gate"):
                p["wg"] = (width, nh)
            width = nh * d
        elif kind == "moe":
            held, n = int(fw["experts_held"]), int(fw["expert_size"])
            s = int(fw["shared_size"])
            p = {"router": (width, int(fw["experts_total"])),
                 "w_gate": (held, width, n), "w_up": (held, width, n),
                 "w_down": (held, n, width),
                 "s_gate": (width, s), "s_up": (width, s),
                 "s_down": (s, width), "s_mix": (width, 1)}
        elif kind == "lm_head":
            p = {"weights": (width, int(fw["n_pred_heads"])
                             * int(fw["vocab_size"]))}
        else:
            raise ValueError(f"reference: unknown layer type {kind!r}")
        out.append(p)
    return out


# -- the arithmetic ---------------------------------------------------

def rotated_count(fw, fault: Optional[str] = None) -> int:
    """Elements of a head that RoPE rotates."""
    d = int(fw["head_size"])
    if fault == "rope_whole":
        return d
    return int(round(d * float(fw["rope"].get("partial_rotary_factor", 1))))


def attention(x, p, fw, precision="f32", fault=None):
    """x [B, T, H] -> [B, T, nh * d]."""
    b, t, _ = x.shape
    nh, nkv = int(fw["n_heads"]), int(fw["n_kv_heads"])
    d = int(fw["head_size"])
    window = None if fault == "no_window" else fw.get("window")
    r = rotated_count(fw, fault)
    inv_freq, a = rope_law(fw["rope"], r)
    rest = a if fault == "yarn_scale_whole" else 1.0

    def heads(w, n):
        return _ein("bth,hk->btk", x, p[w], precision).reshape(b, t, n, d)

    def rotated(w, n):
        h = heads(w, n)
        return jnp.concatenate([rotate(h[..., :r], inv_freq, a),
                                rest * h[..., r:]], -1)

    q, k = rotated("wq", nh), rotated("wk", nkv)
    o = masked_attention(q.reshape(b, t, nkv, nh // nkv, d), k,
                         heads("wv", nkv), window, precision,
                         QUERY_BLOCK).reshape(b, t, nh, d)
    if fw.get("gate") and fault != "no_gate":
        o = o * jax.nn.sigmoid(_ein("bth,hn->btn", x, p["wg"],
                                    precision))[..., None]
    return o.reshape(b, t, nh * d)


def moe(x, p, fw, precision="f32", fault=None, held=None):
    """x [B, T, H] -> [B, T, H]: the held experts' part of the routed
    sum times the routed scale (``held`` = (first, count) overrides the
    layer's own share: the shares-add-up test), plus the shared
    expert."""
    y = _q3.moe(x, p, fw, precision, "no_shared", held)
    if fault != "no_routed_scale":
        y = float(fw.get("routed_scale", 1.0)) * y
    if fault == "no_shared":
        return y
    mix = jax.nn.sigmoid(_ein("bth,hk->btk", x, p["s_mix"], precision))
    return y + mix * swiglu_mlp(x, p["s_gate"], p["s_up"], p["s_down"],
                                precision)


def layer_forward(cfg, p, x, precision="f32", fault=None):
    """One flat layer of the list on x (ids [B, T] for the embedding,
    else [B, T, width])."""
    kind, fw = cfg["type"], cfg.get("->", {})
    if kind == "attention":
        return attention(x, p, fw, precision, fault)
    if kind == "swiglu":
        return jax.nn.silu(_ein("bth,hk->btk", x, p["w_gate"], precision)) \
            * _ein("bth,hk->btk", x, p["w_up"], precision)
    if kind == "moe":
        return moe(x, p, fw, precision, fault)
    return _m2.layer_forward(cfg, p, x, precision)


# -- the walk: reference_mellum2's, over this module's layers -----------
# (its module-level names — ``param_shapes``, ``layer_forward``,
# ``MATRICES``, ``POINTWISE``, ``_Walker`` ... — read from this module)

init_params = rebound(_m2.init_params, globals())
param_count = rebound(_m2.param_count, globals())
entry_forward = rebound(_m2.entry_forward, globals())
model_logits = rebound(_m2.model_logits, globals())
model_loss = rebound(_m2.model_loss, globals())
rates_of = rebound(_m2.rates_of, globals())
_Walker = rebound_class(_m2._Walker, globals(), "_Walker")
follow = rebound(_m2.follow, globals())
