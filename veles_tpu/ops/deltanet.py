"""``gated_delta_net``: a linear-attention layer of the sequence op
family (``ops/sequence.py``) — a causal depthwise convolution, then the
gated delta rule over a ``[d_k, d_v]`` state a head, then a gated RMS
norm (Yang, Kautz, Hatamizadeh: Gated Delta Networks, arXiv:2412.06464).

From ``u [rows, T, hidden]``: ``q, k [T, Hk, dk]``, ``v, z [T, Hv,
dv]``, ``b, a [T, Hv]`` by bias-free projections; ``(q, k, v) <-
silu(conv(q, k, v))`` (``y_t = sum_j c_j x_{t - K + 1 + j}``, zeros
left of the row); ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a
+ dt_bias)``; q and k L2-normalised over the head, ``q <- q /
sqrt(dk)``; value head h reads key head ``h // (Hv / Hk)``.  A head, in
f32, from ``S = 0``:

    S <- exp(g_t) S;  delta_t = beta_t (v_t - S^T k_t);
    S <- S + k_t delta_t^T;  o_t = S^T q_t

and ``y_t = w * o_t / sqrt(mean(o_t^2) + eps) * silu(z_t)`` a head;
the out-projection is the ``dense`` layer that follows.

Two forms of the rule, chosen by :func:`rule_path` from the shapes and
journaled (``gdn.path``): ``chunked`` where the row is whole chunks —
inside a chunk of C positions the deltas solve one unit-lower-
triangular system, ``(I + M) Delta = beta V - (beta Gamma K) S0`` with
``M_ij = beta_i (Gamma_i / Gamma_j) k_i.k_j`` for j < i, whose inverse
is made once for all chunks as the product ``(I + P)(I + P^2)(I +
P^4)...``, ``P = -M`` (M is nilpotent), all large matmuls; only the
``[dk, dv]`` state is carried chunk to chunk — and ``recurrent``, the
recurrence above token by token: the oracle of the chunked form, and
what a ragged row runs (its backward ``jax.vjp`` of its ``lax.scan``).
State, decays and the triangular inverse in f32; the chunk products
take operands in the compute dtype and accumulate in f32.

What the scan over chunks reads — ``u = T (beta V)``, ``w = T (beta
Gamma K)``, the decayed ``q k^T`` and ``log Gamma``, ``T = (I + M)^-1``
— is made in one of two ways, chosen by :func:`products_path` from
what the unit observes (platform, ``vmap``, chunk and head sizes; no
knob) and journaled with the form (``gdn.path``: ``products``,
``reason`` / ``tiles``; gauge ``gdn.fused_layers``): ``fused`` on a TPU
where the shapes tile — the two Pallas kernels of
``ops/deltanet_pallas.py``, a chunk's ``[C, C]`` matrices in VMEM from
birth to last use, forward and backward — else ``xla``,
:func:`_chunk_products`: ``CHUNKS_AT_ONCE`` chunks at a time under
``jax.checkpoint``, its backward ``jax.vjp`` of the ten-product chain;
what every other platform, a cohort under ``vmap`` and a shape that
does not tile run, and the oracle of the kernels.  The fused backward
is no transpose of that chain: with ``A = I + M``, ``T = A^-1``, ``u =
T b_v``, ``w = T b_k`` (``b_v = beta V``, ``b_k = beta Gamma K``),

    d b_v = T^T du,   d b_k = T^T dw,
    dA = -(d b_v) u^T - (d b_k) w^T   (strictly lower; the rest masked)

so it needs ``T`` once — re-made in VMEM exactly as the forward makes
it, from the kernel's inputs, its only residuals — two ``T^T [C, dk +
dv]`` products and two ``[C, d] [d, C]`` products a value head, then
the elementwise chain back to ``k``, ``beta``, ``g`` (``dM_ij`` moves
``beta_i``, ``k_i . k_j`` and ``log Gamma_i - log Gamma_j``) and the
``q k^T`` branch of the decayed scores.

The scan over chunks — a chunk's ``delta = u - w S``, ``o = (Gamma q)
S + a_qk delta``, ``S <- Gamma_C S + ((Gamma_C / Gamma) k)^T delta`` —
runs in one of two forms, chosen by :func:`scan_path` from the same
observations and journaled beside the products (``gdn.path``: ``scan``,
``scan_reason`` / ``scan_tiles``; gauge ``gdn.fused_scans``): ``fused``
on a TPU where the shapes tile — the kernel pair ``gdn_scan_fwd`` /
``gdn_scan_bwd`` of ``ops/deltanet_pallas.py``, each head's f32 state
(and in the backward its cotangent) in VMEM across the row's chunks,
the chunks a grid axis visited in order — else ``xla``,
:func:`_scan_xla`: a ``lax.scan`` of a checkpointed step, its backward
``jax.vjp`` of the scan (that of a carried state), and the kernels'
oracle.

Device ops carry ``gdn/conv``, ``gdn/rule``, ``gdn/gate_norm``; all
four kernels run under ``gdn/rule``.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict

import numpy as np

from veles_tpu import events, telemetry
from veles_tpu.ops import deltanet_pallas
from veles_tpu.ops.sequence import SequenceUnit, under_vmap


#: chunks whose ``[C, C]`` products are made (and kept) at one time
CHUNKS_AT_ONCE = 64


def rule_path(t: int, chunk: int) -> Dict[str, Any]:
    """Which form of the rule a row of ``t`` positions runs."""
    if chunk > 1 and t % chunk == 0:
        return {"form": "chunked", "chunk": chunk}
    return {"form": "recurrent", "reason": "ragged", "chunk": 1}


def _no_kernels(platform: str, key_head_size: int, value_head_size: int,
                batched: bool) -> Any:
    """Why no kernel of ``ops/deltanet_pallas.py`` can run, or None:
    ``platform`` (not a TPU), ``batched`` (under ``vmap``: the kernels'
    blocks have no member axis), ``head_size`` (a head is not whole
    128-lane columns)."""
    lanes = deltanet_pallas.LANES
    if platform != "tpu":
        return "platform"
    if batched:
        return "batched"
    if key_head_size % lanes or value_head_size % lanes:
        return "head_size"
    return None


def products_path(platform: str, chunk: int, key_head_size: int,
                  value_head_size: int, heads_a_key: int,
                  batched: bool = False) -> Dict[str, Any]:
    """Which form makes the chunks' products, from what the code
    observes: ``{"products": "fused", "tiles": Tiles}`` on a TPU where
    the shapes tile, else ``{"products": "xla", "reason": ...}`` —
    :func:`_no_kernels`' reasons, or ``chunk`` (the chunk's systems do
    not fill whole tiles)."""
    reason = _no_kernels(platform, key_head_size, value_head_size, batched)
    tiles = None if reason else deltanet_pallas.tiles_for(
        chunk, key_head_size, value_head_size, heads_a_key)
    if tiles is None:
        return {"products": "xla", "reason": reason or "chunk"}
    return {"products": "fused", "tiles": tiles}


def scan_path(platform: str, chunk: int, key_head_size: int,
              value_head_size: int, key_heads: int, heads_a_key: int,
              batched: bool = False) -> Dict[str, Any]:
    """Which form runs the scan over chunks, from what the code
    observes: ``{"scan": "fused", "scan_tiles": ScanTiles}`` on a TPU
    where the shapes tile, else ``{"scan": "xla", "scan_reason":
    ...}`` — :func:`_no_kernels`' reasons, ``chunk`` (not 64 or 128),
    or ``vmem`` (one key head's blocks do not fit the kernels')."""
    reason = _no_kernels(platform, key_head_size, value_head_size, batched)
    tiles = None if reason else deltanet_pallas.scan_tiles_for(
        chunk, key_head_size, value_head_size, key_heads, heads_a_key)
    if tiles is None:
        lanes = deltanet_pallas.LANES
        return {"scan": "xla", "scan_reason": reason or (
            "chunk" if chunk not in (lanes // 2, lanes) else "vmem")}
    return {"scan": "fused", "scan_tiles": tiles}


def causal_conv(x, kernel):
    """Depthwise causal convolution in f32: x ``[rows, T, C]``, kernel
    ``[K, C]``; zeros left of the row."""
    import jax.numpy as jnp
    k, t = kernel.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    kf = kernel.astype(jnp.float32)
    return sum(xp[:, j:j + t].astype(jnp.float32) * kf[j]
               for j in range(k))


def rule_recurrent(q, k, v, g, beta):
    """The rule token by token.  q, k ``[rows, T, Hk, dk]`` as the rule
    reads them (normalised, q scaled), v ``[rows, T, Hv, dv]``, g,
    beta ``[rows, T, Hv]``; all f32.  Returns o ``[rows, T, Hv, dv]``
    (f32; the chunked form's in its compute dtype)."""
    import jax.numpy as jnp
    from jax import lax
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r = hv // hk

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = s * jnp.exp(g_t)[..., None, None]
        d_t = b_t[..., None] * (
            v_t - jnp.einsum("bhrkv,bhk->bhrv", s, k_t))
        s = s + jnp.einsum("bhk,bhrv->bhrkv", k_t, d_t)
        return s, jnp.einsum("bhrkv,bhk->bhrv", s, q_t)

    first = lambda a: jnp.moveaxis(a, 1, 0)   # noqa: E731  time first
    _, o = lax.scan(
        step, jnp.zeros((b, hk, r, dk, dv), jnp.float32),
        (first(q), first(k), first(v).reshape(t, b, hk, r, dv),
         first(g).reshape(t, b, hk, r), first(beta).reshape(t, b, hk, r)))
    return jnp.moveaxis(o.reshape(t, b, hv, dv), 0, 1)


def _chunk_products(cd, qc, kc, vc, gc, bc):
    """What the scan over chunks reads, for a block of chunks: qc, kc
    ``[n, rows, Hk, C, dk]``, vc ``[n, rows, Hk, r, C, dv]``, gc, bc
    ``[n, rows, Hk, r, C]`` -> ``u = T (beta V)`` (f32), ``w = T (beta
    Gamma K)`` and the decayed ``q k^T`` (compute dtype), and ``log
    Gamma``, with ``T = (I + M)^-1``."""
    import jax.numpy as jnp
    from jax import lax
    # three bf16 passes a product of f32 operands: the error of the
    # inverse stays far under that of the bf16 products it feeds, at
    # half of HIGHEST's six passes — and these [C, C] products are the
    # larger part of the rule's time (PERF.md section 5)
    c, hi = qc.shape[-2], lax.Precision.HIGH
    kf = kc.astype(jnp.float32)
    gsum = jnp.cumsum(gc, axis=-1)              # log Gamma_i
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    # Gamma_i / Gamma_j for j <= i (masked BEFORE the exp: above the
    # diagonal the difference is positive and may overflow)
    decay = jnp.exp(jnp.where(
        i >= j, gsum[..., :, None] - gsum[..., None, :], -jnp.inf))
    k_k = jnp.einsum("nbhcd,nbhmd->nbhcm", kc, kc,
                     preferred_element_type=jnp.float32)
    q_k = jnp.einsum("nbhcd,nbhmd->nbhcm", qc, kc,
                     preferred_element_type=jnp.float32)
    p = -(bc[..., :, None] * k_k[:, :, :, None]
          * jnp.where(i > j, decay, 0.0))
    # (I + M)^-1 = (I + P)(I + P^2)(I + P^4)...: P^c = 0
    inv = jnp.eye(c, dtype=jnp.float32) + p
    for _ in range(max(0, int(np.ceil(np.log2(c))) - 1)):
        p = jnp.matmul(p, p, precision=hi)
        inv = inv + jnp.matmul(inv, p, precision=hi)
    gamma = jnp.exp(gsum)
    u = jnp.matmul(inv, bc[..., None] * vc.astype(jnp.float32),
                   precision=hi)
    w = jnp.matmul(inv, (bc * gamma)[..., None] * kf[:, :, :, None],
                   precision=hi).astype(cd)
    a_qk = (q_k[:, :, :, None] * decay).astype(cd)
    return u, w, a_qk, gsum


def chunk_parts(q, k, v, g, beta, chunk: int, cd):
    """The rule's arguments as the chunk products and the scan read
    them — chunk first (the scan's axis), heads before positions, so
    that every product is a batched matmul over (chunk, row, head): q,
    k ``[n, rows, Hk, C, dk]``, v ``[n, rows, Hk, r, C, dv]`` in
    ``cd``, g, beta ``[n, rows, Hk, r, C]``."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    r, n, c = hv // hk, t // chunk, chunk
    return (
        q.astype(cd).reshape(b, n, c, hk, dk).transpose(1, 0, 3, 2, 4),
        k.astype(cd).reshape(b, n, c, hk, dk).transpose(1, 0, 3, 2, 4),
        v.astype(cd).reshape(b, n, c, hk, r, dv).transpose(
            1, 0, 3, 4, 2, 5),
        g.reshape(b, n, c, hk, r).transpose(1, 0, 3, 4, 2),
        beta.reshape(b, n, c, hk, r).transpose(1, 0, 3, 4, 2))


def products_of(parts, tiles=None, interpret: bool = False):
    """:func:`_chunk_products` of all ``n`` chunks (``parts``: its five
    arrays, q in the compute dtype): by the kernels of
    ``ops/deltanet_pallas.py`` where :func:`products_path` gave their
    ``tiles`` (``interpret``: the tests', off the chip) — nothing to block or checkpoint there: no
    ``[n, ..., C, C]`` f32 array exists in HBM and the backward kernel
    re-makes ``T`` itself — else the XLA form, ``CHUNKS_AT_ONCE``
    chunks at a time and made again for the backward
    (``jax.checkpoint``): whole, a 32 k row's are gigabytes."""
    import jax
    from jax import lax
    if tiles is not None:
        return deltanet_pallas.chunk_products(*parts, tiles, interpret)
    n = parts[0].shape[0]
    at_once = max(d for d in range(1, min(n, CHUNKS_AT_ONCE) + 1)
                  if n % d == 0)
    products = jax.checkpoint(partial(_chunk_products, parts[0].dtype))
    if at_once == n:
        return products(*parts)
    made = lax.map(lambda args: products(*args), jax.tree.map(
        lambda a: a.reshape((n // at_once, at_once) + a.shape[1:]),
        parts))
    return jax.tree.map(lambda a: a.reshape((n,) + a.shape[2:]), made)


def _scan_xla(made, qc, kc):
    """The scan over chunks as XLA ops: a ``lax.scan`` of a
    checkpointed ``step`` (arguments and result as
    ``deltanet_pallas.chunk_scan``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    b, hk, r = made[0].shape[1:4]
    dk, dv, cd = qc.shape[-1], made[0].shape[-1], qc.dtype

    @jax.checkpoint       # the scan keeps a chunk's incoming state alone
    def step(s, xs):
        (u_n, w_n, a_n, gsum), q_n, k_n = xs
        # Gamma q, (Gamma_C / Gamma) k and Gamma_C of this chunk: made
        # here from the chunk's q and k, not kept a value head each
        g_n = jnp.exp(gsum[..., -1])
        q_n = (q_n[:, :, None].astype(jnp.float32)
               * jnp.exp(gsum)[..., None]).astype(cd)
        k_n = (k_n[:, :, None].astype(jnp.float32)
               * jnp.exp(gsum[..., -1:] - gsum)[..., None]).astype(cd)
        sb = s.astype(cd)
        delta = u_n - jnp.einsum("bhrck,bhrkv->bhrcv", w_n, sb,
                                 preferred_element_type=jnp.float32)
        db = delta.astype(cd)
        o = jnp.einsum("bhrck,bhrkv->bhrcv", q_n, sb,
                       preferred_element_type=jnp.float32) \
            + jnp.einsum("bhrcm,bhrmv->bhrcv", a_n, db,
                         preferred_element_type=jnp.float32)
        s = s * g_n[..., None, None] \
            + jnp.einsum("bhrck,bhrcv->bhrkv", k_n, db,
                         preferred_element_type=jnp.float32)
        return s, o.astype(cd)

    return lax.scan(step, jnp.zeros((b, hk, r, dk, dv), jnp.float32),
                    (tuple(made), qc, kc))[1]


def scan_of(made, qc, kc, scan=None, interpret: bool = False):
    """The scan over chunks of :func:`products_of`'s ``made`` (u, w,
    a_qk, log Gamma) with the chunked q, k: by the kernels of
    ``ops/deltanet_pallas.py`` where :func:`scan_path` gave their
    ``scan`` tiles (``interpret``: the tests', off the chip) — each
    head's state in VMEM across the row's chunks — else the XLA
    ``lax.scan``.  o ``[n, rows, Hk, r, C, dv]`` in the compute
    dtype."""
    if scan is not None:
        return deltanet_pallas.chunk_scan(*made, qc, kc, scan, interpret)
    return _scan_xla(made, qc, kc)


def rule_chunked(q, k, v, g, beta, chunk: int, compute_dtype,
                 tiles=None, interpret: bool = False, scan=None):
    """The same rule in chunks of ``chunk`` positions (the module's
    docstring has the algebra); arguments and result as
    :func:`rule_recurrent`, q, k, v taken in ``compute_dtype``; the
    chunks' own products by :func:`products_of` (``tiles``), the scan
    over them by :func:`scan_of` (``scan``)."""
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2], v.shape[3]
    parts = chunk_parts(q, k, v, g, beta, chunk, compute_dtype)
    made = products_of(parts, tiles, interpret)
    o = scan_of(made, parts[0], parts[1], scan, interpret)
    # [n, b, hk, r, c, dv] -> [b, t, hv, dv]
    return o.transpose(1, 0, 4, 2, 3, 5).reshape(b, t, hv, dv)


class GatedDeltaNet(SequenceUnit):
    """Convolution + gated delta rule + gated norm over ``[rows, T,
    hidden]``; the heads' outputs side by side ``[rows, T, Hv * dv]``."""

    matrix_names = ("wq", "wk", "wv", "wz", "wb", "wa")
    param_names = matrix_names + ("conv", "a_log", "dt_bias", "norm")

    def __init__(self, workflow=None, n_key_heads: int = 2,
                 n_value_heads: int = 4, key_head_size: int = 16,
                 value_head_size: int = 16, conv_kernel: int = 4,
                 chunk_size: int = 64, eps: float = 1e-6,
                 **kwargs: Any) -> None:
        super().__init__(workflow, **kwargs)
        if n_value_heads % n_key_heads:
            raise ValueError(f"{self.name}: {n_value_heads} value heads "
                             f"over {n_key_heads} key heads")
        self.n_key_heads, self.n_value_heads = n_key_heads, n_value_heads
        self.key_head_size = key_head_size
        self.value_head_size = value_head_size
        self.conv_kernel, self.chunk_size = conv_kernel, chunk_size
        self.eps = eps
        #: the last path journaled: :func:`rule_path` and, where it
        #: is ``chunked``, :func:`products_path` and :func:`scan_path`
        #: ({} before the first)
        self.path: Dict[str, Any] = {}

    def output_shape_for(self, input_shape):
        return tuple(input_shape[:-1]) + (
            self.n_value_heads * self.value_head_size,)

    def param_shapes(self, input_shape):
        h = int(input_shape[-1])
        hk, hv = self.n_key_heads, self.n_value_heads
        kw, vw = hk * self.key_head_size, hv * self.value_head_size
        return {"wq": (h, kw), "wk": (h, kw), "wv": (h, vw),
                "wz": (h, vw), "wb": (h, hv), "wa": (h, hv),
                "conv": (self.conv_kernel, 2 * kw + vw),
                "a_log": (hv,), "dt_bias": (hv,),
                "norm": (self.value_head_size,)}

    def fill_special(self, name, shape, gen):
        if name in ("norm", "dt_bias"):
            return np.ones(shape, np.float32)
        if name == "a_log":
            return np.log(gen.uniform(1.0, 16.0, shape)).astype(np.float32)
        if name == "conv":
            bound = float(shape[0]) ** -0.5
            return gen.uniform(-bound, bound, shape).astype(np.float32)
        return super().fill_special(name, shape, gen)

    def _path(self, t: int, batched: bool = False) -> Dict[str, Any]:
        """The form of the rule and of its chunk products for rows of
        ``t`` positions, journaled (``gdn.path``) whenever it differs
        from the last one journaled: once at ``initialize``, and again
        only where a later trace must leave it (a ``vmap``)."""
        path = rule_path(t, self.chunk_size)
        if path["form"] == "chunked":
            sizes = (self.platform(), path["chunk"], self.key_head_size,
                     self.value_head_size)
            r = self.n_value_heads // self.n_key_heads
            path.update(products_path(*sizes, r, batched))
            path.update(scan_path(*sizes, self.n_key_heads, r, batched))
        if path != self.path:
            self.path = path
            tiles, scan = path.get("tiles"), path.get("scan_tiles")
            telemetry.event(
                events.EV_GDN_PATH, unit=self.name, form=path["form"],
                chunk=path["chunk"], products=path.get("products"),
                reason=path.get("reason"),
                tiles=tiles and dict(tiles._asdict()),
                scan=path.get("scan"), scan_reason=path.get("scan_reason"),
                scan_tiles=scan and dict(scan._asdict()))
        return path

    def initialize(self, device=None, **kwargs) -> None:
        super().initialize(device=device, **kwargs)
        self._path(int(self.input.shape[1]))
        # units initialize in order: the last one sets the whole count
        peers = getattr(self.workflow, "forwards", None) or [self]
        for gauge, key in ((events.GAUGE_GDN_FUSED_LAYERS, "products"),
                           (events.GAUGE_GDN_FUSED_SCANS, "scan")):
            telemetry.gauge(gauge).set(sum(
                isinstance(f, GatedDeltaNet)
                and f.path.get(key) == "fused" for f in peers))

    def forward(self, params, x):
        import jax
        import jax.numpy as jnp
        from jax import lax
        b, t, _ = x.shape
        hk, hv = self.n_key_heads, self.n_value_heads
        dk, dv = self.key_head_size, self.value_head_size
        kw = hk * dk

        # the six projections first, outside the mechanism's scopes
        pq, pk, pv, pz, pb, pa = (
            jnp.einsum("bth,hk->btk", x, params[name])
            for name in ("wq", "wk", "wv", "wz", "wb", "wa"))
        with jax.named_scope(events.SCOPE_GDN_CONV):
            # depthwise: each projection meets its own columns of the
            # kernel, so the 8192-wide concatenation is never made
            conv = params["conv"]
            q, k, v = (
                jax.nn.silu(causal_conv(a, conv[:, lo:hi])).astype(x.dtype)
                for a, lo, hi in ((pq, 0, kw), (pk, kw, 2 * kw),
                                  (pv, 2 * kw, conv.shape[1])))
        with jax.named_scope(events.SCOPE_GDN_RULE):
            def unit(a):
                a = a.reshape(b, t, hk, dk).astype(jnp.float32)
                return a * lax.rsqrt(
                    jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)

            beta = jax.nn.sigmoid(pb.astype(jnp.float32))
            g = -jnp.exp(params["a_log"].astype(jnp.float32)) \
                * jax.nn.softplus(
                    pa.astype(jnp.float32)
                    + params["dt_bias"].astype(jnp.float32))
            q, k = unit(q) * dk ** -0.5, unit(k)
            v = v.reshape(b, t, hv, dv)
            path = self._path(t, under_vmap(q, k, v))
            if path["form"] == "chunked":
                o = rule_chunked(q, k, v, g, beta, path["chunk"], x.dtype,
                                 path.get("tiles"),
                                 scan=path.get("scan_tiles"))
            else:
                o = rule_recurrent(q, k, v.astype(jnp.float32), g, beta)
        with jax.named_scope(events.SCOPE_GDN_GATE_NORM):
            @jax.checkpoint       # keeps o and z, not their f32 copies
            def gate_norm(gain, o, z):
                o, z = o.astype(jnp.float32), z.astype(jnp.float32)
                ms = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                y = gain.astype(jnp.float32) * o \
                    * lax.rsqrt(ms + self.eps) * jax.nn.silu(z)
                return y.astype(x.dtype)

            y = gate_norm(params["norm"], o, pz.reshape(b, t, hv, dv))
        return y.reshape(b, t, hv * dv)

    def mxu_flops_per_sample(self) -> float:
        t, h = int(self.input.shape[1]), int(self.input.shape[2])
        hk, hv = self.n_key_heads, self.n_value_heads
        dk, dv = self.key_head_size, self.value_head_size
        # projections; the rule as the recurrence counts it (S^T k,
        # k delta^T, S^T q a token and value head)
        return 2.0 * t * h * (2 * hk * dk + 2 * hv * dv + 2 * hv) \
            + 6.0 * t * hv * dk * dv
