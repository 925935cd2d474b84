"""Per-op correctness: numpy-vs-jax forward agreement, finite-difference
gradient checks against the hand-written backward, numpy-vs-jax backward
agreement (SURVEY.md §7 phase 4 test strategy)."""

import numpy as np
import pytest

import jax.numpy as jnp

from veles_tpu import prng
from veles_tpu.ops import activation as act_mod
from veles_tpu.ops import conv as conv_mod
from veles_tpu.ops import dropout as dropout_mod
from veles_tpu.ops import lrn as lrn_mod
from veles_tpu.ops import pooling as pool_mod
from veles_tpu.ops import deconv as deconv_mod
from veles_tpu.ops import depooling as depool_mod
from veles_tpu.ops import all2all as a2a_mod

RNG = np.random.default_rng(3)


def make_params(unit, in_shape):
    params = {}
    for name, shape in unit.param_shapes(in_shape).items():
        params[name] = RNG.standard_normal(shape).astype(np.float32) * 0.3
    return params


def fd_grad(f, x, eps=1e-3, probes=8):
    """Central finite differences of scalar f at a few coordinates."""
    g = np.zeros_like(x, dtype=np.float64)
    flat = x.reshape(-1)
    idxs = RNG.choice(flat.size, size=min(probes, flat.size),
                      replace=False)
    for i in idxs:
        old = flat[i]
        flat[i] = old + eps
        fp = f(x)
        flat[i] = old - eps
        fm = f(x)
        flat[i] = old
        g.reshape(-1)[i] = (fp - fm) / (2 * eps)
    return g, idxs


def check_unit(fwd_unit, gd_cls, in_shape, rtol=1e-4, atol=1e-4,
               fd_rtol=5e-2):
    """Run the full battery on one forward/gd pair."""
    x = RNG.standard_normal(in_shape).astype(np.float32)
    params = make_params(fwd_unit, in_shape)
    gd = gd_cls(forward=fwd_unit)

    # 1. forward numpy vs jax
    y_np, res_np = fwd_unit.apply_fwd(params, x, train=True)
    jparams = {k: jnp.asarray(v) for k, v in params.items()}
    y_jx, res_jx = fwd_unit.apply_fwd(jparams, jnp.asarray(x), train=True)
    np.testing.assert_allclose(np.asarray(y_jx), y_np,
                               rtol=1e-4, atol=1e-4)

    # 2. backward numpy vs jax (same upstream error)
    err = RNG.standard_normal(y_np.shape).astype(np.float32)
    ein_np, g_np = gd.backward_from_saved(params, res_np, err)
    ein_jx, g_jx = gd.backward_from_saved(jparams, res_jx,
                                          jnp.asarray(err))
    np.testing.assert_allclose(np.asarray(ein_jx), ein_np,
                               rtol=rtol, atol=atol)
    for k in g_np:
        np.testing.assert_allclose(np.asarray(g_jx[k]), g_np[k],
                                   rtol=rtol, atol=atol, err_msg=k)

    # 3. finite differences vs numpy backward: L = sum(output * err)
    def loss_x(xx):
        yy, _ = fwd_unit.apply_fwd(params, xx.astype(np.float32),
                                   train=True)
        return float((yy * err).sum())

    fd, idxs = fd_grad(loss_x, x.copy().astype(np.float64))
    got = ein_np.reshape(-1)[idxs]
    want = fd.reshape(-1)[idxs]
    np.testing.assert_allclose(got, want, rtol=fd_rtol, atol=1e-2)

    for pname in g_np:
        def loss_p(pp, pname=pname):
            p2 = dict(params)
            p2[pname] = pp.astype(np.float32)
            yy, _ = fwd_unit.apply_fwd(p2, x, train=True)
            return float((yy * err).sum())

        fd, idxs = fd_grad(loss_p, params[pname].copy().astype(np.float64))
        np.testing.assert_allclose(g_np[pname].reshape(-1)[idxs],
                                   fd.reshape(-1)[idxs],
                                   rtol=fd_rtol, atol=1e-2,
                                   err_msg=pname)


class TestAll2All:
    def test_linear(self):
        u = a2a_mod.All2All(output_sample_shape=7)
        check_unit(u, a2a_mod.GradientDescent, (4, 5))

    def test_tanh(self):
        u = a2a_mod.All2AllTanh(output_sample_shape=6)
        check_unit(u, a2a_mod.GDTanh, (3, 8))

    def test_relu(self):
        u = a2a_mod.All2AllRELU(output_sample_shape=6)
        check_unit(u, a2a_mod.GDRELU, (3, 8))

    def test_flattens_images(self):
        u = a2a_mod.All2All(output_sample_shape=5)
        check_unit(u, a2a_mod.GradientDescent, (2, 4, 4, 3))


class TestConv:
    def test_basic(self):
        u = conv_mod.Conv(n_kernels=4, kx=3, ky=3)
        check_unit(u, conv_mod.GradientDescentConv, (2, 6, 6, 3))

    def test_stride_pad(self):
        u = conv_mod.Conv(n_kernels=3, kx=3, ky=3, padding=1, sliding=2)
        check_unit(u, conv_mod.GradientDescentConv, (2, 7, 7, 2))

    def test_tanh(self):
        u = conv_mod.ConvTanh(n_kernels=2, kx=2, ky=2)
        check_unit(u, conv_mod.GradientDescentConv, (2, 5, 5, 2))

    def test_relu(self):
        u = conv_mod.ConvRELU(n_kernels=2, kx=2, ky=2)
        check_unit(u, conv_mod.GradientDescentConv, (2, 5, 5, 2))

    def test_rect_kernel(self):
        u = conv_mod.Conv(n_kernels=3, kx=2, ky=4, padding=(2, 1),
                          sliding=(2, 1))
        check_unit(u, conv_mod.GradientDescentConv, (2, 9, 8, 2))

    def test_output_shape(self):
        u = conv_mod.Conv(n_kernels=8, kx=11, ky=11, sliding=4)
        assert u.output_shape_for((1, 227, 227, 3)) == (1, 55, 55, 8)


class TestPooling:
    def test_max(self):
        u = pool_mod.MaxPooling(kx=2, ky=2)
        check_unit(u, pool_mod.GDMaxPooling, (2, 6, 6, 3))

    def test_max_overlapping(self):
        u = pool_mod.MaxPooling(kx=3, ky=3, sliding=2)
        check_unit(u, pool_mod.GDMaxPooling, (2, 7, 7, 2))

    def test_avg(self):
        u = pool_mod.AvgPooling(kx=2, ky=2)
        check_unit(u, pool_mod.GDAvgPooling, (2, 6, 6, 3))

    def test_stochastic_eval_mode_deterministic(self):
        u = pool_mod.StochasticPooling(kx=2, ky=2)
        x = RNG.standard_normal((2, 4, 4, 3)).astype(np.float32)
        y1 = u.apply({}, {"input": x})["output"]
        y2 = np.asarray(u.apply({}, {"input": jnp.asarray(x)})["output"])
        np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)

    def test_stochastic_train_samples_window_members(self):
        import jax
        u = pool_mod.StochasticPooling(kx=2, ky=2)
        x = np.abs(RNG.standard_normal((1, 4, 4, 1))).astype(np.float32)
        y, (xx, idx) = u.apply_fwd({}, jnp.asarray(x),
                                   rng=jax.random.key(0), train=True)
        y = np.asarray(y)
        w = u._windows(x)
        # each sampled value must be a member of its window
        for i in range(2):
            for j in range(2):
                assert y[0, i, j, 0] in w[0, i, j, :, 0]


class TestActivations:
    @pytest.mark.parametrize("cls", [
        act_mod.ActivationTanh, act_mod.ActivationSigmoid,
        act_mod.ActivationStrictRELU, act_mod.ActivationRELU,
        act_mod.ActivationLog])
    def test_all(self, cls):
        u = cls()
        check_unit(u, act_mod.GDActivation, (3, 7))


class TestLRN:
    def test_forward_reference_formula(self):
        u = lrn_mod.LRNormalizer(alpha=1e-4, beta=0.75, n=5, k=2.0)
        x = RNG.standard_normal((2, 3, 3, 8)).astype(np.float32)
        y = u.apply({}, {"input": x})["output"]
        # brute-force windowed sum
        c = x.shape[-1]
        want = np.empty_like(x)
        for i in range(c):
            lo, hi = max(0, i - 2), min(c, i + 3)
            s = (x[..., lo:hi] ** 2).sum(-1)
            want[..., i] = x[..., i] / (2.0 + 1e-4 * s) ** 0.75
        np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-6)

    def test_grads(self):
        u = lrn_mod.LRNormalizer(n=5)
        check_unit(u, lrn_mod.GDLRNormalizer, (2, 3, 3, 8))

    def test_grads_even_window(self):
        """Even n: the backward must use the ADJOINT window, which is
        NOT the forward window (fd check caught a 'symmetric window'
        shortcut that was wrong for n=4)."""
        u = lrn_mod.LRNormalizer(n=4, alpha=3e-2)
        check_unit(u, lrn_mod.GDLRNormalizer, (2, 3, 3, 8))

    def test_band_matrix_is_window_adjoint(self):
        """band_matrix(transpose=True) must be the exact matrix
        transpose (the adjoint of the window operator) for both
        parities — the backward pass depends on it."""
        for n in (3, 4, 5, 6):
            b = lrn_mod.band_matrix(12, n)
            bt = lrn_mod.band_matrix(12, n, transpose=True)
            np.testing.assert_array_equal(bt, b.T)
            assert b.sum(axis=0).max() == n  # interior taps

    @pytest.mark.parametrize("c,n", [(8, 4), (8, 5), (96, 5), (256, 5),
                                     (96, 4)])
    def test_jax_banded_matmul_matches_numpy_oracle_both_parities(
            self, c, n):
        """The jax path's banded-matmul window sum must agree with the
        independent numpy shifted-adds oracle for ODD and EVEN window
        sizes (an n+1-tap symmetric band would pass only odd n), at
        AlexNet's real channel widths too (96 aligns to no lane
        boundary; 256 to two)."""
        u = lrn_mod.LRNormalizer(alpha=3e-2, beta=0.75, n=n, k=2.0)
        x = RNG.standard_normal((2, 3, 3, c)).astype(np.float32)
        err = RNG.standard_normal(x.shape).astype(np.float32)

        y_np, res_np = u.apply_fwd({}, x)
        y_jx, res_jx = u.apply_fwd({}, jnp.asarray(x))
        np.testing.assert_allclose(np.asarray(y_jx), y_np,
                                   rtol=2e-5, atol=1e-6)

        gd = lrn_mod.GDLRNormalizer(forward=u)
        ein_np, _ = gd.backward_from_saved({}, res_np, err)
        ein_jx, _ = gd.backward_from_saved({}, res_jx,
                                           jnp.asarray(err))
        np.testing.assert_allclose(np.asarray(ein_jx), ein_np,
                                   rtol=2e-4, atol=1e-5)


class TestDropout:
    def test_eval_identity(self):
        u = dropout_mod.Dropout(dropout_ratio=0.4)
        x = RNG.standard_normal((4, 5)).astype(np.float32)
        y, _ = u.apply_fwd({}, x, train=False)
        np.testing.assert_array_equal(y, x)

    def test_train_mask_and_backward(self):
        prng.seed_all(5)
        u = dropout_mod.Dropout(dropout_ratio=0.5)
        x = np.ones((64, 64), np.float32)
        y, (xx, mask) = u.apply_fwd({}, x, train=True)
        kept = (np.asarray(y) != 0)
        assert 0.3 < kept.mean() < 0.7
        np.testing.assert_allclose(np.asarray(y)[kept], 2.0)  # 1/keep
        gd = dropout_mod.GDDropout(forward=u)
        err = np.ones_like(x)
        ein, _ = gd.backward_from_saved({}, (xx, mask), err)
        np.testing.assert_array_equal(np.asarray(ein), np.asarray(mask))

    def test_jax_train_deterministic_per_key(self):
        import jax
        u = dropout_mod.Dropout(dropout_ratio=0.5)
        x = jnp.ones((8, 8))
        y1, _ = u.apply_fwd({}, x, rng=jax.random.key(7), train=True)
        y2, _ = u.apply_fwd({}, x, rng=jax.random.key(7), train=True)
        np.testing.assert_array_equal(np.asarray(y1), np.asarray(y2))


class TestDeconv:
    def test_basic(self):
        u = deconv_mod.Deconv(n_kernels=3, kx=2, ky=2, sliding=2)
        check_unit(u, deconv_mod.GradientDescentDeconv, (2, 3, 3, 4))

    def test_stride1_pad(self):
        u = deconv_mod.Deconv(n_kernels=2, kx=3, ky=3, padding=1)
        check_unit(u, deconv_mod.GradientDescentDeconv, (2, 5, 5, 3))

    def test_inverts_conv_geometry(self):
        c = conv_mod.Conv(n_kernels=5, kx=4, ky=4, padding=1, sliding=2)
        out = c.output_shape_for((1, 10, 10, 3))
        d = deconv_mod.Deconv(n_kernels=3, kx=4, ky=4, padding=1,
                              sliding=2)
        assert d.output_shape_for(out) == (1, 10, 10, 3)


class TestDepooling:
    def test_forward_and_grads(self):
        u = depool_mod.Depooling(kx=2, ky=2)
        check_unit(u, depool_mod.GDDepooling, (2, 3, 3, 2))

    def test_upsamples(self):
        u = depool_mod.Depooling(kx=2, ky=2)
        x = np.arange(4, dtype=np.float32).reshape(1, 2, 2, 1)
        y = u.apply({}, {"input": x})["output"]
        assert y.shape == (1, 4, 4, 1)
        assert (y[0, :2, :2, 0] == 0).all()


# last in the file: every check above draws from the one module RNG,
# and the finite-difference probes of the pooling checks depend on
# where in its stream they start
@pytest.mark.parametrize("geom", [
    # (kx, ky, pad, stride, in_shape) — AlexNet conv1 miniature,
    # stride not dividing kernel, rectangular stride, with padding
    (11, 11, 0, 4, (2, 31, 31, 3)),
    (5, 5, 0, 3, (2, 17, 17, 2)),
    (3, 4, (1, 2), (2, 3), (2, 11, 13, 3)),
    (2, 2, 0, 2, (1, 8, 8, 4)),
])
def test_conv_strided_geometries_match_im2col_oracle(geom):
    """``lax.conv_general_dilated`` against the numpy im2col /
    col2im oracle at the strided first-layer geometries: forward
    and both cotangents (and finite differences of the oracle)."""
    kx, ky, pad, stride, shp = geom
    u = conv_mod.Conv(n_kernels=5, kx=kx, ky=ky, padding=pad,
                      sliding=stride)
    check_unit(u, conv_mod.GradientDescentConv, shp)
