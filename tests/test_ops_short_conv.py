"""ops/short_conv.py: the gated short convolution against an explicit
loop over positions and taps, values and gradients, and its causality."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafiki_tpu.ops import gated_short_conv


def _inputs(rng, b=2, t=19, d=24, taps=3, dtype=jnp.float32):
    def r(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    return r(b, t, d), r(b, t, d), r(b, t, d), r(taps, d)


def _loop(b, c, u, w):
    """v_t = sum_j w_j * (b * u)_{t - (L-1) + j}, zeros before the
    sequence; out = c * v. Position by position, tap by tap, float64."""
    b, c, u, w = (np.asarray(a, np.float64) for a in (b, c, u, w))
    taps, t = w.shape[0], u.shape[1]
    x = b * u
    out = np.zeros_like(x)
    for pos in range(t):
        for j in range(taps):
            src = pos - (taps - 1) + j
            if src >= 0:
                out[:, pos] += w[j] * x[:, src]
    return c * out


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_values_match_the_explicit_loop(rng, taps):
    b, c, u, w = _inputs(rng, taps=taps)
    got = gated_short_conv(b, c, u, w)
    assert got.shape == u.shape and got.dtype == u.dtype
    np.testing.assert_allclose(got, _loop(b, c, u, w), rtol=1e-5, atol=1e-5)


def test_it_is_a_depthwise_conv1d_with_left_padding(rng):
    """The same numbers as ``lax.conv_general_dilated`` with one filter
    a channel (feature_group_count = channels) and padding (L-1, 0):
    the layout a ``conv1d`` checkpoint of the family holds."""
    b, c, u, w = _inputs(rng)
    d = u.shape[-1]
    conv = jax.lax.conv_general_dilated(
        (b * u).transpose(0, 2, 1), w.T[:, None, :], window_strides=(1,),
        padding=[(w.shape[0] - 1, 0)], feature_group_count=d,
        precision="highest")
    np.testing.assert_allclose(gated_short_conv(b, c, u, w),
                               c * conv.transpose(0, 2, 1), rtol=1e-5,
                               atol=1e-5)


def test_gradients_match_the_explicit_loop(rng):
    b, c, u, w = _inputs(rng, b=1, t=11, d=8)
    ct = jnp.asarray(rng.standard_normal(u.shape), jnp.float32)

    def reference(b, c, u, w):
        taps, t = w.shape[0], u.shape[1]
        x = b * u
        v = sum(w[j] * jnp.pad(x, ((0, 0), (taps - 1 - j, 0), (0, 0))
                               )[:, :t] for j in range(taps))
        return (c * v * ct).sum()

    got = jax.grad(lambda *a: (gated_short_conv(*a) * ct).sum(),
                   argnums=(0, 1, 2, 3))(b, c, u, w)
    want = jax.grad(reference, argnums=(0, 1, 2, 3))(b, c, u, w)
    np.testing.assert_allclose(
        (gated_short_conv(b, c, u, w) * ct).sum(),
        (_loop(b, c, u, w) * np.asarray(ct, np.float64)).sum(), rtol=1e-5)
    for name, a, e in zip("bcuw", got, want):
        np.testing.assert_allclose(a, e, rtol=1e-5, atol=1e-5,
                                   err_msg=name)


def test_position_t_is_unmoved_by_inputs_after_t(rng):
    b, c, u, w = _inputs(rng)
    base = np.asarray(gated_short_conv(b, c, u, w))
    cut = 7
    moved = [a.at[:, cut:].add(1.5) for a in (b, c, u)]
    after = np.asarray(gated_short_conv(*moved, w))
    np.testing.assert_array_equal(after[:, :cut], base[:, :cut])
    assert np.abs(after[:, cut:] - base[:, cut:]).min() > 0
    # and by nothing further back than its taps: d out_t / d u_s = 0
    # outside t - (L-1) <= s <= t
    jac = jax.jacobian(lambda u: gated_short_conv(b, c, u, w)[0, :, 0])(u)
    reach = np.abs(np.asarray(jac)[:, 0, :, 0]) > 0  # (t_out, t_in)
    t = u.shape[1]
    want = np.array([[0 <= o - i < w.shape[0] for i in range(t)]
                     for o in range(t)])
    np.testing.assert_array_equal(reach, want)


def test_bfloat16_operands_accumulate_in_float32(rng):
    b, c, u, w = _inputs(rng, dtype=jnp.bfloat16)
    got = gated_short_conv(b, c, u, w)
    assert got.dtype == jnp.bfloat16
    want = _loop(*(np.asarray(a, np.float32) for a in (b, c, u, w)))
    # one rounding, of the result: 2^-8 of it
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -7, atol=1e-2)
