"""ops/shortconv.py: the gated short convolution against a plain loop over
positions and taps, its value and the gradients of all three thirds of
the in-projection's output and of the taps, in float32 and in bf16; the
shape check; and the scope its forward and backward lie under."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import shortconv


def _loop(bcx, w):
    """y_t = C_t * sum_j w_j B_{t-L+1+j} u_{t-L+1+j}, position by
    position, nothing before position 0."""
    b, c, u = onp.split(onp.asarray(bcx, onp.float64), 3, axis=-1)
    w = onp.asarray(w, onp.float64)
    taps, T = w.shape[0], bcx.shape[1]
    y = onp.zeros_like(b)
    for t in range(T):
        for j in range(taps):
            s = t - (taps - 1) + j
            if s >= 0:
                y[:, t] += w[j] * b[:, s] * u[:, s]
    return c * y


def _inputs(N=2, T=37, h=24, taps=3, seed=0, dtype=jnp.float32):
    rng = onp.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((N, T, 3 * h)), dtype),
            jnp.asarray(rng.standard_normal((taps, h)), dtype))


@pytest.mark.parametrize('taps', [1, 3, 4])
def test_value_and_all_four_gradients_match_the_loop(taps):
    bcx, w = _inputs(taps=taps)
    onp.testing.assert_allclose(shortconv.gated_short_conv(bcx, w),
                                _loop(bcx, w), rtol=1e-5, atol=1e-5)
    dy = jnp.asarray(onp.random.default_rng(1).standard_normal(
        (2, 37, 24)), jnp.float32)

    def plain(bcx, w):      # the loop's mathematics, autodiffed
        b, c, u = jnp.split(bcx, 3, axis=-1)
        v, T = b * u, bcx.shape[1]
        pad = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
        return c * sum(w[j] * pad[:, j:j + T] for j in range(taps))
    got = jax.vjp(shortconv.gated_short_conv, bcx, w)[1](dy)
    want = jax.vjp(plain, bcx, w)[1](dy)
    for name, g, r in zip(('dB', 'dC', 'du'), jnp.split(got[0], 3, -1),
                          jnp.split(want[0], 3, -1)):
        onp.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-5, err_msg=name)
    onp.testing.assert_allclose(got[1], want[1], rtol=1e-5, atol=1e-4,
                                err_msg='dw')


def test_bf16_operands_compute_in_float32_and_keep_their_dtype():
    bcx, w = _inputs(dtype=jnp.bfloat16)
    y = shortconv.gated_short_conv(bcx, w)
    assert y.dtype == jnp.bfloat16
    onp.testing.assert_allclose(onp.asarray(y, onp.float32), _loop(bcx, w),
                                rtol=2e-2, atol=2e-2)
    d_bcx, d_w = jax.grad(lambda a, b: jnp.sum(
        shortconv.gated_short_conv(a, b).astype(jnp.float32)),
        argnums=(0, 1))(bcx, w)
    assert d_bcx.dtype == d_w.dtype == jnp.bfloat16
    assert d_bcx.shape == bcx.shape and d_w.shape == w.shape


def test_shapes_are_checked():
    with pytest.raises(ValueError, match='3h'):
        shortconv.gated_short_conv(jnp.zeros((1, 4, 10)), jnp.zeros((3, 3)))
    with pytest.raises(ValueError, match='3h'):
        shortconv.gated_short_conv(jnp.zeros((1, 4, 12)), jnp.zeros((3, 5)))


def test_forward_and_backward_lie_under_the_core_scope():
    """Every multiply of the core, forward and backward, carries
    ``short_conv_core`` in its name: the roofline metric reads that
    scope."""
    bcx, w = _inputs(N=1, T=8, h=4)
    text = jax.jit(jax.grad(lambda a, b: jnp.sum(
        shortconv.gated_short_conv(a, b)), argnums=(0, 1))).lower(
        bcx, w).compile().as_text()
    muls = [line for line in text.splitlines() if ' multiply(' in line]
    assert muls and all('short_conv_core' in line for line in muls)


@pytest.mark.parametrize('T,h,taps', [(96, 384, 3), (48, 256, 4),
                                      (16, 128, 1), (48, 128, 8)],
                         ids=['3_blocks_3_columns', 'taps_4', 'one_block',
                              'taps_8'])
def test_the_kernels_match_xla(T, h, taps):
    """Forward and backward through the Pallas interpreter against the
    XLA path: blocks of 32 or 16 positions, the taps reaching over a
    block's edge into the 16 positions before it (forward, backward) and
    after it (backward), several channel blocks."""
    bcx, w = _inputs(N=2, T=T, h=h, taps=taps, seed=4)
    assert shortconv._blocks(T, h, taps) is not None
    dy = jnp.asarray(onp.random.default_rng(5).standard_normal(
        (2, T, h)), jnp.float32)
    got = jax.vjp(lambda a, b: shortconv.gated_short_conv(a, b, True),
                  bcx, w)
    want = jax.vjp(shortconv.gated_short_conv, bcx, w)
    onp.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-5)
    for name, g, r in zip(('dbcx', 'dw'), got[1](dy), want[1](dy)):
        onp.testing.assert_allclose(g, r, rtol=1e-5, atol=1e-4,
                                    err_msg=name)


def test_the_kernels_take_the_lfm2_cells_shape_and_xla_the_rest():
    assert shortconv._blocks(8192, 2048, 3) == (256, 512)
    assert shortconv._blocks(37, 2048, 3) is None       # positions
    assert shortconv._blocks(64, 24, 3) is None         # channels
    before = dict(shortconv.route_counts)
    bcx, w = _inputs(N=1, T=37, h=24)
    jax.grad(lambda a: jnp.sum(shortconv.gated_short_conv(a, w, True)))(bcx)
    assert shortconv.route_counts['xla'] == before['xla'] + 2
