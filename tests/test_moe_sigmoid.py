"""ops/moe.py's second scoring rule and second gate: sigmoid scores with a
balancing bias that enters the choice alone, and SiLU between the grouped
matmuls. The routing against a plain top-k; the layer against a dense
loop of einsums, forward and gradients, through the einsums and through
the Pallas kernels in the interpreter, with the rows of the tiles they
skip NaN too, at three routings besides a mixed one (every assignment
here, none here, one expert taking every token); the share test of the
model-configs guide at LFM2's router (64 experts, 4 a token, eight shares
of 8); the assignment counts and the bias's update rule; and that the
new rule and gate are counted apart from softmax and ReGLU."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu.ops import moe
from test_moe import MODES, interpret_for, live_tiles

N, T, H, F = 2, 32, 32, 16


def _weights(experts, held, seed=0):
    rng = onp.random.default_rng(seed)
    return (jnp.asarray(rng.standard_normal((N, T, H)), jnp.float32),
            jnp.asarray(rng.standard_normal((H, experts)), jnp.float32),
            jnp.asarray(rng.standard_normal((held, H, 2 * F)) * 0.2,
                        jnp.float32),
            jnp.asarray(rng.standard_normal((held, F, H)) * 0.2,
                        jnp.float32))


def _plain_route(logits, k, bias):
    """Each token on its own: sort the experts by sigmoid + bias, keep
    the first k, weigh them by their sigmoid over the k's sum."""
    logits, bias = onp.asarray(logits, onp.float64), onp.asarray(bias)
    ids, weights = [], []
    for row in logits.reshape(-1, logits.shape[-1]):
        s = 1 / (1 + onp.exp(-row))
        top = onp.argsort(-(s + bias), kind='stable')[:k]
        ids.append(top)
        weights.append(s[top] / s[top].sum())
    return onp.array(ids), onp.array(weights)


@pytest.mark.parametrize('bias', ['zero', 'mixed'])
def test_sigmoid_routing_is_a_plain_top_k_of_the_biased_scores(bias):
    rng = onp.random.default_rng(3)
    logits = jnp.asarray(rng.standard_normal((200, 64)), jnp.float32)
    b = onp.zeros(64, onp.float32) if bias == 'zero' \
        else rng.standard_normal(64).astype(onp.float32) * 0.3
    ids, weights = moe.route(logits, 4, 'sigmoid', jnp.asarray(b))
    want_ids, want_w = _plain_route(logits, 4, b)
    onp.testing.assert_array_equal(onp.sort(ids, -1),
                                   onp.sort(want_ids, -1))
    order_got, order_want = onp.argsort(ids, -1), onp.argsort(want_ids, -1)
    onp.testing.assert_allclose(
        onp.take_along_axis(onp.asarray(weights), order_got, -1),
        onp.take_along_axis(want_w, order_want, -1), rtol=1e-5)
    # the bias moves the choice and not the weights: with it, some tokens
    # take other experts, and each weight is still the token's own score
    if bias == 'mixed':
        plain_ids, _ = moe.route(logits, 4, 'sigmoid')
        assert onp.any(onp.sort(plain_ids, -1) != onp.sort(ids, -1))
    # and no gradient reaches the bias
    grad = jax.grad(lambda b_: jnp.sum(moe.route(logits, 4, 'sigmoid',
                                                 b_)[1] ** 2))(jnp.asarray(b))
    assert not onp.any(grad)


def test_softmax_routing_is_unchanged_and_unknown_rules_are_refused():
    logits = jnp.asarray(onp.random.default_rng(4).standard_normal(
        (50, 16)), jnp.float32)
    ids, weights = moe.route(logits, 3)
    probs = jax.nn.softmax(logits, -1)
    want_w, want_ids = jax.lax.top_k(probs, 3)
    onp.testing.assert_array_equal(ids, want_ids)
    onp.testing.assert_allclose(weights, want_w / want_w.sum(-1,
                                                             keepdims=True))
    with pytest.raises(ValueError, match='scoring'):
        moe.route(logits, 3, 'sparsemax')
    x, w_r, w_gu, w_d = _weights(8, 2)
    with pytest.raises(ValueError, match='gate'):
        moe.expert_layer(x, x @ w_r, w_gu, w_d, top_k=2, gate='gelu')


def _dense(x, w_router, w_gate_up, w_down, first, k, bias):
    """Every held expert on every token, SiLU-gated, weighted by the
    token's renormalised sigmoid score of it where it is among the k."""
    logits = x @ w_router
    scores = jax.nn.sigmoid(logits)
    _, ids = jax.lax.top_k(scores + bias, k)
    top = jnp.take_along_axis(scores, ids, -1)
    top = top / jnp.sum(top, -1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(w_gate_up.shape[0]):
        gate, up = jnp.split(x @ w_gate_up[e], 2, axis=-1)
        w_e = jnp.sum(jnp.where(ids == first + e, top, 0.0), axis=-1)
        out = out + w_e[..., None] * ((jax.nn.silu(gate) * up) @ w_down[e])
    return out


def _layer(x, w_router, w_gate_up, w_down, first, k, bias, interpret=None):
    return moe.expert_layer(x, x @ w_router, w_gate_up, w_down,
                            first_expert=first, top_k=k,
                            interpret=interpret, scoring='sigmoid',
                            gate='silu', bias=bias)


def _bias(routing, first):
    """A balancing bias that routes as named: ``mixed`` a small random
    one; ``all_here`` every token to the four held experts; ``none_here``
    every token elsewhere; ``one_expert`` every token to the first held."""
    if routing == 'mixed':
        return onp.random.default_rng(5).standard_normal(16) * 0.1
    bias = onp.zeros(16)
    bias[{'all_here': slice(first, first + 4),
          'none_here': slice(8, 16) if first < 8 else slice(0, 8),
          'one_expert': slice(first, first + 1)}[routing]] = 30.0
    return bias


@pytest.mark.parametrize('mode', MODES)
@pytest.mark.parametrize('first,routing', [
    (0, 'mixed'), (4, 'mixed'), (0, 'all_here'), (0, 'none_here'),
    (4, 'one_expert')])
def test_the_silu_layer_matches_the_einsums(first, routing, mode,
                                           monkeypatch):
    """Forward and the gradients of x, the router and both expert
    weights, 16 experts of which 4 are held, 2 a token, a nonzero
    bias; finite with the kernels' dead rows NaN (``poisoned``)."""
    interpret = interpret_for(mode, monkeypatch)
    x, w_r, w_gu, w_d = _weights(16, 4)
    bias = jnp.asarray(_bias(routing, first), jnp.float32)
    args = (x, w_r, w_gu, w_d)
    rows, tile, tiles = moe.plan(N * T, 4, 2)
    ids, _ = moe.route(x.reshape(-1, H) @ w_r, 2, 'sigmoid', bias)
    here = int(jnp.sum((ids >= first) & (ids < first + 4)))
    n_live = live_tiles(ids, first, 4, tile)
    assert 4 <= n_live < tiles
    if routing == 'all_here':
        assert here == rows and n_live >= rows // tile
    if routing == 'none_here':
        assert here == 0 and n_live == 4
    if routing == 'one_expert':
        assert int(jnp.sum(ids == first)) == N * T
    got = _layer(*args, first, 2, bias, interpret)
    assert onp.isfinite(got).all()
    onp.testing.assert_allclose(got, _dense(*args, first, 2, bias),
                                rtol=1e-5, atol=1e-5)

    def loss(fn, **kw):
        return lambda *a: jnp.sum(jnp.sin(fn(*a, first, 2, bias, **kw)))
    got = jax.grad(loss(_layer, interpret=interpret),
                   argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(loss(_dense), argnums=(0, 1, 2, 3))(*args)
    for name, a, b in zip(('x', 'router', 'gate_up', 'down'), got, want):
        assert onp.isfinite(a).all(), name
        onp.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5, err_msg=name)


def test_eight_shares_of_64_experts_add_up_to_the_uncut_layer():
    """model-configs section 4 at LFM2's router: experts 0-7, 8-15, ...,
    56-63 on eight chips, each routing over all 64 with the same bias and
    taking 4 a token, each computing its own part; the parts add up to
    the layer that holds all 64."""
    x, w_r, w_gu, w_d = _weights(64, 64, seed=1)
    bias = jnp.asarray(onp.random.default_rng(6).standard_normal(64) * 0.05,
                       jnp.float32)
    whole = _dense(x, w_r, w_gu, w_d, 0, 4, bias)
    parts = [_layer(x, w_r, w_gu[first:first + 8], w_d[first:first + 8],
                    first, 4, bias) for first in range(0, 64, 8)]
    onp.testing.assert_allclose(sum(parts), whole, rtol=1e-5, atol=1e-5)


def test_assignment_counts_and_the_bias_update():
    ids = jnp.asarray([[0, 3], [3, 1], [3, 0], [2, 3]], jnp.int32)
    counts = moe.assignment_counts(ids, 5)
    onp.testing.assert_array_equal(counts, [2, 1, 1, 4, 0])
    assert int(counts.sum()) == ids.size
    # mean 1.6: experts over it move down by the rate, under it up
    bias = jnp.asarray([0.5, 0.0, -0.25, 0.0, 1.0], jnp.float32)
    onp.testing.assert_allclose(moe.next_bias(bias, counts, 1e-3),
                                [0.499, 0.001, -0.249, -0.001, 1.001])
    # an expert exactly at the mean stays where it is
    onp.testing.assert_allclose(
        moe.next_bias(jnp.zeros(2), jnp.asarray([3, 3]), 1e-3), [0.0, 0.0])
    # with the layer's ids: every token's k assignments are counted
    x, w_r, w_gu, w_d = _weights(16, 4)
    _, taken = moe.expert_layer(x, x @ w_r, w_gu, w_d, top_k=2,
                                scoring='sigmoid', gate='silu',
                                bias=jnp.zeros(16), with_ids=True)
    assert taken.shape == (N, T, 2)
    assert int(moe.assignment_counts(taken, 16).sum()) == N * T * 2


def test_the_rule_and_gate_are_counted_apart():
    before = dict(moe.builds)
    x, w_r, w_gu, w_d = _weights(16, 4)
    moe.expert_layer(x, x @ w_r, w_gu, w_d, top_k=2)
    _layer(x, w_r, w_gu, w_d, 0, 2, jnp.zeros(16))
    rows, tile, _ = moe.plan(N * T, 4, 2)
    plain_key = (16, 4, 2, rows, tile)
    assert moe.builds[plain_key] - before.get(plain_key, 0) == 1
    key = plain_key + ('sigmoid', 'silu')
    assert moe.builds[key] - before.get(key, 0) == 1


def test_the_cells_plan():
    """lfm2_24b_a2b.t8192_ep8: 2 x 8192 tokens, 4 experts a token, 8
    held: 65 536 rows -- every assignment, eight times the balanced
    8192 -- in 256 tiles of 256 and 8 more for the padding."""
    assert moe.plan(2 * 8192, 8, 4) == (65536, 256, 264)
