"""models/decoder.py against the float32 reference of the benchmark's
``smallthinker`` family (chipbench/families/smallthinker.py) at the tiny
preset: logits and loss; the program's experts are followed at a near-tie
and counted, and a flip at a clear gap is a fault; bf16 inside and 8-bit
weights outside the tolerance; the loss against
``masked_cross_entropy``; and the pieces of ops/nn.py it rests on.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import nd
from mxnet_tpu.models import decoder_lm_loss
from mxnet_tpu.models.bert import masked_cross_entropy
from mxnet_tpu.ops import nn as nn_ops

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import manifest, plain, program    # noqa: E402

CELL = manifest.resolve('smallthinker_21b.t8192')
FAMILY = CELL.family
TINY = FAMILY.tiny(CELL.config)
TRAFFIC = dict(CELL.traffic, seq_len=128)


def _model(dtype='float32', seed=5):
    config = dict(TINY, policy=dict(TINY['policy'], param_dtype=dtype))
    program.seed(seed)
    model, loss_fn = FAMILY.build(config)
    model.hybridize()
    return model, loss_fn


def _tokens(n=2, seed=0):
    rng = onp.random.default_rng(seed)
    tokens = rng.integers(0, TINY['vocab_size'], (n, TRAFFIC['seq_len']))
    labels = onp.concatenate([tokens[:, 1:], onp.full((n, 1), -1)], axis=1)
    return tokens.astype(onp.int32), labels.astype(onp.int32)


def _reference(weights, tokens, taken=None):
    with jax.default_matmul_precision('highest'):
        return FAMILY.reference_forward(weights, TINY, jnp.asarray(tokens),
                                        taken)


def _reference_with_routes(weights, tokens, runners_up=False, config=TINY):
    """(logits, the experts taken a layer) of the reference itself on
    ``weights``: a stand-in for a program of lower precision. With
    ``runners_up`` a layer's entry is (its top 3 experts, the gap between
    the second and the third in the logits' standard deviations)."""
    eps, taken = config['rms_norm_eps'], []
    heads, kv = config['num_attention_heads'], config['num_key_value_heads']
    _, _, first, top_k = FAMILY._experts(config)
    with jax.default_matmul_precision('highest'):
        x = weights['embed_weight'][jnp.asarray(tokens)]
        logits, *_ = FAMILY.reference_forward(weights, config,
                                              jnp.asarray(tokens))
        for i, (window, theta) in enumerate(FAMILY._layers(config)):
            p = f'blocks_decoderblock{i}_'
            a = FAMILY._rms_norm(x, weights[p + 'norm1_gamma'], eps)
            router = a @ weights[p + 'experts_router_weight'].T
            top = jax.lax.top_k(jax.nn.softmax(router, -1), top_k + 1)[1]
            edge = jnp.take_along_axis(router, top[..., top_k - 1:], axis=-1)
            taken.append((top, (edge[..., 0] - edge[..., 1])
                          / jnp.std(router, -1)) if runners_up
                         else top[..., :top_k])
            q, k, v = (a @ weights[p + n + '_weight'].T for n in 'qkv')
            if theta is not None:
                q, k = (FAMILY._rope(q, heads, theta),
                        FAMILY._rope(k, kv, theta))
            x = x + FAMILY._attention(q, k, v, heads, kv, window) \
                @ weights[p + 'o_weight'].T
            part, *_ = FAMILY._experts_part(
                FAMILY._rms_norm(x, weights[p + 'norm2_gamma'], eps), router,
                weights[p + 'experts_gate_up_weight'],
                weights[p + 'experts_down_weight'], first, top_k)
            x = x + part
    return logits, taken


def test_the_tiny_preset_keeps_the_structure():
    assert TINY['num_hidden_layers'] == 4
    assert FAMILY._layers(TINY) == [(None, None)] + [(32, 1.5e6)] * 3
    assert FAMILY._experts(TINY) == (8, 4, 0, 2)
    assert TINY['sliding_window_size'] < TRAFFIC['seq_len']


def _forward(model, tokens):
    """(logits, the experts taken a layer) of the compiled forward, as
    reference_check reads them."""
    block, read = FAMILY.observed(model)
    block.hybridize()
    model.hybridize(False)
    return read(block(nd.array(tokens)), 2)


def test_float32_model_matches_the_reference():
    """Logits and loss, every position: in float32 the program takes the
    reference's experts everywhere, so nothing is followed."""
    model, loss_fn = _model()
    tokens, labels = _tokens()
    got, taken = _forward(model, tokens)
    assert len(taken) == 4 and taken[0].shape == (2, 128, 2)
    ref, followed, clear, seen = _reference(program.weights_of(model), tokens,
                                            taken)
    verdict = FAMILY.judge(got, ref, followed, clear, jnp.asarray(labels))
    assert verdict['ok'] and verdict['followed_share'] == 0.0, verdict
    assert verdict['logit_err'] < 1e-4 and verdict['loss_err'] < 1e-5
    assert float(seen['widest_gap_flipped_at']) == 0.0
    # the program's own loss is the reference's
    loss = loss_fn(model(nd.array(tokens)), nd.array(labels)).asscalar()
    assert abs(float(loss) - verdict['reference_loss']) < 1e-4
    # the router is 8 wide and 2 a token: half the assignments land on
    # the four experts here, give or take the draw
    assert all(0.3 < int(r) / (2 * 2 * 128) < 0.7
               for r in seen['rows_routed_here'])
    # and the hooks cost the model nothing: its own forward is the same
    model.hybridize()
    onp.testing.assert_allclose(
        program.payload(model(nd.array(tokens))), got, rtol=1e-5, atol=1e-6)


def test_reference_check_runs_as_the_harness_calls_it():
    model, _ = _model()
    rng = onp.random.default_rng(3)
    from chipbench import tokens as traffic_tokens
    zipf = traffic_tokens.Zipf(rng, TINY['vocab_size'], 1.0)
    verdict = FAMILY.reference_check(model, program.weights_of(model), TINY,
                                     TRAFFIC, rng, zipf)
    assert verdict['ok'], verdict
    assert len(verdict['rows_routed_here']) == 4
    assert verdict['near_tie_threshold'] == FAMILY.NEAR_TIE


@pytest.mark.parametrize('near', [True, False], ids=['near_tie', 'clear'])
def test_a_flip_is_followed_at_a_near_tie_and_a_fault_elsewhere(near):
    """One token of layer 1 handed to the reference with its third expert
    in place of its second. Where the reference's own gap between the two
    is a near-tie the choice is followed and counted; at the widest gap
    it is not followed, counts as a clear flip, and the verdict fails."""
    model, _ = _model()
    tokens, labels = _tokens()
    weights = program.weights_of(model)
    got, taken = _forward(model, tokens)
    _, own = _reference_with_routes(weights, tokens, runners_up=True)
    top3, gap = (onp.asarray(a) for a in own[1])
    token = onp.unravel_index(gap.argmin() if near else gap.argmax(),
                              gap.shape)
    assert (gap[token] < FAMILY.NEAR_TIE) == near
    changed = [onp.array(t) for t in taken]
    changed[1][token] = top3[token][[0, 2]]
    ref, followed, clear, seen = _reference(
        weights, tokens, [jnp.asarray(t) for t in changed])
    verdict = FAMILY.judge(got, ref, followed, clear, jnp.asarray(labels))
    assert float(seen['widest_gap_flipped_at']) == pytest.approx(
        float(gap[token]), rel=1e-4)
    if near:
        assert verdict['followed_share'] == 1 / gap.size
        assert verdict['clear_flips'] == 0
        assert bool(followed[1][token]) and int(jnp.sum(followed)) == 1
    else:
        assert verdict['clear_flips'] == 1 and not verdict['ok']
        assert verdict['followed_share'] == 0.0


def test_too_many_followed_is_a_failure():
    model, _ = _model()
    tokens, labels = _tokens()
    got, taken = _forward(model, tokens)
    ref, followed, clear, _ = _reference(program.weights_of(model), tokens,
                                         taken)
    everywhere = jnp.ones_like(followed)
    verdict = FAMILY.judge(got, ref, everywhere, clear, jnp.asarray(labels))
    assert verdict['followed_share'] == 1.0 and not verdict['ok']
    verdict = FAMILY.judge(got, ref, followed, everywhere,
                           jnp.asarray(labels))
    assert verdict['clear_flips'] == everywhere.size and not verdict['ok']


def _quantized(weights, bits=8):
    """Symmetric per-tensor rounding of every matrix to ``bits`` bits."""
    def q(w):
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w)) / (2 ** (bits - 1) - 1)
        return jnp.round(w / scale) * scale
    return {name: q(w) for name, w in weights.items()}


def test_bf16_is_inside_and_lower_precision_outside_the_tolerance():
    """The model in bf16 (weights and activations, float32 accumulation)
    against the float32 reference on the same bf16-rounded weights: ok, a
    few near-ties followed. The nearest precision below, the reference
    itself with its weights rounded to 8 bits, reads worse on every count
    and, at published widths, outside every limit (my chip run, PR 34,
    seed 11: logit error 0.0499 for 0.0059, 49 % followed for 8.6 %, the
    widest gap flipped at 0.152 for 0.020; PERF.md section 6). At this
    toy width its sums are forty times shorter and 8 bits still read
    inside; 7 bits are outside."""
    model, _ = _model('bfloat16')
    tokens, labels = _tokens()
    weights = program.weights_of(model)

    def verdict_of(got, taken):
        ref, followed, clear, seen = _reference(weights, tokens, taken)
        verdict = FAMILY.judge(got, ref, followed, clear, jnp.asarray(labels))
        return dict(verdict, widest=float(seen['widest_gap_flipped_at']))
    bf16 = verdict_of(*_forward(model, tokens))
    assert bf16['ok'], bf16
    assert bf16['widest'] < FAMILY.NEAR_TIE / 3
    assert bf16['followed_share'] < FAMILY.FOLLOWED_LIMIT / 3
    eight = verdict_of(*_reference_with_routes(_quantized(weights), tokens))
    for count in ('logit_err', 'followed_share', 'widest'):
        assert eight[count] > 2 * bf16[count], (count, eight, bf16)
    seven = verdict_of(*_reference_with_routes(_quantized(weights, 7),
                                               tokens))
    assert not seven['ok'] and seven['clear_flips'] > 0, seven
    assert seven['logit_err'] > plain.LOGIT_TOLERANCE


def test_the_loss_is_masked_cross_entropy_a_chunk_at_a_time():
    """decoder_lm_loss on bf16 logits against masked_cross_entropy on
    their float32 cast, value and gradient; 4096 positions make four
    chunks."""
    rng = onp.random.default_rng(1)
    logits = jnp.asarray(rng.standard_normal((2, 2048, 96)), jnp.bfloat16)
    labels = rng.integers(0, 96, (2, 2048)).astype(onp.int32)
    labels[:, -1] = -1
    labels = jnp.asarray(labels)

    def ours(x):
        return program.payload(decoder_lm_loss(nd.NDArray(x),
                                               nd.NDArray(labels)))

    def theirs(x):
        return program.payload(masked_cross_entropy(
            nd.NDArray(x.astype(jnp.float32)), nd.NDArray(labels)))
    onp.testing.assert_allclose(float(ours(logits)), float(theirs(logits)),
                                rtol=1e-6)
    onp.testing.assert_allclose(
        onp.asarray(jax.grad(ours)(logits), onp.float32),
        onp.asarray(jax.grad(theirs)(logits), onp.float32),
        rtol=1e-2, atol=1e-9)


def test_rms_norm_and_rotary_embedding():
    rng = onp.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 16, 64)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal(64), jnp.float32)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * gamma
    onp.testing.assert_allclose(nn_ops.rms_norm(x, gamma, eps=1e-6), want,
                                rtol=1e-5, atol=1e-6)
    # rotary: position 0 is left alone, norms are kept, and the product
    # of a rotated query and key depends on their distance only
    q = nn_ops.rotary_embedding(x, num_heads=4, theta=1.5e6)
    onp.testing.assert_allclose(q[:, 0], x[:, 0], rtol=1e-6)
    onp.testing.assert_allclose(jnp.linalg.norm(q, axis=-1),
                                jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    one = jnp.broadcast_to(x[:, :1], x.shape)       # the same row everywhere
    r = nn_ops.rotary_embedding(one, num_heads=4, theta=100.0)
    head = r.reshape(2, 16, 4, 16)
    dots = jnp.einsum('nthd,nshd->nhts', head, head)
    onp.testing.assert_allclose(dots[:, :, 3, 5], dots[:, :, 9, 11],
                                rtol=1e-4)
    onp.testing.assert_allclose(
        nn_ops.rotary_embedding(x, num_heads=4, theta=1.5e6),
        FAMILY._rope(x, 4, 1.5e6), rtol=1e-5, atol=1e-6)


def test_a_train_step_learns():
    """ShardedTrainStep on the tiny preset, as the benchmark builds it:
    the loss falls, through the expert layer's hand-written backward."""
    model, loss_fn = _model('bfloat16')
    model.hybridize(False)
    fast = dict(TINY, policy=dict(TINY['policy'],
                                  optimizer_params={'learning_rate': 1e-3}))
    step = program.make_step(model, loss_fn, fast, dict(TRAFFIC, mesh={'dp': 1}),
                             jax.devices()[:1])
    tokens, labels = _tokens()
    losses = [float(step([tokens], [labels]).asscalar()) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.1, losses


def test_the_tiny_model_is_the_one_pr_34_built():
    """The block's later options (a dense gated feed-forward, sandwich
    norms, a looped model: PR 37) cost this path nothing: the parameter
    names and shapes, the float32 loss to the last digit and the lowered
    forward + loss are those read on PR 36's tree, before
    models/decoder.py was touched."""
    import hashlib
    model, loss_fn = _model(seed=5)
    params = model.collect_params()
    order = sorted(params)
    cut = len(model.prefix)
    names = [(n[cut:], params[n].shape) for n in order]
    assert len(names) == 39
    assert hashlib.sha256(repr(names).encode()).hexdigest() == (
        '971da5bfa31544408c2417b2766c2c42960fba5080850210e7c78e5d6f97f132')
    tokens, labels = _tokens()
    loss = loss_fn(model(nd.array(tokens)), nd.array(labels)).asscalar()
    assert float(loss).hex() == '0x1.9037340000000p+2'     # 6.253369331359863

    model.hybridize(False)

    def forward_loss(arrays, tokens, labels):
        for n, a in zip(order, arrays):
            params[n]._set_trace_proxy(nd.NDArray(a))
        try:
            return program.payload(loss_fn(model(nd.NDArray(tokens)),
                                           nd.NDArray(labels)))
        finally:
            for n in order:
                params[n]._clear_trace_proxy()
    text = jax.jit(forward_loss).lower(
        [program.payload(params[n].data()) for n in order], tokens,
        labels).as_text()
    # the expert layer's part of the text moved once since, when its
    # grouped matmuls came to stop at the live tiles: the combine's miss
    # became a select and the layout carries the live tile count; the
    # rest of the text and the loss above are as they were
    assert len(text.splitlines()) == 1928
    assert hashlib.sha256(text.encode()).hexdigest() == (
        '6a119521807704c21ba727ba6ab47aeeae285b7a32fdd47b307bda8650b7dae5')
