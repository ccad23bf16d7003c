"""models/decoder.py's hybrid blocks against the float32 reference of the
benchmark's ``lfm2`` family (chipbench/families/lfm2.py) at the tiny
preset: a gated short-convolution mixer beside QK-normed grouped-query
attention, a leading dense SwiGLU layer, sigmoid-routed SwiGLU experts
read after the mixer with a balancing bias, and a tied head. Logits, loss
and gradients; the near-tie rule; bf16 inside and lower-precision
weights outside the tolerance; the bias the forward moves under training, through
ShardedTrainStep; and that the older blocks are what they were."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import nd
from mxnet_tpu.models.decoder import DecoderModel

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import manifest, plain, program    # noqa: E402

CELL = manifest.resolve('lfm2_24b_a2b.t8192_ep8')
FAMILY = CELL.family
TINY = FAMILY.tiny(CELL.config)
TRAFFIC = dict(CELL.traffic, seq_len=64)


def _model(dtype='float32', seed=5, config=TINY):
    config = dict(config, policy=dict(config['policy'], param_dtype=dtype))
    program.seed(seed)
    model, loss_fn = FAMILY.build(config)
    model.hybridize()
    return model, loss_fn


def _tokens(n=2, seed=0):
    rng = onp.random.default_rng(seed)
    tokens = rng.integers(0, TINY['vocab_size'], (n, TRAFFIC['seq_len']))
    labels = onp.concatenate([tokens[:, 1:], onp.full((n, 1), -1)], axis=1)
    return tokens.astype(onp.int32), labels.astype(onp.int32)


def _forward(model, weights, tokens):
    block, read = FAMILY.observed(model)
    block.hybridize()
    model.hybridize(False)
    return read(block(nd.array(tokens)), FAMILY._biases(weights, TINY),
                TINY['num_experts_per_tok'])


def _reference(weights, tokens, taken=None, config=TINY):
    with jax.default_matmul_precision('highest'):
        return FAMILY.reference_forward(weights, config, jnp.asarray(tokens),
                                        taken)


def test_the_tiny_preset_keeps_the_structure():
    assert FAMILY._layers(TINY) == [('conv', 'dense')] + [
        ('attention', 'experts')] + [('conv', 'experts')] * 3
    assert FAMILY._experts(TINY) == (16, 4, 0, 4)
    model, _ = _model()
    params = {n[len(model.prefix):]: p for n, p in
              model.collect_params().items()}
    # no head matrix: the head is the embedding's rows
    assert not any(n.startswith('head_') for n in params)
    assert params['blocks_decoderblock1_q_norm_gamma'].shape == (16,)
    assert params['blocks_decoderblock0_conv_conv_weight'].shape == (3, 64)
    assert params['blocks_decoderblock0_conv_in_proj_weight'].shape == \
        (192, 64)
    bias = params['blocks_decoderblock2_experts_expert_bias']
    assert bias.shape == (16,) and bias.grad_req == 'null'
    assert 'blocks_decoderblock0_experts_router_weight' not in params


def test_float32_model_matches_the_reference():
    """Logits and loss, every position: in float32 the program takes the
    reference's experts everywhere, so nothing is followed."""
    model, loss_fn = _model()
    tokens, labels = _tokens()
    weights = program.weights_of(model)
    got, taken = _forward(model, weights, tokens)
    assert len(taken) == 4 and taken[0].shape == (2, 64, 4)
    ref, followed, clear, seen = _reference(weights, tokens, taken)
    verdict = FAMILY.judge(got, ref, followed, clear, jnp.asarray(labels))
    assert verdict['ok'] and verdict['followed_share'] == 0.0, verdict
    assert verdict['logit_err'] < 1e-4 and verdict['loss_err'] < 1e-5
    loss = loss_fn(model(nd.array(tokens)), nd.array(labels)).asscalar()
    assert abs(float(loss) - verdict['reference_loss']) < 1e-4
    # 4 of 16 held and 4 a token: a quarter of the assignments land here
    rows = onp.asarray(seen['rows_routed_here']).sum(axis=1)
    assert all(0.15 < r / (2 * 64 * 4) < 0.35 for r in rows), rows


def test_the_gradients_match_the_reference():
    """d loss / d every trainable parameter, the program's hybridized
    loss against the reference's, both in float32 at the same weights;
    the expert bias has no gradient."""
    model, loss_fn = _model()
    tokens, labels = _tokens(seed=1)
    params = model.collect_params()
    cut = len(model.prefix)
    order = sorted(n for n in params if params[n].grad_req != 'null')
    model.hybridize(False)

    def program_loss(arrays):
        for n, a in zip(order, arrays):
            params[n]._set_trace_proxy(nd.NDArray(a))
        try:
            return program.payload(loss_fn(
                model(nd.NDArray(jnp.asarray(tokens))),
                nd.NDArray(jnp.asarray(labels))))
        finally:
            for n in order:
                params[n]._clear_trace_proxy()
    weights = program.weights_of(model)

    def reference_loss(arrays):
        w = dict(weights, **{n[cut:]: a for n, a in zip(order, arrays)})
        logits, *_ = _reference(w, tokens)
        return plain.cross_entropy(logits, jnp.asarray(labels))
    arrays = [weights[n[cut:]] for n in order]
    got = jax.grad(program_loss)(arrays)
    with jax.default_matmul_precision('highest'):
        want = jax.grad(reference_loss)(arrays)
    for n, a, b in zip(order, got, want):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) / scale < 1e-3, n[cut:]
    assert not any('expert_bias' in n for n in order)


def test_a_flip_at_a_near_tie_is_followed_and_elsewhere_counted():
    model, _ = _model()
    tokens, labels = _tokens()
    weights = program.weights_of(model)
    got, taken = _forward(model, weights, tokens)
    # the program takes another expert at token (0, 5) of the first
    # expert layer: the reference follows it only if that was a near-tie
    moved = taken[0].at[0, 5, 0].set(
        (taken[0][0, 5, 0] + 1) % 16)
    while bool(jnp.any(moved[0, 5, 1:] == moved[0, 5, 0])):
        moved = moved.at[0, 5, 0].set((moved[0, 5, 0] + 1) % 16)
    ref, followed, clear, _ = _reference(weights, tokens,
                                         [moved] + taken[1:])
    assert int(jnp.sum(followed)) + int(jnp.sum(clear)) == 1
    verdict = FAMILY.judge(got, ref, followed, clear, jnp.asarray(labels))
    assert verdict['ok'] == bool(jnp.sum(followed) == 1)


def _quantized(weights, bits):
    """Symmetric per-tensor rounding of every matrix to ``bits`` bits; the
    expert bias, a vector, is left as it is."""
    def q(w):
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w)) / (2 ** (bits - 1) - 1)
        return jnp.round(w / scale) * scale
    return {name: q(w) for name, w in weights.items()}


def test_bf16_is_inside_and_lower_precision_outside_the_tolerance():
    """The model in bf16 (weights and activations, float32 accumulation
    and a float32 router) against the float32 reference on the same
    bf16-rounded weights: ok, few near-ties followed. The reference itself
    with its weights rounded to 7 bits, standing in for a program below
    the stated precision, takes other experts at clear gaps and fails.
    (At this toy width 8 bits still read inside, as in
    tests/test_decoder.py; the chip's readings at published widths are in
    PERF.md section 6, PR 41.)"""
    model, _ = _model('bfloat16')
    tokens, labels = _tokens(n=4)
    weights = program.weights_of(model)
    k = TINY['num_experts_per_tok']

    def verdict_of(got, taken):
        ref, followed, clear, _ = _reference(weights, tokens, taken)
        return FAMILY.judge(got, ref, followed, clear, jnp.asarray(labels))
    bf16 = verdict_of(*_forward(model, weights, tokens))
    assert bf16['ok'], bf16
    assert bf16['followed_share'] < FAMILY.FOLLOWED_LIMIT / 2
    low = _quantized(weights, 7)
    logits, *_ = _reference(low, tokens)
    taken = _routes_of(low, tokens, k)
    seven = verdict_of(logits, taken)
    assert not seven['ok'] and seven['clear_flips'] > 0, seven
    assert seven['logit_err'] > bf16['logit_err'] * 3


def _routes_of(weights, tokens, k):
    """The experts the reference takes an expert layer, read through the
    observed program's own route rule on the reference's router logits:
    the hooks of a float32 model loaded with ``weights``."""
    model, _ = _model()
    params = model.collect_params()
    cut = len(model.prefix)
    for name, p in params.items():
        p.set_data(nd.NDArray(weights[name[cut:]]))
    return _forward(model, weights, tokens)[1]


def test_the_bias_moves_under_training_and_only_there():
    """A forward under training returns the bias's next value, which
    ShardedTrainStep writes back as it writes BatchNorm's statistics:
    b + rate * sign(mean(n) - n) of the step's assignment counts. A
    predict-mode forward leaves it alone."""
    from mxnet_tpu.ops import moe
    model, loss_fn = _model('bfloat16')
    tokens, labels = _tokens()
    block = model.blocks[2]
    bias = block.experts.expert_bias
    assert bias.data().dtype == jnp.float32
    model(nd.array(tokens))
    assert not onp.any(program.payload(bias.data()))
    # the counts the first step will see: the program's own choice
    weights = program.weights_of(model)
    _, taken = _forward(model, weights, tokens)
    want = moe.next_bias(jnp.zeros(16), moe.assignment_counts(taken[1], 16),
                         TINY['assumed']['bias_rate'])
    model.hybridize(False)
    step = program.make_step(model, loss_fn, TINY,
                             dict(TRAFFIC, mesh={'dp': 1}),
                             jax.devices()[:1])
    step([tokens], [labels])
    got = program.payload(bias.data())
    assert got.dtype == jnp.float32
    onp.testing.assert_allclose(got, want, atol=1e-7)
    assert float(jnp.max(jnp.abs(got))) == pytest.approx(1e-3)
    step([tokens], [labels])
    assert float(jnp.max(jnp.abs(program.payload(bias.data())))) \
        == pytest.approx(2e-3, rel=1e-3)
    counted = FAMILY.counters()
    assert len(counted['expert_bias_max_abs']) == 4
    assert all(b > 0 for b in counted['expert_bias_max_abs'])


def test_a_train_step_learns():
    model, loss_fn = _model('bfloat16')
    model.hybridize(False)
    fast = dict(TINY, policy=dict(TINY['policy'],
                                  optimizer_params={'learning_rate': 1e-3}))
    step = program.make_step(model, loss_fn, fast,
                             dict(TRAFFIC, mesh={'dp': 1}),
                             jax.devices()[:1])
    tokens, labels = _tokens()
    losses = [float(step([tokens], [labels]).asscalar()) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.1, losses


def test_the_reference_check_runs_as_the_harness_calls_it():
    model, _ = _model('bfloat16')
    rng = onp.random.default_rng(3)
    from chipbench import tokens as tok
    zipf = tok.Zipf(rng, TINY['vocab_size'], 1.0)
    verdict = FAMILY.reference_check(model, program.weights_of(model), TINY,
                                     TRAFFIC, rng, zipf)
    assert verdict['ok'], verdict
    assert len(verdict['largest_expert_over_balanced']) == 4
    # 64 tokens x 4 rows a layer computed; those routed here are read
    assert all(u + sum(r) == 64 * 4 for u, r in zip(
        verdict['rows_computed_unread'], verdict['rows_routed_here']))
    assert any("'sigmoid', 'silu'" in k
               for k in verdict['routes']['expert_layers'])


@pytest.mark.parametrize('bad', [
    dict(mixers=['conv', 'mamba']), dict(router='post_ffn'),
    dict(ffn_kinds=['dense'])])
def test_the_options_are_checked(bad):
    kw = dict(vocab_size=32, hidden=16, heads=2, kv_heads=1, head_dim=8,
              windows=[None, None], rope_thetas=[None, None],
              experts=dict(width=8, experts=4, top_k=2),
              ffn=dict(width=8))
    kw.update(bad)
    with pytest.raises(ValueError):
        DecoderModel(**kw)


def test_a_bias_needs_the_sigmoid_rule():
    with pytest.raises(ValueError, match='sigmoid'):
        DecoderModel(vocab_size=32, hidden=16, heads=2, kv_heads=1,
                     head_dim=8, windows=[None], rope_thetas=[None],
                     experts=dict(width=8, experts=4, top_k=2,
                                  bias_rate=1e-3))
