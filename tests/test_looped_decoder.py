"""A looped decoder (models/decoder.py: ``passes``, ``exit_gate``, the
sandwich norms, the dense gated feed-forward, ``looped_lm_loss``) against
the float32 reference of the benchmark's ``ouro`` family
(chipbench/families/ouro.py) at the tiny preset: hidden 64, 4 heads of
16, feed-forward 96, 2 blocks, 4 passes, vocabulary 512, T = 64. Logits
of every pass, exit probabilities, objective and the gradient of every
shared weight; the loop against the blocks applied by hand; the
checkpoint against none; what a block application's region keeps on the
flash route (the kernel's output and row statistics, scopes.FLASH_KEPT:
ISSUE 38); the faults the comparison has to catch; the counter and the
scopes.
"""
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from mxnet_tpu import autograd, nd, scopes
from mxnet_tpu.models import DecoderModel, decoder, looped_lm_loss
from mxnet_tpu.ops import pallas_attention

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import manifest, program    # noqa: E402

CELL = manifest.resolve('ouro_2_6b.t4096')
FAMILY = CELL.family
TINY = FAMILY.tiny(CELL.config)
BETA = TINY['assumed']['entropy_weight']
T = 64


def _model(dtype='float32', seed=5, **changed):
    config = dict(TINY, policy=dict(TINY['policy'], param_dtype=dtype),
                  **changed)
    program.seed(seed)
    return FAMILY.build(config)


def _tokens(n=2, seed=0):
    rng = onp.random.default_rng(seed)
    tokens = rng.integers(0, TINY['vocab_size'], (n, T))
    labels = onp.concatenate([tokens[:, 1:], onp.full((n, 1), -1)], axis=1)
    return tokens.astype(onp.int32), labels.astype(onp.int32)


def _reference(weights, tokens, config=TINY):
    with jax.default_matmul_precision('highest'):
        return FAMILY.reference_forward(weights, config, jnp.asarray(tokens))


def _verdict(got_logits, got_p, ref_logits, ref_p, labels):
    return FAMILY.judge(FAMILY.errors(
        jnp.asarray(got_logits), jnp.asarray(got_p), ref_logits, ref_p,
        jnp.asarray(labels), BETA))


def _predicted(model, tokens):
    """(logits, exit probabilities) of the predict-mode forward."""
    logits, gates = (program.payload(o) for o in model(nd.array(tokens)))
    return logits.astype(jnp.float32), FAMILY.exit_probabilities(gates)


def _grads(model, loss_fn, tokens, labels):
    """(loss, {name: gradient}) through autograd, as a Gluon user gets
    them."""
    with autograd.record():
        loss = loss_fn(*model(nd.array(tokens)), nd.array(labels))
    loss.backward()
    cut = len(model.prefix)
    return float(loss.asscalar()), {
        name[cut:]: onp.asarray(program.payload(p.grad()), onp.float32)
        for name, p in model.collect_params().items()}


def test_the_tiny_preset_keeps_the_loop():
    assert TINY['total_ut_steps'] == CELL.config['total_ut_steps'] == 4
    assert TINY['num_hidden_layers'] == 2
    assert (TINY['hidden_size'], TINY['num_attention_heads'],
            TINY['head_dim'], TINY['intermediate_size'],
            TINY['vocab_size']) == (64, 4, 16, 96, 512)


def test_every_pass_matches_the_reference():
    """Logits of all four passes, exit probabilities and the objective,
    every position, in float32."""
    model, loss_fn = _model()
    tokens, labels = _tokens()
    logits, exit_p = _predicted(model, tokens)
    assert logits.shape == (4, 2, T, 512) and exit_p.shape == (4, 2, T)
    ref_logits, ref_p = _reference(program.weights_of(model), tokens)
    verdict = _verdict(logits, exit_p, ref_logits, ref_p, labels)
    assert verdict['ok'], verdict
    assert max(verdict['logit_err']) < 1e-4 and verdict['exit_err'] < 1e-5
    assert max(verdict['loss_err']) < 1e-5
    onp.testing.assert_allclose(exit_p.sum(0), 1.0, rtol=1e-6)
    # norm gains ones and the gate's bias zero: near 1/2, 1/4, 1/8, 1/8
    onp.testing.assert_allclose(exit_p.mean((1, 2)),
                                [0.5, 0.25, 0.125, 0.125], atol=0.05)
    # the program's own objective, from the states and the head
    with autograd.train_mode():
        loss = loss_fn(*model(nd.array(tokens)), nd.array(labels))
    assert abs(float(loss.asscalar())
               - verdict['reference_objective']) < 1e-5


def test_gradients_of_every_shared_weight_match_the_reference():
    """Each weight is used four times a forward; its gradient is the sum
    over the uses, and the reference's ``jax.grad`` knows nothing of
    checkpoints."""
    model, loss_fn = _model()
    tokens, labels = _tokens()
    loss, got = _grads(model, loss_fn, tokens, labels)

    def objective(w):
        return FAMILY.reference_loss(
            *FAMILY.reference_forward(w, TINY, jnp.asarray(tokens)),
            jnp.asarray(labels), BETA)
    with jax.default_matmul_precision('highest'):
        want_loss, want = jax.value_and_grad(objective)(
            program.weights_of(model))
    assert abs(loss - float(want_loss)) < 1e-5
    assert set(got) == set(want) and len(got) == 2 * 10 + 5
    for name in sorted(want):
        ref = onp.asarray(want[name]).reshape(got[name].shape)
        assert onp.abs(ref).max() > 0, name
        onp.testing.assert_allclose(got[name], ref, rtol=2e-3,
                                    atol=2e-4 * onp.abs(ref).max(),
                                    err_msg=name)


def _by_hand(model, tokens, passes=4, norm_in_loop=True):
    """The model's own blocks, norm, head and gate applied one call at a
    time: (logits, exit probabilities)."""
    x = model.embed(nd.array(tokens))
    logits, gates = [], []
    for _ in range(passes):
        for blk in model.blocks:
            x = blk(x)
        h = model.norm(x)
        if norm_in_loop:
            x = h
        logits.append(program.payload(model.head(h)))
        gates.append(program.payload(model.exit_gate(h)))
    return jnp.stack(logits).astype(jnp.float32), \
        FAMILY.exit_probabilities(jnp.stack(gates))


def test_four_passes_are_the_one_pass_model_applied_four_times():
    model, _ = _model()
    tokens, _labels = _tokens()
    logits, exit_p = _predicted(model, tokens)
    by_hand, by_hand_p = _by_hand(model, tokens)
    onp.testing.assert_allclose(logits, by_hand, rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(exit_p, by_hand_p, rtol=1e-5, atol=1e-6)
    # and a one-pass model on the same weights is the first pass
    one, _ = _model(total_ut_steps=1)
    first, first_p = _predicted(one, tokens)
    assert first.shape == (1, 2, T, 512)
    onp.testing.assert_allclose(first[0], logits[0], rtol=1e-5, atol=1e-5)
    onp.testing.assert_allclose(first_p, 1.0)


def test_the_parameter_set_does_not_depend_on_passes():
    def names(model):
        cut = len(model.prefix)
        return {n[cut:]: p.shape for n, p in model.collect_params().items()}
    four, one = names(_model()[0]), names(_model(total_ut_steps=1)[0])
    assert four == one and len(four) == 25
    assert {n for n in four if 'decoderblock0' in n} == {
        'blocks_decoderblock0_' + n for n in (
            'norm1_gamma', 'q_weight', 'k_weight', 'v_weight', 'o_weight',
            'post_norm1_gamma', 'norm2_gamma', 'ffn_gate_up_weight',
            'ffn_down_weight', 'post_norm2_gamma')}
    assert four['blocks_decoderblock0_ffn_gate_up_weight'] == (2 * 96, 64)
    assert four['exit_gate_weight'] == (64,)
    assert four['exit_gate_bias'] == (1,)


def test_the_checkpoint_changes_no_value_and_no_gradient(monkeypatch):
    tokens, labels = _tokens()
    model, loss_fn = _model()
    loss, grads = _grads(model, loss_fn, tokens, labels)
    assert decoder.loop_counts['checkpointed'] == 8
    monkeypatch.setattr(decoder, '_recomputed', lambda block: block)
    plain_model, plain_loss_fn = _model()
    plain_loss, plain_grads = _grads(plain_model, plain_loss_fn, tokens,
                                     labels)
    assert decoder.loop_counts['checkpointed'] == 0
    assert loss == pytest.approx(plain_loss, rel=1e-6)
    for name, grad in grads.items():
        onp.testing.assert_allclose(grad, plain_grads[name], rtol=1e-4,
                                    atol=1e-6, err_msg=name)


@pytest.fixture
def flash_route(monkeypatch):
    """The attention of every block on the Pallas route, as on a TPU; the
    kernels run through the interpreter (the backend is the CPU)."""
    monkeypatch.setattr(pallas_attention, 'pallas_available', lambda: True)


def _without_policy(monkeypatch):
    """``jax.checkpoint(apply)``: the region as it was until ISSUE 38,
    which keeps its inputs only."""
    plain = jax.checkpoint
    monkeypatch.setattr(jax, 'checkpoint', lambda f, **policy: plain(f))


def _calls(jaxpr, kernel):
    """How many ``pallas_call``s named ``kernel`` a jaxpr makes, those of
    its nested jaxprs counted where they are called."""
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            total += eqn.params['name'] == kernel
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, 'jaxpr', sub)
                if hasattr(sub, 'eqns'):
                    total += _calls(sub, kernel)
    return total


def _gradient_program(model, loss_fn, tokens, labels):
    """The jaxpr of value-and-gradient of the objective in the model's
    parameters, traced as ShardedTrainStep traces it."""
    params = model.collect_params()
    order = sorted(params)

    def objective(arrays):
        for n, a in zip(order, arrays):
            params[n]._set_trace_proxy(nd.NDArray(a))
        try:
            with autograd.train_mode():
                return program.payload(loss_fn(
                    *model(nd.array(tokens)), nd.array(labels)))
        finally:
            for n in order:
                params[n]._clear_trace_proxy()
    return jax.make_jaxpr(jax.value_and_grad(objective))(
        [program.payload(params[n].data()) for n in order]).jaxpr


def test_the_backward_does_not_run_the_flash_forward_again(
        flash_route, monkeypatch):
    """One forward kernel a block application in the whole gradient
    program, where a region without the policy runs it a second time to
    get ``o`` and ``lse`` back; dq and dk/dv once an application either
    way."""
    tokens, labels = _tokens()
    kept = _gradient_program(*_model(), tokens, labels)
    applications = decoder.loop_counts['block_applications']
    assert applications == 8
    assert decoder.loop_counts['kept'] == scopes.FLASH_KEPT == (
        scopes.FLASH_OUT, scopes.FLASH_LSE)
    _without_policy(monkeypatch)
    plain = _gradient_program(*_model(), tokens, labels)
    assert _calls(kept, scopes.FLASH_FWD) == applications
    assert _calls(plain, scopes.FLASH_FWD) == 2 * applications
    for jaxpr in (kept, plain):
        assert _calls(jaxpr, scopes.FLASH_BWD_DQ) == applications
        assert _calls(jaxpr, scopes.FLASH_BWD_DKV) == applications


def test_a_region_keeps_its_inputs_and_the_two_named_arrays(flash_route,
                                                             capsys):
    """What one block application saves for its backward, as
    ``print_saved_residuals`` lists it: x and the block's ten
    parameters, the kernel's output and its row statistics, and nothing
    else."""
    model, _ = _model()
    tokens, _labels = _tokens()
    model(nd.array(tokens))                 # loop_counts for the region
    block = model.blocks[0]
    region = decoder._recomputed(block)
    x = jnp.ones((2, T, TINY['hidden_size']), jnp.float32)
    capsys.readouterr()
    jax.ad_checkpoint.print_saved_residuals(
        lambda x: program.payload(region(nd.NDArray(x))), x)
    saved = [line.split(' ', 1)
             for line in capsys.readouterr().out.splitlines()]
    # the region's own arguments: x and ten parameters (the key is drawn
    # from by no op of this block)
    own = [line for line in saved if line[1].startswith(
        ('from the argument', 'from a constant'))]
    assert len(own) == 1 + len(block.collect_params()) == 11, saved
    assert [aval for aval, _ in own].count(f'f32[2,{T},64]') == 1
    # and the kernel's two results. lse is listed under its name; o,
    # which the block goes on to read, as the output of the no-op
    # reduce_precision jax.checkpoint puts behind a residual that the
    # forward uses too
    (lse, lse_from), (o, o_from) = sorted(
        line for line in saved if line not in own)
    assert o == f'f32[2,{T},64]' and '(flash_mha)' in o_from, saved
    assert lse == f"f32[2,{TINY['num_attention_heads']},1,{T}]"
    assert lse_from.startswith(f"named '{scopes.FLASH_LSE}'"), saved


def test_keeping_them_changes_no_value_and_no_gradient(flash_route,
                                                       monkeypatch):
    """The kept ``o`` and ``lse`` are the arrays the second run would
    have produced: loss and every gradient, bit for bit."""
    tokens, labels = _tokens()
    loss, grads = _grads(*_model(), tokens, labels)
    _without_policy(monkeypatch)
    plain_loss, plain_grads = _grads(*_model(), tokens, labels)
    assert loss == plain_loss
    assert set(grads) == set(plain_grads) and len(grads) == 25
    for name, grad in grads.items():
        assert onp.abs(grad).max() > 0, name
        onp.testing.assert_array_equal(grad, plain_grads[name], err_msg=name)


def _quantized(weights, bits=8):
    """Symmetric per-tensor rounding of every matrix to ``bits`` bits."""
    def q(w):
        if w.ndim < 2:
            return w
        scale = jnp.max(jnp.abs(w)) / (2 ** (bits - 1) - 1)
        return jnp.round(w / scale) * scale
    return {name: q(w) for name, w in weights.items()}


def _pass_left_out(model, tokens):
    logits, exit_p = _by_hand(model, tokens, passes=3)
    return (jnp.concatenate([logits, logits[-1:]]),
            jnp.concatenate([exit_p[:2], exit_p[2:] / 2, exit_p[2:] / 2]))


def _norm_outside_the_loop(model, tokens):
    return _by_hand(model, tokens, norm_in_loop=False)


def _post_norms_dropped(model, tokens):
    kept = [(blk.post_norm1, blk.post_norm2) for blk in model.blocks]
    for blk in model.blocks:
        blk.post_norm1 = blk.post_norm2 = None
    try:
        return _by_hand(model, tokens)
    finally:
        for blk, norms in zip(model.blocks, kept):
            blk.post_norm1, blk.post_norm2 = norms


def _survival_product_in_the_wrong_order(model, tokens):
    """p_t = l_t prod_{j>t} (1 - l_j): the product taken from the far
    end."""
    logits, _ = _by_hand(model, tokens)
    _, gates = (program.payload(o) for o in model(nd.array(tokens)))
    return logits, FAMILY.exit_probabilities(gates[::-1])[::-1]


def _six_bit_weights(model, tokens):
    return _reference(_quantized(program.weights_of(model), 6), tokens)


@pytest.mark.parametrize('fault', [
    _pass_left_out, _norm_outside_the_loop, _post_norms_dropped,
    _survival_product_in_the_wrong_order, _six_bit_weights],
    ids=lambda f: f.__name__.strip('_'))
def test_a_fault_fails_the_comparison(fault):
    """Each stand-in for a wrong program is outside a limit; the right
    one, by the same route, inside all of them. At this toy width sums
    are thirty times shorter than at the published one, so the nearest
    precision that reads outside is 6 bits (at published widths, on the
    chip, 8 bits read 0.059, 0.093, 0.121, 0.176 of the largest logit in
    passes 1 to 4 against limits of 0.03, 0.045, 0.06, 0.09: PERF.md
    section 6, PR 37)."""
    model, _ = _model()
    tokens, labels = _tokens()
    ref = _reference(program.weights_of(model), tokens)
    assert _verdict(*_by_hand(model, tokens), *ref, labels)['ok']
    verdict = _verdict(*fault(model, tokens), *ref, labels)
    assert not verdict['ok'], verdict
    if fault is _survival_product_in_the_wrong_order:
        assert max(verdict['logit_err']) < 1e-4
        assert verdict['exit_err'] > FAMILY.EXIT_TOLERANCE
    else:
        assert any(e > limit for e, limit in zip(
            verdict['logit_err'], FAMILY.LOGIT_TOLERANCES)), verdict


def test_bf16_is_inside_the_limits():
    """bf16 weights and activations against the float32 reference on the
    same rounded weights: the fourth pass reads worst, and inside."""
    model, _ = _model('bfloat16')
    tokens, labels = _tokens()
    verdict = _verdict(*_predicted(model, tokens),
                       *_reference(program.weights_of(model), tokens),
                       labels)
    assert verdict['ok'], verdict
    assert all(0 < e < limit / 2 for e, limit in zip(
        verdict['logit_err'], FAMILY.LOGIT_TOLERANCES)), verdict


def test_loop_counts_reads_the_last_trace():
    model, _ = _model()
    model(nd.array(_tokens()[0]))
    assert decoder.loop_counts == {'passes': 4, 'blocks': 2,
                                   'block_applications': 8,
                                   'checkpointed': 8,
                                   'kept': ('mxtpu_flash_out',
                                            'mxtpu_flash_lse')}


def test_the_objective_on_hand_made_gates():
    """looped_lm_loss against the formula written out, value and
    gradients, on states and gate logits that are not a model's; 2048
    positions make two chunks, and -1 labels are left out."""
    rng = onp.random.default_rng(1)
    states = jnp.asarray(rng.standard_normal((4, 2, 1024, 16)), jnp.float32)
    gates = jnp.asarray(rng.standard_normal((4, 2, 1024)), jnp.float32)
    head = jnp.asarray(rng.standard_normal((96, 16)) * 0.3, jnp.float32)
    labels = rng.integers(0, 96, (2, 1024)).astype(onp.int32)
    labels[:, -5:] = -1
    labels = jnp.asarray(labels)

    def ours(states, gates, head):
        return program.payload(looped_lm_loss(
            nd.NDArray(states), nd.NDArray(gates), nd.NDArray(head),
            nd.NDArray(labels), beta=0.3))

    def written_out(states, gates, head):
        lam = jax.nn.sigmoid(gates)
        p = jnp.stack([lam[0], lam[1] * (1 - lam[0]),
                       lam[2] * (1 - lam[0]) * (1 - lam[1]),
                       (1 - lam[0]) * (1 - lam[1]) * (1 - lam[2])])
        logp = jax.nn.log_softmax(states @ head.T, axis=-1)
        keep = labels >= 0
        ce = -jnp.take_along_axis(
            logp, jnp.where(keep, labels, 0)[None, ..., None], -1)[..., 0]
        per_position = jnp.sum(p * ce, 0) + 0.3 * jnp.sum(p * jnp.log(p), 0)
        return jnp.sum(per_position * keep) / jnp.sum(keep)
    with jax.default_matmul_precision('highest'):
        got = jax.value_and_grad(ours, argnums=(0, 1, 2))(states, gates, head)
        want = jax.value_and_grad(written_out, argnums=(0, 1, 2))(
            states, gates, head)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(got[1], want[1]):
        onp.testing.assert_allclose(a, b, rtol=2e-4, atol=1e-8)
    # the last pass's own gate is in no term
    assert float(jnp.max(jnp.abs(got[1][1][3]))) == 0.0


def test_a_block_has_one_feed_forward():
    with pytest.raises(ValueError, match='one feed-forward'):
        DecoderModel(64, 16, 2, 2, 8, [None], [None])
    with pytest.raises(ValueError, match='gate activation'):
        DecoderModel(64, 16, 2, 2, 8, [None], [None],
                     ffn=dict(width=32, activation='tanh'))


def _step(model, loss_fn):
    fast = dict(TINY, policy=dict(TINY['policy'],
                                  optimizer_params={'learning_rate': 1e-3}))
    return program.make_step(model, loss_fn, fast,
                             dict(CELL.traffic, mesh={'dp': 1}),
                             jax.devices()[:1])


def test_a_train_step_learns_and_its_program_carries_the_scopes():
    """ShardedTrainStep on the tiny preset, as the benchmark builds it:
    the loss falls, and the compiled step names the loop, its passes, the
    gated feed-forward, the gate, the head inside the loss, and what the
    checkpoint computes again."""
    model, loss_fn = _model('bfloat16')
    step = _step(model, loss_fn)
    tokens, labels = _tokens()
    losses = [float(step([tokens], [labels]).asscalar()) for _ in range(8)]
    assert losses[-1] < losses[0] - 0.1, losses
    names = set(re.findall(r'op_name="([^"]*)"',
                           step.compiled_program().as_text()))

    def some(*parts):
        pattern = re.compile('.*'.join(re.escape(p) for p in parts))
        return any(pattern.search(n) for n in names)
    fwd, bwd = f'{scopes.FWD_BWD}/jvp(', f'{scopes.FWD_BWD}/transpose(jvp('
    for t in range(4):
        assert some(fwd, f'/{scopes.UT_LOOP}/{scopes.UT_PASS}{t}/blocks/')
        assert some(fwd, f'/{scopes.UT_LOOP}/{scopes.UT_PASS}{t}/norm/')
    assert not some(f'{scopes.UT_PASS}4')
    assert some(fwd, scopes.UT_LOOP, 'decoderblock1/ffn/' + scopes.FFN_GLU,
                'gate_up')
    assert some(fwd, scopes.UT_LOOP, f'decoderblock0/{scopes.ATTN_FULL}/')
    assert some(fwd, scopes.UT_LOOP, f'decoderblock0/{scopes.ROPE}/')
    assert some(bwd, scopes.UT_LOOP, 'rematted_computation',
                scopes.FFN_GLU)
    assert some(fwd, f'/{scopes.EXIT_GATE}/')
    assert some(bwd, f'/{scopes.EXIT_GATE}/')
    assert some(f'jvp({scopes.LOSS})', scopes.LM_HEAD, 'dot_general')
    # the head, the gate and the objective lie outside the loop
    assert not any(scopes.UT_LOOP in n and (
        scopes.LM_HEAD in n or scopes.EXIT_GATE in n or scopes.LOSS in n)
        for n in names)
    assert decoder.loop_counts['block_applications'] == 8
