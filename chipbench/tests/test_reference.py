"""The float32 references against the models at a small size, and how
much a fault costs against the tolerance that judges them."""
import jax
import jax.numpy as jnp
import numpy as onp
import pytest

from chipbench import manifest, plain, program, tokens


def small(cell_name, seq_len=128, **overrides):
    cell = manifest.resolve(cell_name)
    config = dict(cell.family.tiny(cell.config), **overrides)
    traffic = dict(cell.traffic, seq_len=seq_len)
    program.seed(7)
    model, _loss = cell.family.build(config)
    model.hybridize()
    rng, zipf = tokens.source(cell.family, config, traffic, 7, 1)
    return cell.family, config, traffic, model, rng, zipf


@pytest.mark.parametrize('cell_name', ['bert_base.t128',
                                       'gpt2_small.t1024'])
def test_model_agrees_with_its_reference(cell_name):
    family, config, traffic, model, rng, zipf = small(cell_name)
    got = family.reference_check(model, program.weights_of(model), config,
                                 traffic, rng, zipf)
    assert got['ok'], got
    worst = got.get('full', got)
    assert worst['logit_err'] < plain.LOGIT_TOLERANCE / 2
    assert worst['loss_err'] < plain.LOSS_TOLERANCE / 2


def bert_reference(scale=8.0, seq_len=128):
    """BERT's reference at toy widths with weights large enough for the
    attention to be peaked, as a trained model's is."""
    family, config, traffic, model, rng, zipf = small('bert_base.t128',
                                                      seq_len)
    weights = {k: v * scale if k.endswith('_weight') else v
               for k, v in program.weights_of(model).items()}
    inputs, _labels = family.make_batch(
        config, traffic, rng, zipf, 2, valid=[seq_len, seq_len // 2 + 3])
    inputs = [jnp.asarray(x) for x in inputs]

    def forward(w, cfg, xs):
        with jax.default_matmul_precision('highest'):
            return family.reference_forward(w, cfg, *xs)[0]

    return forward, weights, config, inputs


def relative(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def test_a_dropped_padding_mask_is_far_outside_the_tolerance():
    forward, weights, config, inputs = bert_reference()
    right = forward(weights, config, inputs)
    unmasked = list(inputs)
    unmasked[2] = jnp.full_like(inputs[2], inputs[0].shape[1])
    wrong = forward(weights, config, unmasked)
    assert relative(wrong[1:], right[1:]) > 3 * plain.LOGIT_TOLERANCE
    # the full-length sequence has no padding to mask
    assert relative(wrong[:1], right[:1]) < 1e-5


def test_a_layer_left_out_is_far_outside_the_tolerance():
    forward, weights, config, inputs = bert_reference()
    right = forward(weights, config, inputs)
    wrong = forward(weights, dict(config, num_hidden_layers=1), inputs)
    assert relative(wrong, right) > 3 * plain.LOGIT_TOLERANCE


def test_eight_bit_weights_are_outside_the_tolerance_and_bf16_inside():
    forward, weights, config, inputs = bert_reference()
    right = forward(weights, config, inputs)

    def rounded(dtype):
        return {k: v.astype(dtype).astype(jnp.float32)
                for k, v in weights.items()}

    assert relative(forward(rounded(jnp.float8_e4m3fn), config, inputs),
                    right) > plain.LOGIT_TOLERANCE
    assert relative(forward(rounded(jnp.bfloat16), config, inputs),
                    right) < plain.LOGIT_TOLERANCE


def test_compare_refuses_what_is_not_finite():
    ref = jnp.ones((2, 3))
    assert plain.compare(ref, ref, 1.0, 1.0)['ok']
    assert not plain.compare(ref.at[0, 0].set(jnp.nan), ref, 1.0, 1.0)['ok']
    assert not plain.compare(ref, ref, float('inf'), 1.0)['ok']
    assert not plain.compare(ref * 1.05, ref, 1.0, 1.0)['ok']
    assert not plain.compare(ref, ref, 1.05, 1.0)['ok']


def test_batches_follow_the_traffic_file():
    cell = manifest.resolve('bert_base.t512')
    rng = onp.random.default_rng(3)
    traffic = dict(cell.traffic, short_seq_prob=0.5)
    zipf = tokens.Zipf(rng, cell.config['vocab_size'], 1.0,
                       cell.family.reserved_ids(cell.config))
    (tok, types, valid, pos), (labels, nsp) = cell.family.make_batch(
        cell.config, traffic, rng, zipf, 64)
    t, slots = traffic['seq_len'], traffic['labelled_positions']
    assert tok.shape == (64, t) and pos.shape == labels.shape == (64, slots)
    assert {a.dtype for a in (tok, types, valid, pos, labels, nsp)} \
        == {onp.dtype('int32')}
    assert (valid == t).any() and (valid < t).any() and valid.min() >= 2
    mask_id = cell.config['assumed']['mask_token_id']
    for row in range(64):
        n = int(valid[row])
        labelled = labels[row] >= 0
        assert labelled.sum() == min(slots, max(1, round(0.15 * n)))
        assert (pos[row][labelled] < n).all()
        assert len(set(pos[row][labelled])) == labelled.sum()
        assert (tok[row, pos[row][labelled]] == mask_id).all()
        assert (tok[row, n:] == 0).all() and (types[row, n:] == 0).all()
        assert (labels[row][labelled] != mask_id).all()
    # Zipf: the commonest token is far commoner than the median one
    counts = onp.bincount(tok[tok > 0], minlength=30522)
    assert counts.max() > 50 * max(1, onp.median(counts[counts > 0]))

    gpt = manifest.resolve('gpt2_small.t1024')
    (tok,), (lab,) = gpt.family.make_batch(gpt.config, gpt.traffic, rng,
                                           tokens.Zipf(rng, 50257, 1.0), 3)
    assert (lab[:, :-1] == tok[:, 1:]).all() and (lab[:, -1] == -1).all()


def test_the_same_seed_gives_the_same_ring():
    cell = manifest.resolve('bert_base.t128')
    traffic = dict(cell.traffic, ring_batches=2)
    a = tokens.make_ring(cell.family, cell.config, traffic, 11, 4)
    b = tokens.make_ring(cell.family, cell.config, traffic, 11, 4)
    c = tokens.make_ring(cell.family, cell.config, traffic, 12, 4)
    assert all((x == y).all() for x, y in zip(a[0][0], b[0][0]))
    assert not (a[0][0][0] == c[0][0][0]).all()
    assert not (a[0][0][0] == a[1][0][0]).all()
