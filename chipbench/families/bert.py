"""Family ``bert``: BERT pretraining (masked LM + next sentence) through
``mxnet_tpu.models.BertForPretraining``. Post-norm encoder, key-padding
mask from ``valid_length``, the MLM head on gathered positions only.

A configuration of this family is its published ``bert_config.json``; this
file maps those keys onto the program, shapes the token stream into
pretraining batches as ``create_pretraining_data.py`` does, counts the
operations a sample needs, and holds the float32 reference forward.
"""
import jax
import jax.numpy as jnp
import numpy as onp

from chipbench import plain, program


def _sizes(config):
    return (config['num_hidden_layers'], config['hidden_size'],
            config['num_attention_heads'], config['intermediate_size'],
            config['vocab_size'])


def build(config):
    """(model, loss_fn) as a user hands them to ShardedTrainStep. The
    caller has seeded ``mx.random``."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import BertForPretraining
    from mxnet_tpu.models.bert import bert_pretrain_loss
    if config['attention_probs_dropout_prob'] != \
            config['hidden_dropout_prob']:
        raise ValueError("models/bert.py takes one dropout rate for the "
                         "attention probabilities and the hidden states")
    if config['hidden_act'] != 'gelu':
        raise ValueError(f"hidden_act {config['hidden_act']!r}: "
                         f"models/bert.py computes the erf GELU")
    layers, hidden, heads, inner, vocab = _sizes(config)
    model = BertForPretraining(dict(
        vocab_size=vocab, hidden=hidden, layers=layers, heads=heads,
        intermediate=inner, max_len=config['max_position_embeddings'],
        type_vocab=config['type_vocab_size'],
        dropout=config['hidden_dropout_prob']))
    model.initialize(mx.init.Normal(config['initializer_range']))
    model.cast(config['policy']['param_dtype'])

    def loss_fn(mlm_logits, nsp_logits, labels, nsp_labels):
        return bert_pretrain_loss(mlm_logits.astype('float32'),
                                  nsp_logits.astype('float32'),
                                  labels, nsp_labels)

    return model, loss_fn


def reserved_ids(config):
    return (config['assumed']['mask_token_id'],
            config['assumed']['pad_token_id'])


def make_batch(config, traffic, rng, zipf, n, valid=None):
    """``n`` pretraining sequences of ``seq_len``: full length with
    probability 1 - short_seq_prob, else uniform in [2, T]; two segments;
    min(labelled_positions, round(masked_lm_prob * length)) positions
    replaced by the mask id and labelled with the token they hid, the
    other prediction slots at position 0 with label -1 (weight 0);
    next-sentence labels at random."""
    t, slots = traffic['seq_len'], traffic['labelled_positions']
    if valid is None:
        valid = onp.where(rng.random(n) < traffic['short_seq_prob'],
                          rng.integers(2, t + 1, n), t)
    valid = onp.asarray(valid, onp.int64)
    at = onp.arange(t)[None, :]
    tokens = onp.where(at < valid[:, None], zipf.draw(rng, (n, t)),
                       config['assumed']['pad_token_id'])
    split = rng.integers(1, valid)
    types = (at >= split[:, None]) & (at < valid[:, None])
    # the `slots` smallest random keys among the valid positions
    keys = onp.where(at < valid[:, None], rng.random((n, t)), 2.0)
    positions = onp.argsort(keys, axis=1)[:, :slots]
    wanted = onp.clip(onp.rint(traffic['masked_lm_prob'] * valid),
                      1, slots)
    labelled = onp.arange(slots)[None, :] < wanted[:, None]
    positions = onp.where(labelled, positions, 0)
    rows = onp.broadcast_to(onp.arange(n)[:, None], positions.shape)
    labels = onp.where(labelled, tokens[rows, positions], -1)
    tokens[rows[labelled], positions[labelled]] = \
        config['assumed']['mask_token_id']
    nsp = rng.integers(0, 2, n)
    i32 = onp.int32
    return ([tokens.astype(i32), types.astype(i32), valid.astype(i32),
             positions.astype(i32)], [labels.astype(i32), nsp.astype(i32)])


def flops_per_sample(config, traffic):
    """Operations forward + backward need for one sequence, counted as
    6 per matmul weight per position it is applied to (2 forward, 4
    backward), plus the attention matmuls. Embedding lookups, LayerNorm,
    GELU, softmax and the optimizer are not matmuls and are not counted;
    recomputation is not counted.

      encoder   6 * L * (4 h^2 + 2 h i)   per token     (qkv, proj, ffn1, ffn2)
      attention 12 * L * h * T            per token     (QK^T and PV, fwd+bwd)
      MLM head  6 * (h^2 + h V)           per prediction slot
      pooler    6 * (h^2 + 2 h)           per sequence  (pooler, NSP)
    """
    layers, h, _heads, inner, vocab = _sizes(config)
    t, slots = traffic['seq_len'], traffic['labelled_positions']
    encoder = 6 * layers * (4 * h * h + 2 * h * inner) * t
    attention = 12 * layers * h * t * t
    head = 6 * (h * h + h * vocab) * slots
    pooler = 6 * (h * h + 2 * h)
    return float(encoder + attention + head + pooler)


def attention_cost(config, traffic):
    """What one sequence's attention forward + backward needs through all
    layers: the four forward and eight backward T x T x D matmul passes
    (2 flops each), and one read or write of each operand in bf16 -- q, k,
    v, o forward; q, k, v, o, do, dq, dk, dv backward -- plus the float32
    row statistics written once and read by the two backward kernels."""
    layers, h, heads, _inner, _vocab = _sizes(config)
    t = traffic['seq_len']
    return {'flops': float(12 * layers * h * t * t),
            'bytes': float(layers * (12 * t * h * 2 + 3 * t * heads * 4))}


def tiny(config):
    """The rehearsal's preset: same structure, toy widths."""
    return dict(config, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, intermediate_size=128,
                vocab_size=512)


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_forward(w, config, tokens, types, valid, positions):
    """BERT as published (Devlin et al., 2018; google-research/bert
    modeling.py), float32, dropout off: sum of word, position and segment
    embeddings, LayerNorm; per layer self-attention under the key-padding
    mask, residual, LayerNorm, GELU feed-forward, residual, LayerNorm;
    tanh pooler on the first token; MLM transform (dense, GELU, LayerNorm)
    and decoder on the gathered positions. Departures, all following the
    program so that the two compute the same function: the erf GELU where
    modeling.py has the tanh approximation, a decoder matrix of its own
    rather than the tied embedding, and LayerNorm with the configuration's
    assumed epsilon. ``w`` maps the model's
    parameter names, less the model's own prefix, to float32 arrays."""
    layers, _h, heads, _inner, _vocab = _sizes(config)
    eps = config['assumed']['layer_norm_eps']
    t = tokens.shape[1]

    def ln(x, name):
        return plain.layer_norm(x, w[name + '_gamma'], w[name + '_beta'],
                                eps)

    def fc(x, name):
        return plain.dense(x, w[name + '_weight'], w[name + '_bias'])

    x = w['bertmodel0_word_embed_weight'][tokens] \
        + w['bertmodel0_pos_embed_weight'][:t][None] \
        + w['bertmodel0_type_embed_weight'][types]
    x = ln(x, 'bertmodel0_layernorm0')
    keep = jnp.arange(t)[None, :] < valid[:, None]
    for i in range(layers):
        p = f'bertmodel0_encoder_bertlayer{i}_'
        q, k, v = jnp.split(fc(x, p + 'bertselfattention0_qkv'), 3, axis=-1)
        a = plain.attention(q, k, v, heads, key_keep=keep)
        x = ln(x + fc(a, p + 'bertselfattention0_proj'), p + 'layernorm0')
        f = fc(plain.gelu(fc(x, p + 'ffn1')), p + 'ffn2')
        x = ln(x + f, p + 'layernorm1')
    pooled = jnp.tanh(fc(x[:, 0], 'bertmodel0_pooler'))
    picked = jnp.take_along_axis(x, positions[:, :, None], axis=1)
    mlm = fc(ln(plain.gelu(fc(picked, 'mlm_dense')), 'layernorm0'),
             'mlm_decoder')
    return mlm, fc(pooled, 'nsp')


def _loss(mlm, nsp, labels, nsp_labels):
    return plain.cross_entropy(mlm, labels) \
        + plain.cross_entropy(nsp, nsp_labels)


def reference_check(model, weights, config, traffic, rng, zipf):
    """The model's hybridized predict-mode forward against the reference
    on two sequences at the cell's length: one full, one padded to just
    over half, so a mask that is left out shows. The padded one is judged
    where the traffic holds padded sequences (short_seq_prob > 0) and is
    reported either way."""
    from mxnet_tpu import nd
    t = traffic['seq_len']
    inputs, labels = make_batch(config, traffic, rng, zipf, 2,
                                valid=[t, t // 2 + 3])
    got = [program.payload(x).astype(jnp.float32)
           for x in model(*[nd.array(x) for x in inputs])]
    with jax.default_matmul_precision('highest'):
        ref = jax.jit(lambda w, *xs: reference_forward(w, config, *xs))(
            weights, *[jnp.asarray(x) for x in inputs])
    labels = [jnp.asarray(x) for x in labels]

    def one(i):
        rows = slice(i, i + 1)
        return plain.compare(
            got[0][rows], ref[0][rows],
            _loss(got[0][rows], got[1][rows], *[x[rows] for x in labels]),
            _loss(ref[0][rows], ref[1][rows], *[x[rows] for x in labels]))

    full, padded = one(0), one(1)
    judged = traffic['short_seq_prob'] > 0
    return {'ok': full['ok'] and (padded['ok'] or not judged),
            'full': full, 'padded': padded, 'padded_is_judged': judged}
