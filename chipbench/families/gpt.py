"""Family ``gpt``: GPT-2 style causal language model pretraining through
``mxnet_tpu.models.gpt.GPTModel``. Pre-norm blocks, the causal kernel path
with no mask tensor, the output head tied to the token embedding and run
over the whole vocabulary on every position.

A configuration of this family is its published ``config.json`` (the
``n_*`` keys); this file maps them onto the program, shapes the token
stream into next-token batches, counts the operations a sample needs, and
holds the float32 reference forward.
"""
import jax
import jax.numpy as jnp
import numpy as onp

from chipbench import plain, program


def _sizes(config):
    return (config['n_layer'], config['n_embd'], config['n_head'],
            config['assumed']['n_inner'], config['vocab_size'])


def build(config):
    """(model, loss_fn) as a user hands them to ShardedTrainStep. The
    caller has seeded ``mx.random``."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.gpt import GPTModel, gpt_lm_loss
    layers, hidden, heads, inner, vocab = _sizes(config)
    if len({config['attn_pdrop'], config['embd_pdrop'],
            config['resid_pdrop']}) != 1:
        raise ValueError("models/gpt.py takes one dropout rate")
    if config['activation_function'] != 'gelu' or inner != 4 * hidden:
        raise ValueError("models/gpt.py computes the erf GELU on a "
                         "feed-forward of 4 * n_embd")
    model = GPTModel(vocab_size=vocab, hidden=hidden, layers=layers,
                     heads=heads, max_len=config['n_positions'],
                     dropout=config['resid_pdrop'])
    model.initialize(mx.init.Normal(config['initializer_range']))
    model.cast(config['policy']['param_dtype'])

    def loss_fn(logits, labels):
        return gpt_lm_loss(logits.astype('float32'), labels)

    return model, loss_fn


def reserved_ids(config):
    return ()


def make_batch(config, traffic, rng, zipf, n):
    """``n`` full sequences of ``seq_len``; the label of a position is the
    next token, and the last position has none (-1)."""
    tokens = zipf.draw(rng, (n, traffic['seq_len']))
    labels = onp.concatenate(
        [tokens[:, 1:], onp.full((n, 1), -1, tokens.dtype)], axis=1)
    return [tokens.astype(onp.int32)], [labels.astype(onp.int32)]


def flops_per_sample(config, traffic):
    """Operations forward + backward need for one sequence: 6 per matmul
    weight per position (2 forward, 4 backward) and the attention matmuls
    on the lower triangle only, which is all a causal model needs.
    Embedding lookups, LayerNorm, GELU, softmax and the optimizer are not
    matmuls and are not counted; recomputation is not counted.

      blocks    6 * L * (4 h^2 + 2 h i)   per token   (qkv, proj, ffn1, ffn2)
      attention 12 * L * h * (T + 1) / 2  per token   (QK^T and PV, fwd+bwd)
      head      6 * h * V                 per token   (the tied embedding)
    """
    layers, h, _heads, inner, vocab = _sizes(config)
    t = traffic['seq_len']
    blocks = 6 * layers * (4 * h * h + 2 * h * inner) * t
    attention = 12 * layers * h * t * (t + 1) // 2
    head = 6 * h * vocab * t
    return float(blocks + attention + head)


def attention_cost(config, traffic):
    """As the bert family's, with the matmul passes on the T (T + 1) / 2
    score entries at or under the diagonal only; every operand is still
    read or written once."""
    layers, h, heads, _inner, _vocab = _sizes(config)
    t = traffic['seq_len']
    return {'flops': float(12 * layers * h * t * (t + 1) // 2),
            'bytes': float(layers * (12 * t * h * 2 + 3 * t * heads * 4))}


def tiny(config):
    """The rehearsal's preset: same structure, toy widths."""
    return dict(config, n_embd=64, n_layer=2, n_head=4, vocab_size=512,
                assumed=dict(config['assumed'], n_inner=256))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def reference_forward(w, config, tokens):
    """GPT-2 (Radford et al., 2019; openai/gpt-2 model.py), float32,
    dropout off: word + position embeddings; per block LayerNorm, causal
    self-attention, residual, LayerNorm, GELU feed-forward, residual; a
    final LayerNorm; logits against the transposed token embedding. One
    departure, following the program: the erf GELU where model.py has the
    tanh approximation (the configuration's ``reduced`` says so). ``w``
    maps the model's parameter names, less the model's own prefix, to
    float32 arrays."""
    layers, _h, heads, _inner, _vocab = _sizes(config)
    eps = config['layer_norm_epsilon']
    t = tokens.shape[1]

    def ln(x, name):
        return plain.layer_norm(x, w[name + '_gamma'], w[name + '_beta'],
                                eps)

    def fc(x, name):
        return plain.dense(x, w[name + '_weight'], w[name + '_bias'])

    x = w['word_embed_weight'][tokens] + w['pos_embed_weight'][:t][None]
    for i in range(layers):
        p = f'blocks_gptblock{i}_'
        q, k, v = jnp.split(fc(ln(x, p + 'layernorm0'), p + 'qkv'), 3,
                            axis=-1)
        x = x + fc(plain.attention(q, k, v, heads, causal=True), p + 'proj')
        x = x + fc(plain.gelu(fc(ln(x, p + 'layernorm1'), p + 'ffn1')),
                   p + 'ffn2')
    return ln(x, 'layernorm0') @ w['word_embed_weight'].T


def reference_check(model, weights, config, traffic, rng, zipf):
    """The model's hybridized predict-mode forward against the reference
    on two sequences at the cell's length, every position."""
    from mxnet_tpu import nd
    (tokens,), (labels,) = make_batch(config, traffic, rng, zipf, 2)
    got = program.payload(model(nd.array(tokens))).astype(jnp.float32)
    with jax.default_matmul_precision('highest'):
        ref = jax.jit(lambda w, x: reference_forward(w, config, x))(
            weights, jnp.asarray(tokens))
    labels = jnp.asarray(labels)
    return plain.compare(got, ref, plain.cross_entropy(got, labels),
                         plain.cross_entropy(ref, labels))
