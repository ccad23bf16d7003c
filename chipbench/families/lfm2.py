"""Family ``lfm2``: causal-LM pretraining of an LFM2-style hybrid
sparse-expert decoder through ``mxnet_tpu.models.DecoderModel``, as one
chip of an expert-parallel group sees it. Pre-norm RMS blocks whose mixer
is a gated short convolution or grouped-query attention (per-head RMS
norm of q and k, rotary positions) by ``layer_types``; a dense SwiGLU
feed-forward in the leading layers and, after them, a sigmoid router with
a balancing bias over all the experts of the model, read from the
feed-forward's normalised input, and SwiGLU experts of which this chip
holds a share; the head tied to the embedding's rows held here; float32
next-token loss on every position.

A configuration of this family is the published ``config.json`` with the
cut written beside it (``deployment``); this file maps the keys onto the
program, shapes the token stream into next-token batches, counts the
operations and bytes a sample needs, and holds the float32 reference.
"""
import math

import jax
import jax.numpy as jnp
import numpy as onp

from chipbench import plain, program

# reference_check: top-k is discrete, and the program keeps the residual
# stream in bf16, so where a token's k-th and (k+1)-th choice scores
# (sigmoid of the router's logit plus the bias) lie within the program's
# rounding of each other the program may take the other expert: both
# choices are right. The reference is handed the program's choice and
# follows it where, and only where, its own gap between the k-th and the
# (k+1)-th choice score is under NEAR_TIE standard deviations of the
# token's choice scores; everywhere else it keeps its own top-k, and a
# different choice there is a fault (``clear_flips`` must be 0). Every
# position is compared. The two readings each limit lies between, at
# published widths and T = 8192 (my chip runs, PR 41; PERF.md section 6):
# the bf16 program flipped at gaps up to 0.028 to 0.033 and followed 11.8
# to 12.9 % of the positions over three seeds (logit error 0.0081 to
# 0.0087); the reference with its weights rounded to 8 bits, the nearest
# precision below, flipped at gaps up to 0.174, followed 52 % and took
# other experts at a clear gap at 578 positions (logit error 0.116): it
# fails all three. A router or an attention softmax rounded to bf16 alone
# moves the choices and the logits less than the bf16 program's own
# residual stream does (6.4 % followed, logit error 0.0001 and 0.0007),
# and no limit can fail them and pass the program.
NEAR_TIE = 0.06
# largest share of positions at which the program's choice may be followed
FOLLOWED_LIMIT = 0.2


def _layers(config):
    """[(mixer 'conv' | 'attention', feed-forward 'dense' | 'experts')] a
    layer."""
    kinds = {'conv': 'conv', 'full_attention': 'attention'}
    return [(kinds[t], 'dense' if i < config['num_dense_layers']
             else 'experts') for i, t in enumerate(config['layer_types'])]


def _experts(config):
    """(experts of the model, held here, first held, a token's)."""
    return (config['deployment']['experts_in_model'],
            config['num_experts'],
            config['deployment']['first_expert_here'],
            config['num_experts_per_tok'])


def build(config):
    """(model, loss_fn) as a user hands them to ShardedTrainStep. The
    caller has seeded ``mx.random``."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.decoder import DecoderModel, decoder_lm_loss
    layers = _layers(config)
    if len(layers) != config['num_hidden_layers']:
        raise ValueError("layer_types names another number of layers than "
                         "num_hidden_layers")
    if config['conv_bias'] or not config['norm_topk_prob'] \
            or not config['use_expert_bias'] \
            or config['routed_scaling_factor'] != 1 \
            or not config['tie_word_embeddings'] \
            or config['rope_parameters']['rope_type'] != 'default':
        raise ValueError("models/decoder.py builds convolutions without a "
                         "bias, a sigmoid router with a balancing bias and "
                         "the chosen scores renormalised and unscaled, "
                         "unscaled rotary positions and a tied head")
    total, held, first, top_k = _experts(config)
    theta = float(config['rope_parameters']['rope_theta'])
    model = DecoderModel(
        vocab_size=config['vocab_size'], hidden=config['hidden_size'],
        heads=config['num_attention_heads'],
        kv_heads=config['num_key_value_heads'], head_dim=config['head_dim'],
        windows=[None] * len(layers),
        rope_thetas=[theta if m == 'attention' else None for m, _ in layers],
        mixers=[m for m, _ in layers], ffn_kinds=[f for _, f in layers],
        experts=dict(width=config['moe_intermediate_size'], experts=total,
                     top_k=top_k, held=held, first_expert=first,
                     scoring='sigmoid', gate='silu',
                     bias_rate=config['assumed']['bias_rate']),
        ffn=dict(width=config['intermediate_size'], activation='silu'),
        epsilon=config['norm_eps'], qk_norm=True, router='pre_ffn',
        conv_taps=config['conv_L_cache'], tie_head=True)
    model.initialize(mx.init.Normal(config['assumed']['initializer_range']))
    model.cast(config['policy']['param_dtype'])
    _BUILT[:] = [model]

    def loss_fn(logits, labels):    # float32 inside, from the bf16 logits
        return decoder_lm_loss(logits, labels)

    return model, loss_fn


# the model ``build`` made last: :func:`counters` reads its bias
_BUILT = []


def reserved_ids(config):
    return ()


def make_batch(config, traffic, rng, zipf, n):
    """``n`` full sequences of ``seq_len``; the label of a position is the
    next token, and the last position has none (-1)."""
    tokens = zipf.draw(rng, (n, traffic['seq_len']))
    labels = onp.concatenate(
        [tokens[:, 1:], onp.full((n, 1), -1, tokens.dtype)], axis=1)
    return [tokens.astype(onp.int32)], [labels.astype(onp.int32)]


def _widths(config):
    h = config['hidden_size']
    hq, hkv, d = (config['num_attention_heads'],
                  config['num_key_value_heads'], config['head_dim'])
    return h, hq, hkv, d


def flops_per_sample(config, traffic):
    """Operations forward + backward need for one sequence: 6 per matmul
    weight per position and the attention matmuls on the causal triangle.
    Norms, rotary positions, the convolution's gates and taps, softmax,
    the routing's sort and gathers and the optimizer are no matmuls and
    are not counted; neither is the padding of the expert rows to their
    fixed size.

      conv layer   6 * h * (3 h + h)                      per token
      attention    6 * h * (Hq + 2 Hkv + Hq) * D          per token
                   + 12 * Hq * D * T (T + 1) / 2          per sequence
      dense        6 * 3 * h * f_dense                    per token
      router       6 * h * E                              per token
      experts      6 * k * held / E * 3 * h * f           per token
                   (a token's k assignments land here at the balanced
                   share: 4 * 8 / 64 = 0.5 expert passes)
      head         6 * h * V                              per token
    """
    t = traffic['seq_len']
    h, hq, hkv, d = _widths(config)
    total, held, _first, top_k = _experts(config)
    per_token, attention = 0, 0
    for mixer, ffn in _layers(config):
        if mixer == 'conv':
            per_token += h * 4 * h
        else:
            per_token += h * (2 * hq + 2 * hkv) * d
            attention += 12 * hq * d * t * (t + 1) // 2
        if ffn == 'dense':
            per_token += 3 * h * config['intermediate_size']
        else:
            per_token += h * total + top_k * held * 3 * h \
                * config['moe_intermediate_size'] // total
    per_token += h * config['vocab_size']
    return float(6 * per_token * t + attention)


def attention_cost(config, traffic):
    """Operations and bytes of one sequence's attention forward +
    backward, summed over the attention layers: the two forward and four
    backward matmul passes on the causal triangle; bytes, each operand
    once in bf16, q, o, and in the backward q, o, dO, dq of (T, Hq D), k,
    v and in the backward k, v, dk, dv of (T, Hkv D), the key/value heads
    once; and three float32 passes over the (T, Hq) row statistics."""
    t = traffic['seq_len']
    _h, hq, hkv, d = _widths(config)
    layers = sum(m == 'attention' for m, _ in _layers(config))
    return {'flops': float(layers * 12 * hq * d * t * (t + 1) // 2),
            'bytes': float(layers * (2 * t * 6 * (hq + hkv) * d
                                     + 3 * t * hq * 4))}


def expert_cost(config, traffic):
    """Operations and bytes of one sequence's grouped matmuls, forward
    and both backward products, at the balanced share of rows (T * k *
    held / E a layer): what the mathematics needs, not the fixed size the
    program pads to. Bytes, each operand once in bf16: the weights three
    times (read forward, read for dx, their gradient written); rows of
    width h six times (x twice, y written, dy twice, dx written), of 2 f
    three times (gate|up written, its cotangent read twice), of f three
    times (the hidden read twice, its cotangent written)."""
    t, h, f = (traffic['seq_len'], config['hidden_size'],
               config['moe_intermediate_size'])
    total, held, _first, top_k = _experts(config)
    layers = sum(kind == 'experts' for _, kind in _layers(config))
    rows = t * top_k * held // total
    weights = held * 3 * h * f
    return {'flops': float(layers * 3 * 2 * rows * 3 * h * f),
            'bytes': float(layers * 2 * (3 * weights
                                         + rows * (6 * h + 3 * 2 * f + 3 * f)))}


def short_conv_cost(config, traffic):
    """Bytes and operations of one sequence's gated short convolutions
    (the core between the projections, ops/shortconv.py), forward +
    backward, summed over the conv layers. Bytes, each operand once in
    bf16: forward the in-projection's (T, 3h) output read and the (T, h)
    result written; backward the (T, 3h) output and the (T, h) cotangent
    read and the (T, 3h) cotangent written: 11 h a position; the taps are
    negligible. Operations, each elementwise multiply or add once: 2 L + 1
    a channel forward (the gate B u, the L taps' multiply-adds, the gate
    C), 4 L + 3 backward; they never bound it."""
    t, h, taps = (traffic['seq_len'], config['hidden_size'],
                  config['conv_L_cache'])
    layers = sum(m == 'conv' for m, _ in _layers(config))
    return {'flops': float(layers * t * h * (6 * taps + 4)),
            'bytes': float(layers * 2 * t * h * 11)}


def tiny(config):
    """The rehearsal's preset: same structure, toy widths."""
    return dict(
        config, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, intermediate_size=96, moe_intermediate_size=32,
        num_experts=4, vocab_size=512,
        deployment=dict(config['deployment'], experts_in_model=16))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

QUERY_BLOCK = 256


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


def _head_rms_norm(x, gamma, heads, eps):
    n, t, width = x.shape
    return _rms_norm(x.reshape(n, t, heads, width // heads), gamma,
                     eps).reshape(n, t, width)


def _rope(x, heads, theta):
    """Rotate-half rotary positions on (N, T, heads * D)."""
    n, t, width = x.shape
    d = width // heads
    inv_freq = theta ** (-jnp.arange(d // 2, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x = x.reshape(n, t, heads, d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).reshape(n, t, width)


def _attention(q, k, v, heads, kv_heads):
    """Causal softmax attention, query head h on key/value head
    h // (heads / kv_heads); computed a block of queries at a time so
    that the (heads, T, T) scores never exist at once."""
    n, t, width = q.shape
    d = width // heads
    rep = heads // kv_heads
    block = math.gcd(t, QUERY_BLOCK)
    q = q.reshape(n, t, kv_heads, rep, d).transpose(0, 2, 3, 1, 4)
    k = k.reshape(n, t, kv_heads, d).transpose(0, 2, 1, 3)
    v = v.reshape(n, t, kv_heads, d).transpose(0, 2, 1, 3)
    j = jnp.arange(t)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=3)
        s = jnp.einsum('ngrqd,ngkd->ngrqk', qb, k) / math.sqrt(d)
        i = start + jnp.arange(block)[:, None]
        p = jax.nn.softmax(jnp.where(j <= i, s, -1e30), axis=-1)
        return jnp.einsum('ngrqk,ngkd->ngrqd', p, v)

    out = jax.lax.map(rows, jnp.arange(0, t, block))
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(n, t, width)


def _short_conv(a, w_in, taps, w_out):
    """[B | C | u] = a W_in; y = (C * z) W_out with z_t = sum_j w_j
    (B u)_{t - (L - 1) + j}, zeros before position 0."""
    b, c, u = jnp.split(a @ w_in.T, 3, axis=-1)
    v = b * u
    t, last = v.shape[1], taps.shape[0] - 1
    padded = jnp.pad(v, ((0, 0), (last, 0), (0, 0)))
    z = sum(taps[j] * padded[:, j:j + t] for j in range(last + 1))
    return (c * z) @ w_out.T


def _swiglu(x, w_gate_up, w_down):
    gate, up = jnp.split(x @ w_gate_up, 2, axis=-1)
    return (jax.nn.silu(gate) * up) @ w_down


def _experts_part(f, logits, bias, w_gate_up, w_down, first, top_k,
                  taken=None):
    """sum over the held experts among a token's top-k of w * expert(f),
    a dense loop over the held experts: s = sigmoid(logits), the k
    largest of s + bias chosen, their s renormalised. ``taken`` (N, T, k):
    the experts the program took; they are followed at the tokens whose
    own k-th and (k+1)-th choice scores are a near-tie. Also returned:
    which tokens were followed to another set of experts, which differ
    from the program at a clear gap, the rows routed to each held expert,
    and the widest gap at which the program took other experts."""
    held = w_gate_up.shape[0]
    scores = jax.nn.sigmoid(logits)
    choice = scores + bias
    _, ids = jax.lax.top_k(choice, top_k + 1)
    edge = jnp.take_along_axis(choice, ids[..., top_k - 1:], axis=-1)
    gap = (edge[..., 0] - edge[..., 1]) / jnp.std(choice, axis=-1)
    ids = ids[..., :top_k]
    followed = clear = jnp.zeros(gap.shape, bool)
    if taken is not None:
        differs = jnp.any(jnp.sort(taken, -1) != jnp.sort(ids, -1), axis=-1)
        followed, clear = differs & (gap < NEAR_TIE), differs & (gap >= NEAR_TIE)
        ids = jnp.where(followed[..., None], taken, ids)
        gap = jnp.where(differs, gap, 0.0)      # the gaps it differs at
    weights = jnp.take_along_axis(scores, ids, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.zeros_like(f)
    for e in range(held):
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        out = out + w_e[..., None] * _swiglu(f, w_gate_up[e], w_down[e])
    rows = jnp.stack([jnp.sum(ids == first + e) for e in range(held)])
    return out, followed, clear, rows, jnp.max(gap)


def reference_forward(w, config, tokens, taken=None):
    """LFM2 (Liquid AI; transformers' modeling_lfm2.py for the conv and
    attention layers, ISSUE 41 for the expert block), float32:

        a = rmsnorm(x; g1)
        conv:       [B | C | u] = a W_in;  x += (C * conv3(B * u)) W_out
        attention:  q = rope(rmsnorm_head(a Wq)), k = rope(rmsnorm_head(a
                    Wk)), v = a Wv;  x += attention(q, k, v) Wo  (causal,
                    grouped heads)
        f = rmsnorm(x; g2)
        dense:      x += (silu(f Wg) * (f Wu)) Wd
        experts:    s = sigmoid(f Wr^T); top-k of s + b; their s
                    renormalised; x += sum over held experts of
                    w * (silu(f Wg_e) * (f Wu_e)) Wd_e

    then a final RMS norm and the logits against the embedding's rows (the
    head is tied). Given the same share as the program: the held experts
    and the vocabulary slice; what the absent experts would add is left
    out. ``taken``: the expert layers' (N, T, k) experts the program took,
    followed at near-ties only (:func:`_experts_part`). Returns (logits,
    followed (expert layers, N, T), clear flips (expert layers, N, T),
    {rows routed to each held expert an expert layer, the widest gap the
    program took other experts at}). ``w`` maps the model's parameter
    names, less the model's own prefix, to float32 arrays; Dense weights
    are (out, in), the experts' (e, in, out), the taps (L, h)."""
    eps = config['norm_eps']
    h, heads, kv_heads, _d = _widths(config)
    theta = float(config['rope_parameters']['rope_theta'])
    _total, _held, first, top_k = _experts(config)
    x = w['embed_weight'][tokens]
    followed, clear, rows, widest = [], [], [], []
    for i, (mixer, ffn) in enumerate(_layers(config)):
        p = f'blocks_decoderblock{i}_'
        a = _rms_norm(x, w[p + 'norm1_gamma'], eps)
        if mixer == 'conv':
            x = x + _short_conv(a, w[p + 'conv_in_proj_weight'],
                                w[p + 'conv_conv_weight'],
                                w[p + 'conv_out_proj_weight'])
        else:
            q, k = (_rope(_head_rms_norm(a @ w[p + n + '_weight'].T,
                                         w[p + n + '_norm_gamma'], n_heads,
                                         eps), n_heads, theta)
                    for n, n_heads in (('q', heads), ('k', kv_heads)))
            v = a @ w[p + 'v_weight'].T
            x = x + _attention(q, k, v, heads, kv_heads) \
                @ w[p + 'o_weight'].T
        f = _rms_norm(x, w[p + 'norm2_gamma'], eps)
        if ffn == 'dense':
            x = x + _swiglu(f, w[p + 'ffn_gate_up_weight'].T,
                            w[p + 'ffn_down_weight'].T)
            continue
        part, *counted = _experts_part(
            f, f @ w[p + 'experts_router_weight'].T,
            w[p + 'experts_expert_bias'], w[p + 'experts_gate_up_weight'],
            w[p + 'experts_down_weight'], first, top_k,
            None if taken is None else taken[len(followed)])
        x = x + part
        for kept, value in zip((followed, clear, rows, widest), counted):
            kept.append(value)
    logits = _rms_norm(x, w['norm_gamma'], eps) @ w['embed_weight'].T
    return logits, jnp.stack(followed), jnp.stack(clear), \
        {'rows_routed_here': jnp.stack(rows),
         'widest_gap_flipped_at': jnp.max(jnp.stack(widest))}


def judge(got, ref, followed, clear, labels):
    """``plain.compare`` with its two tolerances on every position, and
    the two counts of the near-tie rule: the share of positions at which
    the program's experts were followed in some layer (at most
    ``FOLLOWED_LIMIT``) and the positions at which the program took other
    experts at a clear gap (none)."""
    got = jnp.asarray(got, jnp.float32)
    verdict = plain.compare(got, ref, plain.cross_entropy(got, labels),
                            plain.cross_entropy(ref, labels))
    share = float(jnp.mean(jnp.any(followed, axis=0)))
    clear_flips = int(jnp.sum(clear))
    verdict.update(
        ok=verdict['ok'] and share <= FOLLOWED_LIMIT and clear_flips == 0,
        followed_share=share, followed_limit=FOLLOWED_LIMIT,
        clear_flips=clear_flips, near_tie_threshold=NEAR_TIE)
    return verdict


def observed(model):
    """(block, read): ``model`` with each expert layer's router logits
    beside its logits, through Gluon's own forward hooks, so that the
    hybridized forward stays the program's; ``read(outputs, biases,
    top_k)`` gives (logits, the (N, T, k) experts an expert layer the
    program took: the k largest of sigmoid(logit) + bias)."""
    from mxnet_tpu.gluon.block import HybridBlock

    class Observed(HybridBlock):
        def __init__(self):
            super().__init__(prefix='')
            self.model = model
            self.seen = None
            for block in model.blocks:      # args: (f, router logits)
                if block.experts is not None:
                    block.experts.register_forward_hook(
                        lambda _block, args, _out: self.seen is not None
                        and self.seen.append(args[1]))

        def forward(self, tokens):
            self.seen = []
            try:
                return (self.model(tokens), *self.seen)
            finally:
                self.seen = None

    def read(outputs, biases, top_k):
        logits, *router = (program.payload(o) for o in outputs)
        taken = [jax.lax.top_k(jax.nn.sigmoid(r.astype(jnp.float32)) + b,
                               top_k)[1] for r, b in zip(router, biases)]
        return logits.astype(jnp.float32), taken
    return Observed(), read


def _biases(weights, config):
    return [weights[f'blocks_decoderblock{i}_experts_expert_bias']
            for i, (_m, ffn) in enumerate(_layers(config))
            if ffn == 'experts']


def routes():
    """The program's trace-time counts of the routes it built: the expert
    layers' (experts, held, top-k, rows, tile, scoring, gate) and the
    flash kernels' grouped builds by (kind, heads, key/value heads, head
    width)."""
    from mxnet_tpu.ops import moe, pallas_attention
    return {'expert_layers': {repr(k): v for k, v in moe.builds.items()},
            'flash_groups': {repr(k): v for k, v in
                             pallas_attention.group_builds.items()}}


def counters():
    """What the run's program holds besides the trace: its routes
    (:func:`routes`) and the largest magnitude of each expert layer's
    balancing bias as it stands, after the steps run so far."""
    out = routes()
    if _BUILT:
        out['expert_bias_max_abs'] = [
            float(jnp.max(jnp.abs(program.payload(
                block.experts.expert_bias.data()))))
            for block in _BUILT[0].blocks if block.experts is not None]
    return out


def reference_check(model, weights, config, traffic, rng, zipf):
    """The model's hybridized predict-mode forward against the reference
    on one sequence at the cell's length, every position, the reference
    following the program's experts at near-ties (:func:`judge`). Also
    returned: the load the sequence puts on the held experts a layer --
    the rows routed to each, the largest over the balanced share T k / E,
    and the rows the layer computes that no token reads (T k, the fixed
    size, less those routed here) -- the widest gap the program took
    other experts at, and the routes the program built."""
    from mxnet_tpu import nd
    (tokens,), (labels,) = make_batch(config, traffic, rng, zipf, 1)
    block, read = observed(model)
    block.hybridize()           # one compiled forward, the model's inline:
    model.hybridize(False)      # a hook sees values of the trace it runs in
    total, _held, _first, top_k = _experts(config)
    got, taken = read(block(nd.array(tokens)), _biases(weights, config),
                      top_k)
    with jax.default_matmul_precision('highest'):
        ref, followed, clear, seen = jax.jit(
            lambda w, x, taken: reference_forward(w, config, x, taken))(
            weights, jnp.asarray(tokens), taken)
    verdict = judge(got, ref, followed, clear, jnp.asarray(labels))
    rows = onp.asarray(seen['rows_routed_here'])
    balanced = tokens.size * top_k / total
    verdict.update(
        rows_routed_here=rows.tolist(),
        largest_expert_over_balanced=(rows.max(axis=1) / balanced).tolist(),
        rows_computed_unread=(tokens.size * top_k - rows.sum(axis=1)).tolist(),
        widest_gap_flipped_at=float(seen['widest_gap_flipped_at']),
        routes=routes())
    return verdict
