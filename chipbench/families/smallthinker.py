"""Family ``smallthinker``: causal-LM pretraining of a SmallThinker-style
sparse-expert decoder through ``mxnet_tpu.models.DecoderModel``, as one
chip of an expert-parallel group sees it. Pre-norm RMS blocks; a period of
one full-attention layer with no positional encoding and three
sliding-window layers with rotary positions; grouped-query heads; a
softmax router over all the experts of the model, read before attention;
ReGLU experts of which this chip holds a share; an untied head over the
vocabulary slice held here; float32 next-token loss on every position.

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
# stream in bf16, so where a token's k-th and (k+1)-th router logits lie
# within the program's rounding of each other the program may take the
# other expert -- both choices are right, and the logits that follow
# differ by 3 to 5 % of the largest logit; the positions that attend to a
# frequent token that flipped move with it. The reference is therefore
# handed the program's own choice of experts and follows it where, and
# only where, its own gap between the k-th and the (k+1)-th logit is under
# NEAR_TIE standard deviations of the token's logits (about 1.0 at
# initializer_range 0.02 and hidden 2560); everywhere else it keeps its
# own top-k, and a different choice there is a fault that counts
# (``clear_flips`` must be 0) and shows in the logits. Every position is
# compared. The two readings each limit lies between, at published widths
# and T = 8192 (my chip runs, PR 34; PERF.md section 6): the bf16 program
# flipped at gaps up to 0.020 to 0.027 and at 8.5 to 9.9 % of the
# positions over four seeds (logit error 0.0059 to 0.0065); the reference
# with its weights rounded to 8 bits, the nearest precision below, at gaps
# up to 0.152 and at 49 % (logit error 0.0499): it fails all three.
NEAR_TIE = 0.06
# largest share of positions at which the program's choice may be followed
FOLLOWED_LIMIT = 0.2


def _layers(config):
    """[(window or None, rope theta or None)] a layer."""
    return [(config['sliding_window_size'] if swa else None,
             float(config['rope_theta']) if rope else None)
            for swa, rope in zip(config['sliding_window_layout'],
                                 config['rope_layout'])]


def _experts(config):
    """(experts of the model, held here, first held, a token's)."""
    return (config['deployment']['experts_in_model'],
            config['moe_num_primary_experts'],
            config['deployment']['first_expert_here'],
            config['moe_num_active_primary_experts'])


def build(config):
    """(model, loss_fn) as a user hands them to ShardedTrainStep. The
    caller has seeded ``mx.random``."""
    import mxnet_tpu as mx
    from mxnet_tpu.models.decoder import DecoderModel, decoder_lm_loss
    layers = _layers(config)
    if len(layers) != config['num_hidden_layers']:
        raise ValueError("the layout lists name another number of layers "
                         "than num_hidden_layers")
    if not (config['moe_primary_router_apply_softmax']
            and config['norm_topk_prob']) or config['tie_word_embeddings'] \
            or config['rope_scaling'] is not None:
        raise ValueError("models/decoder.py routes by softmax with the "
                         "top-k renormalised, unscaled rotary positions "
                         "and an untied head")
    total, held, first, top_k = _experts(config)
    model = DecoderModel(
        vocab_size=config['vocab_size'], hidden=config['hidden_size'],
        heads=config['num_attention_heads'],
        kv_heads=config['num_key_value_heads'], head_dim=config['head_dim'],
        windows=[w for w, _ in layers], rope_thetas=[t for _, t in layers],
        experts=dict(width=config['moe_ffn_hidden_size'], experts=total,
                     top_k=top_k, held=held, first_expert=first),
        epsilon=config['rms_norm_eps'])
    model.initialize(mx.init.Normal(config['assumed']['initializer_range']))
    model.cast(config['policy']['param_dtype'])

    def loss_fn(logits, labels):    # float32 inside, from the bf16 logits
        return decoder_lm_loss(logits, labels)

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


def _band(t, window):
    """Score entries a causal layer computes on ``t`` positions: row i
    sees min(i + 1, window) keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def flops_per_sample(config, traffic):
    """Operations forward + backward need for one sequence: 6 per matmul
    weight per position and the attention matmuls on the causal band.
    Norms, rotary positions, softmax, the routing's sort and gathers and
    the optimizer are no matmuls and are not counted; neither is the
    padding of the expert rows to their fixed size.

      projections  6 * L * h * (Hq + 2 Hkv + Hq) * D      per token
      router       6 * L * h * E                          per token
      experts      6 * L * k * held / E * 3 * h * f       per token
                   (a token's k assignments land here at the balanced
                   share: 6 * 16 / 64 = 1.5 expert passes)
      attention    12 * Hq * D * entries                  per layer
                   (QK^T and PV, forward + backward; a full layer
                   T (T + 1) / 2 entries, a window layer its band)
      head         6 * h * V                              per token
    """
    t, h = traffic['seq_len'], config['hidden_size']
    hq, hkv, d = (config['num_attention_heads'],
                  config['num_key_value_heads'], config['head_dim'])
    total, held, _first, top_k = _experts(config)
    layers = _layers(config)
    per_token = len(layers) * (
        h * (2 * hq + 2 * hkv) * d + h * total
        + top_k * held * 3 * h * config['moe_ffn_hidden_size'] // total) \
        + h * config['vocab_size']
    attention = 12 * hq * d * sum(_band(t, w) for w, _ in layers)
    return float(6 * per_token * t + attention)


def attention_cost_by_kind(config, traffic):
    """{'window' | 'full': {'flops', 'bytes'}} of one sequence's attention
    forward + backward, summed over the layers of that kind. Operations:
    the two forward and four backward matmul passes on the band's entries
    (the backward kernels compute QK^T again, which recomputation is not
    counted). Bytes as PR 33 counts them, each operand once in bf16:
    q, o, and in the backward q, o, dO, dq are (T, Hq D); k, v and in the
    backward k, v, dk, dv are (T, Hkv D), the key/value heads once; and
    three float32 passes over the (T, Hq) row statistics."""
    t = traffic['seq_len']
    hq, hkv, d = (config['num_attention_heads'],
                  config['num_key_value_heads'], config['head_dim'])
    out = {'window': {'flops': 0.0, 'bytes': 0.0},
           'full': {'flops': 0.0, 'bytes': 0.0}}
    for window, _theta in _layers(config):
        kind = out['full' if window is None else 'window']
        kind['flops'] += float(12 * hq * d * _band(t, window))
        kind['bytes'] += float(2 * t * 6 * (hq + hkv) * d + 3 * t * hq * 4)
    return out


def attention_cost(config, traffic):
    kinds = attention_cost_by_kind(config, traffic).values()
    return {key: sum(kind[key] for kind in kinds)
            for key in ('flops', 'bytes')}


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
               config['moe_ffn_hidden_size'])
    total, held, _first, top_k = _experts(config)
    layers = config['num_hidden_layers']
    rows = t * top_k * held // total
    weights = held * 3 * h * f
    return {'flops': float(layers * 3 * 2 * rows * 3 * h * f),
            'bytes': float(layers * 2 * (3 * weights
                                         + rows * (6 * h + 3 * 2 * f + 3 * f)))}


def tiny(config):
    """The rehearsal's preset: same structure, toy widths; the window
    bites at the rehearsal's T = 128."""
    return dict(
        config, hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=4,
        moe_num_active_primary_experts=2, sliding_window_size=32,
        vocab_size=512,
        deployment=dict(config['deployment'], experts_in_model=8))


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

QUERY_BLOCK = 256


def _rms_norm(x, gamma, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * gamma


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


def _attention(q, k, v, heads, kv_heads, window):
    """Causal softmax attention, query head h on key/value head
    h // (heads / kv_heads), (i, j) kept iff 0 <= i - j < window; computed
    a block of queries at a time so that the (heads, T, T) scores never
    exist at once."""
    n, t, width = q.shape
    d = width // heads
    rep = heads // kv_heads
    block = math.gcd(t, QUERY_BLOCK)
    # (N, kv heads, rep, T, D) against (N, kv heads, T, D)
    q = q.reshape(n, t, kv_heads, rep, d).transpose(0, 2, 3, 1, 4)
    k = k.reshape(n, t, kv_heads, d).transpose(0, 2, 1, 3)
    v = v.reshape(n, t, kv_heads, d).transpose(0, 2, 1, 3)
    j = jnp.arange(t)

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=3)
        s = jnp.einsum('ngrqd,ngkd->ngrqk', qb, k) / math.sqrt(d)
        i = start + jnp.arange(block)[:, None]
        keep = j <= i
        if window is not None:
            keep &= i - j < window
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.einsum('ngrqk,ngkd->ngrqd', p, v)

    out = jax.lax.map(rows, jnp.arange(0, t, block))    # (blocks, n, g, r, b, d)
    return out.transpose(1, 0, 4, 2, 3, 5).reshape(n, t, width)


def _experts_part(b, logits, w_gate_up, w_down, first, top_k, taken=None):
    """sum over the held experts among a token's top-k of w * expert(b),
    a dense loop over the held experts. ``taken`` (N, T, k): the experts
    the program took; they are followed at the tokens whose own k-th and
    (k+1)-th logit are a near-tie. Also returned: which tokens were
    followed to another set of experts, which differ from the program at
    a clear gap, the rows routed here, and the widest gap at which the
    program took other experts."""
    held = w_gate_up.shape[0]
    probs = jax.nn.softmax(logits, axis=-1)
    _, ids = jax.lax.top_k(probs, top_k + 1)
    edge = jnp.take_along_axis(logits, ids[..., top_k - 1:], axis=-1)
    gap = (edge[..., 0] - edge[..., 1]) / jnp.std(logits, axis=-1)
    ids = ids[..., :top_k]
    followed = clear = jnp.zeros(gap.shape, bool)
    if taken is not None:
        differs = jnp.any(jnp.sort(taken, -1) != jnp.sort(ids, -1), axis=-1)
        followed, clear = differs & (gap < NEAR_TIE), differs & (gap >= NEAR_TIE)
        ids = jnp.where(followed[..., None], taken, ids)
        gap = jnp.where(differs, gap, 0.0)      # the gaps it differs at
    weights = jnp.take_along_axis(probs, ids, axis=-1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    out = jnp.zeros_like(b)
    for e in range(held):
        gate, up = jnp.split(b @ w_gate_up[e], 2, axis=-1)
        w_e = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        out = out + w_e[..., None] * ((jnp.maximum(gate, 0) * up) @ w_down[e])
    rows = jnp.sum((ids >= first) & (ids < first + held))
    return out, followed, clear, rows, jnp.max(gap)


def reference_forward(w, config, tokens, taken=None):
    """SmallThinker (PowerInfer, 2025; the catalog row's ``config`` and
    ``described_as``), float32, as ISSUE 34 writes the layer:

        a = rmsnorm(x; g1);  r = a @ Wr            (router, before attention)
        q, k, v = a @ Wq, a @ Wk, a @ Wv;  window layers: q, k = rope(q, k)
        x = x + attention(q, k, v) @ Wo            (grouped heads, causal,
                                                    window on window layers)
        b = rmsnorm(x; g2);  p = softmax(r);  top-k, renormalised
        x = x + sum over held experts of w * (relu(b Wg) * (b Wu)) Wd

    then a final RMS norm and the untied head over the vocabulary slice.
    Given the same share as the program: the held experts and the slice;
    what the absent experts would add is left out. ``taken``: a layer's
    (N, T, k) experts the program took, followed at near-ties only
    (:func:`_experts_part`). Returns (logits, followed (layers, N, T),
    clear flips (layers, N, T), {rows routed here a layer, the widest gap
    the program took other experts at}). ``w`` maps the
    model's parameter names, less the model's own prefix, to float32
    arrays; Dense weights are (out, in), the experts' (e, in, out)."""
    eps = config['rms_norm_eps']
    heads, kv_heads = (config['num_attention_heads'],
                       config['num_key_value_heads'])
    _total, _held, first, top_k = _experts(config)
    x = w['embed_weight'][tokens]
    followed, clear, rows, widest = [], [], [], []
    for i, (window, theta) in enumerate(_layers(config)):
        p = f'blocks_decoderblock{i}_'
        a = _rms_norm(x, w[p + 'norm1_gamma'], eps)
        logits = a @ w[p + 'experts_router_weight'].T
        q, k, v = (a @ w[p + name + '_weight'].T for name in 'qkv')
        if theta is not None:
            q, k = _rope(q, heads, theta), _rope(k, kv_heads, theta)
        x = x + _attention(q, k, v, heads, kv_heads, window) \
            @ w[p + 'o_weight'].T
        part, *counted = _experts_part(
            _rms_norm(x, w[p + 'norm2_gamma'], eps), logits,
            w[p + 'experts_gate_up_weight'], w[p + 'experts_down_weight'],
            first, top_k, None if taken is None else taken[i])
        x = x + part
        for kept, value in zip((followed, clear, rows, widest), counted):
            kept.append(value)
    logits = _rms_norm(x, w['norm_gamma'], eps) @ w['head_weight'].T
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
    """(block, read): ``model`` with each layer's router logits beside its
    logits, through Gluon's own forward hooks, so that the hybridized
    forward stays the program's; ``read(outputs, top_k)`` gives (logits,
    the (N, T, k) experts a layer the program took)."""
    from mxnet_tpu.gluon.block import HybridBlock

    class Observed(HybridBlock):
        def __init__(self):
            super().__init__(prefix='')
            self.model = model
            self.seen = None
            for block in model.blocks:      # args: (tokens' rows, logits)
                block.experts.register_forward_hook(
                    lambda _block, args, _out: self.seen is not None
                    and self.seen.append(args[1]))

        def forward(self, tokens):
            self.seen = []
            try:
                return (self.model(tokens), *self.seen)
            finally:
                self.seen = None

    def read(outputs, top_k):
        logits, *router = (program.payload(o) for o in outputs)
        taken = [jax.lax.top_k(jax.nn.softmax(r.astype(jnp.float32), -1),
                               top_k)[1] for r in router]
        return logits.astype(jnp.float32), taken
    return Observed(), read


def reference_check(model, weights, config, traffic, rng, zipf):
    """The model's hybridized predict-mode forward against the reference
    on one sequence at the cell's length (the cell's batch), every
    position, the reference following the program's experts at near-ties
    (:func:`judge`). Also returned: the rows routed here in each layer
    and the widest gap the program took other experts at."""
    from mxnet_tpu import nd
    (tokens,), (labels,) = make_batch(config, traffic, rng, zipf, 1)
    block, read = observed(model)
    block.hybridize()           # one compiled forward, the model's inline:
    model.hybridize(False)      # a hook sees values of the trace it runs in
    got, taken = read(block(nd.array(tokens)),
                      config['moe_num_active_primary_experts'])
    with jax.default_matmul_precision('highest'):
        ref, followed, clear, seen = jax.jit(
            lambda w, x, taken: reference_forward(w, config, x, taken))(
            weights, jnp.asarray(tokens), taken)
    verdict = judge(got, ref, followed, clear, jnp.asarray(labels))
    verdict.update({name: onp.asarray(value).tolist()
                    for name, value in seen.items()})
    return verdict
