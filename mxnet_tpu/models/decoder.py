"""Decoder-only language model from one block configured by its norm
placement, its positions, its head counts and its feed-forward kind
(ROADMAP D8), run once or several times over: the blocks of today's open
sparse-expert and looped decoders.

A block is RMS norm, grouped-query causal attention (rotary positions or
none, a sliding window or the whole prefix), residual, RMS norm, a
feed-forward, residual. The feed-forward is either a sparse ReGLU expert
layer (``experts=``: the router reads the block's normalised input,
before attention; the layer is one share of an expert-parallel group,
ops/moe.py: it is told which experts it holds and computes their part)
or a dense gated one (``ffn=``: act(x Wg) * (x Wu), then Wd).
``post_norms`` adds an RMS norm on each sub-layer's output before it
joins the residual (the sandwich form). Which layers are windowed and
which carry rotary positions is a per-layer pattern. The output head is a
matrix of its own (untied), over the rows of the vocabulary held here.

A model runs its stack once, or ``passes`` times on one set of weights
(a looped decoder: each pass reads its predecessor's normalised output),
and may carry an exit gate, a learned probability of leaving after each
pass; :func:`looped_lm_loss` is the objective over the passes' heads. A
looped model recomputes: every application of a block is a
``jax.checkpoint`` region that keeps its input and, on the Pallas route,
the flash forward's output and row statistics (``scopes.FLASH_KEPT``), so
the backward runs everything of a block again but that kernel.

models/gpt.py and models/bert.py keep their own blocks.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..gluon import nn
from ..gluon.block import HybridBlock
from .. import scopes as _scopes
from ..ops import attention as attn_ops
from ..ops import moe as moe_ops
from ..ops import nn as nn_ops
from ..ndarray.ndarray import NDArray, _invoke
from ..base import state as _state
from .. import random as _random


class RMSNorm(HybridBlock):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis."""

    def __init__(self, in_channels, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._epsilon = epsilon
        self.gamma = self.params.get('gamma', shape=(in_channels,),
                                     init='ones')

    def forward(self, x):
        with jax.named_scope(_scopes.RMSNORM):
            return _invoke(nn_ops.rms_norm, x, self.gamma.data(),
                           eps=self._epsilon)


class SparseExperts(HybridBlock):
    """The router over all ``experts`` of the model and the ``held``
    experts of this share, ``first_expert`` on: gate and up projections
    side by side as (held, hidden, 2 * width), down as (held, width,
    hidden), ReGLU between them, ``top_k`` experts a token."""

    def __init__(self, hidden, width, experts, top_k, held=None,
                 first_expert=0, **kwargs):
        super().__init__(**kwargs)
        held = experts if held is None else held
        self._top_k, self._first = top_k, first_expert
        with self.name_scope():
            self.router_weight = self.params.get(
                'router_weight', shape=(experts, hidden))
            self.gate_up_weight = self.params.get(
                'gate_up_weight', shape=(held, hidden, 2 * width))
            self.down_weight = self.params.get(
                'down_weight', shape=(held, width, hidden))

    def route(self, x):
        """The router's logits of ``x``, (N, T, experts), float32."""
        with jax.named_scope(_scopes.MOE_ROUTE):
            return _invoke(moe_ops.router_logits, x,
                           self.router_weight.data())

    def forward(self, x, router_logits):
        return _invoke(moe_ops.expert_layer, x, router_logits,
                       self.gate_up_weight.data(), self.down_weight.data(),
                       first_expert=self._first, top_k=self._top_k)


_GATES = {'silu': jax.nn.silu, 'relu': jax.nn.relu, 'gelu': jax.nn.gelu}


def _glu(gate_up, activation):
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return _GATES[activation](gate) * up


class GatedFFN(HybridBlock):
    """Dense gated feed-forward: (act(x Wg) * (x Wu)) Wd, no bias. Gate
    and up projections lie side by side in one (2 * width, hidden) matrix,
    so they are one matmul; down is (hidden, width)."""

    def __init__(self, hidden, width, activation='silu', **kwargs):
        super().__init__(**kwargs)
        if activation not in _GATES:
            raise ValueError(f"gate activation {activation!r}: one of "
                             f"{sorted(_GATES)}")
        self._activation = activation
        with self.name_scope():
            self.gate_up = nn.Dense(2 * width, use_bias=False, flatten=False,
                                    in_units=hidden, prefix='gate_up_')
            self.down = nn.Dense(hidden, use_bias=False, flatten=False,
                                 in_units=width, prefix='down_')

    def forward(self, x):
        with jax.named_scope(_scopes.FFN_GLU):
            return self.down(_invoke(_glu, self.gate_up(x),
                                     activation=self._activation))


class DecoderBlock(HybridBlock):
    """One block. ``window`` None: attention over the whole prefix;
    ``rope_theta`` None: no positional encoding. The feed-forward is
    ``experts`` (SparseExperts' keyword arguments) or ``ffn`` (GatedFFN's:
    width, activation), one of the two. ``post_norms``: an RMS norm of
    its own on the attention's and on the feed-forward's output, before
    the residual add."""

    def __init__(self, hidden, heads, kv_heads, head_dim, experts=None,
                 window=None, rope_theta=None, epsilon=1e-6, ffn=None,
                 post_norms=False, **kwargs):
        super().__init__(**kwargs)
        if (experts is None) == (ffn is None):
            raise ValueError("a block has one feed-forward: experts= or ffn=")
        self._heads, self._kv_heads = heads, kv_heads
        self._window, self._theta = window, rope_theta
        self.experts = self.ffn = self.post_norm1 = self.post_norm2 = None
        with self.name_scope():
            self.norm1 = RMSNorm(hidden, epsilon, prefix='norm1_')
            self.q, self.k, self.v = (
                nn.Dense(n * head_dim, use_bias=False, flatten=False,
                         in_units=hidden, prefix=name)
                for n, name in ((heads, 'q_'), (kv_heads, 'k_'),
                                (kv_heads, 'v_')))
            self.o = nn.Dense(hidden, use_bias=False, flatten=False,
                              in_units=heads * head_dim, prefix='o_')
            self.norm2 = RMSNorm(hidden, epsilon, prefix='norm2_')
            if experts is not None:
                self.experts = SparseExperts(hidden, prefix='experts_',
                                             **experts)
            else:
                self.ffn = GatedFFN(hidden, prefix='ffn_', **ffn)
            if post_norms:
                self.post_norm1 = RMSNorm(hidden, epsilon,
                                          prefix='post_norm1_')
                self.post_norm2 = RMSNorm(hidden, epsilon,
                                          prefix='post_norm2_')

    def forward(self, x):
        a = self.norm1(x)
        if self.experts is not None:
            router_logits = self.experts.route(a)   # read before attention
        q, k, v = self.q(a), self.k(a), self.v(a)
        if self._theta is not None:
            with jax.named_scope(_scopes.ROPE):
                q = _invoke(nn_ops.rotary_embedding, q,
                            num_heads=self._heads, theta=self._theta)
                k = _invoke(nn_ops.rotary_embedding, k,
                            num_heads=self._kv_heads, theta=self._theta)
        with jax.named_scope(_scopes.ATTN_FULL if self._window is None
                             else _scopes.ATTN_SWA):
            attn = _invoke(attn_ops.multi_head_attention, q, k, v, None,
                           num_heads=self._heads, causal=True,
                           num_kv_heads=self._kv_heads, window=self._window)
        attn = self.o(attn)
        if self.post_norm1 is not None:
            attn = self.post_norm1(attn)
        x = x + attn
        b = self.norm2(x)
        out = self.ffn(b) if self.experts is None \
            else self.experts(b, router_logits)
        if self.post_norm2 is not None:
            out = self.post_norm2(out)
        return x + out


class ExitGate(HybridBlock):
    """sigmoid^-1 of the probability of leaving after a pass: one linear
    map of the normalised state to a scalar, with a bias; float32 out of
    whatever the state's dtype is, (..., hidden) -> (...). Its ops lie
    under the block's own name, ``exit_gate`` (scopes.EXIT_GATE)."""

    def __init__(self, hidden, **kwargs):
        super().__init__(**kwargs)
        self.weight = self.params.get('weight', shape=(hidden,))
        self.bias = self.params.get('bias', shape=(1,), init='zeros')

    def forward(self, x):
        def logit(x, weight, bias):
            return jnp.einsum('...h,h->...', x, weight,
                              preferred_element_type=jnp.float32) \
                + bias.astype(jnp.float32)
        return _invoke(logit, x, self.weight.data(), self.bias.data())


# what the last trace of a looped forward did: its passes, the blocks of
# its stack, how often a block is applied, how many jax.checkpoint
# regions the program holds and the names of what each keeps beside its
# inputs. Read by tests, by no metric.
loop_counts = {}


def _recomputed(block):
    """``block`` as a function of x under ``jax.checkpoint``: one region
    of x, the block's parameters and a random key, of which the backward
    keeps the inputs and the two arrays the flash kernel's forward rule
    names (``scopes.FLASH_KEPT``: o, T * hidden * 2 bytes in bf16, and the
    float32 row statistics, a 64th of that at heads of 128) and runs the
    rest of the block forward again: norms, projections, rotary positions
    and the feed-forward, q, k and v for the kernel's backward among them,
    but not the kernel, the dearest recomputation a block has. On the XLA
    route nothing carries the names and the inputs are all. The parameters
    reach the block through their trace proxies and the key through a key
    provider of the region's own, as in CachedOp and ShardedTrainStep, so
    the block is called as it always is, draws the same bits both times,
    and leaves no tracer behind in the provider outside. Made once a
    forward and called once a pass: ``jax.checkpoint`` keeps a function's
    trace by its arguments' shapes, so the later passes reuse the first
    one's."""
    params = list(block.collect_params().values())

    def apply(x, key, *arrays):
        for p, a in zip(params, arrays):
            p._set_trace_proxy(NDArray(a))
        try:
            with _random.key_provider(_random.TraceKeyProvider(key)):
                return block(NDArray(x))._data
        finally:
            for p in params:
                p._clear_trace_proxy()
    region = jax.checkpoint(
        apply, policy=jax.checkpoint_policies.save_only_these_names(
            *_scopes.FLASH_KEPT))

    def call(x):
        loop_counts['checkpointed'] += 1
        return _invoke(region, x, NDArray(_random.next_key()),
                       *(p.data() for p in params))
    return call


class DecoderModel(HybridBlock):
    """forward(tokens) -> (N, T, vocab) logits.

    ``windows`` and ``rope_thetas`` give each layer's window and rotary
    base (None: the whole prefix, no positions); ``experts`` is
    SparseExperts' keyword arguments (width, experts, top_k, held,
    first_expert) or ``ffn`` GatedFFN's (width, activation);
    ``post_norms`` as DecoderBlock's.

    ``passes`` > 1 or ``exit_gate``: a looped model. The stack and the
    final norm are applied ``passes`` times, each pass to its
    predecessor's output, and the head and the gate read every pass's
    normalised state. forward(tokens) then returns two stacked arrays,
    the passes first: ``(logits (passes, N, T, vocab), gate logits
    (passes, N, T) float32)`` in predict mode, and under training
    ``(states (passes, N, T, hidden), gate logits, the head's weight)``,
    which is what :func:`looped_lm_loss` takes: the head is then read a
    chunk of positions at a time inside the loss, and no pass's whole
    logits exist. Without a gate the gate logits are zeros (leaving is
    as likely as staying after every pass)."""

    def __init__(self, vocab_size, hidden, heads, kv_heads, head_dim,
                 windows, rope_thetas, experts=None, epsilon=1e-6, ffn=None,
                 post_norms=False, passes=1, exit_gate=False, **kwargs):
        super().__init__(**kwargs)
        if len(windows) != len(rope_thetas):
            raise ValueError("one window and one rotary base a layer")
        self._passes = passes
        self.exit_gate = None
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden, prefix='embed_')
            self.blocks = nn.HybridSequential(prefix='blocks_')
            with self.blocks.name_scope():
                for window, theta in zip(windows, rope_thetas):
                    self.blocks.add(DecoderBlock(
                        hidden, heads, kv_heads, head_dim, experts,
                        window=window, rope_theta=theta, epsilon=epsilon,
                        ffn=ffn, post_norms=post_norms))
            self.norm = RMSNorm(hidden, epsilon, prefix='norm_')
            self.head = nn.Dense(vocab_size, use_bias=False, flatten=False,
                                 in_units=hidden, prefix='head_')
            if exit_gate:
                self.exit_gate = ExitGate(
                    hidden, prefix=_scopes.EXIT_GATE + '_')

    def forward(self, tokens):
        x = self.embed(tokens)
        if self._passes > 1 or self.exit_gate is not None:
            return self._looped(x)
        with self.blocks._trace_scope():     # iterated, never called
            for blk in self.blocks:
                x = blk(x)
        with jax.named_scope(_scopes.LM_HEAD):
            return self.head(self.norm(x))

    def _looped(self, x):
        loop_counts.update(
            passes=self._passes, blocks=len(self.blocks), checkpointed=0,
            block_applications=self._passes * len(self.blocks),
            kept=_scopes.FLASH_KEPT)
        regions = [_recomputed(blk) for blk in self.blocks]
        states = []
        with jax.named_scope(_scopes.UT_LOOP):
            for t in range(self._passes):
                with jax.named_scope(f'{_scopes.UT_PASS}{t}'):
                    with self.blocks._trace_scope():
                        for region in regions:
                            x = region(x)
                    x = self.norm(x)
                states.append(x)
        states = _invoke(lambda *xs: jnp.stack(xs), *states)
        gates = self.exit_gate(states) if self.exit_gate is not None \
            else _invoke(lambda s: jnp.zeros(s.shape[:-1], jnp.float32),
                         states)
        if _state.is_training:
            return states, gates, self.head.weight.data()
        with jax.named_scope(_scopes.LM_HEAD):
            return self.head(states), gates


# positions the loss takes at a time
_LOSS_ROWS = 1024


def _token_losses(logits, labels):
    """(float32 cross entropy of (..., rows, vocab) logits at (rows,)
    labels, which of the labels count): -1 pads, whose loss is the
    caller's to leave out."""
    logits = logits.astype(jnp.float32)
    valid = labels >= 0
    at = jnp.broadcast_to(jnp.where(valid, labels, 0), logits.shape[:-1])
    picked = jnp.take_along_axis(logits, at[..., None], axis=-1)[..., 0]
    return jax.nn.logsumexp(logits, axis=-1) - picked, valid


def _lm_loss(logits, labels):
    vocab = logits.shape[-1]
    labels = labels.reshape(-1)
    rows = math.gcd(labels.shape[0], _LOSS_ROWS)

    @jax.checkpoint
    def chunk(args):
        losses, valid = _token_losses(*args)
        return jnp.sum(losses * valid)
    sums = lax.map(chunk, (logits.reshape(-1, rows, vocab),
                           labels.reshape(-1, rows)))
    return jnp.sum(sums) / (jnp.sum(labels >= 0) + 1e-6)


def decoder_lm_loss(logits, labels):
    """Next-token cross entropy in float32, whatever the logits' dtype;
    labels = tokens shifted left, -1 pads: the mean of
    ``masked_cross_entropy`` (models/bert.py), taken ``_LOSS_ROWS``
    positions at a time, and only the logits as the head wrote them are
    kept for the backward, which computes a chunk's float32 softmax
    again. Over 8192 positions of a 37 984-row vocabulary slice the
    float32 log-probabilities are 1.2 GB, and XLA:TPU held them four
    times over in three layouts (PERF.md section 6, PR 34); a chunk's are
    156 MB."""
    return _invoke(_lm_loss, logits, labels)


def _exit_distribution(gate_logits):
    """log p of leaving after each pass, passes first: p1 = l1, pt = lt
    prod_{j<t} (1 - lj), and the last pass takes what is left, prod_{j<P}
    (1 - lj), whatever its own gate says; l = sigmoid(gate logit)."""
    stay = jnp.cumsum(jax.nn.log_sigmoid(-gate_logits), axis=0)
    stayed = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
    leave = jnp.concatenate([jax.nn.log_sigmoid(gate_logits[:-1]),
                             jnp.zeros_like(stay[:1])])
    return stayed + leave


def _looped_loss(states, gate_logits, head, labels, beta):
    passes, hidden = states.shape[0], states.shape[-1]
    labels = labels.reshape(-1)
    rows = math.gcd(labels.shape[0], _LOSS_ROWS)

    def by_chunk(x, *tail):     # (passes, positions, ...) -> chunks first
        return jnp.moveaxis(x.reshape(passes, -1, rows, *tail), 1, 0)

    @jax.checkpoint
    def chunk(args):
        states, gate_logits, labels = args
        with jax.named_scope(_scopes.LM_HEAD):
            logits = jnp.einsum('prh,vh->prv', states, head)
        task, valid = _token_losses(logits, labels)
        log_p = _exit_distribution(gate_logits.astype(jnp.float32))
        p = jnp.exp(log_p)
        expected = jnp.sum(p * task, axis=0)
        entropy = -jnp.sum(p * log_p, axis=0)
        return jnp.sum((expected - beta * entropy) * valid)
    sums = lax.map(chunk, (by_chunk(states, hidden), by_chunk(gate_logits),
                           labels.reshape(-1, rows)))
    return jnp.sum(sums) / (jnp.sum(labels >= 0) + 1e-6)


def looped_lm_loss(states, gate_logits, head_weight, labels, beta=0.1):
    """The objective of a looped decoder with an exit gate (Ouro's first
    training stage, arXiv:2510.25741): at every labelled position the
    expected next-token cross entropy under the exit distribution the gate
    defines (:func:`_exit_distribution`), less ``beta`` times that
    distribution's entropy; the mean over the labelled positions, float32.

    Takes what a looped DecoderModel returns under training: the passes'
    normalised states (passes, N, T, hidden), the gate logits (passes, N,
    T) and the head's (vocab, hidden) weight; labels = tokens shifted
    left, -1 pads. ``_LOSS_ROWS`` positions at a time, every pass's head
    inside the chunk and the chunk computed again in the backward, so what
    exists at once of the (passes, N * T, vocab) logits is one chunk's:
    four passes of 4096 positions over 49 152 rows would be 1.5 GiB in
    bf16, as much again for their cotangents and twice that in float32."""
    return _invoke(_looped_loss, states, gate_logits, head_weight, labels,
                   beta=beta)
