"""Decoder-only language model from one block configured by its norm, its
positions, its head counts and its feed-forward kind (ROADMAP D8): the
pre-norm blocks of today's open sparse-expert decoders.

A block is RMS norm, grouped-query causal attention (rotary positions or
none, a sliding window or the whole prefix), residual, RMS norm, a
sparse ReGLU expert layer, residual. The router reads the block's
normalised input, before attention. Which layers are windowed and which
carry rotary positions is a per-layer pattern; the expert layer is one
share of an expert-parallel group (ops/moe.py): it is told which experts
it holds and computes their part. The output head is a matrix of its own
(untied), over the rows of the vocabulary held here.

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
from ..ndarray.ndarray import _invoke


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


class DecoderBlock(HybridBlock):
    """One pre-norm block. ``window`` None: attention over the whole
    prefix; ``rope_theta`` None: no positional encoding."""

    def __init__(self, hidden, heads, kv_heads, head_dim, experts,
                 window=None, rope_theta=None, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        self._heads, self._kv_heads = heads, kv_heads
        self._window, self._theta = window, rope_theta
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
            self.experts = SparseExperts(hidden, prefix='experts_', **experts)

    def forward(self, x):
        a = self.norm1(x)
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
        x = x + self.o(attn)
        return x + self.experts(self.norm2(x), router_logits)


class DecoderModel(HybridBlock):
    """forward(tokens) -> (N, T, vocab) logits.

    ``windows`` and ``rope_thetas`` give each layer's window and rotary
    base (None: the whole prefix, no positions); ``experts`` is
    SparseExperts' keyword arguments (width, experts, top_k, held,
    first_expert)."""

    def __init__(self, vocab_size, hidden, heads, kv_heads, head_dim,
                 windows, rope_thetas, experts, epsilon=1e-6, **kwargs):
        super().__init__(**kwargs)
        if len(windows) != len(rope_thetas):
            raise ValueError("one window and one rotary base a layer")
        with self.name_scope():
            self.embed = nn.Embedding(vocab_size, hidden, prefix='embed_')
            self.blocks = nn.HybridSequential(prefix='blocks_')
            with self.blocks.name_scope():
                for window, theta in zip(windows, rope_thetas):
                    self.blocks.add(DecoderBlock(
                        hidden, heads, kv_heads, head_dim, experts,
                        window=window, rope_theta=theta, epsilon=epsilon))
            self.norm = RMSNorm(hidden, epsilon, prefix='norm_')
            self.head = nn.Dense(vocab_size, use_bias=False, flatten=False,
                                 in_units=hidden, prefix='head_')

    def forward(self, tokens):
        x = self.embed(tokens)
        with self.blocks._trace_scope():     # iterated, never called
            for blk in self.blocks:
                x = blk(x)
        with jax.named_scope(_scopes.LM_HEAD):
            return self.head(self.norm(x))


# positions the loss takes at a time
_LOSS_ROWS = 1024


def _lm_loss(logits, labels):
    vocab = logits.shape[-1]
    labels = labels.reshape(-1)
    rows = math.gcd(labels.shape[0], _LOSS_ROWS)

    @jax.checkpoint
    def chunk(args):
        logits, labels = args
        logits = logits.astype(jnp.float32)
        valid = labels >= 0
        picked = jnp.take_along_axis(
            logits, jnp.where(valid, labels, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - picked) * valid)
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
