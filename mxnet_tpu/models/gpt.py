"""GPT-style decoder-only causal language model.

The autoregressive counterpart of the BERT flagship: pre-norm transformer
decoder blocks over the fused `self_attention` op with
`causal=True`, which routes through the Pallas flash kernel's causal path
on TPU (ops/pallas_attention.py) — no (T, T) mask tensor is ever
materialised. Weight-tied output head (standard GPT recipe).

Ref: the reference ships encoder-style attention kernels
(src/operator/contrib/transformer.cc) and GluonNLP built GPT-2 on top of
them; here the causal variant is first-class.
"""
from __future__ import annotations

import jax

from ..gluon import nn
from ..gluon.block import HybridBlock
from .. import ndarray as nd
from .. import scopes as _scopes
from ..ops import attention as attn_ops
from ..ndarray.ndarray import _invoke
from .bert import masked_cross_entropy


def gpt2_small_config():
    return dict(vocab_size=50257, hidden=768, layers=12, heads=12,
                max_len=1024)


class GPTBlock(HybridBlock):
    def __init__(self, hidden, heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._heads = heads
        self._attn_dropout = dropout
        with self.name_scope():
            self.ln1 = nn.LayerNorm(in_channels=hidden)
            self.qkv = nn.Dense(3 * hidden, flatten=False,
                                in_units=hidden, prefix='qkv_')
            self.proj = nn.Dense(hidden, flatten=False, in_units=hidden,
                                 prefix='proj_')
            self.ln2 = nn.LayerNorm(in_channels=hidden)
            self.ffn1 = nn.Dense(4 * hidden, flatten=False,
                                 in_units=hidden, prefix='ffn1_')
            self.ffn2 = nn.Dense(hidden, flatten=False,
                                 in_units=4 * hidden, prefix='ffn2_')
            self.dropout = nn.Dropout(dropout)

    def forward(self, x):
        # pre-norm residual blocks (GPT-2 recipe)
        h = self.ln1(x)
        qkv = self.qkv(h)
        # no block of its own: a plain scope names it in a device trace
        with jax.named_scope(_scopes.ATTN_CORE):
            attn = _invoke(attn_ops.self_attention, qkv, None,
                           num_heads=self._heads,
                           dropout_p=self._attn_dropout, causal=True)
        x = x + self.dropout(self.proj(attn))
        h = nd.activation(self.ffn1(self.ln2(x)), act_type='gelu')
        return x + self.dropout(self.ffn2(h))


class GPTModel(HybridBlock):
    """Decoder-only LM. forward(tokens) -> (N, T, vocab) logits with the
    output projection tied to the token embedding."""

    def __init__(self, vocab_size=50257, hidden=768, layers=12, heads=12,
                 max_len=1024, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._cfg = dict(vocab_size=vocab_size, hidden=hidden,
                         layers=layers, heads=heads, max_len=max_len)
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, hidden,
                                           prefix='word_embed_')
            self.pos_embed = nn.Embedding(max_len, hidden,
                                          prefix='pos_embed_')
            self.embed_dropout = nn.Dropout(dropout)
            self.blocks = nn.HybridSequential(prefix='blocks_')
            with self.blocks.name_scope():
                for _ in range(layers):
                    self.blocks.add(GPTBlock(hidden, heads, dropout))
            self.ln_f = nn.LayerNorm(in_channels=hidden)

    def forward(self, tokens):
        T = tokens.shape[1]
        pos = nd.arange(0, T, dtype='int32').reshape(1, T)
        x = self.embed_dropout(self.word_embed(tokens)
                               + self.pos_embed(pos))
        with self.blocks._trace_scope():     # iterated, never called
            for blk in self.blocks:
                x = blk(x)
        x = self.ln_f(x)
        # weight-tied LM head: logits = x @ E^T (data() resolves to the
        # trace proxy inside a compiled step)
        with jax.named_scope(_scopes.LM_HEAD):
            return nd.dot(x, self.word_embed.weight.data(),
                          transpose_b=True)


def gpt_lm_loss(logits, labels):
    """Next-token cross entropy; labels = tokens shifted left, -1 pads."""
    return masked_cross_entropy(logits, labels)
