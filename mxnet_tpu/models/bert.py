"""BERT for pretraining — the flagship/north-star model.

Ref: the GluonNLP BERT-base recipe named in BASELINE.json; attention kernels
correspond to the reference's interleaved_matmul selfatt ops
(src/operator/contrib/transformer.cc:650-828), realised here as the fused
self_attention op over the fused qkv projection (XLA/Pallas flash path).

bf16-friendly: activations run in the block dtype; layernorm statistics in
fp32 (see ops/nn.py layer_norm).
"""
from __future__ import annotations

import math

import jax

from ..gluon import nn
from ..gluon.block import HybridBlock
from .. import ndarray as nd
from .. import scopes as _scopes
from ..ops import attention as attn_ops
from ..ndarray.ndarray import _invoke


def bert_base_config():
    return dict(vocab_size=30522, hidden=768, layers=12, heads=12,
                intermediate=3072, max_len=512, type_vocab=2)


def bert_large_config():
    return dict(vocab_size=30522, hidden=1024, layers=24, heads=16,
                intermediate=4096, max_len=512, type_vocab=2)


class BertSelfAttention(HybridBlock):
    def __init__(self, hidden, heads, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        self._heads = heads
        self._hidden = hidden
        self._attn_dropout = dropout
        with self.name_scope():
            self.qkv = nn.Dense(3 * hidden, flatten=False,
                                in_units=hidden, prefix='qkv_')
            self.proj = nn.Dense(hidden, flatten=False, in_units=hidden,
                                 prefix='proj_')
            self.dropout = nn.Dropout(dropout)

    def forward(self, x, mask=None):
        # x: (N, T, C)
        qkv = self.qkv(x)
        # no block of its own: a plain scope names it in a device trace
        with jax.named_scope(_scopes.ATTN_CORE):
            out = _invoke(attn_ops.self_attention, qkv, mask,
                          num_heads=self._heads,
                          dropout_p=self._attn_dropout)
        return self.dropout(self.proj(out))


class BertLayer(HybridBlock):
    def __init__(self, hidden, heads, intermediate, dropout=0.1, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.attention = BertSelfAttention(hidden, heads, dropout)
            self.ln1 = nn.LayerNorm(in_channels=hidden)
            self.ffn1 = nn.Dense(intermediate, flatten=False,
                                 in_units=hidden, prefix='ffn1_')
            self.ffn2 = nn.Dense(hidden, flatten=False,
                                 in_units=intermediate, prefix='ffn2_')
            self.ln2 = nn.LayerNorm(in_channels=hidden)
            self.dropout = nn.Dropout(dropout)

    def _add_ln(self, ln, x, sub):
        # residual + LN through one op so the fused Pallas epilogue can
        # take it when MXTPU_PALLAS_LN=1 (ops/nn.py add_layer_norm)
        from ..ops import nn as _nn_ops
        return _invoke(_nn_ops.add_layer_norm, x, sub,
                       ln.gamma.data(), ln.beta.data(), eps=ln._epsilon)

    def forward(self, x, mask=None):
        attn = self.attention(x, mask)
        # ln1, ffn1 and ln2 go through fused ops and not through their
        # blocks' forward, so each gets the plain scope a block would
        with jax.named_scope(_scopes.LN1):
            x = self._add_ln(self.ln1, x, attn)
        # FFN1 matmul + bias + GELU through one op so the fused Pallas
        # epilogue can take it when MXTPU_PALLAS_FFN=1 (ops/nn.py
        # dense_gelu; the XLA default is the same Dense+gelu math)
        from ..ops import nn as _nn_ops
        with jax.named_scope(_scopes.FFN1):
            h = _invoke(_nn_ops.dense_gelu, x, self.ffn1.weight.data(),
                        self.ffn1.bias.data())
        h = self.dropout(self.ffn2(h))
        with jax.named_scope(_scopes.LN2):
            return self._add_ln(self.ln2, x, h)


class BertModel(HybridBlock):
    def __init__(self, vocab_size=30522, hidden=768, layers=12, heads=12,
                 intermediate=3072, max_len=512, type_vocab=2, dropout=0.1,
                 **kwargs):
        super().__init__(**kwargs)
        self._hidden = hidden
        with self.name_scope():
            self.word_embed = nn.Embedding(vocab_size, hidden,
                                           prefix='word_embed_')
            self.pos_embed = nn.Embedding(max_len, hidden,
                                          prefix='pos_embed_')
            self.type_embed = nn.Embedding(type_vocab, hidden,
                                           prefix='type_embed_')
            self.embed_ln = nn.LayerNorm(in_channels=hidden)
            self.embed_dropout = nn.Dropout(dropout)
            self.encoder = nn.HybridSequential(prefix='encoder_')
            with self.encoder.name_scope():
                for _ in range(layers):
                    self.encoder.add(BertLayer(hidden, heads, intermediate,
                                               dropout))
            self.pooler = nn.Dense(hidden, flatten=False, in_units=hidden,
                                   activation='tanh', prefix='pooler_')

    def forward(self, tokens, token_types=None, valid_length=None):
        # tokens: (N, T) int32
        T = tokens.shape[1]
        pos = nd.arange(0, T, dtype='int32').reshape(1, T)
        emb = self.word_embed(tokens) + self.pos_embed(pos)
        if token_types is not None:
            emb = emb + self.type_embed(token_types)
        x = self.embed_dropout(self.embed_ln(emb))
        mask = None
        if valid_length is not None:
            ar = nd.arange(0, T, dtype='float32')
            mask = (ar.reshape(1, 1, 1, T) <
                    valid_length.reshape(-1, 1, 1, 1))
        with self.encoder._trace_scope():    # iterated, never called
            for layer in self.encoder:
                x = layer(x, mask)
        pooled = self.pooler(nd.slice_axis(x, axis=1, begin=0, end=1)
                             .squeeze(axis=1))
        return x, pooled


def _gather_positions(seq, positions):
    """(N, T, C) gathered at (N, M) int positions -> (N, M, C)."""
    import jax.numpy as jnp
    return jnp.take_along_axis(
        seq, positions.astype('int32')[:, :, None], axis=1)


class BertForPretraining(HybridBlock):
    """MLM + NSP heads (the pretraining objective in the north-star recipe)."""

    def __init__(self, config=None, **kwargs):
        super().__init__(**kwargs)
        cfg = config or bert_base_config()
        self._cfg = cfg
        with self.name_scope():
            self.bert = BertModel(**cfg)
            self.mlm_dense = nn.Dense(cfg['hidden'], flatten=False,
                                      in_units=cfg['hidden'],
                                      activation='gelu',
                                      prefix='mlm_dense_')
            self.mlm_ln = nn.LayerNorm(in_channels=cfg['hidden'])
            self.mlm_decoder = nn.Dense(cfg['vocab_size'], flatten=False,
                                        in_units=cfg['hidden'],
                                        prefix='mlm_decoder_')
            self.nsp = nn.Dense(2, in_units=cfg['hidden'], prefix='nsp_')

    def forward(self, tokens, token_types=None, valid_length=None,
                masked_positions=None):
        """masked_positions: optional (N, M) int32 — the MLM-masked token
        positions. When given, the decoder runs only on those M positions
        (the GluonNLP pretraining recipe: ~15% of tokens are masked, so
        decoding all T positions wastes ~21% of step FLOPs on logits the
        loss discards). mlm is then (N, M, vocab) instead of (N, T, vocab).
        """
        seq, pooled = self.bert(tokens, token_types, valid_length)
        if masked_positions is not None:
            seq = _invoke(_gather_positions, seq, masked_positions)
        mlm = self.mlm_decoder(self.mlm_ln(self.mlm_dense(seq)))
        nsp = self.nsp(pooled)
        return mlm, nsp


def masked_cross_entropy(logits, labels):
    """Mean cross entropy over the positions where labels >= 0 (-1 marks
    padding/unmasked). Shared by the BERT MLM and GPT LM objectives."""
    logp = nd.log_softmax(logits, axis=-1)
    valid = (labels >= 0)
    safe_labels = nd.where(valid, labels, nd.zeros_like(labels))
    token_loss = -nd.pick(logp, safe_labels, axis=-1) * valid
    return nd.sum(token_loss) / (nd.sum(valid) + 1e-6)


def bert_pretrain_loss(mlm_logits, nsp_logits, labels, nsp_labels,
                       mask_weight=None):
    """Masked-LM + NSP cross entropy. labels: (N, T) with -1 for unmasked."""
    mlm_loss = masked_cross_entropy(mlm_logits, labels)
    nsp_logp = nd.log_softmax(nsp_logits, axis=-1)
    nsp_loss = nd.mean(-nd.pick(nsp_logp, nsp_labels, axis=-1))
    return mlm_loss + nsp_loss


# ---------------------------------------------------------------------------
# Pipeline-parallel bridge (VERDICT r4 #6): express the Gluon BERT as the
# embed → encoder-stages → head split that parallel/pipeline.py
# pipelines over a 'pp' mesh axis. The functional stage math mirrors
# BertLayer.forward exactly (eval mode — GPipe microbatching assumes
# deterministic stages), so a pipelined step is parity-comparable
# against the same Gluon model on the pure-DP path.
# ---------------------------------------------------------------------------

def _p(param):
    """A Gluon Parameter's jax payload."""
    return param.data()._data


def bert_pipeline_funcs(model: 'BertForPretraining', n_stages,
                        mesh=None, pp_axis='pp'):
    """Extract (params, embed_fn, stage_fn, head_fn, loss_fn) for
    parallel.PipelineTrainStep from an initialized BertForPretraining.

    The encoder's layers split evenly into `n_stages` pipeline stages
    (layers % n_stages == 0); embedding and the MLM/NSP heads replicate
    outside the pipeline.

    Constraints (validated, not assumed): the model must be built with
    dropout=0 — GPipe microbatch stages must be deterministic — and the
    pipelined forward is the token_types=None path (type_embed gets no
    gradient on the DP path either when token_types is never fed, so the
    two paths train the same weights).
    """
    import jax
    import jax.numpy as jnp
    from ..base import MXNetError
    from ..ops import nn as F
    from ..ops import attention as attn_ops
    from ..parallel.pipeline import split_layers_into_stages

    bert = model.bert
    heads = bert.encoder[0].attention._heads
    eps = bert.embed_ln._epsilon
    drop = bert.encoder[0].attention._attn_dropout
    if drop:
        raise MXNetError(
            f"bert_pipeline_funcs: model was built with dropout={drop}; "
            "pipeline stages must be deterministic — rebuild the model "
            "with dropout=0.0 (GPipe recomputes microbatches in bubble "
            "ticks, so stochastic stages would diverge from the DP path)")

    layer_params = []
    for layer in bert.encoder:
        a = layer.attention
        layer_params.append({
            'qkv_w': _p(a.qkv.weight), 'qkv_b': _p(a.qkv.bias),
            'proj_w': _p(a.proj.weight), 'proj_b': _p(a.proj.bias),
            'ln1_g': _p(layer.ln1.gamma), 'ln1_b': _p(layer.ln1.beta),
            'ffn1_w': _p(layer.ffn1.weight), 'ffn1_b': _p(layer.ffn1.bias),
            'ffn2_w': _p(layer.ffn2.weight), 'ffn2_b': _p(layer.ffn2.bias),
            'ln2_g': _p(layer.ln2.gamma), 'ln2_b': _p(layer.ln2.beta),
        })

    params = {
        'embed': {
            'word': _p(bert.word_embed.weight),
            'pos': _p(bert.pos_embed.weight),
            'ln_g': _p(bert.embed_ln.gamma),
            'ln_b': _p(bert.embed_ln.beta),
        },
        'stages': split_layers_into_stages(layer_params, n_stages),
        'head': {
            'pooler_w': _p(bert.pooler.weight),
            'pooler_b': _p(bert.pooler.bias),
            'mlm_w': _p(model.mlm_dense.weight),
            'mlm_b': _p(model.mlm_dense.bias),
            'mlm_ln_g': _p(model.mlm_ln.gamma),
            'mlm_ln_b': _p(model.mlm_ln.beta),
            'dec_w': _p(model.mlm_decoder.weight),
            'dec_b': _p(model.mlm_decoder.bias),
            'nsp_w': _p(model.nsp.weight),
            'nsp_b': _p(model.nsp.bias),
        },
    }

    def embed_fn(p, tokens):
        T = tokens.shape[-1]
        emb = p['word'][tokens.astype(jnp.int32)] \
            + p['pos'][jnp.arange(T, dtype=jnp.int32)][None, :, :]
        return F.layer_norm(emb, p['ln_g'], p['ln_b'], eps=eps)

    def one_layer(x, lp):
        qkv = x @ lp['qkv_w'].T + lp['qkv_b']
        attn = attn_ops.self_attention(qkv, num_heads=heads, dropout_p=0.0)
        attn = attn @ lp['proj_w'].T + lp['proj_b']
        x = F.layer_norm(x + attn, lp['ln1_g'], lp['ln1_b'], eps=eps)
        h = F.dense_gelu(x, lp['ffn1_w'], lp['ffn1_b'])
        h = h @ lp['ffn2_w'].T + lp['ffn2_b']
        return F.layer_norm(x + h, lp['ln2_g'], lp['ln2_b'], eps=eps)

    def stage_fn(sp, x):
        # sp leaves: (layers_per_stage, ...) — scan over the layer axis
        def body(carry, lp):
            return one_layer(carry, lp), None
        out, _ = jax.lax.scan(body, x, sp)
        return out

    def head_fn(p, seq):
        pooled = jnp.tanh(seq[:, 0, :] @ p['pooler_w'].T + p['pooler_b'])
        h = F.activation(seq @ p['mlm_w'].T + p['mlm_b'], act_type='gelu')
        h = F.layer_norm(h, p['mlm_ln_g'], p['mlm_ln_b'], eps=eps)
        mlm = h @ p['dec_w'].T + p['dec_b']
        nsp = pooled @ p['nsp_w'].T + p['nsp_b']
        return mlm, nsp

    def loss_fn(outputs, y):
        mlm_logits, nsp_logits = outputs
        labels, nsp_labels = y
        logp = jax.nn.log_softmax(mlm_logits, axis=-1)
        valid = (labels >= 0)
        safe = jnp.where(valid, labels, 0)
        tok = -jnp.take_along_axis(logp, safe[..., None].astype(jnp.int32),
                                   axis=-1)[..., 0] * valid
        mlm_loss = jnp.sum(tok) / (jnp.sum(valid) + 1e-6)
        nlogp = jax.nn.log_softmax(nsp_logits, axis=-1)
        nsp_loss = jnp.mean(-jnp.take_along_axis(
            nlogp, nsp_labels[:, None].astype(jnp.int32), axis=-1))
        return mlm_loss + nsp_loss

    return params, embed_fn, stage_fn, head_fn, loss_fn
