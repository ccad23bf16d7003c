"""Transformer encoder-decoder training example (ref: the WMT
transformer-big verification config, BASELINE.json; model in
models/transformer.py).

Trains seq2seq on a synthetic reversal task (target = reversed source) —
the standard smoke objective for enc-dec attention: the decoder must
attend across the whole source. Runs through the fused ShardedTrainStep
(one XLA program per step). Use --big for the transformer-big
(1024/16/4096) configuration.

Run: python examples/train_transformer.py [--steps 30] [--big]
"""
import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import TransformerModel
from mxnet_tpu.models.bert import masked_cross_entropy
from mxnet_tpu.parallel import make_mesh, ShardedTrainStep


def make_batch(rng, batch, seq, vocab):
    src = rng.randint(4, vocab, (batch, seq)).astype(onp.int32)
    tgt_out = src[:, ::-1].copy()
    # teacher forcing: decoder input is <bos>=1 + shifted target
    tgt_in = onp.concatenate(
        [onp.ones((batch, 1), onp.int32), tgt_out[:, :-1]], axis=1)
    return nd.array(src), nd.array(tgt_in), nd.array(tgt_out)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=30)
    p.add_argument('--batch-size', type=int, default=16)
    p.add_argument('--seq', type=int, default=24)
    p.add_argument('--vocab', type=int, default=64)
    p.add_argument('--big', action='store_true',
                   help='transformer-big dims (1024/16/4096, 6+6 layers)')
    args = p.parse_args()
    mx.telemetry.compile.use_default_cache()

    if args.big:
        cfg = dict(hidden=1024, enc_layers=6, dec_layers=6, heads=16,
                   ffn_hidden=4096)
    else:
        cfg = dict(hidden=64, enc_layers=2, dec_layers=2, heads=4,
                   ffn_hidden=128)
    net = TransformerModel(args.vocab, args.vocab, max_len=256,
                           dropout=0.1, **cfg)
    net.initialize(mx.init.Xavier())

    def loss_fn(logits, labels):
        return masked_cross_entropy(logits, labels)

    import jax
    mesh = make_mesh((len(jax.devices()),), ('dp',))
    step = ShardedTrainStep(net, loss_fn, 'adam',
                            {'learning_rate': 3e-4}, mesh=mesh)

    assert args.steps > 0, "--steps must be positive"
    rng = onp.random.RandomState(0)
    first = None
    for i in range(args.steps):
        src, tgt_in, tgt_out = make_batch(rng, args.batch_size, args.seq,
                                          args.vocab)
        loss = float(step([src, tgt_in], [tgt_out]).asnumpy())
        if first is None:
            first = loss
        if i % 10 == 0 or i == args.steps - 1:
            print(f"step {i}: loss {loss:.4f}")
    print(f"loss {first:.4f} -> {loss:.4f}")


if __name__ == '__main__':
    main()
