"""BERT pretraining example — the flagship path.

One ShardedTrainStep call = forward + backward + AdamW update + gradient
all-reduce as a single pjit-compiled XLA program over the device mesh.
The MLM decoder runs only on the masked positions (GluonNLP recipe) and
attention routes through the Pallas flash kernel on TPU.

Run (synthetic data):
  python examples/pretrain_bert.py --layers 2 --hidden 128 --steps 10
"""
import argparse
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import BertForPretraining, bert_pretrain_loss
from mxnet_tpu.parallel import make_mesh, ShardedTrainStep


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--layers', type=int, default=12)
    p.add_argument('--hidden', type=int, default=768)
    p.add_argument('--heads', type=int, default=12)
    p.add_argument('--seq', type=int, default=512)
    p.add_argument('--batch-size', type=int, default=32)
    p.add_argument('--steps', type=int, default=30)
    p.add_argument('--vocab', type=int, default=30522)
    p.add_argument('--bf16', action='store_true')
    args = p.parse_args()
    mx.telemetry.compile.use_default_cache()

    cfg = dict(vocab_size=args.vocab, hidden=args.hidden,
               layers=args.layers, heads=args.heads,
               intermediate=4 * args.hidden, max_len=args.seq,
               type_vocab=2)
    mx.random.seed(0)
    model = BertForPretraining(cfg)
    model.initialize(mx.init.Normal(0.02))
    if args.bf16:
        model.cast('bfloat16')

    import jax
    mesh = make_mesh((len(jax.devices()),), ('dp',))
    step = ShardedTrainStep(model, bert_pretrain_loss, 'adamw',
                            {'learning_rate': 1e-4}, mesh=mesh)

    rng = onp.random.RandomState(0)
    B, T = args.batch_size, args.seq
    M = max(8, int(0.15 * T) // 8 * 8)          # masked positions
    tokens = nd.array(rng.randint(0, args.vocab, (B, T)).astype('int32'))
    types = nd.array(onp.zeros((B, T), 'int32'))
    valid = nd.array(rng.randint(T // 2, T + 1, (B,)).astype('int32'))
    mpos = nd.array(onp.stack([rng.choice(T, M, replace=False)
                               for _ in range(B)]).astype('int32'))
    labels = nd.array(rng.randint(0, args.vocab, (B, M)).astype('int32'))
    nsp = nd.array(rng.randint(0, 2, (B,)).astype('int32'))

    inputs, targets = [tokens, types, valid, mpos], [labels, nsp]
    loss = step(inputs, targets)                # compile
    print(f"step 0: loss={float(loss.asscalar()):.4f}")
    t0 = time.time()
    for i in range(1, args.steps):
        loss = step(inputs, targets)
    l = float(loss.asscalar())
    dt = (time.time() - t0) / max(args.steps - 1, 1)
    print(f"step {args.steps - 1}: loss={l:.4f}  "
          f"{dt * 1e3:.1f} ms/step  "
          f"{B / dt:.1f} samples/sec")


if __name__ == '__main__':
    main()
