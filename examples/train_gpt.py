"""GPT-style causal LM training example.

Decoder-only transformer over the flash kernel's causal path; next-token
loss; one compiled train step per iteration.

Run (synthetic data):
  python examples/train_gpt.py --layers 2 --hidden 128 --steps 20
"""
import argparse
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.models import GPTModel, gpt_lm_loss
from mxnet_tpu.parallel import make_mesh, ShardedTrainStep


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--layers', type=int, default=12)
    p.add_argument('--hidden', type=int, default=768)
    p.add_argument('--heads', type=int, default=12)
    p.add_argument('--seq', type=int, default=1024)
    p.add_argument('--batch-size', type=int, default=8)
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--vocab', type=int, default=50257)
    args = p.parse_args()
    mx.telemetry.compile.use_default_cache()

    mx.random.seed(0)
    model = GPTModel(vocab_size=args.vocab, hidden=args.hidden,
                     layers=args.layers, heads=args.heads,
                     max_len=args.seq)
    model.initialize(mx.init.Normal(0.02))

    import jax
    mesh = make_mesh((len(jax.devices()),), ('dp',))
    step = ShardedTrainStep(model, gpt_lm_loss, 'adamw',
                            {'learning_rate': 3e-4}, mesh=mesh)

    rng = onp.random.RandomState(0)
    B, T = args.batch_size, args.seq
    toks = rng.randint(0, args.vocab, (B, T)).astype('int32')
    labels = onp.full_like(toks, -1)
    labels[:, :-1] = toks[:, 1:]
    tokens, labels = nd.array(toks), nd.array(labels)

    loss = step([tokens], [labels])
    print(f"step 0: loss={float(loss.asscalar()):.4f}")
    t0 = time.time()
    for i in range(1, args.steps):
        loss = step([tokens], [labels])
    l = float(loss.asscalar())
    dt = (time.time() - t0) / max(args.steps - 1, 1)
    tps = B * T / dt
    print(f"step {args.steps - 1}: loss={l:.4f}  "
          f"{dt * 1e3:.1f} ms/step  {tps / 1e3:.1f}k tokens/sec")


if __name__ == '__main__':
    main()
