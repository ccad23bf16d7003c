"""Wide & Deep CTR model on the RowSparse embedding fast path.

The recsys shape the sparse path exists for: embedding tables hold
almost all the parameters, but each step touches only the rows its
batch's categorical features hit. With ``sparse_grad=True`` the tables
carry RowSparse gradients — the one pjit train step dedups the batch's
ids, updates only the live rows (lazy adam), and the analytic
``sparse_report()`` shows the update-bytes shrink vs dense.

Run (synthetic CTR data; any host):
  python examples/train_wide_deep.py --steps 20

Shard the deep table over a model axis (needs a multi-device mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  JAX_PLATFORMS=cpu MXTPU_SPARSE_TABLE_AXIS=tp \
  python examples/train_wide_deep.py --tp 4
"""
import argparse
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon import loss as gloss
from mxnet_tpu.gluon import nn
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.parallel import make_mesh, ShardedTrainStep


class WideDeep(nn.HybridBlock):
    """Cheng et al. 2016: a wide (linear-in-crosses) head plus a deep
    MLP over shared categorical fields, summed into one CTR logit.
    Both tables are ``sparse_grad`` — the wide one is vocab x 1."""

    def __init__(self, vocab, dim=16, hidden=64, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.wide = nn.Embedding(vocab, 1, sparse_grad=True)
            self.deep = nn.Embedding(vocab, dim, sparse_grad=True)
            self.mlp = nn.HybridSequential()
            with self.mlp.name_scope():
                self.mlp.add(nn.Dense(hidden, activation='relu'))
                self.mlp.add(nn.Dense(hidden // 2, activation='relu'))
                self.mlp.add(nn.Dense(1))

    def hybrid_forward(self, F, x):
        wide = self.wide(x).sum(axis=(1, 2))         # (B,)
        deep = self.mlp(self.deep(x))                # (B, 1), flattened in
        return wide + deep.reshape((-1,))            # CTR logit


def synthetic_ctr(n_rows, fields, vocab, hot_fraction, seed=0):
    """Synthetic impressions: ids zipf-ish concentrated in the hot
    prefix of the vocabulary, labels from a hidden linear model."""
    rng = onp.random.RandomState(seed)
    hot = max(fields, int(vocab * hot_fraction))
    ids = rng.randint(0, hot, size=(n_rows, fields))
    w = rng.randn(vocab) * 0.3
    logits = w[ids].sum(axis=1)
    y = (rng.rand(n_rows) < 1.0 / (1.0 + onp.exp(-logits)))
    return ids.astype('float32'), y.astype('float32')


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--vocab', type=int, default=100000)
    p.add_argument('--fields', type=int, default=20)
    p.add_argument('--dim', type=int, default=16)
    p.add_argument('--batch-size', type=int, default=128)
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--hot-fraction', type=float, default=0.05)
    p.add_argument('--tp', type=int, default=1,
                   help='model-axis extent for MXTPU_SPARSE_TABLE_AXIS')
    args = p.parse_args()
    mx.telemetry.compile.use_default_cache()

    mx.random.seed(0)
    model = WideDeep(args.vocab, args.dim)
    model.initialize(mx.init.Normal(0.01))

    import jax
    n_dev = len(jax.devices())
    if args.tp > 1:
        mesh = make_mesh((n_dev // args.tp, args.tp), ('dp', 'tp'))
    else:
        mesh = make_mesh((n_dev,), ('dp',))
    bce = gloss.SigmoidBinaryCrossEntropyLoss()
    step = ShardedTrainStep(model, lambda o, y: bce(o, y), 'adam',
                            {'learning_rate': 0.01}, mesh=mesh)

    ids, y = synthetic_ctr(args.batch_size * args.steps, args.fields,
                           args.vocab, args.hot_fraction)
    train = NDArrayIter(ids, y, args.batch_size)

    t0, losses = time.time(), []
    for i, batch in enumerate(train):
        loss = step(batch.data[0], batch.label[0])
        losses.append(float(loss.asnumpy()))
        if i % 5 == 0:
            print(f"step {i:4d}  loss {losses[-1]:.4f}")
    dt = time.time() - t0

    rep = step.sparse_report()
    print(f"\n{len(losses)} steps in {dt:.1f}s "
          f"({dt / max(1, len(losses)) * 1e3:.1f} ms/step), "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if rep:
        print(f"sparse mode={rep['mode']} "
              f"tables={list(rep['tables'])} "
              f"update {rep['update_bytes_per_step']} B/step vs dense "
              f"{rep['dense_update_bytes_per_step']} "
              f"({rep['update_shrink']:.1f}x shrink)")
        for axis, hop in rep['exchange_bytes_per_hop'].items():
            print(f"  grad hop [{axis}]: {hop['bytes']} B/step "
                  f"(dense-equiv {hop['dense_bytes']})")
    else:
        print("sparse path off (MXTPU_SPARSE=0 or no sparse tables)")


if __name__ == '__main__':
    main()
