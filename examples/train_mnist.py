"""Gluon training example: MLP on synthetic MNIST-shaped data.

The canonical user loop (ref: example/gluon/mnist.py): HybridBlock +
Trainer + autograd. Trainer.step compiles every parameter update into one
XLA program; hybridize() compiles the forward.

Run: python examples/train_mnist.py [--epochs 3]
"""
import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.gluon import nn


def make_data(n=2048, seed=0):
    rng = onp.random.RandomState(seed)
    x = rng.rand(n, 1, 28, 28).astype(onp.float32)
    w = rng.randn(784, 10).astype(onp.float32)
    y = (x.reshape(n, 784) @ w).argmax(1).astype(onp.int32)
    return x, y


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--epochs', type=int, default=3)
    p.add_argument('--batch-size', type=int, default=128)
    p.add_argument('--lr', type=float, default=1e-3)
    args = p.parse_args()
    mx.telemetry.compile.use_default_cache()

    net = nn.HybridSequential()
    net.add(nn.Dense(256, activation='relu'),
            nn.Dense(128, activation='relu'),
            nn.Dense(10))
    net.initialize(mx.init.Xavier())
    net.hybridize()

    x, y = make_data()
    dataset = gluon.data.ArrayDataset(nd.array(x), nd.array(y))
    loader = gluon.data.DataLoader(dataset, batch_size=args.batch_size,
                                   shuffle=True)
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    metric = mx.metric.Accuracy()

    import time
    for epoch in range(args.epochs):
        metric.reset()
        tic = time.time()
        for data, label in loader:
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            metric.update([label], [out])
        print(f"Epoch[{epoch}] Train-accuracy={metric.get()[1]:.4f}")
        print(f"Epoch[{epoch}] Time cost={time.time() - tic:.2f}")


if __name__ == '__main__':
    main()
