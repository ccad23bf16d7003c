"""SSD detection training example on synthetic boxes (ref: example/ssd).

Drives the SSD model family end to end: multibox anchors + targets,
mined classification + smooth-L1 box loss, fused Trainer updates, and
NMS-decoded detections. Synthetic data (one colored rectangle per image)
keeps it runnable anywhere; swap in ImageDetIter/ImageRecordIter for VOC.

Run: python examples/train_ssd.py [--steps 20] [--size 128]
"""
import argparse

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.models import SSD, ssd_train_loss


def make_batch(rng, batch, size, num_classes):
    """Images with one axis-aligned bright rectangle; label is its class
    (by color channel) and normalized corner box, padded to M=4 rows."""
    x = rng.rand(batch, 3, size, size).astype(onp.float32) * 0.1
    label = onp.full((batch, 4, 5), -1.0, onp.float32)
    for i in range(batch):
        cls = rng.randint(num_classes)
        w, h = rng.randint(size // 4, size // 2, 2)
        x0, y0 = rng.randint(0, size - w), rng.randint(0, size - h)
        x[i, cls, y0:y0 + h, x0:x0 + w] += 0.8
        label[i, 0] = [cls, x0 / size, y0 / size,
                       (x0 + w) / size, (y0 + h) / size]
    return nd.array(x), nd.array(label)


def main():
    p = argparse.ArgumentParser()
    p.add_argument('--steps', type=int, default=20)
    p.add_argument('--batch-size', type=int, default=8)
    p.add_argument('--size', type=int, default=128,
                   help='input resolution (512 = the reference config)')
    p.add_argument('--lr', type=float, default=1e-3)
    args = p.parse_args()
    mx.telemetry.compile.use_default_cache()

    num_classes = 3
    net = SSD(num_classes=num_classes, image_size=args.size,
              sizes=[(.15, .25), (.35, .45), (.6, .7)],
              ratios=[[1, 2, .5]] * 3)
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), 'adam',
                            {'learning_rate': args.lr})

    rng = onp.random.RandomState(0)
    for step in range(args.steps):
        x, label = make_batch(rng, args.batch_size, args.size, num_classes)
        with autograd.record():
            anchor, cls_pred, loc_pred = net(x)
            loss = ssd_train_loss(anchor, cls_pred, loc_pred, label)
        loss.backward()
        trainer.step(args.batch_size)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss.asnumpy()):.4f}")

    x, _ = make_batch(rng, 2, args.size, num_classes)
    det = net.detect(x, threshold=0.1)
    d = det.asnumpy()
    kept = d[0][d[0, :, 0] >= 0]
    print(f"detections on image 0: {len(kept)} boxes, "
          f"top score {kept[:, 1].max() if len(kept) else 0:.3f}")


if __name__ == '__main__':
    main()
