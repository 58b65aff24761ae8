#!/usr/bin/env python
"""Image classification with model-zoo networks (parity: reference
example/gluon/image_classification.py — reference configs #2/#4 seed).

Usage:
  python example/gluon/image_classification.py --model resnet18_v1 \
      --dataset synthetic --batch-size 32 --epochs 1 --kvstore device
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", ".."))

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu import np as mxnp
from mxnet_tpu.gluon.model_zoo.vision import get_model


def get_data(args):
    if args.dataset == "synthetic":
        rng = onp.random.RandomState(0)
        n = args.batch_size * max(args.max_batches or 8, 1)
        x = rng.rand(n, 3, args.image_shape, args.image_shape) \
            .astype(onp.float32)
        y = rng.randint(0, args.classes, n).astype(onp.float32)
        ds = gluon.data.ArrayDataset(mxnp.array(x), mxnp.array(y))
        return gluon.data.DataLoader(ds, batch_size=args.batch_size,
                                     shuffle=True)
    if args.dataset == "cifar10":
        tf = gluon.data.vision.transforms.ToTensor()
        return gluon.data.DataLoader(
            gluon.data.vision.CIFAR10(train=True).transform_first(tf),
            batch_size=args.batch_size, shuffle=True)
    if args.rec:
        from mxnet_tpu import io as mio
        return mio.ImageRecordIter(
            path_imgrec=args.rec, data_shape=(3, args.image_shape,
                                              args.image_shape),
            batch_size=args.batch_size, shuffle=True, rand_mirror=True)
    raise ValueError("unknown dataset %r" % args.dataset)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--dataset", default="synthetic",
                    choices=["synthetic", "cifar10", "rec"])
    ap.add_argument("--rec", default=None, help=".rec path for --dataset rec")
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--image-shape", type=int, default=32)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--kvstore", default="device")
    ap.add_argument("--no-hybridize", action="store_true",
                    help="run eagerly instead of whole-graph XLA")
    ap.add_argument("--max-batches", type=int, default=0)
    args = ap.parse_args()

    net = get_model(args.model, classes=args.classes)
    net.initialize(mx.init.Xavier())
    if not args.no_hybridize:
        net.hybridize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9,
                             "wd": 1e-4}, kvstore=args.kvstore)
    metric = gluon.metric.Accuracy()

    for epoch in range(args.epochs):
        data = get_data(args)
        metric.reset()
        tic = time.time()
        n_img = 0
        for i, batch in enumerate(data):
            if args.max_batches and i >= args.max_batches:
                break
            if isinstance(batch, (tuple, list)):
                x, y = batch
            else:
                x, y = batch.data[0], batch.label[0]
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update(y, out)
            n_img += x.shape[0]
        mx.waitall()
        dur = time.time() - tic
        name, acc = metric.get()
        print("Epoch %d: %s=%.4f  %.1f img/s" % (epoch, name, acc,
                                                 n_img / dur))


if __name__ == "__main__":
    main()
