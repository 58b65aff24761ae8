#!/usr/bin/env python
"""MNIST training (parity: reference example/gluon/mnist/mnist.py —
reference config #1: the minimum end-to-end slice).

Usage: python example/gluon/mnist/mnist.py [--epochs 3] [--hybridize]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "..", ".."))

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn


def build_net(hybridize):
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"),
            nn.Dense(64, activation="relu"),
            nn.Dense(10))
    net.initialize(mx.init.Xavier())
    if hybridize:
        net.hybridize()
    return net


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=100)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--hybridize", action="store_true")
    ap.add_argument("--max-batches", type=int, default=0,
                    help="truncate epochs (smoke testing)")
    args = ap.parse_args()

    tf = gluon.data.vision.transforms.ToTensor()
    train_data = gluon.data.DataLoader(
        gluon.data.vision.MNIST(train=True).transform_first(tf),
        batch_size=args.batch_size, shuffle=True)
    val_data = gluon.data.DataLoader(
        gluon.data.vision.MNIST(train=False).transform_first(tf),
        batch_size=args.batch_size)

    net = build_net(args.hybridize)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr})
    metric = gluon.metric.Accuracy()

    for epoch in range(args.epochs):
        metric.reset()
        tic = time.time()
        for i, (x, y) in enumerate(train_data):
            if args.max_batches and i >= args.max_batches:
                break
            x = x.reshape(x.shape[0], -1)
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update(y, out)
        name, acc = metric.get()
        print("Epoch %d: train %s=%.4f (%.1fs)" % (
            epoch, name, acc, time.time() - tic))

    metric.reset()
    for i, (x, y) in enumerate(val_data):
        if args.max_batches and i >= args.max_batches:
            break
        metric.update(y, net(x.reshape(x.shape[0], -1)))
    print("Validation %s=%.4f" % metric.get())


if __name__ == "__main__":
    main()
