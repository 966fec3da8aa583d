#!/usr/bin/env python
"""Gluon MLP on MNIST (north-star config 1; reference:
example/gluon/mnist/mnist.py — unmodified script shape)."""
from __future__ import annotations

import argparse
import time

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, metric, np
from mxnet_tpu.gluon import nn
from mxnet_tpu.gluon.data import DataLoader
from mxnet_tpu.gluon.data.vision import MNIST


def build_net():
    net = nn.HybridSequential()
    net.add(nn.Dense(128, activation="relu"),
            nn.Dense(64, activation="relu"),
            nn.Dense(10))
    return net


def transform(sample):
    img, label = sample
    return img.astype("float32") / 255.0, label


def evaluate(net, loader):
    acc = metric.Accuracy()
    for data, label in loader:
        out = net(data.reshape((data.shape[0], -1)))
        acc.update(label, out)
    return acc.get()[1]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=5)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--no-hybridize", action="store_true")
    args = ap.parse_args()

    train_loader = DataLoader(MNIST(train=True).transform(transform),
                              batch_size=args.batch_size, shuffle=True,
                              num_workers=2)
    val_loader = DataLoader(MNIST(train=False).transform(transform),
                            batch_size=args.batch_size)

    net = build_net()
    net.initialize(mx.initializer.Xavier(), ctx=mx.tpu()
                   if mx.num_tpus() else mx.cpu())
    if not args.no_hybridize:
        net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": args.lr})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for epoch in range(args.epochs):
        tic = time.time()
        train_loss = 0.0
        nbatch = 0
        for data, label in train_loader:
            data = data.reshape((data.shape[0], -1))
            with autograd.record():
                out = net(data)
                loss = loss_fn(out, label)
            loss.backward()
            trainer.step(data.shape[0])
            train_loss += float(loss.mean())
            nbatch += 1
        acc = evaluate(net, val_loader)
        print(f"Epoch {epoch}: loss {train_loss / nbatch:.4f} "
              f"val acc {acc:.4f} ({time.time() - tic:.1f}s)")
    net.save_parameters("mnist_mlp.params.npz")


if __name__ == "__main__":
    main()
