#!/usr/bin/env python
"""Distributed data-parallel ResNet training (north-star config 5 shape).

Single process: Learner compiles fwd+bwd+update over the local mesh.
Multi process (tools/launch.py): each worker trains on its data shard and
grads allreduce through the dist_sync KVStore (Gloo on CPU, ICI/DCN on TPU).

    # single host / chip
    python examples/train_resnet_dist.py --depth 18 --epochs 2
    # 3-way data parallel without a cluster
    python tools/launch.py -n 3 python examples/train_resnet_dist.py --dist
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as onp  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--depth", type=int, default=18, choices=[18, 34, 50])
    ap.add_argument("--classes", type=int, default=10)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-size", type=int, default=32)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--dist", action="store_true",
                    help="multi-worker via kvstore dist_sync")
    ap.add_argument("--samples", type=int, default=512)
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, kvstore, metric
    from mxnet_tpu.gluon.model_zoo import vision

    kv = kvstore.create("dist_sync") if args.dist else None
    rank = kv.rank if kv else 0
    nworker = kv.num_workers if kv else 1

    mx.random.seed(42)  # identical init across workers
    net = vision.get_resnet(1, args.depth, classes=args.classes)
    net.initialize(mx.initializer.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9},
                            kvstore=kv)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    # synthetic class-separable shard: each worker sees its own slice
    rng = onp.random.RandomState(1234)
    n = args.samples
    labels = rng.randint(0, args.classes, n).astype("float32")
    images = (rng.rand(n, 3, args.image_size, args.image_size)
              .astype("float32") * 0.1)
    for c in range(args.classes):
        images[labels == c, c % 3] += 0.5 + 0.05 * c
    # equal shard sizes (floor) so every worker runs the SAME number of
    # steps — uneven shards would desynchronize the allreduce collectives
    per = n // nworker
    shard = slice(rank * per, (rank + 1) * per)
    images, labels = images[shard], labels[shard]

    acc = metric.Accuracy()
    for epoch in range(args.epochs):
        tic = time.time()
        acc.reset()
        perm = onp.random.permutation(len(images))
        for i in range(0, len(images) - args.batch_size + 1,
                       args.batch_size):
            idx = perm[i:i + args.batch_size]
            x = mx.np.array(images[idx])
            y = mx.np.array(labels[idx])
            with autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(args.batch_size * nworker)
            acc.update(y, out)
        print(f"[worker {rank}] epoch {epoch}: "
              f"acc {acc.get()[1]:.3f} ({time.time() - tic:.1f}s)",
              flush=True)
    if kv:
        kv.barrier()
    print(f"[worker {rank}] done", flush=True)


if __name__ == "__main__":
    main()
