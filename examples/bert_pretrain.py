#!/usr/bin/env python
"""BERT-Base masked-LM pretraining step demo (north-star config 4).

Synthetic token streams (zero-egress); shows both the script-parity path
(Trainer + autograd) and the SPMD path (parallel.Learner, one compiled
fwd+bwd+update program, grads allreduced on ICI when a mesh is present)."""
from __future__ import annotations

import argparse
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon, np, parallel
from mxnet_tpu.gluon.model_zoo.bert import BERTForPretraining, bert_base


def synth_batch(rng, batch, seq, vocab):
    tokens = rng.randint(0, vocab, (batch, seq)).astype("int32")
    mlm_labels = rng.randint(0, vocab, (batch, seq)).astype("float32")
    nsp = rng.randint(0, 2, (batch, 1)).astype("float32")
    return (np.array(tokens),
            np.concatenate([np.array(mlm_labels), np.array(nsp)], axis=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=30522)
    ap.add_argument("--mode", choices=["learner", "trainer"],
                    default="learner")
    args = ap.parse_args()

    amp.init("bfloat16")
    bert = bert_base(vocab_size=args.vocab, max_length=args.seq_len)
    model = BERTForPretraining(bert, vocab_size=args.vocab)
    model.initialize(mx.initializer.Normal(0.02))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = onp.random.RandomState(0)

    if args.mode == "learner":
        def pretrain_loss(pair, labels):
            mlm_scores, nsp_scores = pair
            return loss_fn(mlm_scores, labels[:, :-1]).mean() + \
                loss_fn(nsp_scores, labels[:, -1]).mean()

        learner = parallel.Learner(
            model, pretrain_loss,
            mx.optimizer.AdamW(learning_rate=1e-4, wd=0.01))
        tokens, labels = synth_batch(rng, args.batch_size, args.seq_len,
                                     args.vocab)
        learner.step(tokens, labels).wait_to_read()  # compile
        tic = time.time()
        for step in range(args.steps):
            loss = learner.step(tokens, labels)
        v = float(loss)
        dt = time.time() - tic
    else:
        trainer = gluon.Trainer(model.collect_params(), "adamw",
                                {"learning_rate": 1e-4, "wd": 0.01})
        tokens, labels = synth_batch(rng, args.batch_size, args.seq_len,
                                     args.vocab)
        tic = time.time()
        for step in range(args.steps):
            with autograd.record():
                mlm, nsp = model(tokens)
                loss = loss_fn(mlm, labels[:, :-1]).mean() + \
                    loss_fn(nsp, labels[:, -1]).mean()
            loss.backward()
            trainer.step(args.batch_size)
        v = float(loss)
        dt = time.time() - tic

    tok_s = args.steps * args.batch_size * args.seq_len / dt
    print(f"{args.mode}: final loss {v:.3f}, {tok_s:.0f} tokens/s")


if __name__ == "__main__":
    main()
