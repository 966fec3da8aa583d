#!/usr/bin/env python
"""LSTM word language model (north-star config 3; reference:
example/rnn/word_lm). Uses a local text file if given, else a synthetic
character stream, so it runs in zero-egress environments."""
from __future__ import annotations

import argparse
import time

import numpy as onp

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, np
from mxnet_tpu.gluon.model_zoo.rnn_lm import RNNModel


def load_corpus(path=None, length=100000, vocab=64):
    if path:
        with open(path, "rb") as f:
            raw = f.read()
        chars = sorted(set(raw))
        table = {c: i for i, c in enumerate(chars)}
        data = onp.array([table[c] for c in raw], dtype="int32")
        return data, len(chars)
    rng = onp.random.RandomState(0)
    # synthetic markov-ish stream: next token depends on previous
    data = onp.zeros(length, dtype="int32")
    for i in range(1, length):
        data[i] = (data[i - 1] * 31 + rng.randint(0, 7)) % vocab
    return data, vocab


def batchify(data, batch_size):
    nb = len(data) // batch_size
    return data[:nb * batch_size].reshape(batch_size, nb)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--data", default=None, help="optional corpus file")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--bptt", type=int, default=35)
    ap.add_argument("--hidden", type=int, default=200)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--export-onnx", default=None, metavar="PATH",
                    help="after training, export the LM to this .onnx file "
                         "(fused LSTM -> ONNX LSTM nodes) and verify the "
                         "re-import numerically")
    args = ap.parse_args()

    corpus, vocab = load_corpus(args.data)
    stream = batchify(corpus, args.batch_size)

    model = RNNModel(vocab_size=vocab, embed_size=args.hidden,
                     hidden_size=args.hidden, num_layers=args.layers,
                     dropout=0.2, tie_weights=True)
    model.initialize(mx.initializer.Xavier())
    trainer = gluon.Trainer(model.collect_params(), "adam",
                            {"learning_rate": args.lr, "clip_gradient": 0.25})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    for epoch in range(args.epochs):
        tic = time.time()
        states = model.begin_state(args.batch_size)
        total, count = 0.0, 0
        for i in range(0, stream.shape[1] - 1 - args.bptt, args.bptt):
            data = np.array(stream[:, i:i + args.bptt])
            target = np.array(stream[:, i + 1:i + 1 + args.bptt])
            states = [s.detach() for s in states]
            with autograd.record():
                logits, states = model(data, states)
                loss = loss_fn(logits, target).mean()
            loss.backward()
            trainer.step(1)
            total += float(loss)
            count += 1
        ppl = onp.exp(total / count)
        print(f"Epoch {epoch}: loss {total / count:.3f} ppl {ppl:.2f} "
              f"({time.time() - tic:.1f}s)")

    if args.export_onnx:
        from mxnet_tpu.contrib import onnx as mxonnx

        # stateless forward (states=None) is the inference entry point
        path = mxonnx.export_model(model, input_shape=(1, args.bptt),
                                   input_type="int32",
                                   onnx_file_path=args.export_onnx)
        blk = mxonnx.import_to_gluon(path)
        probe = np.array(onp.array(stream[:1, :args.bptt], "int32"))
        with autograd.predict_mode():
            want = model(probe).asnumpy()
        got = blk(probe).asnumpy()
        err = float(onp.abs(got - want).max())
        print(f"ONNX export -> {path}; re-import max |diff| = {err:.2e}")


if __name__ == "__main__":
    main()
