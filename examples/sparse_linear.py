"""Sparse linear model on LibSVM data, trained on device in CSR form.

Reference flow: example/sparse/linear_classification (LibSVMIter feeding a
sparse dot) — here the CSR triple lives in HBM and every step is
``sparse.dot`` (gather × multiply → segment_sum on device); the feature
matrix is never densified.

Run:  python examples/sparse_linear.py [path.libsvm]
(with no path, a synthetic sparse binary-classification set is generated)
"""
import os
import sys
import tempfile

import numpy as onp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, io, nd  # noqa: E402
from mxnet_tpu.ndarray.ndarray import NDArray  # noqa: E402
from mxnet_tpu.ndarray import sparse  # noqa: E402


def make_synthetic(path, n=512, d=100, density=0.05, seed=7):
    rng = onp.random.RandomState(seed)
    w_true = rng.randn(d)
    with open(path, "w") as f:
        for _ in range(n):
            nnz = max(1, int(d * density))
            cols = sorted(rng.choice(d, nnz, replace=False))
            vals = rng.randn(nnz)
            y = 1 if sum(w_true[c] * v for c, v in zip(cols, vals)) > 0 \
                else 0
            f.write(str(y) + " " +
                    " ".join(f"{c}:{v:.4f}" for c, v in zip(cols, vals)) +
                    "\n")
    return d


def main():
    if len(sys.argv) > 1:
        path, d = sys.argv[1], None
    else:
        path = os.path.join(tempfile.gettempdir(), "sparse_linear.libsvm")
        d = make_synthetic(path)

    it = io.LibSVMIter(path, data_shape=(d,), batch_size=64, sparse=True,
                       last_batch_handle="discard")
    w = NDArray(onp.zeros((d,), "float32"))
    b = NDArray(onp.zeros((), "float32"))
    w.attach_grad()
    b.attach_grad()
    lr = 1.0

    for epoch in range(25):
        it.reset()
        total, count = 0.0, 0
        for batch in it:
            x, y = batch.data[0], batch.label[0]  # x: device CSRNDArray
            with autograd.record():
                logit = sparse.dot(x, w) + b
                # logistic loss, numerically stable
                loss = nd.mean(nd.relu(logit) - logit * y +
                               nd.log1p(nd.exp(-nd.abs(logit))))
            loss.backward()
            w._set_data(w._data - lr * w.grad._data)
            b._set_data(b._data - lr * b.grad._data)
            w.grad._set_data(w.grad._data * 0)
            b.grad._set_data(b.grad._data * 0)
            total += float(loss.asnumpy())
            count += 1
        print(f"epoch {epoch}: loss {total / max(count, 1):.4f}")

    # train accuracy
    it.reset()
    hit = tot = 0
    for batch in it:
        x, y = batch.data[0], batch.label[0].asnumpy()
        p = (sparse.dot(x, w) + b).asnumpy() > 0
        hit += int((p == (y > 0.5)).sum())
        tot += len(y)
    print(f"train accuracy: {hit / tot:.3f}")
    return hit / tot


if __name__ == "__main__":
    acc = main()
    assert acc > 0.9, f"sparse linear model failed to fit: acc={acc}"
