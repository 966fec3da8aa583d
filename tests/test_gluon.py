"""Gluon blocks/layers (reference: tests/python/unittest/test_gluon.py)."""
import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import np, npx, autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.base import MXNetError
from mxnet_tpu.test_utils import assert_almost_equal


def test_dense_shapes_and_deferred_init():
    layer = nn.Dense(5)
    layer.initialize()
    x = np.ones((2, 7))
    out = layer(x)
    assert out.shape == (2, 5)
    assert layer.weight.shape == (5, 7)
    assert layer.bias.shape == (5,)


def test_dense_no_flatten():
    layer = nn.Dense(5, flatten=False)
    layer.initialize()
    out = layer(np.ones((2, 3, 7)))
    assert out.shape == (2, 3, 5)


def test_collect_params_names():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(2))
    params = net.collect_params()
    assert "0.weight" in params and "1.bias" in params


def test_param_grad_after_backward():
    layer = nn.Dense(3)
    layer.initialize()
    x = np.ones((2, 4))
    with autograd.record():
        loss = layer(x).sum()
    loss.backward()
    assert layer.weight.grad().shape == (3, 4)
    assert float(abs(layer.bias.grad()).sum()) > 0


def test_hybridize_consistency():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize()
    x = mx.np.random.uniform(size=(4, 6))
    eager = net(x).asnumpy()
    net.hybridize()
    hybrid = net(x).asnumpy()
    assert_almost_equal(eager, hybrid, rtol=1e-5, atol=1e-5)
    # cache hit for same signature, retrace for new shape
    y = net(mx.np.random.uniform(size=(2, 6)))
    assert y.shape == (2, 3)


def test_hybridize_param_update_visible():
    net = nn.Dense(2, in_units=2)
    net.initialize()
    net.hybridize()
    x = np.ones((1, 2))
    out1 = net(x).asnumpy()
    net.weight.set_data(net.weight.data() + 1)
    out2 = net(x).asnumpy()
    assert not onp.allclose(out1, out2)


def test_conv_pool_shapes():
    x = np.ones((2, 3, 16, 16))
    conv = nn.Conv2D(8, kernel_size=3, padding=1)
    conv.initialize()
    assert conv(x).shape == (2, 8, 16, 16)
    assert conv.weight.shape == (8, 3, 3, 3)
    conv_s = nn.Conv2D(8, kernel_size=3, strides=2, padding=1)
    conv_s.initialize()
    assert conv_s(x).shape == (2, 8, 8, 8)
    assert nn.MaxPool2D(2, 2)(x).shape == (2, 3, 8, 8)
    assert nn.AvgPool2D(2, 2)(x).shape == (2, 3, 8, 8)
    assert nn.GlobalAvgPool2D()(x).shape == (2, 3, 1, 1)
    assert nn.GlobalMaxPool2D()(x).shape == (2, 3, 1, 1)


def test_conv1d_3d():
    x1 = np.ones((2, 3, 20))
    c1 = nn.Conv1D(4, kernel_size=3, padding=1)
    c1.initialize()
    assert c1(x1).shape == (2, 4, 20)
    x3 = np.ones((1, 2, 4, 8, 8))
    c3 = nn.Conv3D(4, kernel_size=3, padding=1)
    c3.initialize()
    assert c3(x3).shape == (1, 4, 4, 8, 8)


def test_conv_groups():
    x = np.ones((2, 4, 8, 8))
    conv = nn.Conv2D(8, kernel_size=3, padding=1, groups=2)
    conv.initialize()
    assert conv(x).shape == (2, 8, 8, 8)
    assert conv.weight.shape == (8, 2, 3, 3)


def test_conv_transpose():
    x = np.ones((2, 3, 8, 8))
    deconv = nn.Conv2DTranspose(4, kernel_size=2, strides=2)
    deconv.initialize()
    assert deconv(x).shape == (2, 4, 16, 16)


def test_conv_vs_numpy_reference():
    # 1x1 conv equals matmul over channels
    x = onp.random.randn(1, 3, 4, 4).astype("float32")
    conv = nn.Conv2D(2, kernel_size=1, use_bias=False)
    conv.initialize()
    out = conv(np.array(x)).asnumpy()
    w = conv.weight.data().asnumpy()  # (2, 3, 1, 1)
    ref = onp.einsum("nchw,oc->nohw", x, w[:, :, 0, 0])
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-4)


def test_batchnorm_train_vs_eval():
    bn = nn.BatchNorm()
    bn.initialize()
    x = mx.np.random.uniform(1.0, 2.0, size=(4, 3, 5, 5))
    with autograd.record():
        out_train = bn(x)
    # batch-normalized output: ~zero mean per channel
    m = out_train.asnumpy().mean(axis=(0, 2, 3))
    assert onp.allclose(m, 0, atol=1e-4)
    # running stats moved toward batch stats
    rm = bn.running_mean.data().asnumpy()
    assert (rm > 0).all()
    out_eval = bn(x)  # uses running stats now
    assert not onp.allclose(out_eval.asnumpy(), out_train.asnumpy())


def test_layernorm_groupnorm():
    # rows of 32: over 4 uniform values a row's variance can near epsilon
    # (1e-5), and the normalised row's std then falls short of 1
    x = mx.np.random.uniform(size=(2, 6, 32))
    ln = nn.LayerNorm()
    ln.initialize()
    out = ln(x).asnumpy()
    assert onp.allclose(out.mean(-1), 0, atol=1e-5)
    assert onp.allclose(out.std(-1), 1, atol=1e-2)
    gn = nn.GroupNorm(num_groups=3)
    gn.initialize()
    assert gn(x).shape == x.shape


def test_dropout_train_eval():
    do = nn.Dropout(0.5)
    x = np.ones((100, 100))
    out_eval = do(x)
    assert_almost_equal(out_eval, x.asnumpy())  # identity at predict
    with autograd.record():
        out_train = do(x).asnumpy()
    assert (out_train == 0).mean() > 0.3  # roughly half dropped
    kept = out_train[out_train != 0]
    assert onp.allclose(kept, 2.0)  # scaled by 1/keep


def test_embedding():
    emb = nn.Embedding(10, 4)
    emb.initialize()
    idx = np.array([[1, 2], [3, 4]])
    assert emb(idx).shape == (2, 2, 4)


def test_save_load_parameters(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net.initialize()
    f = str(tmp_path / "net.params.npz")
    net.save_parameters(f)
    net2 = nn.HybridSequential()
    net2.add(nn.Dense(4, in_units=3), nn.Dense(2, in_units=4))
    net2.load_parameters(f)
    x = mx.np.random.uniform(size=(2, 3))
    assert_almost_equal(net(x), net2(x))


def test_sequential_slicing():
    net = nn.HybridSequential()
    net.add(nn.Dense(4), nn.Dense(3), nn.Dense(2))
    assert len(net) == 3
    sub = net[1:]
    assert len(sub) == 2


def test_cast():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    net.cast("float16")
    assert str(net.weight.data().dtype) == "float16"
    net.cast("float32")
    out = net(np.ones((1, 3)))
    assert str(out.dtype) == "float32"


def test_export_symbolblock_import(tmp_path):
    net = nn.HybridSequential()
    net.add(nn.Dense(4, activation="relu", in_units=3), nn.Dense(2,
                                                                 in_units=4))
    net.initialize()
    net.hybridize()
    x = mx.np.random.uniform(size=(2, 3))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "model")
    sym_file, param_file = net.export(prefix)
    loaded = gluon.SymbolBlock.imports(sym_file, "data0", param_file)
    got = loaded(x).asnumpy()
    assert_almost_equal(ref, got, rtol=1e-5, atol=1e-5)


def test_uninitialized_raises():
    net = nn.Dense(2, in_units=3)
    with pytest.raises(MXNetError):
        net(np.ones((1, 3)))


def test_zero_grad():
    net = nn.Dense(2, in_units=3)
    net.initialize()
    with autograd.record():
        net(np.ones((1, 3))).sum().backward()
    assert float(abs(net.weight.grad()).sum()) > 0
    net.zero_grad()
    assert float(abs(net.weight.grad()).sum()) == 0


def test_contrib_data_vision_bbox_transforms():
    """gluon.contrib.data.vision (reference: contrib/data/vision): bbox
    Block transforms keep images and boxes consistent, and the detection
    loader pads ragged box counts with -1."""
    import random as pyrandom

    from mxnet_tpu.gluon.contrib.data import vision as cdv
    from mxnet_tpu.ndarray.ndarray import NDArray

    pyrandom.seed(11)
    img = onp.arange(20 * 30 * 3, dtype="uint8").reshape(20, 30, 3)
    bbox = onp.array([[2, 3, 10, 12, 7], [15, 5, 28, 18, 2]], "float32")

    # flip with p=1: x coords mirror, extra column intact
    fi, fb = cdv.ImageBboxRandomFlipLeftRight(p=1.0)(NDArray(img),
                                                     NDArray(bbox))
    assert (fi.asnumpy() == img[:, ::-1]).all()
    got = fb.asnumpy()
    assert_almost_equal(got[0, :4], [30 - 10, 3, 30 - 2, 12], rtol=1e-6)
    assert got[0, 4] == 7 and got[1, 4] == 2

    # crop: second box's center is outside -> dropped; first translated
    ci, cb = cdv.ImageBboxCrop((0, 0, 14, 14))(NDArray(img), NDArray(bbox))
    assert ci.shape == (14, 14, 3)
    assert cb.shape[0] == 1
    assert_almost_equal(cb.asnumpy()[0, :4], [2, 3, 10, 12], rtol=1e-6)

    # expand: boxes translate by the offset; canvas filled
    ei, eb = cdv.ImageBboxRandomExpand(p=1.0, max_ratio=2, fill=9)(
        NDArray(img), NDArray(bbox))
    eia = ei.asnumpy()
    assert eia.shape[0] >= 20 and eia.shape[1] >= 30
    w_off = eb.asnumpy()[0, 0] - 2
    h_off = eb.asnumpy()[0, 1] - 3
    assert w_off >= 0 and h_off >= 0
    assert (eia[int(h_off):int(h_off) + 20,
                int(w_off):int(w_off) + 30] == img).all()

    # resize: coordinates scale with the image
    ri, rb = cdv.ImageBboxResize(60, 40)(NDArray(img), NDArray(bbox))
    assert ri.shape[:2] == (40, 60)
    assert_almost_equal(rb.asnumpy()[0, :4], [4, 6, 20, 24], rtol=1e-5)

    # constrained random crop keeps at least one valid box
    ki, kb = cdv.ImageBboxRandomCropWithConstraints(p=1.0)(
        NDArray(img), NDArray(bbox))
    assert kb.shape[0] >= 1 and ki.asnumpy().ndim == 3

    # detection loader pads ragged box counts with -1
    samples = [(onp.zeros((8, 8, 3), "float32"),
                onp.ones((n, 5), "float32")) for n in (1, 3, 2, 3)]
    ds = gluon.data.SimpleDataset(samples)
    loader = cdv.ImageBboxDataLoader(ds, batch_size=2)
    batches = list(loader)
    assert batches[0][1].shape == (2, 3, 5)
    lbl = batches[0][1].asnumpy()
    assert (lbl[0, 1:] == -1).all() and (lbl[1] == 1).all()


def test_transforms_random_apply():
    from mxnet_tpu.gluon.data.vision import transforms as T
    from mxnet_tpu import random as mxrand

    flip = T.RandomFlipLeftRight()
    img = onp.zeros((4, 4, 3), "uint8")
    img[:, 0] = 255  # left column marked
    always = T.RandomApply([flip], p=1.0)
    never = T.RandomApply([flip], p=0.0)
    out_never = never(img)
    assert (onp.asarray(out_never) == img).all()
    # p=1: the wrapped flip itself is random; apply several times and
    # require at least one flip to have occurred
    flipped = any((onp.asarray(always(img)) != img).any()
                  for _ in range(16))
    assert flipped
    assert T.HybridCompose is T.Compose
    assert T.HybridRandomApply is T.RandomApply


def test_image_record_and_list_datasets(tmp_path):
    """RecordFileDataset / ImageRecordDataset / ImageListDataset
    (reference: gluon/data/dataset.py:390, vision/datasets.py:238+)."""
    from mxnet_tpu import image, recordio
    from mxnet_tpu.gluon.data import RecordFileDataset
    from mxnet_tpu.gluon.data.vision.datasets import (ImageListDataset,
                                                      ImageRecordDataset)

    prefix = str(tmp_path / "pack")
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(4):
        img = onp.full((8, 8, 3), 10 * i, dtype="uint8")
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, quality=95))
    w.close()

    raw = RecordFileDataset(prefix + ".rec")
    assert len(raw) == 4 and isinstance(raw[0], bytes)

    ds = ImageRecordDataset(prefix + ".rec")
    assert len(ds) == 4
    img0, label0 = ds[2]
    assert float(label0) == 2.0
    assert img0.shape[2] == 3 and abs(float(img0.asnumpy().mean()) - 20) < 6

    # list dataset from an in-memory list and a .lst file
    import os
    pngs = []
    for i in range(2):
        arr = onp.full((4, 5, 3), 30 * i, "uint8")
        path = tmp_path / f"im{i}.png"
        image.imwrite(str(path), arr) if hasattr(image, "imwrite") else \
            __import__("PIL.Image", fromlist=["Image"]).fromarray(arr).save(
                str(path))
        pngs.append(path.name)
    lst = ImageListDataset(root=str(tmp_path),
                           imglist=[(0.0, pngs[0]), (1.0, pngs[1])])
    im, lab = lst[1]
    assert float(lab) == 1.0 and im.shape[:2] == (4, 5)
    (tmp_path / "files.lst").write_text(
        f"0\t0.0\t{pngs[0]}\n1\t1.0\t{pngs[1]}\n")
    lst2 = ImageListDataset(root=str(tmp_path), imglist="files.lst")
    assert len(lst2) == 2 and float(lst2[0][1]) == 0.0


def _rec_to_float(sample):
    return onp.asarray(sample[0], "float32"), sample[1]


def test_record_dataset_process_workers_and_guards(tmp_path):
    """RecordFileDataset pickles for spawned workers; missing .idx raises."""
    from mxnet_tpu import recordio
    from mxnet_tpu.gluon.data import DataLoader
    from mxnet_tpu.gluon.data.vision import ImageRecordDataset

    prefix = str(tmp_path / "p")
    w = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(8):
        img = onp.full((6, 6, 3), 5 * i, dtype="uint8")
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, quality=95))
    w.close()
    ds = ImageRecordDataset(prefix + ".rec").transform(_rec_to_float)
    # pickles and round-trips through spawned worker processes
    import pickle

    pickle.loads(pickle.dumps(ds))
    out = [b for b in DataLoader(ds, batch_size=4, num_workers=1)]
    assert len(out) == 2 and out[0][0].shape == (4, 6, 6, 3)

    import os
    os.remove(prefix + ".idx")
    with pytest.raises(MXNetError, match="idx"):
        ImageRecordDataset(prefix + ".rec")
