"""Vision model zoo, transforms and datasets (SURVEY §2.2 vision).  The
PP-OCR det/rec tests are in test_quality_gate_ocr.py, at the gates' shapes:
a file is one worker's unit of work under `--dist loadfile`."""

import numpy as np
import jax.numpy as jnp

from paddle_tpu import nn, optimizer as opt
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.vision import (FakeData, LeNet, MobileNetV3Small, resnet18,
                               resnet50, transforms)


def _img(*shape, seed=0):
    return Tensor(jnp.asarray(
        np.random.RandomState(seed).rand(*shape).astype(np.float32)))


class TestModels:
    def test_lenet_forward(self):
        m = LeNet(num_classes=10)
        out = m(_img(2, 1, 28, 28))
        assert tuple(out.shape) == (2, 10)

    def test_resnet18_forward_and_train_step(self):
        m = resnet18(num_classes=10)
        x = _img(2, 3, 32, 32, seed=1)
        y = m(x)
        assert tuple(y.shape) == (2, 10)
        labels = Tensor(jnp.asarray([1, 2], jnp.int64))
        loss = nn.CrossEntropyLoss()(y, labels)
        loss.backward()
        o = opt.SGD(learning_rate=0.1, parameters=m.parameters())
        o.step()
        assert np.isfinite(float(loss))

    def test_resnet50_forward(self):
        m = resnet50(num_classes=4)
        out = m(_img(1, 3, 64, 64, seed=2))
        assert tuple(out.shape) == (1, 4)

    def test_mobilenetv3_forward_and_features(self):
        m = MobileNetV3Small(num_classes=5, scale=0.5)
        out = m(_img(1, 3, 64, 64, seed=3))
        assert tuple(out.shape) == (1, 5)
        fe = MobileNetV3Small(num_classes=0, with_pool=False, scale=0.5,
                              feature_only=True)
        feats = fe(_img(1, 3, 64, 64, seed=4))
        assert len(feats) == 4
        # strides: 4, 8, 16, 32
        assert feats[0].shape[2] == 16 and feats[-1].shape[2] == 2


class TestTransformsDatasets:
    def test_pipeline(self):
        tf = transforms.Compose([
            transforms.Resize(40),
            transforms.RandomCrop(32),
            transforms.RandomHorizontalFlip(0.5),
            transforms.ToTensor(),
            transforms.Normalize([0.5] * 3, [0.5] * 3),
        ])
        img = (np.random.RandomState(0).rand(48, 48, 3) * 255).astype(
            np.uint8)
        out = tf(img)
        assert out.shape == (3, 32, 32)
        assert out.dtype == np.float32
        assert -1.1 <= out.min() and out.max() <= 1.1

    def test_fakedata_with_loader(self):
        from paddle_tpu.io import DataLoader
        ds = FakeData(num_samples=16, image_shape=(3, 8, 8), num_classes=3)
        dl = DataLoader(ds, batch_size=4, shuffle=True)
        batches = list(dl)
        assert len(batches) == 4
        xb, yb = batches[0]
        assert tuple(np.asarray(xb._data if hasattr(xb, "_data") else xb)
                     .shape) == (4, 3, 8, 8)
