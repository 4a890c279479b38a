"""PP-OCR det/rec (SURVEY §2.4 config 4).  The vision model zoo's tests
are in test_vision_zoo.py."""

import numpy as np
import jax.numpy as jnp

from paddle_tpu import nn, optimizer as opt
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.ocr import PPOCRDet, PPOCRRec, db_postprocess


def _img(*shape, seed=0):
    return Tensor(jnp.asarray(
        np.random.RandomState(seed).rand(*shape).astype(np.float32)))


class TestOCR:
    def test_det_train_maps_and_grad(self):
        det = PPOCRDet(scale=0.5)
        det.train()
        x = _img(1, 3, 64, 64, seed=5)
        out = det(x)["maps"]
        assert tuple(out.shape) == (1, 3, 64, 64)  # p, t, b maps at input res
        # BCE on prob map flows gradients to the backbone
        target = Tensor(jnp.zeros((1, 1, 64, 64), jnp.float32))
        p = out[:, :1]
        loss = nn.BCELoss()(p, target)
        loss.backward()
        g = det.backbone.stem[0].weight.grad
        assert g is not None and float(jnp.abs(g._data).max()) > 0

    def test_det_eval_mode_prob_only(self):
        det = PPOCRDet(scale=0.5)
        det.eval()
        out = det(_img(1, 3, 32, 32, seed=6))["maps"]
        assert tuple(out.shape) == (1, 1, 32, 32)

    def test_db_postprocess_finds_blob(self):
        pm = np.zeros((32, 32), np.float32)
        pm[5:10, 6:12] = 0.9
        boxes = db_postprocess(pm, thresh=0.5)
        assert len(boxes) == 1
        x0, y0, x1, y1 = boxes[0]
        assert (x0, y0, x1, y1) == (6, 5, 11, 9)

    def test_rec_ctc_training_step_reduces_loss(self):
        rec = PPOCRRec(num_classes=11, scale=0.5)
        x = _img(2, 3, 32, 256, seed=7)           # T = 8 columns
        labels = Tensor(jnp.asarray(
            np.random.RandomState(8).randint(1, 11, (2, 3)), jnp.int32))
        lens = Tensor(jnp.asarray([3, 3], jnp.int32))
        o = opt.Adam(learning_rate=3e-3, parameters=rec.parameters())
        losses = []
        for _ in range(4):
            logits = rec(x)
            loss = rec.loss(logits, labels, lens)
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]
