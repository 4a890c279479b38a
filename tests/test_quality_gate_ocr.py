"""Deterministic proxy quality gates (VERDICT r1 item 10; SURVEY §6):
PP-OCR detection (DB hmean), recognition (CTC character accuracy) and
detect -> crop -> recognise.

The reference's quality bars (BERT-base SST-2 92-93%, PP-OCRv4 accuracy)
need corpora this environment cannot download, so these gates train the
SAME model/loss/optimizer stacks on bundled synthetic data with fixed
seeds and assert accuracy thresholds — a regression tripwire for the
end-to-end training paths, not a replica of the published numbers
(documented in BASELINE.md rows 4-5).

The three cases share two trained nets and one set of shapes (ISSUE 52).
The eager tape compiles every primitive of every op at every shape: the
first step of either net is ~90 s and the later ones ~1 s, so the gates'
time is the number of (net, mode, shape) combinations, not of steps. The
det net trains ONCE at [8, 1, 64, 64], on batches that hold the det
gate's textured boxes and the pipeline's digit lines side by side, under
the whole `db_loss` (threshold map and mask included); the rec net
trains ONCE at [16, 1, 32, 64] on strips at random offsets, of which the
rec gate's fixed offset is one. Every evaluation pads to those batch
shapes, so each net is compiled twice (train, eval) in the whole file.
`TestOCR` (the models' own cases, from test_vision_ocr.py, which ended
the run alone at 306 s for three more net-compiles at shapes of its own)
comes last and runs at the same shapes, on what is compiled by then.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core import autograd as ag
from paddle_tpu.models.ocr import PPOCRDet, PPOCRRec, db_loss, db_postprocess
from _ocr_data import det_batch, line, rec_batch, strip_image

DET_B, REC_B = 8, 16


def _recalibrate(model, batches):
    """Run BatchNorm's running stats up to the FINAL weights (they lag by
    ~1/(1-momentum) steps on these short schedules; the update_bn pass
    torch's SWA uses for the same reason), then switch to eval."""
    with ag.no_grad():
        for imgs in batches:
            model(paddle.to_tensor(imgs))
    model.eval()


@pytest.fixture(scope="module")
def det():
    paddle.seed(7)
    model = PPOCRDet(in_channels=1, scale=0.5)
    opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                parameters=list(model.parameters()))
    rng = np.random.RandomState(0)
    for _ in range(40):
        imgs, tgt, _ = det_batch(rng, DET_B // 2, DET_B // 2)
        out = model(paddle.to_tensor(imgs))["maps"]
        loss = db_loss(out, tgt[:, 0], np.ones_like(tgt[:, 0]),
                       tgt[:, 1], tgt[:, 2])
        loss.backward()
        opt.step()
        opt.clear_grad()
    _recalibrate(model, (det_batch(rng, DET_B // 2, DET_B // 2)[0]
                         for _ in range(5)))
    return model


@pytest.fixture(scope="module")
def rec():
    paddle.seed(1)
    model = PPOCRRec(num_classes=11, in_channels=1)  # blank + 10
    opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                 parameters=list(model.parameters()))
    rng = np.random.RandomState(0)
    lens = paddle.to_tensor(np.full((REC_B,), 4, np.int32))
    for _ in range(70):
        imgs, labs = rec_batch(rng, REC_B)
        loss = model.loss(model(paddle.to_tensor(imgs)),
                          paddle.to_tensor(labs), lens)
        loss.backward()
        opt.step()
        opt.clear_grad()
    _recalibrate(model, (rec_batch(rng, REC_B)[0] for _ in range(8)))
    return model


def _prob_maps(det, imgs):
    """Eval-mode probability maps [N, 64, 64], DET_B scenes a call."""
    pad = -len(imgs) % DET_B
    imgs = np.concatenate([imgs, np.zeros((pad,) + imgs.shape[1:],
                                          np.float32)])
    out = [np.asarray(det(paddle.to_tensor(imgs[i:i + DET_B]))["maps"]
                      .numpy())[:, 0] for i in range(0, len(imgs), DET_B)]
    return np.concatenate(out)[:len(imgs) - pad]


def _read(rec, strips):
    """Greedy CTC decode of up to REC_B [1, 32, 64] strips, in one call."""
    imgs = np.zeros((REC_B, 1, 32, 64), np.float32)
    imgs[:len(strips)] = strips
    logits = np.asarray(rec(paddle.to_tensor(imgs)).numpy())[:len(strips)]
    out = []
    for path in logits.argmax(-1):
        keep = (path != 0) & (path != np.concatenate([[-1], path[:-1]]))
        out.append([int(p) - 1 for p in path[keep]])
    return out


def _chars_right(decoded, labels):
    return sum(int(d == r) for dec, ref in zip(decoded, labels)
               for d, r in zip(dec, ref))


def _iou(a, b):
    ix = max(0, min(a[2], b[2]) - max(a[0], b[0]) + 1)
    iy = max(0, min(a[3], b[3]) - max(a[1], b[1]) + 1)
    inter = ix * iy
    ua = ((a[2] - a[0] + 1) * (a[3] - a[1] + 1)
          + (b[2] - b[0] + 1) * (b[3] - b[1] + 1) - inter)
    return inter / ua


class TestOCRDetGate:
    def test_db_det_hmean(self, det):
        """The PP-OCR det path (backbone + DBFPN + DBHead + db_loss with
        OHEM/dice/threshold terms + db_postprocess) must reach hmean
        >= 0.70 at IoU 0.5 on the synthetic textured-box set (measured
        1.00 at these settings; the bar leaves seed/backend slack)."""
        imgs, _, gtb = det_batch(np.random.RandomState(123), 16, 0)
        tp = fp = fn = 0
        for prob, gt in zip(_prob_maps(det, imgs), gtb):
            matched = set()
            for pb in db_postprocess(prob, thresh=0.5, min_area=16):
                best, bi = 0.0, -1
                for gi, g in enumerate(gt):
                    if gi not in matched and _iou(pb, g) > best:
                        best, bi = _iou(pb, g), gi
                if best >= 0.5:
                    matched.add(bi)
                    tp += 1
                else:
                    fp += 1
            fn += len(gt) - len(matched)
        prec = tp / max(tp + fp, 1)
        rcl = tp / max(tp + fn, 1)
        hmean = 2 * prec * rcl / max(prec + rcl, 1e-9)
        assert hmean >= 0.70, \
            f"ocr det gate: hmean {hmean:.3f} (p={prec:.3f} r={rcl:.3f})"


class TestOCRRecGate:
    def test_ctc_rec_char_accuracy(self, rec):
        """The PP-OCR rec path (rec_mode backbone + CTC head + CTC loss)
        must read >= 80% of characters on the synthetic glyph set (the
        line 6 rows down a 32x64 strip: W/2 = 32 CTC steps, 4 labels)."""
        rng = np.random.RandomState(99)
        strips, labels = zip(*(line(rng) for _ in range(REC_B)))
        decoded = _read(rec, [strip_image(s, 6) for s in strips])
        acc = _chars_right(decoded, labels) / (4 * REC_B)
        assert acc >= 0.80, f"ocr rec gate: char acc {acc:.3f}"


class TestOCREndToEnd:
    def test_det_crop_rec_pipeline(self, det, rec):
        """End-to-end PP-OCR pipeline (VERDICT r2 item 8): det on 64x64
        scenes with a digit line at a random vertical offset -> band crop
        -> rec must read >= 50% of characters (measured ~0.9 at these
        settings; the bar leaves slack for seed/backend drift)."""
        rng = np.random.RandomState(321)
        N = 12
        imgs, _, labels = det_batch(rng, 0, N)
        crops, truth = [], []
        for im, prob, label in zip(imgs, _prob_maps(det, imgs), labels):
            boxes = db_postprocess(prob, thresh=0.5, min_area=16)
            if not boxes:
                continue
            x0, y0, x1, y1 = max(
                boxes, key=lambda b: (b[2] - b[0]) * (b[3] - b[1]))
            top = int(np.clip((y0 + y1) // 2 - 16, 0, 32))
            crops.append(im[:, top:top + 32])
            truth.append(label)
        assert len(crops) >= N - 2, f"det found only {len(crops)}/{N} lines"
        acc = _chars_right(_read(rec, crops), truth) / (4 * N)
        assert acc >= 0.50, f"ocr e2e gate: char acc {acc:.3f}"


def _noise(*shape, seed):
    return paddle.to_tensor(
        np.random.RandomState(seed).rand(*shape).astype(np.float32))


class TestOCR:
    """PP-OCR det/rec (SURVEY §2.4 config 4)."""

    def test_det_train_maps_and_grad(self):
        det = PPOCRDet(in_channels=1, scale=0.5)
        det.train()
        out = det(_noise(DET_B, 1, 64, 64, seed=5))["maps"]
        assert tuple(out.shape) == (DET_B, 3, 64, 64)  # p, t, b at input res
        # BCE on prob map flows gradients to the backbone
        target = paddle.to_tensor(np.zeros((DET_B, 1, 64, 64), np.float32))
        loss = nn.BCELoss()(out[:, :1], target)
        loss.backward()
        g = det.backbone.stem[0].weight.grad
        assert g is not None and float(jnp.abs(g._data).max()) > 0

    def test_det_eval_mode_prob_only(self, det):
        out = det(_noise(DET_B, 1, 64, 64, seed=6))["maps"]
        assert tuple(out.shape) == (DET_B, 1, 64, 64)

    def test_db_postprocess_finds_blob(self):
        pm = np.zeros((32, 32), np.float32)
        pm[5:10, 6:12] = 0.9
        assert db_postprocess(pm, thresh=0.5) == [(6, 5, 11, 9)]

    def test_rec_ctc_training_step_reduces_loss(self):
        rec = PPOCRRec(num_classes=11, in_channels=1)
        x = _noise(REC_B, 1, 32, 64, seed=7)            # T = 32 columns
        labels = paddle.to_tensor(np.random.RandomState(8).randint(
            1, 11, (REC_B, 4)).astype(np.int32))
        lens = paddle.to_tensor(np.full((REC_B,), 4, np.int32))
        o = paddle.optimizer.AdamW(learning_rate=3e-3,
                                   parameters=rec.parameters())
        losses = []
        for _ in range(4):
            loss = rec.loss(rec(x), labels, lens)
            loss.backward()
            o.step()
            o.clear_grad()
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]
