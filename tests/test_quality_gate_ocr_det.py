"""Deterministic proxy quality gate (VERDICT r1 item 10; SURVEY §6):
PP-OCR detection, DB hmean.

The reference's quality bars (BERT-base SST-2 92-93%, PP-OCRv4 accuracy)
need corpora this environment cannot download, so these gates train the
SAME model/loss/optimizer stacks on bundled synthetic data with fixed
seeds and assert accuracy thresholds — a regression tripwire for the
end-to-end training paths, not a replica of the published numbers
(documented in BASELINE.md rows 4-5).  One gate a file (four, since
ISSUE 30: under `--dist loadfile` a file is one worker's unit of work,
and the four gates together were the run's longest, 599 s).
"""

import numpy as np

import paddle_tpu as paddle


def _det_sample(rng, H=64, W=64):
    """1-2 textured (checkerboard) rectangles on a noisy background +
    DB targets (shrink map, border-band threshold map/mask) + GT boxes."""
    img = rng.uniform(0.0, 0.15, (1, H, W)).astype(np.float32)
    shrink = np.zeros((H, W), np.float32)
    tmap = np.zeros((H, W), np.float32)
    tmask = np.zeros((H, W), np.float32)
    boxes = []
    for _ in range(rng.randint(1, 3)):
        for _try in range(20):
            bh, bw = rng.randint(12, 22), rng.randint(14, 26)
            y0 = rng.randint(2, H - bh - 2)
            x0 = rng.randint(2, W - bw - 2)
            if all(x0 + bw + 4 < px0 or px1 + 4 < x0
                   or y0 + bh + 4 < py0 or py1 + 4 < y0
                   for (px0, py0, px1, py1) in boxes):
                break
        else:
            continue
        yy, xx = np.mgrid[0:bh, 0:bw]
        img[0, y0:y0 + bh, x0:x0 + bw] = \
            0.55 + 0.45 * (((yy // 2) + (xx // 2)) % 2)
        shrink[y0 + 2:y0 + bh - 2, x0 + 2:x0 + bw - 2] = 1.0
        band = np.zeros((H, W), np.float32)
        band[max(0, y0 - 2):y0 + bh + 2, max(0, x0 - 2):x0 + bw + 2] = 1.0
        band[y0 + 2:y0 + bh - 2, x0 + 2:x0 + bw - 2] = 0.0
        tmap = np.maximum(tmap, band * 0.55)
        tmask = np.maximum(tmask, band)
        boxes.append((x0, y0, x0 + bw - 1, y0 + bh - 1))
    return img, shrink, tmap, tmask, boxes


def _det_batch(rng, B):
    cols = [[], [], [], [], []]
    for _ in range(B):
        for c, v in zip(cols, _det_sample(rng)):
            c.append(v)
    return (np.stack(cols[0]), np.stack(cols[1]), np.stack(cols[2]),
            np.stack(cols[3]), cols[4])


def _iou(a, b):
    ix = max(0, min(a[2], b[2]) - max(a[0], b[0]) + 1)
    iy = max(0, min(a[3], b[3]) - max(a[1], b[1]) + 1)
    inter = ix * iy
    ua = ((a[2] - a[0] + 1) * (a[3] - a[1] + 1)
          + (b[2] - b[0] + 1) * (b[3] - b[1] + 1) - inter)
    return inter / ua


class TestOCRDetGate:
    def test_db_det_hmean(self):
        """The PP-OCR det path (backbone + DBFPN + DBHead + db_loss with
        OHEM/dice/threshold terms + db_postprocess) must reach hmean
        >= 0.70 at IoU 0.5 on the synthetic textured-box set (measured
        1.00 at these settings; the bar leaves seed/backend slack)."""
        from paddle_tpu.models.ocr import PPOCRDet, db_loss, db_postprocess
        paddle.seed(7)
        model = PPOCRDet(in_channels=1, scale=0.5)
        opt = paddle.optimizer.Adam(learning_rate=3e-3,
                                    parameters=list(model.parameters()))
        rng = np.random.RandomState(0)
        for step in range(60):
            imgs, shr, tm, tk, _ = _det_batch(rng, 8)
            out = model(paddle.to_tensor(imgs))["maps"]
            loss = db_loss(out, shr, np.ones_like(shr), tm, tk)
            loss.backward()
            opt.step()
            opt.clear_grad()
        from paddle_tpu.core import autograd as ag
        with ag.no_grad():   # recalibrate BN running stats (as rec gate)
            for _ in range(10):
                imgs, *_ = _det_batch(rng, 8)
                model(paddle.to_tensor(imgs))
        model.eval()
        rng_eval = np.random.RandomState(123)
        tp = fp = fn = 0
        for _ in range(4):
            imgs, _, _, _, gtb = _det_batch(rng_eval, 4)
            probs = np.asarray(
                model(paddle.to_tensor(imgs))["maps"].numpy())
            for b in range(4):
                pred = db_postprocess(probs[b, 0], thresh=0.5, min_area=16)
                matched = set()
                for pb in pred:
                    best, bi = 0.0, -1
                    for gi, g in enumerate(gtb[b]):
                        if gi not in matched and _iou(pb, g) > best:
                            best, bi = _iou(pb, g), gi
                    if best >= 0.5:
                        matched.add(bi)
                        tp += 1
                    else:
                        fp += 1
                fn += len(gtb[b]) - len(matched)
        prec = tp / max(tp + fp, 1)
        rec = tp / max(tp + fn, 1)
        hmean = 2 * prec * rec / max(prec + rec, 1e-9)
        assert hmean >= 0.70, \
            f"ocr det gate: hmean {hmean:.3f} (p={prec:.3f} r={rec:.3f})"
