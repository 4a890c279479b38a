"""Deterministic proxy quality gate (VERDICT r1 item 10; SURVEY §6):
PP-OCR detect -> crop -> recognise.

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
from _ocr_data import glyph


class TestOCREndToEnd:
    def test_det_crop_rec_pipeline(self):
        """End-to-end PP-OCR pipeline (VERDICT r2 item 8): train det on
        64x64 scenes with a digit line at a random vertical offset, train
        rec on 32x64 line strips, then det -> band crop -> rec on fresh
        scenes must read >= 50% of characters (measured ~0.9 at these
        settings; the bar leaves slack for seed/backend drift)."""
        from paddle_tpu.models.ocr import (PPOCRDet, PPOCRRec, db_loss,
                                           db_postprocess)
        from paddle_tpu.core import autograd as ag
        paddle.seed(11)
        rng = np.random.RandomState(0)

        def line(rng):
            strip = np.zeros((20, 64), np.float32)
            label = rng.randint(0, 10, 4)
            for i, d in enumerate(label):
                g = np.kron(glyph(int(d)), np.ones((4, 4), np.float32))
                strip[:, i * 16 + 2:i * 16 + 14] = g
            return strip, label

        def scene(rng):
            img = np.zeros((1, 64, 64), np.float32)
            strip, label = line(rng)
            dy = rng.randint(2, 42)
            img[0, dy:dy + 20] = strip
            shrink = np.zeros((64, 64), np.float32)
            shrink[dy + 2:dy + 18, 4:60] = 1.0
            return img, shrink, label

        det = PPOCRDet(in_channels=1, scale=0.5)
        dopt = paddle.optimizer.Adam(learning_rate=3e-3,
                                     parameters=list(det.parameters()))
        for _ in range(35):
            imgs, shr = zip(*((im, s) for im, s, _ in
                              (scene(rng) for _ in range(8))))
            imgs, shr = np.stack(imgs), np.stack(shr)
            out = det(paddle.to_tensor(imgs))["maps"]
            loss = db_loss(out, shr, np.ones_like(shr))
            loss.backward()
            dopt.step()
            dopt.clear_grad()

        rec = PPOCRRec(num_classes=11, in_channels=1)
        ropt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                      parameters=list(rec.parameters()))
        for _ in range(60):
            imgs, labs = [], []
            for _ in range(16):
                strip, lb = line(rng)
                im = np.zeros((1, 32, 64), np.float32)
                # random vertical offset: the det crop centers the line
                # only approximately, so rec must train offset-robust
                off = rng.randint(0, 12)
                im[0, off:off + 20] = strip
                imgs.append(im)
                labs.append(lb + 1)
            logits = rec(paddle.to_tensor(np.stack(imgs)))
            loss = rec.loss(logits, paddle.to_tensor(
                np.stack(labs).astype(np.int32)),
                paddle.to_tensor(np.full((16,), 4, np.int32)))
            loss.backward()
            ropt.step()
            ropt.clear_grad()

        with ag.no_grad():   # BN recalibration for both nets
            for _ in range(8):
                det(paddle.to_tensor(np.stack(
                    [scene(rng)[0] for _ in range(8)])))
                imgs = []
                for _ in range(16):
                    strip, _ = line(rng)
                    im = np.zeros((1, 32, 64), np.float32)
                    off = rng.randint(0, 12)
                    im[0, off:off + 20] = strip
                    imgs.append(im)
                rec(paddle.to_tensor(np.stack(imgs)))

        det.eval()
        rec.eval()
        rng_eval = np.random.RandomState(321)
        total = correct = found = 0
        N = 12
        for _ in range(N):
            im, _, label = scene(rng_eval)
            pm = np.asarray(det(paddle.to_tensor(im[None]))["maps"].numpy())
            boxes = db_postprocess(pm[0, 0], thresh=0.5, min_area=16)
            total += 4
            if not boxes:
                continue
            found += 1
            x0, y0, x1, y1 = max(
                boxes, key=lambda b: (b[2] - b[0]) * (b[3] - b[1]))
            top = int(np.clip((y0 + y1) // 2 - 16, 0, 32))
            crop = im[0, top:top + 32, :64]
            logits = np.asarray(
                rec(paddle.to_tensor(crop[None, None])).numpy())
            path = logits[0].argmax(-1)
            dec, prev = [], -1
            for p in path:
                if p != prev and p != 0:
                    dec.append(int(p) - 1)
                prev = p
            correct += sum(1 for i in range(min(len(dec), 4))
                           if dec[i] == label[i])
        assert found >= N - 2, f"det found only {found}/{N} lines"
        acc = correct / total
        assert acc >= 0.50, f"ocr e2e gate: char acc {acc:.3f}"
