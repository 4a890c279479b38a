"""Deterministic proxy quality gate (VERDICT r1 item 10; SURVEY §6):
PP-OCR recognition, CTC character accuracy.

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


def _rec_sample(rng, n_digits, H=32, pitch=16):
    """Render a digit string into a [1, H, W] image at fixed pitch.
    W = n_digits*16 gives the rec backbone (W/2 time axis) T=32 CTC
    steps for 4 labels."""
    W = n_digits * pitch
    img = np.zeros((1, H, W), np.float32)
    label = rng.randint(0, 10, n_digits)
    for i, d in enumerate(label):
        g = np.kron(glyph(int(d)), np.ones((4, 4), np.float32))  # 20x12
        img[0, 6:26, i * pitch + 2:i * pitch + 14] = g
    return img, label


class TestOCRRecGate:
    def test_ctc_rec_char_accuracy(self):
        """The PP-OCR rec path (rec_mode backbone + CTC head + CTC loss)
        must read >= 80% of characters on the synthetic glyph set."""
        from paddle_tpu.models.ocr import PPOCRRec
        paddle.seed(1)
        n_digits = 4
        model = PPOCRRec(num_classes=11, in_channels=1)  # blank + 10
        opt = paddle.optimizer.AdamW(learning_rate=3e-3,
                                     parameters=list(model.parameters()))
        rng = np.random.RandomState(0)
        B = 16

        def batch():
            imgs, labs = [], []
            for _ in range(B):
                im, lb = _rec_sample(rng, n_digits)
                imgs.append(im)
                labs.append(lb + 1)  # 0 is the CTC blank
            return (np.stack(imgs), np.stack(labs).astype(np.int32),
                    np.full((B,), n_digits, np.int32))

        for step in range(50):
            imgs, labs, lens = batch()
            logits = model(paddle.to_tensor(imgs))
            loss = model.loss(logits, paddle.to_tensor(labs),
                              paddle.to_tensor(lens))
            loss.backward()
            opt.step()
            opt.clear_grad()

        # recalibrate BatchNorm running stats against the FINAL weights
        # (they lag by ~1/(1-momentum) steps on this short schedule; the
        # update_bn pass torch's SWA uses for the same reason)
        from paddle_tpu.core import autograd as ag
        with ag.no_grad():
            for _ in range(15):
                imgs, _, _ = batch()
                model(paddle.to_tensor(imgs))

        # greedy CTC decode on a fresh eval batch
        rng_eval = np.random.RandomState(99)
        imgs, labs = [], []
        for _ in range(B):
            im, lb = _rec_sample(rng_eval, n_digits)
            imgs.append(im)
            labs.append(lb + 1)
        model.eval()
        logits = np.asarray(model(paddle.to_tensor(np.stack(imgs))).numpy())
        total = correct = 0
        for b in range(B):
            path = logits[b].argmax(-1)
            dec = []
            prev = -1
            for p in path:
                if p != prev and p != 0:
                    dec.append(int(p))
                prev = p
            ref = list(labs[b])
            L = min(len(dec), len(ref))
            correct += sum(1 for i in range(L) if dec[i] == ref[i])
            total += len(ref)
        acc = correct / total
        assert acc >= 0.80, f"ocr rec gate: char acc {acc:.3f}"
