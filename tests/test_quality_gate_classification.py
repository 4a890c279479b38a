"""Deterministic proxy quality gate (VERDICT r1 item 10; SURVEY §6):
BERT-style fine-tune accuracy.

The reference's quality bars (BERT-base SST-2 92-93%, PP-OCRv4 accuracy)
need corpora this environment cannot download, so these gates train the
SAME model/loss/optimizer stacks on bundled synthetic data with fixed
seeds and assert accuracy thresholds — a regression tripwire for the
end-to-end training paths, not a replica of the published numbers
(documented in BASELINE.md rows 4-5).

The PTQ gate (from test_quantization_depth.py, which trained the same
model on the same data in another process) reads a copy of the one
fine-tuned model: one training, two gates (ISSUE 52).
"""

import copy

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.quantization import PTQ, HistObserver, QuantConfig


def _sentiment_corpus(n, seed, seq=16):
    """Label = which polarity's words dominate; >=5-token margin keeps
    the task separable for a tiny counting transformer; token 1 = CLS."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, seq), np.int32)
    y = np.zeros((n,), np.int64)
    for i in range(n):
        while True:
            k = rng.randint(2, seq - 2)
            if abs(2 * k - (seq - 1)) >= 5:
                break
        pos = rng.choice(np.arange(10, 30), k)
        neg = rng.choice(np.arange(30, 50), seq - 1 - k)
        toks = np.concatenate([pos, neg])
        rng.shuffle(toks)
        X[i, 0] = 1
        X[i, 1:] = toks
        y[i] = int(k > (seq - 1 - k))
    return X, y


@pytest.fixture(scope="module")
def finetuned():
    """The SST-2 fine-tune path (model + CE loss + AdamW + scheduler), in
    eval mode, and the dev set."""
    from paddle_tpu.models.bert import (BertForSequenceClassification,
                                        bert_tiny_config)
    paddle.seed(0)
    cfg = bert_tiny_config(vocab_size=64, hidden_size=64,
                           num_hidden_layers=2, num_attention_heads=4,
                           intermediate_size=128,
                           max_position_embeddings=32, num_labels=2)
    model = BertForSequenceClassification(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=list(model.parameters()))
    Xtr, ytr = _sentiment_corpus(512, 0)
    B = 32
    for epoch in range(10):
        perm = np.random.RandomState(epoch).permutation(len(Xtr))
        for i in range(0, len(Xtr), B):
            idx = perm[i:i + B]
            loss, _ = model(paddle.to_tensor(Xtr[idx]),
                            labels=paddle.to_tensor(ytr[idx]))
            loss.backward()
            opt.step()
            opt.clear_grad()
    model.eval()
    return (model,) + _sentiment_corpus(128, 1)


def _accuracy(model, X, y):
    return (np.asarray(model(paddle.to_tensor(X)).numpy()).argmax(-1)
            == y).mean()


class TestClassificationGate:
    def test_bert_style_finetune_accuracy(self, finetuned):
        """The fine-tuned model must reach >= 92% on the separable
        synthetic dev set."""
        acc = _accuracy(*finetuned)
        assert acc >= 0.92, f"classification gate: dev acc {acc:.3f}"


class TestPTQAccuracyGate:
    def test_bert_gate_survives_ptq_int8(self, finetuned):
        """PTQ weight-only-int8 must not break the classification gate:
        quantized accuracy within 2 points of the fp32 model's."""
        model, Xdev, ydev = finetuned
        fp_acc = _accuracy(model, Xdev, ydev)
        model = copy.deepcopy(model)
        ptq = PTQ(QuantConfig(activation=HistObserver))
        ptq.quantize(model)
        model(paddle.to_tensor(Xdev[:64]))       # calibration pass
        ptq.convert(model)
        q_acc = _accuracy(model, Xdev, ydev)
        assert len(ptq.observers) > 0
        assert q_acc >= fp_acc - 0.02, (q_acc, fp_acc)
        assert q_acc >= 0.90, q_acc
