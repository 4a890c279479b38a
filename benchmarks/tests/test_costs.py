"""The operation and byte counts, checked by hand at the serving
configuration (ISSUE 23: weights 7.5 GB, 64 KiB of KV a token)."""

import json
import os

import pytest

from benchmarks.lib import costs
from benchmarks.lib.peaks import PEAKS

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_serving_configuration_by_hand():
    c = cfg("mistral-7b-v0.3-serve-d16")
    layer, outer = costs.dense_params(c)
    # q 4096x4096, k and v 4096x1024, o 4096x4096, three 4096x14336, 2 norms
    assert layer == 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 \
        + 2 * 4096 == 218_112_000
    assert outer == 2 * 32768 * 4096 + 4096
    assert costs.n_params(c) == 16 * 218_112_000 + 268_439_552
    assert costs.n_params(c) * 2 / 1e9 == pytest.approx(7.52, abs=0.01)
    assert costs.kv_bytes_per_token(c) == 64 * 1024
    # decode_step_budget by hand: 32 sequences of 1000 tokens
    assert costs.serve_step_bytes(7_516_000_000, c, 32_000) == \
        7_516_000_000 + 32_000 * 65_536


def test_attention_counts():
    c = cfg("mistral-7b-v0.3-serve-d16")
    assert costs.attended_pairs(1, 100) == 100
    assert costs.attended_pairs(4, 4) == 10          # 1 + 2 + 3 + 4
    assert costs.attended_pairs(256, 1024) == 256 * 1024 - 256 * 255 // 2
    flops, byts = costs.ragged_attention_cost(c, [(1, 100), (0, 50)], 16)
    assert flops == 4 * 32 * 128 * 100
    # 7 pages of 16 tokens, K and V, 8 kv heads x 128 x 2 B; q and out
    assert byts == 2 * 8 * 7 * 16 * 128 * 2 + 2 * 32 * 128 * 2
    t, which = costs.roofline_seconds(flops, byts, PEAKS["TPU v5 lite"])
    assert which == "bytes"


def test_training_counts():
    c = cfg("mistral-7b-v0.3-train-zero2-mp2")
    n = costs.n_params(c)
    assert n == c["num_hidden_layers"] * 218_112_000 + 268_439_552
    assert costs.train_flops_per_token(c, 8192) == \
        6.0 * n + 12.0 * c["num_hidden_layers"] * 32 * 128 * 8192
    flops, byts = costs.flash_causal_cost(c, 2, 8192, 16)
    pairs = 8192 * 8193 // 2
    assert flops == 3.5 * 4 * 2 * 16 * 128 * pairs
    t, which = costs.roofline_seconds(flops, byts, PEAKS["TPU v5 lite"])
    assert which == "flops"
