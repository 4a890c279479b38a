"""The traffic generator: every seed gets the same multiset of work."""

import json
import os

import numpy as np

from benchmarks.lib import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


def test_open_loop_same_work_for_every_seed():
    m = dict(mix("chat-0.8knee"), order="seeded")
    a = traffic.open_loop(m, 40, 1, 32768, 4096)
    b = traffic.open_loop(m, 40, 3_000_000_001, 32768, 4096)
    assert len(a) == len(b) == round(m["rate_per_s"] * 40)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    ga = np.diff([r.due for r in a])
    gb = np.diff([r.due for r in b])
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert abs(ga.sum() - gb.sum()) < 2.0       # same gaps, other order
    assert a[0].due == 0 and a[-1].due < 40
    assert all(64 <= len(r.prompt) <= 3072 and 16 <= r.max_new <= 384
               and len(r.prompt) + r.max_new <= 4096 for r in a)
    again = traffic.open_loop(m, 40, 1, 32768, 4096)
    assert all(np.array_equal(x.prompt, y.prompt) and x.due == y.due
               for x, y in zip(a, again))


def test_fixed_order_replays_one_trace_with_other_tokens():
    m = mix("chat-0.8knee")
    assert m["order"] == "fixed"
    a = traffic.open_loop(m, 50, 1, 32768, 4096)
    b = traffic.open_loop(m, 50, 2, 32768, 4096)
    assert [(len(r.prompt), r.max_new, r.due) for r in a] == \
        [(len(r.prompt), r.max_new, r.due) for r in b]
    assert not np.array_equal(a[1].prompt, b[1].prompt)


def test_closed_loop_pool_and_shared_prefix():
    m = mix("decode-saturated")
    pool = traffic.closed_loop(m, 40, 5, 32768, 4096)
    assert len(pool) >= 2 * m["clients"]
    assert all(64 <= len(r.prompt) <= 256 and 512 <= r.max_new <= 1024
               for r in pool)
    shared = dict(m, shared_prefix={"tokens": 32, "groups": 2})
    pool = traffic.closed_loop(shared, 10, 5, 32768, 4096)
    assert np.array_equal(pool[0].prompt[:32], pool[2].prompt[:32])
    assert not np.array_equal(pool[0].prompt[:32], pool[1].prompt[:32])


def test_gamma_arrivals_keep_the_rate():
    g = traffic.draw_gaps({"process": "gamma", "cv": 3}, 6.0, 240,
                          np.random.default_rng(0))
    assert abs(g.sum() - 40.0) < 1e-9 and g.std() / g.mean() > 1.5
