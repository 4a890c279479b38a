"""`moe_moved_pair_share.train` (PR 67): the entry of BENCHMARK.json has
its reader file, and the reader gives 100 x pair rows moved / pairs
routed over the window's steps — and nothing, not an error, where a step
hands out no such count (the parent's steps, which the driver reads with
this reader) or the system keeps no routing rows."""

import json
import os
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = "moe_moved_pair_share.train"


def _reader():
    from benchmarks.run import load_reader
    return load_reader(NAME)


def _harness(rows, steps=None):
    system = types.SimpleNamespace(routing=rows) if rows is not None \
        else types.SimpleNamespace()
    return types.SimpleNamespace(
        counters={"system": system,
                  "steps_in_window": len(rows or []) if steps is None
                  else steps},
        reduced=None)


def test_the_entry_has_its_reader_file():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "trainer step",
        "moves": "train_tok_s_chip",
        "workloads": ["mellum2-train-ep4share-8k"]}
    assert entry["moves"] in {m["name"] for m in bench["end_to_end"]}
    assert hasattr(_reader(), "read")


STEP = {"loss": 1.0, "moe_pairs_routed": 524288.0,
        "moe_pairs_held": 131000.0}


@pytest.mark.parametrize("rows, steps, want", [
    # every pass walks all T k rows
    ([dict(STEP, moe_pair_rows_moved=524288.0)] * 3, None, 100.0),
    # whole chunks up to the last owned row: 3 of 8 in each of 4 layers
    ([dict(STEP, moe_pair_rows_moved=196608.0)] * 3, None, 37.5),
    # the window's steps alone: the warm-up's first row is not read
    ([dict(STEP, moe_pair_rows_moved=524288.0),
      dict(STEP, moe_pair_rows_moved=131072.0),
      dict(STEP, moe_pair_rows_moved=196608.0)], 2, 31.25),
    # the parent's steps: the routing counts without the new key
    ([dict(STEP)] * 3, None, None),
    # a system with no routing rows, and one with none in the window
    (None, 3, None),
    ([dict(STEP, moe_pair_rows_moved=524288.0)], 0, None),
])
def test_reads_moved_over_routed_or_nothing(rows, steps, want):
    got = _reader().read(_harness(rows, steps))
    assert got == want if want is None else got == pytest.approx(want)
