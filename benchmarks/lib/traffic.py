"""The one general traffic generator.

A mix is a data file under ``traffic/``: lengths, rates, bursts and
sharing as parameters.  Every seed gets the SAME multiset of lengths and
of gaps between arrivals and other token values: the multiset is drawn
with the mix's own fixed ``set_seed``.  With ``"order": "seeded"`` (the
default) ``--seed`` permutes it; with ``"order": "fixed"`` every seed
replays the one arrival trace that ``set_seed`` drew, and only the token
values (and the weights) follow ``--seed``.  So two seeds give a run the
same amount of work, and a run-to-run difference is the system's, not
the draw's.  A tail under queueing needs the fixed order: which long
prompts meet decides it (PERF.md, Findings of PR 23).
"""

from __future__ import annotations

from typing import List, Mapping, NamedTuple, Optional

import numpy as np


class Req(NamedTuple):
    due: float              # seconds from the window's start (open loop)
    prompt: np.ndarray      # int32 token ids
    max_new: int


def draw_lengths(spec: Mapping, n: int, rng: np.random.Generator
                 ) -> np.ndarray:
    """``n`` whole numbers from ``{"dist": lognormal|uniform, ...,
    "min": a, "max": b}``, clipped to [min, max]."""
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif dist == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", int(x.max()) + 1)
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def draw_gaps(spec: Mapping, rate: float, n: int, rng: np.random.Generator
              ) -> np.ndarray:
    """``n`` gaps between arrivals that add up to exactly ``n / rate``
    seconds: Poisson (``{"process": "poisson"}``) or gamma with a stated
    coefficient of variation (``{"process": "gamma", "cv": 3}``)."""
    proc = spec.get("process", "poisson")
    if proc == "poisson":
        g = rng.exponential(1.0, n)
    elif proc == "gamma":
        k = 1.0 / float(spec["cv"]) ** 2
        g = rng.gamma(k, 1.0 / k, n)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return g * (n / rate) / g.sum()


def _order(mix: Mapping, n: int, rng) -> np.ndarray:
    order = mix.get("order", "seeded")
    if order == "fixed":
        return np.arange(n)
    if order != "seeded":
        raise ValueError(f"unknown order {order!r}")
    return rng.permutation(n)


def _prompts(mix: Mapping, n: int, vocab: int, set_rng, rng,
             max_total: Optional[int]) -> List[Req]:
    plen = draw_lengths(mix["prompt_len"], n, set_rng)
    olen = draw_lengths(mix["output_len"], n, set_rng)
    order = _order(mix, n, rng)
    plen, olen = plen[order], olen[order]
    shared = mix.get("shared_prefix") or {}
    groups = int(shared.get("groups", 1))
    prefixes = [rng.integers(0, vocab, int(shared["tokens"]), np.int32)
                for _ in range(groups)] if shared else []
    out = []
    for i in range(n):
        p, o = int(plen[i]), int(olen[i])
        if max_total is not None and p + o > max_total:
            p = max_total - o
        toks = rng.integers(0, vocab, p, dtype=np.int32)
        if prefixes:
            pre = prefixes[i % groups][:max(p - 1, 0)]
            toks[:len(pre)] = pre
        out.append(Req(0.0, toks, o))
    return out


def open_loop(mix: Mapping, seconds: float, seed: int, vocab: int,
              max_total: Optional[int] = None) -> List[Req]:
    """Requests due inside a window of ``seconds``, by arrival time."""
    rate = float(mix["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    set_rng = np.random.default_rng(int(mix.get("set_seed", 0)))
    rng = np.random.default_rng(int(seed))
    gaps = draw_gaps(mix.get("arrivals", {}), rate, n, set_rng)
    gaps = gaps[_order(mix, n, rng)]
    due = np.cumsum(gaps) - gaps[0]        # the first is due at 0
    reqs = _prompts(mix, n, vocab, set_rng, rng, max_total)
    return [r._replace(due=float(t)) for r, t in zip(reqs, due)]


def closed_loop(mix: Mapping, seconds: float, seed: int, vocab: int,
                max_total: Optional[int] = None) -> List[Req]:
    """The ``pool`` requests that the clients draw from, in order and
    round again: the same pool whatever the window's length."""
    n = int(mix.get("pool", 8 * int(mix["clients"])))
    set_rng = np.random.default_rng(int(mix.get("set_seed", 0)))
    rng = np.random.default_rng(int(seed))
    return _prompts(mix, n, vocab, set_rng, rng, max_total)
