"""Whole optimiser steps of a trainer system, fed by its own input
pipeline, each ending in a wait for the step's loss on the host (as
``run_pretrain.run`` logs it)."""

from __future__ import annotations

import importlib
import math
import time
from typing import Mapping

from ..lib.harness import Harness, say


def run(h: Harness, config: Mapping, mix: Mapping, seed: int,
        seconds: float) -> dict:
    mod = importlib.import_module(f"benchmarks.systems.{config['system']}")
    system = mod.System(config, h.rehearse, seed, h.devices)
    span = h.spans.span
    it = system.batches()

    # correctness, outside the window: the first step against the plain
    # reference at the same weights on the same batch
    check = system.check_first_step(*next(it))
    losses = [check["loss"]]
    t1 = time.perf_counter()
    # warm-up: the step compiles for the initial layouts and once more
    # for the donated ones
    for _ in range(int(mix.get("compile_warmup_steps", 2))):
        losses.append(system.step(*system.put(*next(it))))
    say(f"first step against the plain reference: {check}; warm-up "
        f"losses {losses} ({time.perf_counter() - t1:.1f}s)")

    trace_steps = int(system.trainer.get("trace_steps", 3))
    trace_at = float(mix.get("trace_after_share", 0.25)) * seconds \
        if h.trace else None
    tracing, traced = False, trace_at is None
    traced_left = 0
    in_window = h.compiles.window()
    setup_s = time.time() - h.t_start
    clock = time.perf_counter
    w0 = clock()
    step_ends = []
    while True:
        now = clock() - w0
        if now >= seconds:
            break
        if not traced and not tracing and now >= trace_at:
            h.start_trace()
            tracing, traced_left = True, trace_steps
        with span("next_batch"):
            ids, labels = system.put(*next(it))
        with span("train_step"):
            losses.append(system.step(ids, labels))
        step_ends.append(clock() - w0)
        if tracing:
            traced_left -= 1
            if traced_left == 0:
                h.stop_trace()
                tracing, traced = False, True
    if tracing:
        h.stop_trace()
    comp = in_window()
    n = len(step_ends)
    elapsed = step_ends[-1] if step_ends else float("nan")
    chips = len(h.devices)
    tok_s_chip = n * system.tokens_per_step / elapsed / chips
    finite = all(math.isfinite(x) for x in losses)
    ok = check["ok"] and finite and comp["compiles"] == 0
    say(f"window: {n} whole steps in {elapsed:.3f}s, "
        f"{system.tokens_per_step} tokens a step on {chips} chips; losses "
        f"finite: {finite}; last {losses[-1]:.4f}; compiles in the "
        f"window: {comp['compiles']} {comp['names']}")
    h.counters.update(tok_s_chip=tok_s_chip, system=system, cfg=system.cfg,
                      trainer=system.trainer, steps_in_window=n,
                      flops_per_token=system.flops_per_token)
    return {"correct": bool(ok), "attempted": n,
            "failed": sum(not math.isfinite(x) for x in losses),
            "end_to_end": {"setup_s": setup_s,
                           "train_tok_s_chip": tok_s_chip}}
