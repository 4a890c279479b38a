"""What the two serving kinds share: build, warm up, check, measure."""

from __future__ import annotations

import importlib
import time
from typing import Mapping

import numpy as np

from ..lib import serving, stats, traffic
from ..lib.harness import Harness, as_run, say


def kv_tokens_of(req) -> int:
    """Cache tokens a live request holds: what it has prefilled plus
    what it has generated and fed back."""
    return int(req.prefill_pos) + max(len(req.tokens) - 1, 0)


def run(h: Harness, config: Mapping, mix: Mapping, seed: int,
        seconds: float, open_loop: bool) -> dict:
    mix = as_run(mix, h.rehearse)
    system = importlib.import_module(
        f"benchmarks.systems.{config['system']}").System(config, h.rehearse, seed)
    eng = system.engine
    gen = traffic.open_loop if open_loop else traffic.closed_loop
    reqs = gen(mix, seconds, seed, system.vocab, system.max_total)

    # warm-up and correctness, outside the window: a sample drawn from
    # the seed, of the lengths the mix names (its own contexts) or else
    # one that crosses a prefill chunk, through the engine; then the
    # plain float32 reference over prompt + output
    rng = np.random.default_rng(seed + 1)
    chunk = eng.prefill_chunk
    sample_lens = mix.get("check_prompt_lens") or \
        [chunk + chunk // 4, chunk // 2, max(chunk // 4, 2)]
    n_out = int(mix.get("check_output_len", 24))
    sample = [traffic.Req(0.0, rng.integers(0, system.vocab, n,
                                            dtype=np.int32), n_out)
              for n in sample_lens]
    t0 = time.perf_counter()
    outs = serving.run_requests(eng, sample)
    t1 = time.perf_counter()
    check = system.check([{"prompt": r.prompt, "output": o}
                          for r, o in zip(sample, outs)])
    say(f"warm-up {t1 - t0:.1f}s, reference check "
        f"{time.perf_counter() - t1:.1f}s: {check}")
    cache0 = eng.program_cache_sizes()

    source = serving.OpenSource(reqs) if open_loop \
        else serving.ClosedSource(reqs, int(mix["clients"]))
    drain = float(mix.get("drain_s", 30.0)) if open_loop else 0.0
    trace_at = float(mix.get("trace_after_share", 0.25)) * seconds \
        if h.trace else None
    trace_s = min(float(mix.get("trace_s", 3.0)), 0.5 * seconds)
    in_window = h.compiles.window()
    setup_s = time.time() - h.t_start
    obs = serving.window(eng, source, h, seconds, drain, kv_tokens_of,
                         trace_at, trace_s)
    comp = in_window()
    res = serving.reduce_window(obs, open_loop)
    cache1 = eng.program_cache_sizes()
    ok = (check["ok"] and comp["compiles"] == 0 and cache0 == cache1
          and all(v == 1 for v in cache1.values()))
    say(f"window {obs['elapsed']:.1f}s: {res['steps_in_window']} steps, "
        f"{res['completed']} completed, {res['cut_at_end']} unfinished, "
        f"{res['failed']} failed of {res['attempted']}; prefill "
        f"{res['prefill_tokens']} + new {res['new_tokens']} tokens; "
        f"mean live requests {res['mean_live_requests']:.1f}, live KV "
        f"tokens {res['mean_live_kv_tokens']:.0f}")
    say(f"samples: ttft {stats.summary(res['ttft_ms'])} tpot "
        f"{stats.summary(res['tpot_ms'])} queue "
        f"{stats.summary(res['queue_wait_ms'])}")
    say(f"generator lateness ms: {res['lateness_ms']}; compiles in the "
        f"window: {comp['compiles']} {comp['names']}; program cache "
        f"{cache1}")
    e2e = {"setup_s": setup_s, "serve_tok_s": res["serve_tok_s"]}
    if res["ttft_ms"]:
        e2e["ttft_p95_ms"] = stats.percentile(res["ttft_ms"], 95)
    if res["tpot_ms"]:
        e2e["tpot_p95_ms"] = stats.percentile(res["tpot_ms"], 95)
    h.counters.update(res, system=system, page_size=eng.page_size,
                      weight_bytes=system.weight_bytes, cfg=system.cfg,
                      steps=obs["steps"], paths=system.paths)
    return {"correct": bool(ok), "attempted": res["attempted"],
            "failed": res["failed"], "end_to_end": e2e}
