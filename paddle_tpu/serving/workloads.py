"""Seeded, replayable hostile-traffic workloads for a serving fleet.

ISSUE 16 tentpole (3): the scenario suite that makes "millions of
users" testable in CI. Each generator produces a deterministic arrival
plan from a seed — what a hostile slice of production traffic looks
like, shrunk to tiny models so tier-1 (CPU) replays it exactly:

  - ``burst``        — thundering-herd arrivals: whole waves land on the
                       same step, far beyond slot capacity, so admission
                       queueing and handoff brokering are saturated.
  - ``agentic``      — multi-turn agent chains: every turn's prompt is
                       the previous turn's prompt + output + a new tail,
                       building deep shared prefixes the radix trie
                       should turn into prefill skips.
  - ``mixed``        — long-context analysis jobs interleaved with
                       short chats: the classic head-of-line blocking
                       mix for chunked prefill + paged decode.
  - ``thrash``       — an adversarial tenant streaming never-repeating
                       prompts through a deliberately small page pool,
                       trying to evict a well-behaved tenant's shared
                       prefix out of the trie.
  - ``replica_kill`` — chaos: a decode replica is drained mid-burst
                       (the `CollectiveTimeout` path) and later
                       re-admitted; the scenario asserts zero request
                       loss and exact greedy outputs anyway.

The step-indexed targets (`ttft_p90_steps`, `e2e_p90_steps`) count
router steps, and a step is a call of `ServingEngine.step()`: since the
engine keeps one launch queued ahead (PR 34) a token surfaces in the
call AFTER the one that dispatched its launch, so a request's first
token shows one call later on its prefill replica and its last one call
later on its decode replica. Every scenario's targets moved by that
(+1 / +2 calls; a call itself got shorter), and `replica_kill`'s chaos
by one step so that the kill still lands on a running decode.

`run_scenario` drives a fresh two/three-replica fleet through a plan
and emits one flat SERVING_BENCH-style row: fleet tokens/s, TTFT/e2e
percentiles (from the before/after delta of the router-measured
``serving.fleet.*`` histograms, so concurrent scenarios sharing one
process registry stay self-contained), prefill-skip rate, handoff
count/latency, a zero-request-loss flag, and an output-token checksum —
the deterministic fields are what `tools/perf_gate.py` locks with exact
bands and `tools/fleetboard.py --selftest` replays.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import observability as _obs
from .. import resilience as _res
from ..observability import fleet as _fleet
from ..observability import tracing as _tracing
from .controller import FleetController, SLOTargets
from .engine import ServingEngine
from .router import FleetRouter

__all__ = ["SCENARIOS", "Arrival", "Chaos", "Plan", "make_plan",
           "build_fleet", "run_scenario", "run_all", "ROW_DETERMINISTIC",
           "ROW_TIMING"]

#: the five hostile-traffic scenarios, in canonical order
SCENARIOS: Tuple[str, ...] = ("burst", "agentic", "mixed", "thrash",
                              "replica_kill")

#: row fields that replay bit-exactly from the seed (perf_gate locks
#: these with exact [v, v] bands; fleetboard --selftest re-checks them)
ROW_DETERMINISTIC: Tuple[str, ...] = (
    "requests", "completed", "zero_loss", "output_checksum", "handoffs",
    "shed", "ttft_p90_steps", "e2e_p90_steps")
#: machine-dependent row fields (noise-banded, regenerated on-machine)
ROW_TIMING: Tuple[str, ...] = (
    "fleet_tokens_per_s", "ttft_p50_ms", "ttft_p90_ms", "ttft_p99_ms",
    "e2e_p50_ms", "e2e_p90_ms", "e2e_p99_ms", "handoff_latency_ms",
    "wall_s")


@dataclass
class Arrival:
    """One planned request. `after` chains multi-turn agents: the
    arrival is held until the named parent's result lands, then its
    prompt becomes parent_prompt + parent_output + `prompt` (the new
    user turn) — the deep-shared-prefix shape agentic traffic has."""
    request_id: str
    prompt: np.ndarray
    max_new: int
    at_step: int = 0
    tenant: Optional[str] = None
    priority: int = 0
    after: Optional[str] = None


@dataclass
class Chaos:
    """Kill `replica` (router.drain — the CollectiveTimeout path) once
    `at_step` is reached, re-admitting it `readmit_after` steps later."""
    replica: str
    at_step: int
    readmit_after: int = 4


@dataclass
class Plan:
    name: str
    seed: int
    arrivals: List[Arrival]
    #: replica name -> role, in construction order
    roles: Dict[str, str]
    chaos: Optional[Chaos] = None
    #: engine kwargs applied to every replica
    engine_kw: Dict[str, Any] = field(default_factory=dict)
    #: per-replica overrides (thrash squeezes only the prefill pool —
    #: a starved decode pool would just park handoffs forever)
    replica_kw: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    #: replica_kill compares every output against solo greedy decode
    check_exact: bool = False
    #: declared SLO targets — what "holding the SLO" means for this
    #: traffic shape; recorded in the emitted row, actuated by the
    #: autopilot when `run_scenario(autopilot=True)`
    slo: Optional[SLOTargets] = None


def _prompt(rng: np.random.Generator, vocab: int, n: int) -> np.ndarray:
    return rng.integers(1, vocab, size=n).astype(np.int32)


def make_plan(name: str, seed: int = 0, vocab: int = 128) -> Plan:
    """Build the named scenario's deterministic arrival plan. `vocab`
    must not exceed the serving model's vocab size."""
    rng = np.random.default_rng([seed, SCENARIOS.index(name)])
    two = {"pf0": "prefill", "dec0": "decode"}
    arr: List[Arrival] = []
    if name == "burst":
        # three waves of 4, each wave landing on one step
        for wave, step in enumerate((0, 2, 4)):
            for i in range(4):
                arr.append(Arrival(f"burst-{wave}-{i}",
                                   _prompt(rng, vocab, int(rng.integers(5, 9))),
                                   int(rng.integers(3, 6)), at_step=step,
                                   tenant="burst"))
        return Plan(name, seed, arr, two,
                    slo=SLOTargets(ttft_p90_ms=500.0, e2e_p90_ms=2000.0,
                                   ttft_p90_steps=13, e2e_p90_steps=20,
                                   queue_depth=4))
    if name == "agentic":
        # 3 agents x 3 turns; turns 2..3 extend the previous turn
        for a in range(3):
            root = _prompt(rng, vocab, int(rng.integers(6, 10)))
            arr.append(Arrival(f"agent{a}-t0", root, 3, at_step=a,
                               tenant=f"agent{a}"))
            for t in (1, 2):
                arr.append(Arrival(
                    f"agent{a}-t{t}", _prompt(rng, vocab, 2), 3,
                    tenant=f"agent{a}", after=f"agent{a}-t{t - 1}"))
        return Plan(name, seed, arr, two,
                    slo=SLOTargets(ttft_p90_ms=500.0, e2e_p90_ms=3000.0,
                                   ttft_p90_steps=9, e2e_p90_steps=12,
                                   queue_depth=4))
    if name == "mixed":
        # two long-context jobs up front, six short chats trickling in
        for i in range(2):
            arr.append(Arrival(f"long{i}", _prompt(rng, vocab, 24), 4,
                               at_step=0, tenant="analyst"))
        for i in range(6):
            arr.append(Arrival(f"chat{i}",
                               _prompt(rng, vocab, int(rng.integers(4, 7))),
                               int(rng.integers(2, 5)), at_step=i,
                               tenant="chat"))
        return Plan(name, seed, arr, two,
                    slo=SLOTargets(ttft_p90_ms=800.0, e2e_p90_ms=3000.0,
                                   ttft_p90_steps=14, e2e_p90_steps=17,
                                   queue_depth=4))
    if name == "thrash":
        # a good tenant re-using one prefix vs an adversary streaming
        # unique prompts through a small pool (num_pages squeezed)
        shared = _prompt(rng, vocab, 8)
        for i in range(4):
            arr.append(Arrival(
                f"good{i}",
                np.concatenate([shared, _prompt(rng, vocab, 2)]),
                3, at_step=2 * i, tenant="good"))
        for i in range(6):
            arr.append(Arrival(f"evil{i}", _prompt(rng, vocab, 12), 2,
                               at_step=i, tenant="adversary",
                               priority=0))
        return Plan(name, seed, arr, two,
                    replica_kw={"pf0": {"num_pages": 24}},
                    slo=SLOTargets(ttft_p90_ms=800.0, e2e_p90_ms=3000.0,
                                   ttft_p90_steps=16, e2e_p90_steps=18,
                                   queue_depth=3, pool_high=0.7,
                                   pool_low=0.4))
    if name == "replica_kill":
        roles = {"pf0": "prefill", "dec0": "decode", "dec1": "decode"}
        for i in range(8):
            arr.append(Arrival(f"kill{i}",
                               _prompt(rng, vocab, int(rng.integers(5, 9))),
                               int(rng.integers(3, 6)),
                               at_step=i // 2, tenant="burst"))
        return Plan(name, seed, arr, roles,
                    chaos=Chaos("dec0", at_step=7, readmit_after=3),
                    check_exact=True,
                    slo=SLOTargets(ttft_p90_ms=800.0, e2e_p90_ms=4000.0,
                                   ttft_p90_steps=11, e2e_p90_steps=17,
                                   queue_depth=4))
    raise ValueError(f"unknown scenario {name!r} (one of {SCENARIOS})")


def build_fleet(model, roles: Dict[str, str],
                replica_kw: Optional[Dict[str, Dict[str, Any]]] = None,
                **engine_kw) -> FleetRouter:
    """Fresh fleet of tiny replicas sharing `model` weights (page_size 4
    / 2 slots / prefill_chunk 4 unless overridden; `replica_kw` layers
    per-replica overrides on top)."""
    replicas = {}
    for name, role in roles.items():
        kw = {"max_slots": 2, "page_size": 4, "prefill_chunk": 4}
        kw.update(engine_kw)
        kw.update((replica_kw or {}).get(name, {}))
        replicas[name] = ServingEngine(model, role=role, replica=name,
                                       **kw)
    return FleetRouter(replicas)


_SHARED_TOKENS = "serving.prefix_cache.shared_tokens"


def _fleet_hist_snapshot() -> Dict[str, Any]:
    snap = _obs.snapshot()
    keep = _fleet.FLEET_SLO_METRICS + (_SHARED_TOKENS,)
    return {n: snap[n] for n in keep if n in snap}


def _counter_value(snap: Dict[str, Any], name: str) -> float:
    e = snap.get(name)
    if not e or not e["series"]:
        return 0.0
    return float(e["series"][0]["value"])


def _delta_pXX(before: Dict[str, Any], after: Dict[str, Any],
               name: str, q: float) -> Optional[float]:
    """Percentile of ONLY this scenario's observations: the bucket-count
    delta between the before/after snapshots of one fleet histogram
    (scenarios share the process-wide default registry)."""
    b, a = before.get(name), after.get(name)
    if a is None:
        return None
    sa = a["series"][0]
    counts = list(sa["counts"])
    total = sa["count"]
    if b is not None:
        sb = b["series"][0]
        counts = [x - y for x, y in zip(counts, sb["counts"])]
        total -= sb["count"]
    if total <= 0:
        return None
    series = {"counts": counts, "sum": 0.0, "count": total}
    return _tracing.percentile(series, q, buckets=a["buckets"])


def run_scenario(name: str, model, seed: int = 0,
                 vocab: Optional[int] = None,
                 max_steps: int = 100000,
                 autopilot: bool = False) -> Dict[str, Any]:
    """Replay one scenario against a fresh fleet; return its
    SERVING_BENCH row (see module docstring for the field split).

    With `autopilot=True` the SAME traffic replays with the ISSUE-18
    feedback controllers closed around the declared `Plan.slo` targets:
    every replica gets an `EngineController` (via the engine's
    `slo_targets` kwarg) and the router a `FleetController`. All
    controller sensors are deterministic, so the autopilot rows replay
    bit-exactly too — fleetboard commits them side by side with the
    static rows."""
    if vocab is None:
        vocab = int(getattr(model.config, "vocab_size", 128))
    plan = make_plan(name, seed=seed, vocab=min(vocab, 128))
    engine_kw = dict(plan.engine_kw)
    if autopilot:
        engine_kw["slo_targets"] = plan.slo
    router = build_fleet(model, plan.roles, replica_kw=plan.replica_kw,
                         **engine_kw)
    if autopilot:
        FleetController(router, plan.slo)
    before = _fleet_hist_snapshot()
    pending = list(plan.arrivals)
    held = {a.request_id: a for a in pending if a.after}
    ready = [a for a in pending if not a.after]
    prompts: Dict[str, np.ndarray] = {}
    results: Dict[str, np.ndarray] = {}
    submitted: List[str] = []
    shed: List[str] = []
    chaos_done = readmit_at = None
    t0 = time.perf_counter()
    step = 0
    while ready or held or router.has_work():
        if step >= max_steps:
            raise RuntimeError(f"scenario {name} did not drain "
                               f"({router.stats()})")
        for a in [a for a in ready if a.at_step <= step]:
            ready.remove(a)
            try:
                router.submit(a.prompt, a.max_new,
                              request_id=a.request_id,
                              priority=a.priority, tenant=a.tenant)
            except _res.Shed:
                # the controller refused it at the door: a deliberate,
                # traced outcome — NOT a lost request
                shed.append(a.request_id)
                continue
            except _res.Overloaded:
                # admission backpressure: retry the arrival next step
                a.at_step = step + 1
                ready.append(a)
                continue
            prompts[a.request_id] = a.prompt
            submitted.append(a.request_id)
        if plan.chaos is not None and chaos_done is None \
                and step >= plan.chaos.at_step:
            router.drain(plan.chaos.replica)
            chaos_done = step
            readmit_at = step + plan.chaos.readmit_after
        if readmit_at is not None and step >= readmit_at:
            router.readmit(plan.chaos.replica)
            readmit_at = None
        router.step()
        for rid, res in router.collect().items():
            assert isinstance(res, np.ndarray), \
                f"scenario {name}: request {rid} lost -> {res!r}"
            results[rid] = res
            # release any turn chained on this result: its prompt is
            # the full conversation so far plus the new user tail
            for child in [c for c in held.values() if c.after == rid]:
                del held[child.request_id]
                child.prompt = np.concatenate(
                    [prompts[rid], res.astype(np.int32), child.prompt])
                child.after = None
                child.at_step = step + 1
                ready.append(child)
        step += 1
    wall = time.perf_counter() - t0
    after = _fleet_hist_snapshot()
    zero_loss = int(set(submitted) == set(results)
                    and all(isinstance(r, np.ndarray)
                            for r in results.values()))
    if plan.check_exact:
        from ..generation import generate_cached
        import paddle_tpu as paddle
        for rid in submitted:
            want, _ = generate_cached(
                model, paddle.to_tensor(prompts[rid][None]),
                max_new_tokens=len(results[rid]),
                decode_strategy="greedy_search")
            got = results[rid]
            if not np.array_equal(want.numpy()[0], got):
                raise AssertionError(
                    f"scenario {name}: request {rid} diverged from "
                    f"solo greedy decode after chaos")
    new_tokens = int(sum(r.size for r in results.values()))
    prompt_tokens = int(sum(p.size for p in prompts.values()))
    steps_slo = router.step_slo_summary()
    row: Dict[str, Any] = {
        "scenario": name + ("_autopilot" if autopilot else ""),
        "seed": seed, "autopilot": int(autopilot),
        "requests": len(submitted), "completed": len(results),
        "zero_loss": zero_loss,
        "shed": len(shed),
        # step-indexed fleet latencies: deterministic on a seeded
        # replay, so they live in ROW_DETERMINISTIC and pin the
        # autopilot's latency win with exact perf_gate bands
        "ttft_p90_steps": steps_slo["ttft_p90_steps"],
        "e2e_p90_steps": steps_slo["e2e_p90_steps"],
        "ttft_p50_steps": steps_slo["ttft_p50_steps"],
        "e2e_p50_steps": steps_slo["e2e_p50_steps"],
        # what "holding the SLO" meant for this traffic shape
        "slo": plan.slo.as_row() if plan.slo is not None else {},
        "output_checksum": int(sum(int(t) for r in results.values()
                                   for t in r.tolist()) % 1_000_000_007),
        "handoffs": router.handoff_count,
        # prompt tokens whose prefill the fleet skipped via the trie,
        # scenario-scoped through the before/after counter delta
        "prefill_skip_rate": (
            (_counter_value(after, _SHARED_TOKENS)
             - _counter_value(before, _SHARED_TOKENS)) / prompt_tokens
            if prompt_tokens else 0.0),
        "fleet_tokens_per_s": new_tokens / wall if wall > 0 else 0.0,
        "handoff_latency_ms": router.stats()["handoff_latency_s"] * 1e3,
        "wall_s": wall,
        "steps": step,
    }
    for metric, key in (("serving.fleet.ttft_seconds", "ttft"),
                        ("serving.fleet.e2e_seconds", "e2e")):
        for q in (50, 90, 99):
            v = _delta_pXX(before, after, metric, q)
            row[f"{key}_p{q}_ms"] = (v * 1e3) if v is not None else None
    return row


def run_all(model, seed: int = 0,
            autopilot: bool = False) -> Dict[str, Dict[str, Any]]:
    """All five scenarios, canonical order: {scenario: row}. With
    `autopilot=True` the rows are keyed ``<scenario>_autopilot``."""
    suffix = "_autopilot" if autopilot else ""
    return {name + suffix: run_scenario(name, model, seed=seed,
                                        autopilot=autopilot)
            for name in SCENARIOS}
