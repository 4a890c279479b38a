"""Prefix-cache-locality fleet router over N serving replicas.

`FleetRouter` spreads requests across engine replicas (ROADMAP item 2)
using the radix-trie prefix overlap as the placement signal: each
prefill-capable replica is scored by

    locality_weight * match_length(prompt)          (trie overlap, tokens)
  - queue_cost_tokens * (inflight + waiting)        (queue depth penalty)

with free pages then submission order as deterministic tiebreaks — a
cold prompt degenerates to least-loaded placement. The same
`PrefixCache.match_length` tokens feed the per-replica
`serving.prefix_cache.replica_hit_tokens` counters, so the router's
score is computed from the numbers operators already see.

Disaggregation: prefill-role replicas stage completed prefills on
`engine.handoff_ready`; after each fleet step the router exports them
(`KVPageHandoff`) and imports into the least-loaded decode-capable
replica. An import refused with `Overloaded` (pool or admission gate)
parks the handoff on a pending queue and retries next step — the
export pins keep the protocol window consistent however long that
takes.

Resilience: a replica whose `step()` raises
`distributed.watchdog.CollectiveTimeout` (or any fault the caller
reports via `drain()`) is taken out of rotation. Every in-flight
request with complete KV — running decodes, staged handoffs,
preempted waiters — is exported pages-intact and requeued on the
survivors (no re-prefill, the PR-10 resume path); mid-prefill and
still-waiting requests are resubmitted fresh (chunked prefill replays
deterministically). `readmit()` puts a healed replica back, and
`poll_elastic()` drives both transitions from an `ElasticManager`'s
heartbeat view when one is attached.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import observability as _obs
from .. import resilience as _res
from ..distributed.watchdog import CollectiveTimeout
from ..observability import fleet as _fleet
from ..observability import tracing as _tracing
from .engine import ServingEngine
from .handoff import KVPageHandoff
from .scheduler import DECODE, PREFILL, Request

__all__ = ["FleetRouter"]

_PLACED = _obs.registry().counter(
    "serving.router.placements",
    "requests placed, by replica and placement signal",
    labels=("replica", "signal"))
_ROUTED_HANDOFFS = _obs.registry().counter(
    "serving.router.handoffs", "prefill→decode handoffs routed")
_DRAINS = _obs.registry().counter(
    "serving.router.drains", "replicas drained on fault",
    labels=("replica",))
_REQUEUED = _obs.registry().counter(
    "serving.router.requeued",
    "in-flight requests moved pages-intact off a drained replica")
_RESUBMITTED = _obs.registry().counter(
    "serving.router.resubmitted",
    "waiting/mid-prefill requests restarted off a drained replica")
_READMITS = _obs.registry().counter(
    "serving.router.readmits", "healed replicas re-admitted",
    labels=("replica",))
_UP = _obs.registry().gauge(
    "serving.router.replicas_up", "replicas in rotation")
_TRACE = _tracing.recorder()


class FleetRouter:
    """Route requests across N `ServingEngine` replicas by prefix-cache
    locality; drive their steps; broker prefill→decode handoffs; drain
    and re-admit replicas on faults.

    Typical loop::

        router = FleetRouter({"pf0": prefill_eng, "dec0": decode_eng})
        router.submit(prompt_ids, max_new_tokens=32)
        results = router.run_to_completion()

    Replicas may be any role mix: `prefill`/`colocated` replicas take
    fresh prompts, `decode`/`colocated` replicas take handoffs. All
    replicas must share model weights, family, and page_size for the
    exactness contract to hold.
    """

    def __init__(self, replicas: Dict[str, ServingEngine],
                 locality_weight: float = 1.0,
                 queue_cost_tokens: float = 32.0,
                 elastic=None,
                 node_ranks: Optional[Dict[str, int]] = None,
                 readmit_warmup: float = 0.5,
                 warmup_load: float = 2.0,
                 weight_recovery: float = 0.25):
        if not replicas:
            raise ValueError("FleetRouter needs at least one replica")
        self.replicas = dict(replicas)
        self.locality_weight = float(locality_weight)
        self.queue_cost_tokens = float(queue_cost_tokens)
        for name, eng in self.replicas.items():
            if eng.replica is None:
                eng.set_replica(name)
        self._order = list(self.replicas)     # deterministic tiebreak
        self._down: set = set()
        self._pending: List[KVPageHandoff] = []
        self._export_t: Dict[object, float] = {}
        self._results: Dict[object, object] = {}
        self.handoff_count = 0
        self.handoff_seconds = 0.0
        # placement weights (ISSUE 18): [0, 1] per replica. A weight
        # below 1 scales down the locality signal and charges
        # `warmup_load` phantom queue entries, so a just-readmitted
        # replica is neither dogpiled (its empty queue looks loaded)
        # nor starved (the weight ramps back by `weight_recovery` per
        # fleet step). `readmit()` seeds the weight from the last
        # federated scrape when one was taken, else `readmit_warmup`.
        self.placement_weight: Dict[str, float] = \
            {name: 1.0 for name in self._order}
        self.readmit_warmup = float(readmit_warmup)
        self.warmup_load = float(warmup_load)
        self.weight_recovery = float(weight_recovery)
        self._last_scrape: Dict[str, Dict[str, float]] = {}
        # optional fleet-scope SLO autopilot (serving.controller):
        # attach_controller wires on_step / on_capacity_loss
        self.controller = None
        # fleet-scope SLO tracking, router-measured: request_id ->
        # [submit_t, first_token_seen, Request, submit_step].
        # Drain-resubmits keep the ORIGINAL submit time/step, so fleet
        # TTFT/e2e include the retry cost a client of the fleet
        # actually pays. Step-indexed latencies (ttft_steps/e2e_steps,
        # in router steps) are kept unconditionally — they are the
        # deterministic SLO signal seeded CI replays bit-exactly.
        self._slo: Dict[object, list] = {}
        self._step_idx = 0
        self.ttft_steps: Dict[object, int] = {}
        self.e2e_steps: Dict[object, int] = {}
        # optional ElasticManager heartbeat view: replica name -> node
        # rank (defaults to listing order)
        self._elastic = elastic
        self._ranks = dict(node_ranks) if node_ranks else \
            {name: i for i, name in enumerate(self._order)}
        if _obs.enabled():
            _UP.set(len(self._live()))

    # ------------------------------------------------------------- queries
    def _live(self) -> List[Tuple[str, ServingEngine]]:
        return [(n, self.replicas[n]) for n in self._order
                if n not in self._down]

    def live_replicas(self) -> List[str]:
        return [n for n, _ in self._live()]

    def has_work(self) -> bool:
        return bool(self._pending) or any(
            eng.has_work() or eng.handoff_ready for _, eng in self._live())

    def stats(self) -> Dict[str, object]:
        return {
            "replicas": len(self.replicas),
            "up": len(self._live()),
            "down": sorted(self._down),
            "pending_handoffs": len(self._pending),
            "handoffs": self.handoff_count,
            "handoff_latency_s": (self.handoff_seconds
                                  / self.handoff_count
                                  if self.handoff_count else 0.0),
        }

    def attach_controller(self, controller) -> None:
        """Wire a `FleetController`: `step()` calls its `on_step` and
        `drain()` its `on_capacity_loss`."""
        self.controller = controller

    # ----------------------------------------------------------- placement
    def _weight(self, name: Optional[str]) -> float:
        return self.placement_weight.get(name or "", 1.0)

    def _score(self, eng: ServingEngine, prompt) -> Tuple[float, int]:
        hit = eng.prefix_cache.match_length(prompt) \
            if eng.prefix_cache is not None else 0
        load = eng.scheduler.inflight + len(eng.scheduler.waiting)
        w = self._weight(eng.replica)
        return (self.locality_weight * hit * w
                - self.queue_cost_tokens
                * (load + (1.0 - w) * self.warmup_load), hit)

    def submit(self, prompt, max_new_tokens: int = 20, **kw) -> Request:
        """Place one fresh request on the best prefill-capable replica:
        highest locality-vs-load score, free pages then listing order as
        tiebreaks, falling back down the ranking when a replica refuses
        with `Overloaded`. Raises `Overloaded` only when every live
        prefill-capable replica refused."""
        targets = [(n, e) for n, e in self._live()
                   if e.role in ("prefill", "colocated")]
        if not targets:
            raise _res.Overloaded("no prefill-capable replica in rotation")
        ranked = []
        for idx, (name, eng) in enumerate(targets):
            score, hit = self._score(eng, prompt)
            ranked.append((-score, -eng.allocator.available_pages, idx,
                           name, eng, hit))
        ranked.sort(key=lambda t: t[:3])
        err: Optional[Exception] = None
        for _, _, _, name, eng, hit in ranked:
            try:
                req = eng.add_request(prompt, max_new_tokens, **kw)
            except _res.Overloaded as e:
                err = e
                continue
            ent = self._slo.get(req.request_id)
            if ent is None:
                self._slo[req.request_id] = [time.monotonic(), False,
                                             req, self._step_idx]
            else:
                ent[2] = req     # drain-resubmit: keep original t0/step
            if _obs.enabled():
                _PLACED.labels(replica=name,
                               signal="prefix" if hit else "load").inc()
            _TRACE.stamp(req.request_id, "routed", replica=name,
                         hit_tokens=hit)
            return req
        raise err if err is not None else _res.Overloaded(
            "all prefill-capable replicas refused")

    def place_of(self, request_id) -> Optional[str]:
        """Replica currently holding `request_id` (None if unknown/done)."""
        for name, eng in self._live():
            if any(r.request_id == request_id
                   for r in eng.handoff_ready):
                return name
            if any(r.request_id == request_id
                   for r in eng.scheduler.waiting):
                return name
            if any(r is not None and r.request_id == request_id
                   for r in eng.scheduler.slots):
                return name
        return None

    # ------------------------------------------------------------ stepping
    def step(self) -> Dict[str, int]:
        """One fleet iteration: step every live replica (a
        `CollectiveTimeout` drains it instead of propagating), export
        freshly completed prefills, then try to place pending handoffs
        on decode-capable replicas."""
        out = {"admitted": 0, "prefill_tokens": 0, "decoded": 0,
               "finished": 0, "handoffs": 0}
        self._step_idx += 1
        for name in list(self._order):
            if name in self._down:
                continue
            eng = self.replicas[name]
            try:
                st = eng.step()
            except CollectiveTimeout as err:
                self.drain(name, err)
                continue
            for k in ("admitted", "prefill_tokens", "decoded",
                      "finished"):
                out[k] += st.get(k, 0)
            self._observe_first_tokens()
            for req in list(eng.handoff_ready):
                self._export(eng, req)
            self._absorb(eng.collect())
        pending, self._pending = self._pending, []
        for handoff in pending:
            out["handoffs"] += self._import(handoff)
        # warmup ramp: discounted replicas recover toward full weight
        for name in self._order:
            if name not in self._down:
                w = self.placement_weight[name]
                if w < 1.0:
                    self.placement_weight[name] = \
                        min(1.0, w + self.weight_recovery)
        if self.controller is not None:
            self.controller.on_step(out)
        return out

    def collect(self) -> Dict[object, object]:
        """Results finished anywhere in the fleet since last collect."""
        for _, eng in self._live():
            self._absorb(eng.collect())
        done, self._results = self._results, {}
        return done

    def run_to_completion(self, max_steps: int = 100000) \
            -> Dict[object, object]:
        """Step until the fleet is idle; collect everything."""
        results: Dict[object, object] = {}
        steps = 0
        while self.has_work():
            if steps >= max_steps:
                raise RuntimeError(
                    f"fleet did not drain in {max_steps} steps "
                    f"({self.stats()})")
            self.step()
            results.update(self.collect())
            steps += 1
        results.update(self.collect())
        return results

    # ------------------------------------------------------- fleet SLOs
    def _observe_first_tokens(self) -> None:
        """Fleet TTFT, measured from OUTSIDE the replicas: scanned right
        after each engine step so the router sees a first token at the
        earliest moment a fleet client could (within one step of the
        trace's own token stamp)."""
        if not self._slo:
            return
        now = time.monotonic()
        for rid, ent in self._slo.items():
            if not ent[1] and ent[2] is not None and ent[2].tokens:
                ent[1] = True
                self.ttft_steps[rid] = self._step_idx - ent[3]
                if _obs.enabled():
                    _fleet.observe_ttft(now - ent[0])

    def _absorb(self, done: Dict[object, object]) -> None:
        """Fold one engine's collected results into the fleet result
        set, observing fleet e2e + per-phase attribution for every
        request that completed with tokens."""
        self._results.update(done)
        if not self._slo or not done:
            return
        now = time.monotonic()
        finished = None
        for rid, res in done.items():
            ent = self._slo.pop(rid, None)
            if ent is None or not isinstance(res, np.ndarray):
                continue
            self.e2e_steps[rid] = self._step_idx - ent[3]
            if not _obs.enabled():
                continue
            _fleet.observe_e2e(now - ent[0])
            if finished is None:
                finished = {t.request_id: t for t in _TRACE.finished()}
            _fleet.observe_phases(finished.get(rid))

    def scrape(self) -> _obs.Registry:
        """Fleet metric federation: collect every live replica's
        `ServingEngine.scrape()` snapshot into one rollup registry
        (counters summed, gauges/histograms re-labeled with
        ``replica=...``) plus the router-measured ``serving.fleet.*``
        SLO histograms — ready for `obs.to_prometheus(rollup)` /
        `rollup.snapshot()`. Returns an empty registry with metrics
        disabled."""
        snaps = {n: e.scrape() for n, e in self._live()}
        # remember each replica's scraped queue view: `readmit()` seeds
        # a healed replica's placement weight from its LAST known load
        # instead of treating it as a brand-new cold replica
        for n, e in self._live():
            self._last_scrape[n] = {
                "waiting": float(len(e.scheduler.waiting)),
                "inflight": float(e.scheduler.inflight),
                "utilization": float(
                    e.allocator.stats()["utilization"]),
            }
        rollup = _fleet.federate(
            {n: s for n, s in snaps.items() if s})
        snap = _obs.snapshot()
        for name in sorted(snap):
            if not name.startswith("serving.fleet."):
                continue
            e = snap[name]
            if e.get("kind") != "histogram":
                continue    # serving.fleet.controller.* counters/gauges
            m = rollup.histogram(name, e["help"], tuple(e["labels"]),
                                 buckets=tuple(e["buckets"]))
            for s in e["series"]:
                tgt = m.labels(**s["labels"]) if e["labels"] else m
                tgt._counts = list(s["counts"])
                tgt._sum = float(s["sum"])
                tgt._count = int(s["count"])
        return rollup

    def slo_summary(self, qs=(50, 90, 99)) -> Dict[str, object]:
        """Fleet-scope SLO table ({metric: {count, mean, pXX}}) over the
        router-measured serving.fleet.* histograms."""
        return _fleet.fleet_slo_summary(qs=qs)

    @staticmethod
    def _step_pct(vals: List[int], q: int) -> Optional[int]:
        """Nearest-rank percentile over integer step counts —
        deterministic on a seeded replay (no interpolation)."""
        if not vals:
            return None
        s = sorted(vals)
        return s[max(0, -(-q * len(s) // 100) - 1)]

    def step_slo_summary(self, qs=(50, 90, 99)) -> Dict[str, object]:
        """Step-indexed fleet SLOs: TTFT / e2e measured in ROUTER STEPS
        from original submission (drain-resubmits keep their first
        step). Wall-clock percentiles are machine-dependent; these
        replay bit-exactly from a seed, so `SLOTargets.*_steps` targets
        can be asserted in CI."""
        out: Dict[str, object] = {}
        for key, d in (("ttft", self.ttft_steps),
                       ("e2e", self.e2e_steps)):
            vals = list(d.values())
            for q in qs:
                out[f"{key}_p{q}_steps"] = self._step_pct(vals, q)
        return out

    # ------------------------------------------------------------- handoff
    def _export(self, eng: ServingEngine, req: Request) -> None:
        self._export_t[req.request_id] = time.monotonic()
        self._pending.append(eng.export_request(req))

    def _import(self, handoff: KVPageHandoff) -> int:
        """Place one handoff on the least-loaded decode-capable replica
        (free pages, then listing order). Refused everywhere → back on
        the pending queue for the next step."""
        ranked = []
        for idx, (name, eng) in enumerate(self._live()):
            if eng.role not in ("decode", "colocated"):
                continue
            w = self._weight(name)
            load = (eng.scheduler.inflight + len(eng.scheduler.waiting)
                    + (1.0 - w) * self.warmup_load)
            ranked.append((load, -eng.allocator.available_pages, idx,
                           name, eng))
        ranked.sort(key=lambda t: t[:3])
        for _, _, _, name, eng in ranked:
            try:
                req = eng.import_request(handoff)
            except _res.Overloaded:
                continue
            ent = self._slo.get(handoff.request_id)
            if ent is not None:
                ent[2] = req    # the importer's Request is live now
            t0 = self._export_t.pop(handoff.request_id, None)
            if t0 is not None:
                dt = time.monotonic() - t0
                self.handoff_seconds += dt
                _fleet.observe_handoff(dt)
            self.handoff_count += 1
            if _obs.enabled():
                _ROUTED_HANDOFFS.inc()
            return 1
        self._pending.append(handoff)
        return 0

    # ---------------------------------------------------------- resilience
    def drain(self, name: str, err: Optional[BaseException] = None,
              notify: bool = True) -> int:
        """Take `name` out of rotation and move its work to survivors:
        requests with complete KV (running decodes, staged handoffs,
        preempted waiters) are exported pages-intact onto the pending
        handoff queue — they resume elsewhere WITHOUT re-prefill;
        waiting/mid-prefill requests are resubmitted fresh. Returns how
        many requests were moved or resubmitted."""
        if name in self._down:
            return 0
        eng = self.replicas[name]
        self._down.add(name)
        if _obs.enabled():
            _DRAINS.labels(replica=name).inc()
            _UP.set(len(self._live()))
        # what the replica had dispatched is read back first: its
        # requests are sorted below by what they show once it retired
        eng.retire()
        # results finished before the fault survive the drain
        self._absorb(eng.collect())
        moved = resubmitted = 0
        for req in list(eng.handoff_ready):
            self._export(eng, req)
            moved += 1
        for _, req in list(eng.scheduler.active(DECODE)):
            self._export(eng, req)
            moved += 1
        fresh: List[Request] = []
        for _, req in list(eng.scheduler.active(PREFILL)):
            # partial prefill is discarded: chunked prefill replays
            # deterministically on the new replica
            if req in eng._prefill_fifo:
                eng._prefill_fifo.remove(req)
            eng.scheduler.detach(req)
            if eng.allocator.has_seq(req.request_id):
                eng.allocator.free(req.request_id)
            fresh.append(req)
        for req in list(eng.scheduler.waiting):
            if req.preempted and eng.allocator.has_seq(req.request_id):
                self._export(eng, req)
                moved += 1
            else:
                eng.scheduler.waiting.remove(req)
                fresh.append(req)
        for req in fresh:
            self.submit(req.prompt, req.max_new_tokens,
                        eos_token_id=req.eos_token_id,
                        pad_token_id=req.pad_token_id,
                        deadline_s=req.deadline_s,
                        request_id=req.request_id,
                        priority=req.priority, tenant=req.tenant)
            resubmitted += 1
        if _obs.enabled():
            _REQUEUED.inc(moved)
            _RESUBMITTED.inc(resubmitted)
        _TRACE.stamp(f"drain:{name}", "drain", moved=moved,
                     resubmitted=resubmitted,
                     reason=type(err).__name__ if err else "manual")
        if notify and self.controller is not None:
            # capacity-loss event: the fleet controller pre-emptively
            # tightens the survivors' admission instead of waiting for
            # their queues to cross the SLO threshold
            self.controller.on_capacity_loss(name)
        return moved + resubmitted

    def readmit(self, name: str,
                weight: Optional[float] = None) -> None:
        """Put a healed replica back in rotation (its pool is empty —
        drain exported or resubmitted everything). Its locality and
        queue stats are COLD, so the placement weight is seeded below
        1.0 — from the last federated scrape when one was taken (the
        more loaded it went down, the deeper the discount), else the
        `readmit_warmup` default — and ramps back to full weight by
        `weight_recovery` per fleet step. That keeps the router from
        dogpiling an empty-looking replica or starving a healed one."""
        if name not in self.replicas:
            raise KeyError(name)
        if name in self._down:
            self._down.discard(name)
            if weight is None:
                last = self._last_scrape.get(name)
                if last is None:
                    weight = self.readmit_warmup
                else:
                    gone_load = last.get("waiting", 0.0) \
                        + last.get("inflight", 0.0)
                    weight = self.readmit_warmup / (1.0 + gone_load)
            self.placement_weight[name] = max(0.1, min(1.0, weight))
            if _obs.enabled():
                _READMITS.labels(replica=name).inc()
                _UP.set(len(self._live()))

    def set_role(self, name: str, role: str) -> None:
        """Shift `name` between prefill/decode duty through the PR-15
        drain/handoff path: in-flight work leaves pages-intact (or is
        resubmitted fresh), the role flips, and the replica re-enters
        rotation at FULL weight — it was repurposed, not unhealthy.
        Callers must leave at least one replica of each needed role
        (the FleetController guards this)."""
        if role not in ("prefill", "decode", "colocated"):
            raise ValueError(
                f"role must be prefill/decode/colocated, got {role!r}")
        eng = self.replicas[name]
        if eng.role == role:
            return
        was_down = name in self._down
        if not was_down:
            # not a capacity loss: survivors need no guard tightening
            self.drain(name, notify=False)
        eng.role = role
        if not was_down:
            self.readmit(name, weight=1.0)

    def poll_elastic(self) -> None:
        """Reconcile rotation with an `ElasticManager` membership view:
        replicas whose node stopped heartbeating are drained; nodes
        alive again are re-admitted."""
        if self._elastic is None:
            return
        alive = set(self._elastic.alive_nodes(len(self.replicas)))
        for name, rank in self._ranks.items():
            if rank in alive:
                self.readmit(name)
            elif name not in self._down:
                self.drain(name)
