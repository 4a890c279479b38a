"""Continuous-batching serving subsystem.

Modules over the Pallas ragged paged-attention kernel
(`ops/pallas_ragged.py`):

  - `block_allocator`: fixed pool of page_size-token KV blocks with
    refcounts, per-sequence page tables, copy-on-write prefix sharing,
    trie pins, and utilization/fragmentation gauges;
  - `prefix_cache`: global radix trie of pinned prompt pages — a new
    request whose prompt extends a cached prefix admits with those
    pages shared and only the tail prefilled (LRU eviction under pool
    pressure);
  - `scheduler`: in-flight request scheduler — FCFS within a priority
    class, per-tenant token budgets, page-intact preemption, admission
    backpressure (`inference.Config.set_admission`) and per-request
    deadlines (`set_deadline` → falsy TimeoutResult partials);
  - `spec_decode`: n-gram self-drafting speculative decoding, verified
    in the engine's single ragged launch per step;
  - `engine`: `ServingEngine.add_request/step/collect`, ONE fixed-shape
    jitted step (one compile per model/slot-count) that carries every
    decode row and a chunk of prefill in one launch — each engine runs
    as a `prefill`, `decode`, or `colocated` (default) replica;
  - `handoff`: `KVPageHandoff`, the pin → export → import → unpin
    KV-page transfer between a prefill replica and a decode replica
    (bit-identical resume, no re-prefill);
  - `router`: `FleetRouter` spreading requests over N replicas by
    radix-trie prefix overlap vs queue depth (scaled by per-replica
    placement weights), brokering handoffs, and draining/re-admitting
    replicas on `CollectiveTimeout` faults;
  - `controller`: the SLO autopilot — `SLOTargets` plus the
    `EngineController` / `FleetController` feedback loops that actuate
    chunk size, spec-decode k, prefix-cache admission, graduated load
    shedding, placement weights and replica roles against declared
    targets (see docs/SERVING.md "Autopilot").

See docs/SERVING.md ("Continuous batching", "Disaggregated serving")
for sizing and usage.
"""

from typing import Any, Dict

from .. import observability as _obs
from ..observability import tracing as _tracing
from .block_allocator import PageBlockAllocator
from .controller import EngineController, FleetController, SLOTargets
from .engine import ServingEngine
from .handoff import KVPageHandoff
from .prefix_cache import PrefixCache
from .router import FleetRouter
from .scheduler import Request, Scheduler

__all__ = ["ServingEngine", "Request", "Scheduler", "PageBlockAllocator",
           "PrefixCache", "KVPageHandoff", "FleetRouter", "SLOTargets",
           "EngineController", "FleetController", "metrics", "slo"]


def metrics() -> Dict[str, Any]:
    """The serving.* slice of the registry snapshot (engine, prefix
    cache, and speculative-decode metric families)."""
    return {k: v for k, v in _obs.registry().snapshot().items()
            if k.startswith("serving.")}


def slo(qs=(50, 90, 99)) -> Dict[str, Any]:
    """Percentile summary of the per-request SLO histograms the tracing
    layer derives at each terminal event:
    {"serving.engine.ttft_seconds": {count, mean, p50, p90, p99}, ...}
    for queue-wait / TTFT / TPOT / e2e. Histograms with no finished
    requests yet report count 0 with None quantiles."""
    return _tracing.slo_summary(qs=qs)
