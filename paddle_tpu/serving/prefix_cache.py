"""Global radix prefix cache over the paged KV pool.

A trie keyed on token prefixes at PAGE granularity: each node is one
FULL page of `page_size` tokens, its edge key is that page's token
tuple, and its payload is the physical page id in the engine's KV pools.
The trie holds each page alive with a `PageBlockAllocator.pin()`
refcount, so prompt pages survive the request that prefilled them and a
later request whose prompt extends a cached prefix admits with those
pages shared (`allocator.adopt`) and only the tail prefilled.

Exactness discipline (why sharing is safe):

  - causal attention + absolute position embeddings mean a page's KV
    rows depend only on the token prefix up to and through that page —
    the trie path IS that prefix, so a path match is an exact KV match;
  - where the model's state blocks are FINITE HISTORIES (a short
    convolution's last K - 1 rows: LFM2), a page id names, beside its
    K/V rows, the TAILS at its last row in every such block (the
    engine's snapshot planes, written by the chunk that fills the page):
    they too depend on the token prefix through that page and on nothing
    else, so an adopter that continues from the last adopted page's
    tails is exact; a RECURRENT state has no such cut, and the engine
    builds no trie for those models;
  - only FULL pages are cached, so an adopter's first write lands on a
    page boundary (a fresh page) — trie pages are never written after
    insertion and need no COW;
  - the match is capped at `(len(prompt) - 1) // page_size` pages: the
    last prompt token is always recomputed so the engine still produces
    first-token logits.

Eviction is LRU over leaves whose page refcount equals its pin count
(i.e. no live sequence shares it): under pool pressure the engine calls
`evict()` to return cold pages to the free list, cascading to parents
as leaves disappear. All trie state is guarded by one lock so a future
multi-threaded scheduler stays PT006-clean.
"""

from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional, Tuple

from .. import observability as _obs
from .block_allocator import PageBlockAllocator

__all__ = ["PrefixCache", "PrefixMatch"]

_HITS = _obs.registry().counter(
    "serving.prefix_cache.hits",
    "admissions whose prompt matched >= 1 cached page")
_MISSES = _obs.registry().counter(
    "serving.prefix_cache.misses",
    "admissions with no cached prefix page")
_EVICTED = _obs.registry().counter(
    "serving.prefix_cache.evicted_pages",
    "trie pages evicted under pool pressure")
_SHARED = _obs.registry().counter(
    "serving.prefix_cache.shared_tokens",
    "prompt tokens whose prefill was skipped via the prefix cache")
_PAGES = _obs.registry().gauge(
    "serving.prefix_cache.pages", "pages currently pinned by the trie")
# per-replica families (ROADMAP item 2): the fleet router's locality
# score is computed from the SAME counters operators see — a replica's
# trie labels its hit/pin/eviction traffic with its name
_R_HIT_TOK = _obs.registry().counter(
    "serving.prefix_cache.replica_hit_tokens",
    "prompt tokens matched in the trie at lookup, by replica",
    labels=("replica",))
_R_PINNED = _obs.registry().gauge(
    "serving.prefix_cache.replica_pinned_pages",
    "pages currently pinned by the replica's trie", labels=("replica",))
_R_EVICTED = _obs.registry().counter(
    "serving.prefix_cache.replica_evicted_pages",
    "trie pages evicted under pool pressure, by replica",
    labels=("replica",))


class _Node:
    __slots__ = ("key", "page", "parent", "children", "tick")

    def __init__(self, key: Optional[Tuple[int, ...]], page: Optional[int],
                 parent: Optional["_Node"]):
        self.key = key              # page_size-token tuple (None at root)
        self.page = page            # physical page id (None at root)
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.tick = 0               # LRU clock value of last touch


class PrefixMatch:
    """Result of a `lookup`: the matched pages, pinned against eviction
    until `release()`. The engine adopts the pages (taking its own
    refcounts) and then ALWAYS releases the match — also on every
    refusal path, so no admission failure leaks a pin."""

    __slots__ = ("_cache", "pages", "tokens", "_released")

    def __init__(self, cache: "PrefixCache", pages: List[int], tokens: int):
        self._cache = cache
        self.pages = pages
        self.tokens = tokens
        self._released = False

    def release(self) -> None:
        if self._released:
            return
        self._released = True
        self._cache._release_pins(self.pages)


class PrefixCache:
    """Radix trie of pinned KV pages shared across requests/tenants."""

    def __init__(self, allocator: PageBlockAllocator,
                 replica: Optional[str] = None):
        self._alloc = allocator
        self._ps = allocator.page_size
        self._root = _Node(None, None, None)
        self._lock = threading.Lock()
        # deterministic LRU clock (no wall time: seeded traces replay)
        self._clock = itertools.count(1)
        self._pages = 0
        self._replica = replica

    def set_replica(self, name: str) -> None:
        """Adopt a replica name for the labeled metric families (the
        FleetRouter names engines it was handed anonymously)."""
        self._replica = name
        if _obs.enabled():
            _R_PINNED.labels(replica=name).set(self._pages)

    # ---------------------------------------------------------------- keys
    def _chunk(self, prompt, i: int) -> Tuple[int, ...]:
        ps = self._ps
        return tuple(int(t) for t in prompt[i * ps:(i + 1) * ps])

    def _max_pages(self, prompt) -> int:
        # never match the LAST prompt token: the engine must recompute
        # it to produce the first output logits
        return max(0, (len(prompt) - 1) // self._ps)

    # -------------------------------------------------------------- lookup
    def lookup(self, prompt) -> PrefixMatch:
        """Longest cached prefix of `prompt`, capped one token short of
        the full prompt. Matched pages are pinned until `release()`."""
        pages: List[int] = []
        with self._lock:
            tick = next(self._clock)
            node = self._root
            for i in range(self._max_pages(prompt)):
                child = node.children.get(self._chunk(prompt, i))
                if child is None:
                    break
                child.tick = tick
                pages.append(child.page)
                node = child
            for pg in pages:
                self._alloc.pin(pg)
            if _obs.enabled():
                (_HITS if pages else _MISSES).inc()
                if pages and self._replica is not None:
                    _R_HIT_TOK.labels(replica=self._replica).inc(
                        len(pages) * self._ps)
        return PrefixMatch(self, pages, len(pages) * self._ps)

    def match_length(self, prompt) -> int:
        """Tokens a `lookup` would share, without pinning or touching
        LRU state (used by the preemption fit guard)."""
        n = 0
        with self._lock:
            node = self._root
            for i in range(self._max_pages(prompt)):
                node = node.children.get(self._chunk(prompt, i))
                if node is None:
                    break
                n += 1
        return n * self._ps

    def note_adopted(self, tokens: int) -> None:
        """The engine admitted a request on `tokens` cached tokens."""
        if _obs.enabled():
            _SHARED.inc(tokens)

    def _release_pins(self, pages: List[int]) -> None:
        with self._lock:
            for pg in pages:
                self._alloc.unpin(pg)

    # -------------------------------------------------------------- insert
    def insert(self, prompt, seq_pages: List[int]) -> int:
        """Cache the FULL prompt pages of a sequence that just finished
        prefill (`seq_pages` is its physical page list). Existing nodes
        are kept (first writer wins — its KV is exact by construction);
        new nodes pin their page. Returns pages newly inserted."""
        n_full = len(prompt) // self._ps
        added = 0
        with self._lock:
            tick = next(self._clock)
            node = self._root
            for i in range(n_full):
                key = self._chunk(prompt, i)
                child = node.children.get(key)
                if child is None:
                    pg = seq_pages[i]
                    self._alloc.pin(pg)
                    child = _Node(key, pg, node)
                    node.children[key] = child
                    self._pages += 1
                    added += 1
                child.tick = tick
                node = child
            if _obs.enabled():
                _PAGES.set(self._pages)
                if self._replica is not None:
                    _R_PINNED.labels(
                        replica=self._replica).set(self._pages)
        return added

    # ------------------------------------------------------------ eviction
    def _evictable_locked(self) -> List[_Node]:
        out, stack = [], [self._root]
        while stack:
            n = stack.pop()
            stack.extend(n.children.values())
            if n.parent is not None and not n.children \
                    and self._alloc.refcount(n.page) \
                    == self._alloc.pinned(n.page):
                out.append(n)
        return out

    def evictable_pages(self) -> int:
        """Trie leaves no live sequence shares (an upper bound on what
        `evict` could free right now; cascading can expose more)."""
        with self._lock:
            return len(self._evictable_locked())

    def evict(self, need_pages: int) -> int:
        """LRU-evict cold leaves until `need_pages` pages went back to
        the free list or nothing evictable remains. Returns pages
        actually freed. Leaves still pinned by an outstanding
        `PrefixMatch` count as evictable but are the warmest (the
        lookup just touched them), so LRU takes them last — and their
        match pin keeps the page alive for the adopter regardless."""
        freed = 0
        with self._lock:
            while freed < need_pages:
                leaves = self._evictable_locked()
                if not leaves:
                    break
                victim = min(leaves, key=lambda n: n.tick)
                del victim.parent.children[victim.key]
                self._pages -= 1
                if self._alloc.unpin(victim.page):
                    freed += 1
                if _obs.enabled():
                    _EVICTED.inc()
                    if self._replica is not None:
                        _R_EVICTED.labels(replica=self._replica).inc()
            if _obs.enabled():
                _PAGES.set(self._pages)
                if self._replica is not None:
                    _R_PINNED.labels(
                        replica=self._replica).set(self._pages)
        return freed

    def flush(self) -> int:
        """Evict everything evictable (tests / engine shutdown)."""
        return self.evict(1 << 30)

    # --------------------------------------------------------------- stats
    @property
    def pages(self) -> int:
        with self._lock:
            return self._pages

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"pages": self._pages,
                    "evictable": len(self._evictable_locked())}
