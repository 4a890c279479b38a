"""Paged KV block allocator for the continuous-batching engine.

Host-side bookkeeping over a FIXED pool of `page_size`-token blocks laid
out exactly as ops/pallas_paged.py consumes them (k/v_pages
[KV, total_pages, page_size, D]; per-sequence page table [pages_per_seq]
int32). The allocator never touches device memory: it hands out physical
page ids, tracks per-page refcounts for copy-on-write prefix sharing,
and returns (src, dst) page-copy ops the engine applies to the device
pools before a shared page is written.

Design (vLLM PagedAttention block manager, PAPERS "Ragged Paged
Attention"):

  - page 0 is the TRASH page: inactive engine slots point their whole
    page table at it so the fixed-shape decode step can write somewhere
    without corrupting live pages. It is never handed out.
  - admission is CONSERVATIVE: a sequence reserves every page it could
    ever need (ceil(total_tokens / page_size), minus pages it shares
    with a prefix donor) up front, so a mid-flight `extend` can never
    fail — OOM surfaces as a clean `resilience.Overloaded` at admission
    time, before any state changed.
  - `fork` shares the donor's prefix pages by refcount (full pages AND
    the trailing partial page); the first write into a shared page
    copies it (COW), so donors and forks never observe each other's
    tokens.
  - TWO PAGE LIFETIMES (`window=`): a model whose layers are of two
    kinds keeps two pools under this one manager. The FULL kind is
    everything above: a sequence keeps every page until it ends. The
    WINDOW kind (sliding-window layers) has its own pool, free list and
    page table per sequence; `extend` hands out its pages at the same
    positions, and `release_window` returns to the free list the pages
    that lie wholly below `length - window + 1` — the oldest key the
    NEXT step's oldest query still sees — and points their table
    entries back at the trash page. A sequence reserves in that pool
    only what it can hold at once: the pages spanned by `window - 1`
    old positions plus one step's new ones (`window_span`). Window
    pages are never shared, so `fork` / `adopt` / `export_seq` /
    `import_seq` refuse an allocator with a window.
  - TWO PAGE LISTS IN ONE LAYER (`ChunkSummaryAllocator`): chunk-summary
    attention keeps, in EVERY layer, the exact rows of the current
    tumbling window and one pooled row for each closed chunk. Both
    lists draw from the ONE pool; they differ in growth and lifetime.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from .. import observability as _obs
from .. import resilience as _res

__all__ = ["PageBlockAllocator", "ChunkSummaryAllocator"]

_PAGES_USED = _obs.registry().gauge(
    "serving.engine.pages_used", "pool pages currently allocated to "
    "sequences (trash page excluded)")
_PAGES_FREE = _obs.registry().gauge(
    "serving.engine.pages_free", "pool pages on the free list")
_UTIL = _obs.registry().gauge(
    "serving.engine.page_utilization",
    "allocated pages / usable pool pages")
_FRAG = _obs.registry().gauge(
    "serving.engine.page_fragmentation",
    "1 - live tokens / allocated page capacity (wasted tail slots)")
_COW = _obs.registry().counter(
    "serving.engine.cow_copies", "copy-on-write page copies")
_SHARED_TOK = _obs.registry().counter(
    "serving.engine.prefix_shared_tokens",
    "prompt tokens whose prefill was skipped via prefix sharing")


class _Seq:
    __slots__ = ("pages", "length", "reserved", "wpages", "wfirst",
                 "wreserved")

    def __init__(self, pages: List[int], length: int, reserved: int):
        self.pages = pages          # physical page ids, in position order
        self.length = length        # tokens logically present
        self.reserved = reserved    # pages still owed from the free list
        # the window kind: physical page by logical index (0 = released
        # or not yet written), the first index still held, pages owed
        self.wpages: List[int] = []
        self.wfirst = 0
        self.wreserved = 0


class PageBlockAllocator:
    """Fixed pool of KV pages with refcounted copy-on-write sharing."""

    def __init__(self, num_pages: int, page_size: int, pages_per_seq: int,
                 window: Optional[int] = None,
                 window_pages: Optional[int] = None,
                 window_span: int = 1):
        """`window` (tokens) switches the second page lifetime on:
        `window_pages` is that pool's size (its page 0 is its trash
        page too), `window_span` the most tokens one `extend` adds (the
        engine's prefill chunk)."""
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved "
                             "as the inactive-slot trash page)")
        if page_size < 1 or pages_per_seq < 1:
            raise ValueError("page_size and pages_per_seq must be >= 1")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.pages_per_seq = int(pages_per_seq)
        # pop() yields ascending ids — deterministic allocation order for
        # the seeded-trace tests
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))
        self._ref = np.zeros(self.num_pages, np.int64)
        self._ref[0] = 1            # trash page: pinned forever
        self._seqs: Dict[object, _Seq] = {}
        self._reserved_total = 0
        # pins: refcounts held by parties that are not sequences (the
        # prefix-cache trie). A pin keeps a page alive across free().
        self._pinned = np.zeros(self.num_pages, np.int64)
        self.window = None if window is None else int(window)
        self.window_pages = 0
        self._wfree: List[int] = []
        self._wreserved_total = 0
        if self.window is not None:
            if self.window < 1 or window_span < 1:
                raise ValueError("window and window_span must be >= 1")
            self.window_span = int(window_span)
            if window_pages is None or window_pages < 1 + self.window_cap:
                raise ValueError(
                    f"window_pages {window_pages} cannot hold one sequence "
                    f"({self.window_cap} pages + the trash page)")
            self.window_pages = int(window_pages)
            self._wfree = list(range(self.window_pages - 1, 0, -1))

    # ------------------------------------------------- the window kind
    @property
    def window_cap(self) -> int:
        """Window-pool pages one sequence can hold at once: `window - 1`
        old positions and one step's new ones, wherever they fall on
        the page grid."""
        return -(-(self.window - 1 + self.window_span)
                 // self.page_size) + 1

    def _wneed(self, total_tokens: int) -> int:
        if self.window is None:
            return 0
        return min(-(-total_tokens // self.page_size), self.window_cap)

    @property
    def free_window_pages(self) -> int:
        return len(self._wfree)

    @property
    def available_window_pages(self) -> int:
        return len(self._wfree) - self._wreserved_total

    def release_window(self, seq_id) -> int:
        """Return to the window pool the pages of `seq_id` that no
        future query can see: those wholly below `length - window + 1`
        (the next step's oldest query sits at `length`). Returns how
        many were freed."""
        seq = self._seqs[seq_id]
        keep_from = max(seq.length - self.window + 1, 0) // self.page_size
        freed = 0
        for idx in range(seq.wfirst, min(keep_from, len(seq.wpages))):
            if seq.wpages[idx]:
                self._wfree.append(seq.wpages[idx])
                seq.wpages[idx] = 0
                seq.wreserved += 1      # it may be needed again ahead
                self._wreserved_total += 1
                freed += 1
        seq.wfirst = max(seq.wfirst, min(keep_from, len(seq.wpages)))
        return freed

    def window_table(self, seq_id) -> np.ndarray:
        """[pages_per_seq] int32 page table of the window kind: trash
        where a page was released or is not written yet."""
        t = np.zeros(self.pages_per_seq, np.int32)
        pages = self._seqs[seq_id].wpages
        t[:len(pages)] = pages
        return t

    def _no_window(self, what: str) -> None:
        if self.window is not None:
            raise NotImplementedError(
                f"{what} shares or moves a sequence's pages; pages of the "
                f"window kind are released as the window passes them and "
                f"are never shared")

    # ---------------------------------------------------------------- pool
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def available_pages(self) -> int:
        """Pages not yet handed out AND not promised to a live sequence."""
        return len(self._free) - self._reserved_total

    def refcount(self, page: int) -> int:
        return int(self._ref[page])

    def pinned(self, page: int) -> int:
        """Pin count on `page` (refcounts held by non-sequence owners)."""
        return int(self._pinned[page])

    def pin(self, page: int) -> None:
        """Take an extra refcount on an ALLOCATED page so it survives
        every holder's `free()`. Used by the prefix-cache trie to keep
        prompt pages warm across requests."""
        if page <= 0 or page >= self.num_pages:
            raise ValueError(f"cannot pin page {page}")
        if self._ref[page] < 1:
            raise ValueError(f"cannot pin free page {page}")
        self._ref[page] += 1
        self._pinned[page] += 1

    def unpin(self, page: int) -> bool:
        """Drop one pin; returns True when the page went back to the
        free list (no sequence and no other pin still holds it)."""
        if page <= 0 or page >= self.num_pages or self._pinned[page] < 1:
            raise ValueError(f"page {page} is not pinned")
        self._pinned[page] -= 1
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)
            self.publish_gauges()
            return True
        return False

    def _need_pages(self, total_tokens: int, share_tokens: int = 0) -> int:
        """Free-list pages a sequence of `total_tokens` may consume when
        `share_tokens` of its prefix ride on a donor's pages: every
        non-shared page, plus one for the COW of a partially-shared
        page (its first write copies it)."""
        ps = self.page_size
        n_total = -(-total_tokens // ps)
        return n_total - share_tokens // ps

    def pages_needed(self, total_tokens: int, share_tokens: int = 0) -> int:
        """Free-list pages an admission would consume (public mirror of
        the internal reservation math, used by the engine's
        evict-then-retry path)."""
        return self._need_pages(total_tokens, share_tokens)

    def can_admit(self, total_tokens: int, share_tokens: int = 0) -> bool:
        return (self._need_pages(total_tokens, share_tokens)
                <= self.available_pages
                and self._wneed(total_tokens)
                <= self.available_window_pages)

    # ------------------------------------------------------------ lifecycle
    def allocate(self, seq_id, total_tokens: int) -> None:
        """Admit a sequence that will hold at most `total_tokens` tokens
        (prompt + max_new), reserving every page it could need. Raises
        `resilience.Overloaded` (no state change) if the pool cannot
        guarantee it."""
        self._check_new(seq_id, total_tokens)
        need = self._need_pages(total_tokens)
        if need > self.available_pages:
            raise _res.Overloaded(
                f"page pool exhausted: sequence needs {need} pages, "
                f"{self.available_pages} available "
                f"({self.num_pages - 1} usable)")
        wneed = self._wneed(total_tokens)
        if wneed > self.available_window_pages:
            raise _res.Overloaded(
                f"window page pool exhausted: sequence needs {wneed} "
                f"pages, {self.available_window_pages} available "
                f"({self.window_pages - 1} usable)")
        seq = self._seqs[seq_id] = _Seq([], 0, need)
        seq.wreserved = wneed
        self._reserved_total += need
        self._wreserved_total += wneed
        self.publish_gauges()

    def fork(self, parent_id, child_id, share_tokens: int,
             total_tokens: int) -> None:
        """Admit `child_id` sharing the first `share_tokens` tokens of
        `parent_id`'s cache by refcount. The child starts at
        length == share_tokens; its first write into the trailing
        partially-shared page copies it (COW)."""
        self._no_window("fork")
        parent = self._seqs[parent_id]
        if share_tokens < 0 or share_tokens > parent.length:
            raise ValueError(
                f"share_tokens {share_tokens} outside parent's "
                f"{parent.length} cached tokens")
        if share_tokens == 0:
            return self.allocate(child_id, total_tokens)
        self._check_new(child_id, total_tokens)
        if total_tokens < share_tokens:
            raise ValueError("total_tokens < share_tokens")
        need = self._need_pages(total_tokens, share_tokens)
        n_share = -(-share_tokens // self.page_size)
        # sharing a PARTIAL page puts the donor on the COW hook too: its
        # next write into that page must copy it, a pop its own
        # reservation never covered. Charge the donor one page now (only
        # on the 1->2 refcount transition — after the first COW the page
        # is private again and later forks re-charge it themselves).
        donor_extra = 1 if (share_tokens % self.page_size
                            and parent.length < n_share * self.page_size
                            and self._ref[parent.pages[n_share - 1]] == 1) \
            else 0
        if need + donor_extra > self.available_pages:
            raise _res.Overloaded(
                f"page pool exhausted: fork needs {need + donor_extra} "
                f"pages, {self.available_pages} available")
        shared = parent.pages[:n_share]
        for pg in shared:
            self._ref[pg] += 1
        parent.reserved += donor_extra
        self._seqs[child_id] = _Seq(list(shared), share_tokens, need)
        self._reserved_total += need + donor_extra
        if _obs.enabled():
            _SHARED_TOK.inc(share_tokens)
        self.publish_gauges()

    def adopt(self, seq_id, pages: List[int], share_tokens: int,
              total_tokens: int) -> None:
        """Admit `seq_id` sharing `share_tokens` tokens that live in the
        given FULL `pages` (a prefix-cache trie match). Unlike `fork`
        there is no donor sequence: the pages are held alive by trie
        pins, the share is page-aligned (share_tokens == len(pages) *
        page_size), so the adopter's first write lands on a fresh page —
        no COW and no donor_extra charge. Raises `resilience.Overloaded`
        pre-mutation when the pool cannot cover the tail."""
        self._no_window("adopt")
        self._check_new(seq_id, total_tokens)
        ps = self.page_size
        if share_tokens != len(pages) * ps:
            raise ValueError(
                f"adopt share must be page-aligned: {share_tokens} tokens "
                f"vs {len(pages)} pages of {ps}")
        if total_tokens < share_tokens:
            raise ValueError("total_tokens < share_tokens")
        for pg in pages:
            if pg <= 0 or pg >= self.num_pages or self._ref[pg] < 1:
                raise ValueError(f"cannot adopt dead page {pg}")
        need = self._need_pages(total_tokens, share_tokens)
        if need > self.available_pages:
            raise _res.Overloaded(
                f"page pool exhausted: adopt needs {need} pages, "
                f"{self.available_pages} available")
        for pg in pages:
            self._ref[pg] += 1
        self._seqs[seq_id] = _Seq(list(pages), share_tokens, need)
        self._reserved_total += need
        if _obs.enabled():
            _SHARED_TOK.inc(share_tokens)
        self.publish_gauges()

    def extend(self, seq_id, n_tokens: int = 1) -> List[Tuple[int, int]]:
        """Make the next `n_tokens` write slots physically writable:
        allocates fresh pages at page boundaries and copies-on-write any
        shared page about to be written. Returns [(src_page, dst_page)]
        copy ops the engine must apply to the device pools BEFORE the
        write. Never raises for a sequence admitted by allocate/fork
        (the reservation covers the worst case)."""
        seq = self._seqs[seq_id]
        ps = self.page_size
        copies: List[Tuple[int, int]] = []
        for pos in range(seq.length, seq.length + n_tokens):
            idx = pos // ps
            if idx >= self.pages_per_seq:
                raise ValueError(
                    f"sequence {seq_id!r} overflows pages_per_seq="
                    f"{self.pages_per_seq} at token {pos}")
            if idx == len(seq.pages):
                seq.pages.append(self._pop_page(seq))
            elif self._ref[seq.pages[idx]] > 1:
                src = seq.pages[idx]
                dst = self._pop_page(seq)
                self._ref[src] -= 1
                seq.pages[idx] = dst
                copies.append((src, dst))
                if _obs.enabled():
                    _COW.inc()
            if self.window is not None:
                while len(seq.wpages) <= idx:
                    seq.wpages.append(0)
                if not seq.wpages[idx]:
                    seq.wpages[idx] = self._pop_window_page(seq)
        seq.length += n_tokens
        return copies

    def shrink(self, seq_id, n_tokens: int) -> None:
        """Roll the sequence's logical length back by `n_tokens`
        (speculative-decode rejection). Pages stay attached — the
        positions are within the reservation and will be rewritten; the
        attention row tables never read past `seq_length`, so stale KV
        beyond the new length is unobservable."""
        if n_tokens < 0:
            raise ValueError("n_tokens must be >= 0")
        seq = self._seqs[seq_id]
        if n_tokens > seq.length:
            raise ValueError(
                f"cannot shrink {seq.length}-token sequence by {n_tokens}")
        seq.length -= n_tokens

    # ------------------------------------------------------------- handoff
    def export_seq(self, seq_id) -> Dict[str, object]:
        """Snapshot `seq_id` for a cross-replica KV-page handoff: its
        page list (position order), logical length, and remaining
        reservation, with ONE pin taken on every page. The pins keep the
        payload readable for the whole pin → export → import → unpin
        window even if the sequence is freed in between (a preemption or
        queue expiry landing mid-handoff must leave both replicas
        consistent), and they stack on top of trie pins, so shared-
        prefix pages come back with their refcounts intact when
        `release_export` drops them.

        Only pages covering the LOGICAL length are exported: after a
        speculative-decode `shrink` a sequence may keep a trailing page
        whose KV beyond `length` is stale-but-unobservable, and the
        importer materializes exactly `ceil(length / page_size)` pages."""
        self._no_window("export_seq")
        seq = self._seqs[seq_id]
        n_pages = -(-seq.length // self.page_size)
        pages = list(seq.pages[:n_pages])
        for pg in pages:
            self.pin(pg)
        return {"pages": pages, "length": seq.length,
                "reserved": seq.reserved}

    def release_export(self, export: Dict[str, object]) -> int:
        """Drop an export's pins once the importer holds its own copy.
        Returns how many pages went back to the free list — pages whose
        owning sequence was freed mid-handoff and that nothing else
        (another sequence, the trie) still shares."""
        freed = 0
        for pg in export["pages"]:
            if self.unpin(pg):
                freed += 1
        return freed

    def import_seq(self, seq_id, length: int,
                   total_tokens: int) -> List[int]:
        """Admit `seq_id` with `length` tokens already materialized on
        another replica (the receive side of a KV-page handoff):
        reserves the full `total_tokens` worst case like `allocate`,
        then claims fresh pages for the first `length` tokens. Returns
        the destination page list in position order — the engine copies
        the handoff payload into exactly these pages. Raises
        `resilience.Overloaded` pre-mutation when the pool cannot cover
        the sequence."""
        self._no_window("import_seq")
        if length < 1 or length > total_tokens:
            raise ValueError(
                f"import length {length} outside [1, {total_tokens}]")
        self.allocate(seq_id, total_tokens)
        # fresh pages only — nothing is shared yet, so extend can never
        # produce COW copies here
        copies = self.extend(seq_id, length)
        assert not copies
        return self.seq_pages(seq_id)

    def free(self, seq_id) -> None:
        """Release a finished sequence: derefs its pages (returning
        refcount-0 pages to the free list) and drops its remaining
        reservation."""
        seq = self._seqs.pop(seq_id)
        for pg in seq.pages:
            self._ref[pg] -= 1
            if self._ref[pg] == 0:
                self._free.append(pg)
        self._reserved_total -= seq.reserved
        self._wfree.extend(pg for pg in seq.wpages if pg)
        self._wreserved_total -= seq.wreserved
        self.publish_gauges()

    # -------------------------------------------------------------- queries
    def table(self, seq_id) -> np.ndarray:
        """[pages_per_seq] int32 page table, trash-padded past the end."""
        t = np.zeros(self.pages_per_seq, np.int32)
        pages = self._seqs[seq_id].pages
        t[:len(pages)] = pages
        return t

    def has_seq(self, seq_id) -> bool:
        return seq_id in self._seqs

    def seq_length(self, seq_id) -> int:
        return self._seqs[seq_id].length

    def seq_pages(self, seq_id) -> List[int]:
        return list(self._seqs[seq_id].pages)

    def stats(self) -> Dict[str, float]:
        used = self.num_pages - 1 - len(self._free)
        usable = self.num_pages - 1
        # per-page occupancy: shared prefix pages hold the same tokens
        # for every sharer, so count each physical page once at its
        # deepest fill
        occ: Dict[int, int] = {}
        for seq in self._seqs.values():
            for i, pg in enumerate(seq.pages):
                filled = min(seq.length - i * self.page_size,
                             self.page_size)
                if filled > 0:
                    occ[pg] = max(occ.get(pg, 0), filled)
        # trie-pinned pages are full by construction (only whole prompt
        # pages are inserted), so they are occupancy, not waste
        for pg in np.nonzero(self._pinned)[0]:
            occ[int(pg)] = self.page_size
        cap = used * self.page_size
        live = sum(occ.values())
        return {
            "pages_used": used,
            "pages_free": len(self._free),
            "utilization": used / usable if usable else 0.0,
            "fragmentation": 1.0 - live / cap if cap else 0.0,
            "reserved": self._reserved_total,
            "sequences": len(self._seqs),
            "pinned_pages": int((self._pinned > 0).sum()),
            "window_pages_used": max(self.window_pages - 1, 0)
            - len(self._wfree),
            "window_pages_free": len(self._wfree),
        }

    def publish_gauges(self) -> None:
        if not _obs.enabled():
            return
        st = self.stats()
        _PAGES_USED.set(st["pages_used"])
        _PAGES_FREE.set(st["pages_free"])
        _UTIL.set(st["utilization"])
        _FRAG.set(st["fragmentation"])

    # ------------------------------------------------------------ internals
    def _check_new(self, seq_id, total_tokens: int) -> None:
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if total_tokens < 1:
            raise ValueError("total_tokens must be >= 1")
        if total_tokens > self.pages_per_seq * self.page_size:
            raise ValueError(
                f"{total_tokens} tokens exceed pages_per_seq * page_size "
                f"= {self.pages_per_seq * self.page_size}")

    def _pop_page(self, seq: _Seq) -> int:
        if not self._free:
            # unreachable for sequences admitted through allocate/fork —
            # the reservation is the no-corruption guarantee — but a
            # clean typed error beats an IndexError if bookkeeping ever
            # drifts
            raise _res.Overloaded("page pool exhausted mid-flight")
        pg = self._free.pop()
        if seq.reserved > 0:
            seq.reserved -= 1
            self._reserved_total -= 1
        self._ref[pg] = 1
        return pg

    def _pop_window_page(self, seq: _Seq) -> int:
        if not self._wfree or seq.wreserved < 1:
            # as above: the reservation (window_cap) is the guarantee
            raise _res.Overloaded("window page pool exhausted mid-flight")
        seq.wreserved -= 1
        self._wreserved_total -= 1
        return self._wfree.pop()


class ChunkSummaryAllocator(PageBlockAllocator):
    """Page manager of chunk-summary (EVA) attention: a sequence's cache
    in every layer is TWO lists of rows of one shape, from ONE pool.

      - the WINDOW list: the exact K/V rows of the current window of
        `window` tokens, one row a token. The window tumbles: when the
        sequence's length reaches a multiple of `window` all its pages
        go back to the pool together (`release_window`) and the next
        token starts a new list.
      - the SUMMARY list: one pooled row for each chunk of `chunk`
        tokens, written when the chunk's last token is (`extend` hands
        out its page then), visible to attention only from the close of
        the chunk's window on, kept until the sequence ends.

    A sequence of `total` tokens reserves min(window pages, ceil(total /
    page_size)) + ceil(total / (chunk * page_size)) pages; a window's
    close returns its pages to the free list and re-reserves what the
    next window can need. Rows of neither list are shared or moved:
    `fork` / `adopt` / `export_seq` / `import_seq` / `shrink` refuse.

    `attention_view` is the sequence as attention reads it: the pages of
    the VISIBLE summary rows, then the window's pages — one page table,
    one KV length counted from its first row, and the count of summary
    rows (the rest of their last page is a hole that
    `ragged_paged_attention(summary_rows=)` masks)."""

    def __init__(self, num_pages: int, page_size: int, window: int,
                 chunk: int, max_tokens: int):
        if window % page_size or page_size % chunk:
            raise ValueError(
                f"a window ({window}) is whole pages ({page_size}) and a "
                f"page whole chunks ({chunk})")
        self.span = int(window)         # (`window` is the sliding kind's)
        self.chunk = int(chunk)
        self.window_list_pages = window // page_size
        super().__init__(num_pages, page_size, self.table_pages(
            page_size, window, chunk, max_tokens))
        self.max_tokens = int(max_tokens)
        self._total: Dict[object, int] = {}

    @staticmethod
    def table_pages(page_size: int, window: int, chunk: int,
                    max_tokens: int) -> int:
        """Entries of a sequence's page table as attention reads it:
        the pooled pages of `max_tokens` tokens, then a window's."""
        return -(-max_tokens // (chunk * page_size)) + window // page_size

    # ---------------------------------------------------- reservation
    def _check_new(self, seq_id, total_tokens: int) -> None:
        if seq_id in self._seqs:
            raise ValueError(f"sequence {seq_id!r} already allocated")
        if not 1 <= total_tokens <= self.max_tokens:
            raise ValueError(f"{total_tokens} tokens outside "
                             f"[1, {self.max_tokens}]")

    def _window_need(self, tokens_left: int) -> int:
        return min(self.window_list_pages, -(-tokens_left // self.page_size))

    def _need_pages(self, total_tokens: int, share_tokens: int = 0) -> int:
        return (self._window_need(total_tokens)
                + -(-total_tokens // (self.chunk * self.page_size)))

    def _refuse(self, what: str):
        raise NotImplementedError(
            f"{what}: pooled rows and a tumbling window's pages are never "
            f"shared, moved or rolled back (chunk-summary attention)")

    def fork(self, *a, **k):
        self._refuse("fork")

    def adopt(self, *a, **k):
        self._refuse("adopt")

    def export_seq(self, *a, **k):
        self._refuse("export_seq")

    def import_seq(self, *a, **k):
        self._refuse("import_seq")

    def shrink(self, *a, **k):
        self._refuse("shrink")

    # ------------------------------------------------------ lifecycle
    def allocate(self, seq_id, total_tokens: int) -> None:
        super().allocate(seq_id, total_tokens)
        self._total[seq_id] = int(total_tokens)

    def extend(self, seq_id, n_tokens: int = 1) -> List[Tuple[int, int]]:
        """Make the next `n_tokens` rows writable: a window page at each
        page boundary, and the summary page of each chunk that closes.
        The new tokens may not straddle a window, and the window before
        must have been released. Never copies."""
        seq = self._seqs[seq_id]
        ps, W, c = self.page_size, self.span, self.chunk
        first, last = seq.length, seq.length + n_tokens - 1
        if n_tokens < 1 or first // W != last // W:
            raise ValueError(
                f"sequence {seq_id!r}: tokens {first}..{last} straddle a "
                f"window of {W}")
        if first % W == 0 and seq.wpages:
            raise RuntimeError(
                f"sequence {seq_id!r} starts a window at {first} with the "
                f"last one's pages not released")
        if last >= self._total[seq_id]:
            raise ValueError(
                f"sequence {seq_id!r} overflows its {self._total[seq_id]} "
                f"reserved tokens at token {last}")
        while len(seq.wpages) <= last % W // ps:
            seq.wpages.append(self._pop_page(seq))
        while len(seq.pages) < -(-((last + 1) // c) // ps):
            seq.pages.append(self._pop_page(seq))
        seq.length += n_tokens
        return []

    def release_window(self, seq_id) -> int:
        """If the sequence stands at a window's close, return the
        window's pages to the pool, all together, and reserve what the
        next window can need. Returns how many were freed. A launch in
        flight may still read them: every later write goes through a
        later launch, which the device runs after it."""
        seq = self._seqs[seq_id]
        if not seq.wpages or seq.length % self.span:
            return 0
        freed = len(seq.wpages)
        for pg in seq.wpages:
            self._ref[pg] -= 1
            self._free.append(pg)
        seq.wpages = []
        owed = self._window_need(self._total[seq_id] - seq.length)
        seq.reserved += owed
        self._reserved_total += owed
        return freed

    def free(self, seq_id) -> None:
        seq = self._seqs[seq_id]
        seq.pages, seq.wpages = seq.pages + seq.wpages, []
        del self._total[seq_id]
        super().free(seq_id)

    # -------------------------------------------------------- queries
    def attention_view(self, seq_id) -> Tuple[np.ndarray, int, int]:
        """(page table [pages_per_seq], summary rows, KV length) of the
        sequence as its NEWEST tokens' queries read it: the summaries
        of every closed window, then the current window's rows."""
        seq = self._seqs[seq_id]
        closed = max(seq.length - 1, 0) // self.span
        rows = closed * (self.span // self.chunk)
        n_sp = -(-rows // self.page_size)
        t = np.zeros(self.pages_per_seq, np.int32)
        t[:n_sp] = seq.pages[:n_sp]
        t[n_sp:n_sp + len(seq.wpages)] = seq.wpages
        return t, rows, (n_sp * self.page_size
                         + seq.length - closed * self.span)

    def token_pages(self, seq_id, positions: np.ndarray) -> np.ndarray:
        """Physical page of each position of the CURRENT window (its
        row is `position % page_size`)."""
        wp = np.asarray(self._seqs[seq_id].wpages, np.int32)
        return wp[positions % self.span // self.page_size]

    def closing_chunks(self, seq_id, start: int, n: int) -> np.ndarray:
        """[k, 4] int32 (source page, chunk within it, summary page,
        summary row) of each chunk whose last token is among positions
        [start, start + n) of the current window."""
        seq = self._seqs[seq_id]
        ps, c = self.page_size, self.chunk
        ends = np.arange(-(-(start + 1) // c) * c - 1, start + n, c)
        j = ends // c
        return np.stack([self.token_pages(seq_id, ends), ends % ps // c,
                         np.asarray(seq.pages, np.int32)[j // ps],
                         j % ps], 1).astype(np.int32).reshape(-1, 4)

    def table(self, seq_id) -> np.ndarray:
        return self.attention_view(seq_id)[0]

    def pages_by_list(self) -> Tuple[int, int]:
        """Pool pages held by (summary lists, window lists)."""
        return (sum(len(s.pages) for s in self._seqs.values()),
                sum(len(s.wpages) for s in self._seqs.values()))

    def stats(self) -> Dict[str, float]:
        st = super().stats()
        live = sum(
            s.length // self.chunk + (s.length - max(s.length - 1, 0)
                                      // self.span * self.span
                                      if s.wpages else 0)
            for s in self._seqs.values())
        cap = st["pages_used"] * self.page_size
        st["fragmentation"] = 1.0 - live / cap if cap else 0.0
        return st
