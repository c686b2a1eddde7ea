"""Host-side paged KV-cache bookkeeping, with prefix caching.

The device holds the pages (``models.llama.make_cache``: ``[L, N, P, K,
D]``, or ``[L, N, P, K*D]`` where fewer than 8 kv heads would leave the
TPU's (8, 128) tile part empty and cost a re-tiling of the whole cache in
every layer; the same bytes, and a page is ``[P, K, D]`` wherever it goes
off the device, ``ops.attention.page_view``). A page index means the same
in either form; this module owns the free list, per-sequence page tables, and the **prefix trie**: finished
sequences donate their full pages (keyed by page-aligned token content) so a
later request whose prompt shares the prefix skips re-prefilling it. The
ReAct loop re-sends the whole chat history every iteration (reference
pkg/assistants/simple.go:497-515) — prefix reuse turns that O(n²) re-prefill
into O(n) (SURVEY.md §5 checkpoint note, §7 step 5).

States of a page:
- **free**: on the free list.
- **owned**: exclusively held by a live sequence (its tail / generated pages).
- **shared**: in the trie with refcount = number of live sequences using it.
- **cached**: in the trie with refcount 0 — content retained, evictable LRU
  when the free list runs dry.

Allocation is O(pages) against a free list plus O(prompt/page_size) trie
walks. Nothing here walks the whole trie on the serving path: the count of
cached pages is kept (``free_pages`` is read several times a tick), and the
least recently used cached page, or snapshot, comes off a heap whose entries
are checked against their node when popped (``_edit`` is the one place a
node's refcount, children or stamp changes). A pool of 16k pages held by a
full trie otherwise costs a scan of it a page taken and a gauge read.

**Recurrent state** (``state_slots`` > 0: a model with linear-attention
layers, ``models.llama.make_state``). Such a layer keeps a fixed-size state
per sequence, whatever its length, and a page chain alone cannot stand in
for it: a prefix is reusable only up to a position for which every such
layer's state was kept. Slots of one device pool are either **live** (one
per running sequence, taken at admission and freed with it) or
**snapshots**: each running sequence also holds one snapshot slot that the
step programs overwrite whenever a pass leaves the sequence on a page
boundary (the slots ride in the sequence's table row, ``page_table_row``);
when the sequence's pages are donated to the trie, the snapshot goes on the
node that ends the chain at the snapshot's position. ``match_prefix_state``
returns the longest chain that ends in a node with a snapshot. Snapshots
are evicted with their node, or alone (LRU by their node's stamp) when a
new sequence needs a slot; evicting one never touches a page.
"""

from __future__ import annotations

import heapq
import itertools
from dataclasses import dataclass, field

import numpy as np


class OutOfPages(Exception):
    """No free KV pages right now; the scheduler should queue the request."""


class PromptTooLong(Exception):
    """The request can NEVER be admitted (exceeds max_pages_per_seq or the
    largest prefill bucket); fail fast instead of queueing."""


class InvalidRequest(ValueError):
    """Malformed request (client's fault, HTTP 400) — distinct from engine
    bugs that happen to raise ValueError, which must stay 5xx."""


@dataclass
class SeqAlloc:
    seq_id: int
    pages: list[int] = field(default_factory=list)
    length: int = 0          # tokens currently in cache
    num_shared: int = 0      # leading pages borrowed from the prefix trie
    # recurrent state (allocators with state slots; -1 = none)
    state_slot: int = -1     # the live slot the sequence's state is in
    snap_slot: int = -1      # the snapshot slot its step programs write
    snap_pos: int = 0        # position the newest pass may have left in it
    state_from: int = 0      # position its state started from (0 or restored)


@dataclass
class TrieNode:
    """One cached page: identified by (parent page, its page of tokens)."""

    page: int
    parent: int                      # parent page id, or -1 at the root
    key: tuple[int, ...]             # the page_size tokens this page holds
    refcount: int = 0                # live sequences sharing this page
    children: int = 0                # child nodes (only leaves are evictable)
    last_use: int = 0                # LRU stamp
    snapshot: int = -1               # state-snapshot slot at this page's end
    order: int = 0                   # rank of insertion: equal stamps fall
    #                                  to the older node, as a scan did


class PageAllocator:
    def __init__(
        self,
        num_pages: int,
        page_size: int,
        max_pages_per_seq: int,
        prefix_cache: bool = True,
        state_slots: int = 0,
        state_snapshots: int = 0,
    ):
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_pages_per_seq = max_pages_per_seq
        self.prefix_cache = prefix_cache
        # Recurrent-state slots: [0, state_slots) live, the rest snapshots.
        self.state_slots = state_slots
        self.state_snapshots = state_snapshots if state_slots else 0
        self._free_live: list[int] = list(range(state_slots - 1, -1, -1))
        self._free_snaps: list[int] = list(range(
            state_slots + self.state_snapshots - 1, state_slots - 1, -1))
        self.snapshots_taken = 0     # attached to a trie node
        self.snapshots_evicted = 0   # dropped, alone or with their node
        # a row of the step programs' table: the pages, then (with state)
        # the live slot and the snapshot slot
        self.table_width = max_pages_per_seq + (2 if state_slots else 0)
        self._free: list[int] = list(range(num_pages - 1, -1, -1))
        self._seqs: dict[int, SeqAlloc] = {}
        self._next_id = 0
        # Prefix trie: (parent_page, token_tuple) -> TrieNode; page -> node.
        self._trie: dict[tuple[int, tuple[int, ...]], TrieNode] = {}
        self._by_page: dict[int, TrieNode] = {}
        self._clock = itertools.count()
        # Cached nodes (refcount 0, no children: evictable), counted, and
        # the LRU queues of them and of the nodes that hold a snapshot:
        # (last_use, order, page), true while the node still reads so.
        self._cached = 0
        self._order = itertools.count()
        self._lru: list[tuple[int, int, int]] = []
        self._snap_lru: list[tuple[int, int, int]] = []
        self.hit_tokens = 0   # cumulative prefix-cache hits (stats)
        self.miss_tokens = 0
        self.evictions = 0    # cumulative trie-leaf evictions (stats)
        # Hierarchical KV tier hooks (serving/offload): ``_spill`` is
        # called with (page, full token chain) right before a trie node's
        # content is dropped from HBM, so the host tier can keep it;
        # ``_host_pool`` is a HostPagePool surfaced through accounting().
        self._spill = None
        self._host_pool = None

    # -- offload tier hooks ------------------------------------------------
    def set_spill(self, fn) -> None:
        """Install the device->host spill hook: ``fn(page, chain_tokens)``
        fires inside every trie eviction BEFORE the page returns to the
        free list (chain_tokens = the page-aligned token prefix whose KV
        the page holds). Exceptions are swallowed — losing a spill only
        costs a future re-prefill, never correctness."""
        self._spill = fn

    def attach_host_pool(self, pool) -> None:
        """Surface a HostPagePool's residency in ``accounting()`` (the
        page-conservation snapshot stays about HBM; host fields ride
        alongside)."""
        self._host_pool = pool

    def _chain_tokens(self, node: TrieNode) -> list[int]:
        """The full page-aligned token prefix ``node``'s page covers,
        reconstructed by walking parent links. Empty when the chain is
        broken (cannot happen for live trie nodes — a parent with children
        is not evictable — but defended anyway)."""
        keys: list[tuple[int, ...]] = []
        cur: TrieNode | None = node
        while cur is not None:
            keys.append(cur.key)
            if cur.parent < 0:
                return [t for k in reversed(keys) for t in k]
            cur = self._by_page.get(cur.parent)
        return []

    def trie_chains(self) -> list[list[int]]:
        """Full page-aligned token chain of every trie-resident page (one
        chain per node, each covering the node and all its ancestors).
        Feeds the fleet registry's prefix digest: the router scores
        longest-cached-prefix affinity against these chains' digests."""
        out: list[list[int]] = []
        for node in self._by_page.values():
            chain = self._chain_tokens(node)
            if chain:
                out.append(chain)
        return out

    # -- queries -----------------------------------------------------------
    @property
    def free_pages(self) -> int:
        return len(self._free) + self._cached

    def accounting(self) -> dict[str, int]:
        """Page-conservation snapshot: every page is exactly one of free,
        trie-resident (shared or cached), or exclusively owned by a live
        sequence — so free + trie + owned == num_pages always. The
        concurrency stress test asserts this under load (the Python answer
        to the reference's missing `go test -race`, SURVEY section 5)."""
        owned = sum(
            len(s.pages) - s.num_shared for s in self._seqs.values()
        )
        out = {
            "free": len(self._free),
            "trie": len(self._by_page),
            "owned": owned,
            "total": len(self._free) + len(self._by_page) + owned,
        }
        if self._host_pool is not None:
            # Tier-2 residency rides along (NOT part of the HBM page
            # conservation sum — host pages are copies, not allocations).
            st = self._host_pool.stats()
            out["host_pool_pages"] = st["pages"]
            out["host_pool_bytes"] = st["bytes"]
            out["host_pool_capacity_bytes"] = st["capacity_bytes"]
        return out

    def pages_needed(self, num_tokens: int) -> int:
        return -(-num_tokens // self.page_size)

    def can_admit(self, num_tokens: int) -> bool:
        return self.pages_needed(num_tokens) <= self.free_pages

    def length(self, seq_id: int) -> int:
        return self._seqs[seq_id].length

    # -- prefix trie -------------------------------------------------------
    def match_prefix(self, tokens: list[int]) -> list[int]:
        """Longest cached page-aligned prefix of ``tokens``; returns the page
        ids WITHOUT taking references (call ``allocate`` with the result)."""
        if not self.prefix_cache:
            return []
        P = self.page_size
        stamp = next(self._clock)
        pages: list[int] = []
        parent = -1
        for i in range(len(tokens) // P):
            node = self._trie.get((parent, tuple(tokens[i * P:(i + 1) * P])))
            if node is None:
                break
            # matched chains are fresh, not LRU bait
            self._edit(node, stamp=stamp)
            pages.append(node.page)
            parent = node.page
        return pages

    def match_prefix_state(
        self, tokens: list[int]
    ) -> tuple[list[int], int, int]:
        """``match_prefix`` for a model with recurrent state: the longest
        cached chain that ENDS in a node holding a state snapshot, that
        snapshot's slot (-1: none, and no page is reused), and how many
        pages the full page match had (what was given up for want of a
        snapshot is the difference)."""
        pages = self.match_prefix(tokens)
        keep = 0
        for i, page in enumerate(pages):
            if self._by_page[page].snapshot >= 0:
                keep = i + 1
        slot = self._by_page[pages[keep - 1]].snapshot if keep else -1
        return pages[:keep], slot, len(pages)

    # -- recurrent-state slots ----------------------------------------------
    def _take_snapshot_slot(self, keep: int = -1) -> int:
        """A free snapshot slot, else the least recently used one that a
        trie node holds (never ``keep``: the one being restored from);
        -1 when running sequences hold them all."""
        if self._free_snaps:
            return self._free_snaps.pop()
        kept = None
        slot = -1
        while self._snap_lru and slot < 0:
            entry = heapq.heappop(self._snap_lru)
            node = self._node_of(entry)
            if node is None or node.snapshot < 0:
                continue
            if node.snapshot == keep:
                kept = entry
                continue
            slot, node.snapshot = node.snapshot, -1
            self.snapshots_evicted += 1
        if kept is not None:
            heapq.heappush(self._snap_lru, kept)
        return slot

    def _drop_snapshot(self, node: TrieNode) -> None:
        if node.snapshot >= 0:
            self._free_snaps.append(node.snapshot)
            node.snapshot = -1
            self.snapshots_evicted += 1

    def _release_state(self, seq: SeqAlloc) -> None:
        if seq.state_slot >= 0:
            self._free_live.append(seq.state_slot)
        if seq.snap_slot >= 0:
            self._free_snaps.append(seq.snap_slot)
        seq.state_slot = seq.snap_slot = -1

    def state_slots_in_use(self) -> tuple[int, int]:
        """(live slots held by sequences, snapshot slots not free)."""
        return (self.state_slots - len(self._free_live),
                self.state_snapshots - len(self._free_snaps))

    def state_slot(self, seq_id: int) -> int:
        return self._seqs[seq_id].state_slot

    def note_pass(self, seq_id: int, start: int, end: int,
                  each_token: bool = False) -> None:
        """A step program is about to take the sequence from ``start`` to
        ``end`` tokens: in one pass (its snapshot slot is written if ``end``
        is a page boundary), or with ``each_token`` a token a pass (every
        boundary on the way is written, the last one stays)."""
        if not self.state_slots:
            return
        seq = self._seqs[seq_id]
        P = self.page_size
        last = end // P * P
        if seq.snap_slot >= 0 and last > start and (
            each_token or last == end
        ):
            seq.snap_pos = last

    def snapshot_boundary(self, seq_id: int, done: int, n: int) -> int:
        """Where a prefill of a prompt of ``n`` tokens that stands at
        ``done`` must end a chunk so that the sequence's snapshot is taken:
        the last page boundary before the prompt's final token (a later
        turn re-sends the prompt and more, and can restore there), or 0
        when there is none ahead."""
        if not self.state_slots or self._seqs[seq_id].snap_slot < 0:
            return 0
        at = (n - 1) // self.page_size * self.page_size
        return at if at > done else 0

    def clamp_chunk(self, seq_id: int, done: int, n: int, chunk: int) -> int:
        """``chunk`` cut so that it does not pass ``snapshot_boundary``."""
        at = self.snapshot_boundary(seq_id, done, n)
        return min(chunk, at - done) if at else chunk

    # -- the trie's nodes: every change goes through these -----------------
    def _node_of(self, entry: tuple[int, int, int]) -> TrieNode | None:
        """The node a queue's entry still describes, else None."""
        stamp, order, page = entry
        node = self._by_page.get(page)
        if node is None or node.order != order or node.last_use != stamp:
            return None
        return node

    def _queue(self, heap: list, node: TrieNode) -> None:
        heapq.heappush(heap, (node.last_use, node.order, node.page))
        if len(heap) > 4 * len(self._by_page) + 1024:
            # stale entries outnumber the nodes: start both queues afresh
            nodes = self._by_page.values()
            self._lru[:] = [
                (n.last_use, n.order, n.page) for n in nodes
                if n.refcount == 0 and n.children == 0]
            self._snap_lru[:] = [
                (n.last_use, n.order, n.page) for n in nodes
                if n.snapshot >= 0]
            heapq.heapify(self._lru)
            heapq.heapify(self._snap_lru)

    def _edit(self, node: TrieNode, refs: int = 0, kids: int = 0,
              stamp: int | None = None) -> None:
        """Change a trie node's refcount, children or LRU stamp, and keep
        the count of cached nodes and both queues true to it."""
        was = node.refcount == 0 and node.children == 0
        node.refcount += refs
        node.children += kids
        if stamp is not None:
            node.last_use = stamp
            if node.snapshot >= 0:
                self._queue(self._snap_lru, node)
        now = node.refcount == 0 and node.children == 0
        self._cached += now - was
        if now and (stamp is not None or not was):
            self._queue(self._lru, node)

    def _insert(self, node: TrieNode) -> None:
        node.order = next(self._order)
        self._trie[(node.parent, node.key)] = node
        self._by_page[node.page] = node
        if node.parent >= 0 and node.parent in self._by_page:
            self._edit(self._by_page[node.parent], kids=1)
        if node.refcount == 0 and node.children == 0:
            self._cached += 1
            self._queue(self._lru, node)

    def _take_free_page(self) -> int:
        """Pop a free page, evicting the LRU unreferenced trie leaf if the
        free list is dry. Raises OutOfPages when nothing is evictable."""
        if self._free:
            return self._free.pop()
        while self._lru:
            node = self._node_of(heapq.heappop(self._lru))
            if node is not None and node.refcount == 0 and node.children == 0:
                self._evict(node)
                return self._free.pop()
        raise OutOfPages("no free pages and no evictable cached pages")

    def _evict(self, node: TrieNode) -> None:
        """Drop a cached node (refcount 0, no children) from the trie."""
        if self._spill is not None:
            # Host tier: copy the content out before the page is reused.
            # The chain is reconstructed BEFORE the node leaves the trie.
            try:
                chain = self._chain_tokens(node)
                if chain:
                    self._spill(node.page, chain)
            except Exception:  # noqa: BLE001 - offload is best-effort
                pass
        self.evictions += 1
        self._cached -= 1
        self._drop_snapshot(node)
        del self._trie[(node.parent, node.key)]
        del self._by_page[node.page]
        if node.parent >= 0 and node.parent in self._by_page:
            self._edit(self._by_page[node.parent], kids=-1)
        self._free.append(node.page)

    def evict_chain(self, pages: list[int]) -> int:
        """Evict a matched prefix chain (``match_prefix`` result) AND the
        trie subtree hanging off its tail, spilling every page through the
        offload hook; used by tool-time parking to free HBM a blocked
        session will not touch for seconds. The subtree matters because
        the parked history is RE-tokenized from chat messages: the
        generated turn's content usually re-renders to different token
        ids than the engine emitted, so the session's own generated pages
        sit BELOW the matched chain as a divergent continuation — exactly
        the pages parking exists to free. Pages another live sequence
        still references (refcount > 0) are left in place, as is
        everything above them. Returns pages evicted."""
        if not pages:
            return 0
        kids: dict[int, list[int]] = {}
        for node in self._by_page.values():
            kids.setdefault(node.parent, []).append(node.page)
        n = 0

        def _evict_down(page: int) -> bool:
            nonlocal n
            node = self._by_page.get(page)
            if node is None:
                return True
            clear = True
            for c in kids.get(page, ()):
                clear = _evict_down(c) and clear
            if clear and node.refcount == 0 and node.children == 0:
                self._evict(node)
                n += 1
                return True
            return False

        # Tail's whole subtree first (leaf-first), then the chain upward.
        if not _evict_down(pages[-1]):
            return n
        for p in reversed(pages[:-1]):
            node = self._by_page.get(p)
            if node is None or node.refcount > 0 or node.children > 0:
                break
            self._evict(node)
            n += 1
        return n

    def promote_prefix(self, seq_id: int, tokens: list[int]) -> int:
        """Register a LIVE sequence's leading full pages into the prefix
        trie as shared references (extending ``num_shared``), so pages
        just restored from the host tier become prefix hits for concurrent
        admissions immediately — not only after the sequence finishes.
        ``tokens`` bounds the promotion (its full pages). Stops early if
        an equal-content chain already exists under a DIFFERENT page (a
        live page table cannot be rewritten to dedup). Returns the number
        of pages promoted."""
        if not self.prefix_cache:
            return 0
        seq = self._seqs[seq_id]
        P = self.page_size
        stamp = next(self._clock)
        full = min(len(tokens) // P, len(seq.pages))
        parent = -1 if seq.num_shared == 0 else seq.pages[seq.num_shared - 1]
        promoted = 0
        for i in range(seq.num_shared, full):
            key = tuple(tokens[i * P : (i + 1) * P])
            page = seq.pages[i]
            node = self._trie.get((parent, key))
            if node is not None and node.page != page:
                break
            if node is None:
                self._insert(TrieNode(
                    page=page, parent=parent, key=key,
                    refcount=1, last_use=stamp,
                ))
            else:
                self._edit(node, refs=1, stamp=stamp)
            seq.num_shared = i + 1
            promoted += 1
            parent = page
        return promoted

    def pages_of(self, seq_id: int) -> list[int]:
        """Snapshot of a sequence's page list (restore targeting)."""
        return list(self._seqs[seq_id].pages)

    def _register_pages(self, seq: SeqAlloc, tokens: list[int]) -> list[int]:
        """Donate a finished sequence's full pages to the trie; returns the
        pages to put back on the free list (duplicates of already-cached
        content, the partial last page, over-allocated pages). ``tokens`` =
        the sequence's full token history (prompt + generated).

        Invariant: pages[0:num_shared] ARE trie nodes we hold a reference on
        (matched at admission against these exact tokens), so the walk just
        releases those references; owned full pages either become new trie
        nodes (kept) or are duplicates of a concurrently-registered chain
        (freed)."""
        P = self.page_size
        stamp = next(self._clock)
        full_pages = min(len(tokens) // P, len(seq.pages))
        absorbed: set[int] = set()
        parent = -1
        # The sequence's snapshot is good for the trie when the newest pass
        # that could have written it left it at the last page boundary of
        # what the cache really holds (a pass that ran past it, and was
        # rolled back, has overwritten it with a later state), and some
        # pass wrote it at all.
        at = len(tokens) // P * P
        snap_page = at // P - 1 if (
            seq.snap_slot >= 0 and seq.snap_pos == at > seq.state_from
        ) else -1
        for i in range(full_pages):
            key = tuple(tokens[i * P:(i + 1) * P])
            page = seq.pages[i]
            if i < seq.num_shared:
                node = self._by_page[page]   # we hold a ref: cannot be evicted
                self._edit(node, refs=-1, stamp=stamp)
                parent = page
            elif (node := self._trie.get((parent, key))) is not None:
                # Same content already cached by someone else: our page is a
                # duplicate — follow the canonical chain, free ours.
                self._edit(node, stamp=stamp)
                parent = node.page
            else:
                node = TrieNode(
                    page=page, parent=parent, key=key, last_use=stamp)
                self._insert(node)
                absorbed.add(page)
                parent = page
            if i == snap_page and node.snapshot < 0:
                node.snapshot, seq.snap_slot = seq.snap_slot, -1
                self.snapshots_taken += 1
                self._queue(self._snap_lru, node)
        # Shared pages past the registered walk (can happen only if tokens
        # shrank, which callers never do — defensive deref).
        for i in range(full_pages, seq.num_shared):
            node = self._by_page.get(seq.pages[i])
            if node is not None:
                self._edit(node, refs=-1)
        return [
            p for i, p in enumerate(seq.pages)
            if i >= seq.num_shared and p not in absorbed
        ]

    # -- lifecycle ---------------------------------------------------------
    def allocate(
        self, num_tokens: int, prefix_pages: list[int] | None = None
    ) -> int:
        """Allocate pages for a new sequence of ``num_tokens``, reusing
        ``prefix_pages`` (from ``match_prefix``) for its head. Returns
        seq_id. Raises OutOfPages when the pool is exhausted."""
        prefix_pages = prefix_pages or []
        need_total = self.pages_needed(max(1, num_tokens))
        if need_total > self.max_pages_per_seq:
            raise PromptTooLong(
                f"sequence needs {need_total} pages > max_pages_per_seq="
                f"{self.max_pages_per_seq} "
                f"({self.max_pages_per_seq * self.page_size} tokens)"
            )
        shared = [p for p in prefix_pages if p in self._by_page][
            : need_total
        ]
        # Reference the shared chain BEFORE popping fresh pages: with the
        # refcounts at 0 the matched pages themselves would be LRU-eviction
        # candidates while _take_free_page hunts for fresh ones — handing
        # the same physical page out as both prefix and tail.
        for p in shared:
            self._edit(self._by_page[p], refs=1, stamp=next(self._clock))
        need_fresh = need_total - len(shared)
        fresh: list[int] = []
        try:
            for _ in range(need_fresh):
                fresh.append(self._take_free_page())
        except OutOfPages:
            self._free.extend(fresh)
            for p in shared:
                self._edit(self._by_page[p], refs=-1)
            raise
        seq = SeqAlloc(self._next_id)
        if self.state_slots:
            if not self._free_live:
                self._free.extend(fresh)
                for p in shared:
                    self._edit(self._by_page[p], refs=-1)
                raise OutOfPages("no free recurrent-state slot")
            seq.state_slot = self._free_live.pop()
            restored_from = (
                self._by_page[shared[-1]].snapshot if shared else -1)
            seq.snap_slot = self._take_snapshot_slot(keep=restored_from)
            seq.state_from = seq.snap_pos = len(shared) * self.page_size
        self._next_id += 1
        seq.pages = shared + fresh
        seq.num_shared = len(shared)
        seq.length = num_tokens
        self._seqs[seq.seq_id] = seq
        self.hit_tokens += len(shared) * self.page_size
        self.miss_tokens += max(
            0, num_tokens - len(shared) * self.page_size
        )
        return seq.seq_id

    def extend(self, seq_id: int, new_tokens: int = 1) -> None:
        """Account for appended tokens, growing by a page when crossing a
        boundary. Raises OutOfPages when the pool is exhausted (caller may
        preempt another sequence and retry)."""
        seq = self._seqs[seq_id]
        target = seq.length + new_tokens
        while len(seq.pages) * self.page_size < target:
            if len(seq.pages) >= self.max_pages_per_seq:
                raise OutOfPages(f"seq {seq_id} hit max_pages_per_seq")
            seq.pages.append(self._take_free_page())
        seq.length = target

    def extend_upto(self, seq_id: int, want: int) -> int:
        """Best-effort ``extend``: grow by as many of ``want`` tokens as the
        per-seq cap and page pool allow; returns the number granted (0 when
        the sequence cannot grow at all). Used by block decode to pre-book
        pages for a whole dispatch, then ``truncate`` back what the device
        did not use."""
        seq = self._seqs[seq_id]
        got = min(want, len(seq.pages) * self.page_size - seq.length)
        seq.length += got
        while got < want:
            if len(seq.pages) >= self.max_pages_per_seq:
                break
            try:
                seq.pages.append(self._take_free_page())
            except OutOfPages:
                break
            take = min(want - got, self.page_size)
            seq.length += take
            got += take
        return got

    def truncate(self, seq_id: int, new_length: int) -> None:
        """Shrink a sequence's accounted length (block-decode rollback of
        pre-booked-but-unused tokens), releasing whole pages that fall past
        the new length. Never touches shared (prefix-trie) pages: truncation
        targets are >= the prompt length, whose pages cover the shared
        chain."""
        seq = self._seqs[seq_id]
        if new_length > seq.length:
            raise ValueError(
                f"truncate to {new_length} > current length {seq.length}"
            )
        seq.length = new_length
        keep = max(self.pages_needed(max(1, new_length)), seq.num_shared)
        while len(seq.pages) > keep:
            self._free.append(seq.pages.pop())

    def free(self, seq_id: int, tokens: list[int] | None = None) -> None:
        """Release a sequence. With ``tokens`` (its full token history) and
        prefix caching on, full pages are donated to the trie instead of
        freed; shared pages are dereferenced either way."""
        seq = self._seqs.pop(seq_id, None)
        if seq is None:
            return
        if self.prefix_cache and tokens is not None:
            self._free.extend(self._register_pages(seq, tokens))
        else:
            for i, p in enumerate(seq.pages):
                if i < seq.num_shared:
                    node = self._by_page.get(p)
                    if node is not None:
                        self._edit(node, refs=-1)
                else:
                    self._free.append(p)
        self._release_state(seq)

    # -- device views ------------------------------------------------------
    def page_table_row(self, seq_id: int) -> np.ndarray:
        """This sequence's page table padded to max_pages_per_seq with -1;
        with recurrent state, then its live slot and its snapshot slot."""
        row = np.full((self.table_width,), -1, np.int32)
        seq = self._seqs[seq_id]
        row[: len(seq.pages)] = seq.pages
        if self.state_slots:
            row[-2:] = seq.state_slot, seq.snap_slot
        return row

    def batch_views(
        self, seq_ids: list[int], batch_size: int
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """(page_table [batch, MaxP], lengths [batch], active [batch]) for a
        decode batch; unused slots are inactive with empty tables."""
        table = np.full((batch_size, self.table_width), -1, np.int32)
        lengths = np.zeros((batch_size,), np.int32)
        active = np.zeros((batch_size,), bool)
        for i, sid in enumerate(seq_ids):
            if sid is None:
                continue
            table[i] = self.page_table_row(sid)
            lengths[i] = self._seqs[sid].length
            active[i] = True
        return table, lengths, active
