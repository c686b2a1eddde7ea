"""Device<->host KV page copies: the data plane of the offload tier.

Two jitted programs per page-count bucket:

- **gather** (device->host direction): slice ``cache[:, page_ids]`` out of
  every cache leaf — one dispatch regardless of layer count — returning
  fresh device buffers that a later ``flush()`` pulls to host numpy. The
  pull is DOUBLE-BUFFERED: dispatching a gather costs one enqueue (the
  device copies concurrently with whatever decode work follows), and the
  blocking device->host transfer happens at the next flush point, so page
  offload overlaps decode dispatches instead of stalling them.
- **scatter** (host->device): ``cache.at[:, page_ids].set(data)`` with the
  cache donated — the restore path writes straight into the live pages.

Shapes are static per bucket (page-id vectors pad by DUPLICATING a real
id, so padded scatter lanes rewrite identical content — a no-op), which
keeps the restore path inside the zero-post-warmup-compiles invariant:
``Engine.warmup`` runs both programs per bucket once.

Every cache leaf keeps its page axis at index 1 (``[L, N, P, ...]``, both
the fp and the QuantizedPages int8+scale layouts), so one ``tree.map``
covers all layouts. A page leaves the device as ``[L, P, K, D]`` whichever
form the cache holds it in (``ops.attention.page_view``: merged pages
``[L, N, P, K*D]`` are the same bytes), so the host pool, snapshots and
the fleet's wire have one format.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.attention import page_view

_PAGE_AXIS = 1  # cache leaves are [L, num_pages, page_size, ...]


class PageCopyEngine:
    def __init__(self, wire, mesh_ctx=None, copy_pages: int = 8):
        """``mesh_ctx`` is the engine's mesh context factory
        (``Engine.mesh_ctx``): the copy programs must compile under the
        same ambient mesh as every other engine program or the jit cache
        forks (see Engine._mesh_tls). ``wire`` is the cache's shape tree
        in the split form (``Engine.cache_wire``), the format pages have
        off the device."""
        import contextlib

        self._mesh_ctx = mesh_ctx or contextlib.nullcontext
        copy_pages = max(1, int(copy_pages))
        self.buckets = (1,) if copy_pages == 1 else (1, copy_pages)

        def _gather(cache, ids):
            return jax.tree_util.tree_map(
                lambda c, w: page_view(
                    jnp.take(c, ids, axis=_PAGE_AXIS), w.shape[2:]
                ),
                cache, wire,
            )

        def _scatter(cache, ids, data):
            return jax.tree_util.tree_map(
                lambda c, d: c.at[:, ids].set(
                    page_view(d, c.shape[2:]).astype(c.dtype)
                ),
                cache, data,
            )

        self._gather_jit = jax.jit(_gather)
        self._scatter_jit = jax.jit(_scatter, donate_argnums=(0,))
        # Double buffer: gathers dispatched but not yet pulled to host.
        # Each entry is (metas, device_tree) where metas[j] describes the
        # j-th REAL page of the batch (padding lanes carry no meta).
        self._pending: list[tuple[list[Any], Any]] = []

    def _bucket(self, n: int) -> int:
        for b in self.buckets:
            if n <= b:
                return b
        return self.buckets[-1]

    # -- device -> host ----------------------------------------------------
    def dispatch_gather(self, cache: Any, pages: list[int], metas: list[Any]) -> None:
        """Enqueue device->host copies of ``pages`` (chunked into buckets).
        ``metas[j]`` rides along to ``flush()`` with page ``pages[j]``'s
        content; the source pages must not be REWRITTEN by a dispatch
        enqueued before this call returns (device execution is in dispatch
        order, so anything dispatched after is safe)."""
        assert len(pages) == len(metas)
        for off in range(0, len(pages), self.buckets[-1]):
            chunk = pages[off : off + self.buckets[-1]]
            ms = metas[off : off + len(chunk)]
            b = self._bucket(len(chunk))
            ids = np.full((b,), chunk[0], np.int32)
            ids[: len(chunk)] = chunk
            with self._mesh_ctx():
                dev = self._gather_jit(cache, jnp.asarray(ids))
            self._pending.append((ms, dev))

    def flush(self) -> list[tuple[Any, Any]]:
        """Pull every pending gather to host numpy. Returns a flat list of
        (meta, host_page_tree): each host tree mirrors the cache structure
        with the page axis removed (one page: ``[L, P, ...]`` leaves).
        The blocking pull's wall time and byte volume feed the goodput
        ledger (attribution kind="other"): offload copies ride the same
        HBM the decode stream uses."""
        import time

        out: list[tuple[Any, Any]] = []
        pending, self._pending = self._pending, []
        if not pending:
            return out
        t0 = time.perf_counter()
        nbytes = 0
        for metas, dev in pending:
            host = jax.tree_util.tree_map(np.asarray, dev)
            nbytes += sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(host)
            )
            for j, meta in enumerate(metas):
                page_tree = jax.tree_util.tree_map(
                    lambda leaf, _j=j: np.ascontiguousarray(leaf[:, _j]), host
                )
                out.append((meta, page_tree))
        from ...obs import attribution

        attribution.record_copy(
            nbytes, "gather", seconds=time.perf_counter() - t0
        )
        return out

    @property
    def pending_pages(self) -> int:
        return sum(len(m) for m, _ in self._pending)

    # -- host -> device ----------------------------------------------------
    def scatter(
        self, cache: Any, pages: list[int], page_trees: list[Any],
        on_update=None,
    ) -> Any:
        """Write host page contents into device ``pages``; returns the new
        (donated) cache. Chunked into buckets; padding lanes rewrite the
        first real page with its own data. ``on_update(cache)`` fires after
        every chunk so the caller's cache reference never dangles on a
        donated buffer if a later chunk raises."""
        assert len(pages) == len(page_trees) and pages
        import time

        t0 = time.perf_counter()
        nbytes = sum(
            leaf.nbytes
            for tree in page_trees
            for leaf in jax.tree_util.tree_leaves(tree)
        )
        for off in range(0, len(pages), self.buckets[-1]):
            chunk = pages[off : off + self.buckets[-1]]
            trees = page_trees[off : off + len(chunk)]
            b = self._bucket(len(chunk))
            ids = np.full((b,), chunk[0], np.int32)
            ids[: len(chunk)] = chunk
            pad = [trees[0]] * (b - len(chunk))
            stacked = jax.tree_util.tree_map(
                lambda *leaves: np.stack(leaves, axis=_PAGE_AXIS),
                *(trees + pad),
            )
            with self._mesh_ctx():
                cache = self._scatter_jit(
                    cache,
                    jnp.asarray(ids),
                    jax.tree_util.tree_map(jnp.asarray, stacked),
                )
            if on_update is not None:
                on_update(cache)
        from ...obs import attribution

        attribution.record_copy(
            nbytes, "scatter", seconds=time.perf_counter() - t0
        )
        return cache

    def warm(self, cache: Any) -> Any:
        """Compile every bucket's gather and scatter once, content-
        preservingly: the scatter rewrites page 0 with its own gathered
        content. Returns the (donated-through) cache."""
        for b in self.buckets:
            ids = np.zeros((b,), np.int32)
            with self._mesh_ctx():
                dev = self._gather_jit(cache, jnp.asarray(ids))
                host = jax.tree_util.tree_map(np.asarray, dev)
                cache = self._scatter_jit(
                    cache,
                    jnp.asarray(ids),
                    jax.tree_util.tree_map(jnp.asarray, host),
                )
        return cache
