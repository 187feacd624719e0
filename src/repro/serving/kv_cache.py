"""Paged sequence-parallel KV cache: the serving memory subsystem.

The dense serving cache (`models/transformer.py::init_decode_cache`) reserves
a contiguous ``max_len`` region per slot, so one long request dictates memory
for every short one and no prompt longer than a slot can ever be served.
Production million-token inference pages the cache instead (Context
Parallelism for Scalable Million-Token Inference, arXiv:2411.01783; vLLM's
PagedAttention): KV lives in fixed-size **pages** drawn from one shared pool,
and each request holds a **block table** mapping its logical token positions
to pages.  Ring Attention's observation (arXiv:2310.01889) that decode math
only ever needs per-device *partials* carries over unchanged — a paged read
gathers the mapped pages into a position-masked view and reuses the existing
``(out, lse)`` merge (``core/decode.py``), so paged attention is numerically
the dense attention.

Three layers live here:

  * :class:`PageAllocator` — host-side bookkeeping: a free list over
    ``n_pages`` physical pages with alloc/free/high-water/utilization.
    Allocation decisions are inherently dynamic (admission, growth,
    preemption), so they stay in Python; nothing here touches device memory.
  * device-state construction (:func:`init_paged_cache`) — the page pool
    pytree: per-layer K/V of shape ``(L, n_pages, page_size, Hkv, Dh)``, a
    position pool ``(n_pages, page_size)`` and per-slot block tables
    ``(B, slot_pages)``.  Under a mesh the *page* dimension shards over the
    SP axes, so a prompt whose pages exceed one device's page budget simply
    stripes across the ring — the gather re-establishes the sequence-sharded
    view ``sp_decode`` / ``sp_prefill`` already consume.
  * pure-JAX index helpers (:func:`view_indices`, :func:`write_coords`,
    :func:`gather_pages`) shared by the paged model steps, which carry the
    stacked pool through their layer scan and address a layer by index
    (``models/transformer.py``) — one place owns the page-table arithmetic.

Sentinel convention: an unmapped block-table entry holds ``n_pages`` (one
past the last page).  Gathers use ``mode="fill"`` (K/V fill 0, positions fill
``PAD_POS`` so the kernel masks them); scatters use ``mode="drop"`` so writes
through unmapped entries vanish.  This keeps every shape static — one
compiled step for the engine's whole life, exactly like the dense path.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import PAD_POS

__all__ = [
    "PageAllocator",
    "PageAllocatorError",
    "PrefixIndex",
    "PrefixHit",
    "init_paged_cache",
    "view_indices",
    "write_coords",
    "gather_pages",
    "gather_positions",
    "pages_for",
    "paged_cache_bytes",
    "dense_cache_bytes",
]


def pages_for(n_tokens: int, page_size: int) -> int:
    """Pages needed to hold ``n_tokens`` cache slots (at least one: a slot
    admitted for decode writes immediately)."""
    return max(1, -(-int(n_tokens) // page_size))


class PageAllocatorError(ValueError):
    """Page bookkeeping corruption: double free or foreign-page free.

    Subclasses ``ValueError`` (the historical type) so existing callers
    keep working; the distinct type lets the serving resilience layer
    route allocator corruption into its integrity-recovery path instead
    of conflating it with ordinary argument errors."""


class PageAllocator:
    """Free-list allocator over ``n_pages`` physical pages (host-side).

    Pages are plain ints ``[0, n_pages)``; ``n_pages`` itself is the unmapped
    sentinel used by the device block tables.  Tracks a high-water mark so
    benchmarks can report the true memory footprint paging achieves versus
    the dense worst-case slab.
    """

    def __init__(self, n_pages: int):
        if n_pages < 1:
            raise ValueError(f"need at least one page, got {n_pages}")
        self.n_pages = n_pages
        self._free = list(range(n_pages - 1, -1, -1))  # pop() -> low ids first
        self._free_set = set(self._free)  # O(1) double-free detection
        self.high_water = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    @property
    def free_set(self) -> frozenset:
        """The free pages as a set (read-only view for invariant audits)."""
        return frozenset(self._free_set)

    def alloc(self, n: int) -> list[int]:
        """Allocate ``n`` pages or raise ``MemoryError`` (caller preempts or
        defers admission; nothing is allocated on failure)."""
        if n > len(self._free):
            raise MemoryError(
                f"{n} pages requested, {len(self._free)} free of {self.n_pages}"
            )
        got = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(got)
        self.high_water = max(self.high_water, self.pages_in_use)
        return got

    def free(self, pages) -> None:
        """Return ``pages`` to the pool.  A page outside ``[0, n_pages)``
        (foreign — never ours to hand out) or already free (double free)
        raises :class:`PageAllocatorError` with nothing freed up to that
        point rolled back — corruption is not a state to limp through."""
        for p in pages:
            p = int(p)
            if not 0 <= p < self.n_pages:
                raise PageAllocatorError(
                    f"foreign page {p} out of range [0, {self.n_pages})"
                )
            if p in self._free_set:
                raise PageAllocatorError(f"double free of page {p}")
            self._free.append(p)
            self._free_set.add(p)

    def defrag_order(self) -> None:
        """Re-sort the free list so future allocations prefer low page ids.

        Physical pages are interchangeable (the block table is the only
        ordering), so "defragmentation" here is purely about keeping the
        in-use region compact for cheaper pool resizing / nicer utilization
        telemetry — no device data ever moves.
        """
        self._free.sort(reverse=True)

    def utilization(self) -> dict:
        return {
            "pages_total": self.n_pages,
            "pages_in_use": self.pages_in_use,
            "pages_free": self.free_pages,
            "high_water": self.high_water,
            "frac_in_use": self.pages_in_use / self.n_pages,
        }


# ---------------------------------------------------------------------------
# content-addressed prefix index (host-side, like the allocator)
# ---------------------------------------------------------------------------


@dataclass
class PrefixHit:
    """Result of :meth:`PrefixIndex.lookup` for one request's token ids.

    ``pages``: resident page ids whose *full* pages match the request's
    prefix, in chain order — the caller maps them into its block table after
    :meth:`PrefixIndex.acquire`.  ``cow_page``/``cow_keep``: when the first
    divergence falls *inside* a resident page, the page to copy and how many
    of its leading K/V rows are still valid (copy-on-write: the sharer gets
    a private duplicate, the resident page is never touched).  ``tokens`` is
    the total reusable prefix length, ``len(pages) * page_size + cow_keep``.
    """

    pages: list[int]
    tokens: int
    cow_page: int | None = None
    cow_keep: int = 0


class PrefixIndex:
    """Content-addressed index over resident KV pages (host-side).

    Every *full* page of a prefilled prompt is keyed by a hash chain over
    token ids: ``key_i = H(key_{i-1} || tokens[i*ps:(i+1)*ps])``, so a key
    names the page's tokens *and* its entire left context — two requests
    share page ``i`` iff their first ``(i+1)*ps`` tokens agree, which (with
    causal attention) is exactly the condition under which their K/V rows
    are identical.  Requests sharing a system prompt therefore map the same
    physical pages and prefill skips straight to the miss suffix.

    Pages referenced here are **owned by the index**, refcounted by the
    number of slots currently mapping them: the engine routes releases
    through :meth:`release` instead of the allocator, and a page only
    returns to the allocator when :meth:`evict` pops it (refcount 0, least
    recently touched, leaf-most first so chains stay reachable).  A
    divergence inside a page is never resolved by writing the shared page —
    :meth:`lookup` reports it as a copy-on-write candidate and the engine
    duplicates it into a private page first (docs/serving.md §7 has the
    state machine).
    """

    ROOT = b""

    def __init__(self, page_size: int):
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.page_size = page_size
        self._page_of: dict[bytes, int] = {}  # key -> physical page
        self._key_of: dict[int, bytes] = {}  # physical page -> key
        self._refs: dict[int, int] = {}  # physical page -> mapping slots
        self._tokens: dict[bytes, tuple[int, ...]] = {}  # key -> page tokens
        self._children: dict[bytes, set[bytes]] = {}  # parent key -> keys
        self._parent: dict[bytes, bytes] = {}  # key -> parent key
        self._touch: dict[bytes, int] = {}  # key -> LRU tick
        self._tick = 0
        # token-level counters feeding the planner's measured hit rate
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.cow_copies = 0
        self.evictions = 0

    # -- invariants (the property-test surface) ----------------------------

    @property
    def pages(self) -> set[int]:
        """Physical pages the index currently owns."""
        return set(self._key_of)

    def refcount(self, page: int) -> int:
        """Live mappings of ``page`` (0 = resident but evictable)."""
        return self._refs.get(int(page), 0)

    def total_refs(self) -> int:
        return sum(self._refs.values())

    # -- hashing -----------------------------------------------------------

    def _chain_keys(self, tokens) -> list[bytes]:
        """Hash-chain keys of every *full* page of ``tokens``."""
        import hashlib

        ids = [int(t) for t in tokens]
        keys = []
        key = self.ROOT
        ps = self.page_size
        for i in range(len(ids) // ps):
            page_tokens = ids[i * ps:(i + 1) * ps]
            h = hashlib.sha256(key)
            h.update(np.asarray(page_tokens, np.int64).tobytes())
            key = h.digest()
            keys.append(key)
        return keys

    def _note(self, key: bytes) -> None:
        self._tick += 1
        self._touch[key] = self._tick

    # -- lookup / acquire / register / release -----------------------------

    def lookup(self, tokens) -> PrefixHit:
        """Longest reusable prefix of ``tokens`` among resident pages.

        Walks the hash chain while keys resolve; at the first non-resident
        key, checks the matched tail's children for the longest shared
        token run *inside* the divergence page (the COW candidate).  Hit
        length is monotone in the shared-token count by construction: every
        shared full page extends the chain walk, every shared token inside
        the divergence page extends ``cow_keep``.
        """
        ids = [int(t) for t in tokens]
        ps = self.page_size
        keys = self._chain_keys(ids)
        pages: list[int] = []
        parent = self.ROOT
        for key in keys:
            page = self._page_of.get(key)
            if page is None:
                break
            pages.append(page)
            parent = key
            self._note(key)
        hit = len(pages) * ps
        cow_page, cow_keep = None, 0
        rest = ids[hit:]
        if rest:
            for child in self._children.get(parent, ()):
                resident = self._tokens[child]
                common = 0
                for a, b in zip(rest, resident):
                    if a != b:
                        break
                    common += 1
                # Only a *strictly partial* match is a COW candidate: a full
                # page match would have resolved in the chain walk above.
                if common > cow_keep and common < ps:
                    cow_page, cow_keep = self._page_of[child], common
        self.lookup_tokens += len(ids)
        self.hit_tokens += hit + cow_keep
        return PrefixHit(
            pages=pages, tokens=hit + cow_keep,
            cow_page=cow_page, cow_keep=cow_keep,
        )

    def acquire(self, pages) -> None:
        """Map index-owned ``pages`` into one more slot (refcount += 1)."""
        for p in pages:
            p = int(p)
            if p not in self._key_of:
                raise ValueError(f"page {p} is not index-owned")
            self._refs[p] += 1
            self._note(self._key_of[p])

    def register(self, tokens, pages) -> int:
        """Index a prefilled prompt's full pages, claiming this request's
        mapping as one reference each.

        ``pages`` are the request's block-table pages in logical order;
        only the first ``len(tokens) // page_size`` (full) pages are
        indexable.  A key that is already resident is skipped — the
        duplicate page stays private to its request (first writer wins; the
        engine frees the duplicate through the allocator when the request
        ends).  Returns the number of newly indexed pages.
        """
        ids = [int(t) for t in tokens]
        ps = self.page_size
        keys = self._chain_keys(ids)
        new = 0
        parent = self.ROOT
        for i, (key, page) in enumerate(zip(keys, pages)):
            page = int(page)
            if key not in self._page_of:
                if page in self._key_of:
                    raise ValueError(
                        f"page {page} already indexed under another key"
                    )
                self._page_of[key] = page
                self._key_of[page] = key
                self._refs[page] = 1
                self._tokens[key] = tuple(ids[i * ps:(i + 1) * ps])
                self._parent[key] = parent
                self._children.setdefault(parent, set()).add(key)
                self._note(key)
                new += 1
            parent = key
        return new

    def release(self, page) -> bool:
        """Drop one slot's mapping of ``page``.

        Returns True when the page is index-owned (the caller must NOT free
        it to the allocator — it stays resident for future hits until
        :meth:`evict` pops it); False when the page is unknown here (a
        private page the caller frees normally).
        """
        page = int(page)
        key = self._key_of.get(page)
        if key is None:
            return False
        if self._refs[page] <= 0:
            raise ValueError(f"release of page {page} with refcount 0")
        self._refs[page] -= 1
        self._note(key)
        return True

    def evict(self, n: int) -> list[int]:
        """Un-index up to ``n`` refcount-0 pages, least recently touched
        first with leaves before interior nodes (evicting a chain's interior
        strands its resident descendants for future lookups — they stay
        refcounted and safe, just unreachable).  Returns the page ids; the
        caller resets their position rows and frees them to the allocator.
        """
        out: list[int] = []
        while len(out) < n:
            candidates = [
                key for key, page in self._page_of.items()
                if self._refs[page] == 0
            ]
            if not candidates:
                break
            candidates.sort(
                key=lambda k: (bool(self._children.get(k)), self._touch[k])
            )
            out.append(self._drop(candidates[0]))
        self.evictions += len(out)
        return out

    def _drop(self, key: bytes) -> int:
        page = self._page_of.pop(key)
        del self._key_of[page]
        del self._refs[page]
        del self._tokens[key]
        parent = self._parent.pop(key)
        kids = self._children.get(parent)
        if kids:
            kids.discard(key)
            if not kids:
                del self._children[parent]
        self._children.pop(key, None)
        self._touch.pop(key, None)
        return page

    def stats(self) -> dict:
        rate = self.hit_tokens / self.lookup_tokens if self.lookup_tokens else 0.0
        return {
            "indexed_pages": len(self._key_of),
            "shared_refs": self.total_refs(),
            "hit_tokens": self.hit_tokens,
            "lookup_tokens": self.lookup_tokens,
            "hit_rate": rate,
            "cow_copies": self.cow_copies,
            "evictions": self.evictions,
        }

    # -- snapshot round-trip (serving-state checkpoints) --------------------

    def export_state(self) -> dict:
        """JSON-safe snapshot of the whole index: chain keys (hex),
        page ownership, refcounts, per-page tokens, parent links and LRU
        order.  ``children`` is derivable from ``parent`` and rebuilt on
        load."""
        return {
            "page_size": self.page_size,
            "pages": [
                {
                    "key": key.hex(),
                    "page": page,
                    "tokens": list(self._tokens[key]),
                    "parent": self._parent[key].hex(),
                    "refs": self._refs[page],
                    "touch": self._touch.get(key, 0),
                }
                for key, page in self._page_of.items()
            ],
            "tick": self._tick,
            "counters": {
                "hit_tokens": self.hit_tokens,
                "lookup_tokens": self.lookup_tokens,
                "cow_copies": self.cow_copies,
                "evictions": self.evictions,
            },
        }

    @classmethod
    def from_state(cls, blob: dict) -> "PrefixIndex":
        """Rebuild an index from :meth:`export_state` output."""
        idx = cls(int(blob["page_size"]))
        for rec in blob["pages"]:
            key = bytes.fromhex(rec["key"])
            parent = bytes.fromhex(rec["parent"])
            page = int(rec["page"])
            idx._page_of[key] = page
            idx._key_of[page] = key
            idx._refs[page] = int(rec["refs"])
            idx._tokens[key] = tuple(int(t) for t in rec["tokens"])
            idx._parent[key] = parent
            idx._children.setdefault(parent, set()).add(key)
            idx._touch[key] = int(rec["touch"])
        idx._tick = int(blob["tick"])
        for name, value in blob["counters"].items():
            setattr(idx, name, int(value))
        return idx


# ---------------------------------------------------------------------------
# device state
# ---------------------------------------------------------------------------


def init_paged_cache(
    n_layers: int,
    n_kv_heads: int,
    head_dim: int,
    *,
    n_pages: int,
    page_size: int,
    max_batch: int,
    slot_pages: int,
    dtype=jnp.bfloat16,
    pctx=None,
):
    """Page-pool serve state: the paged replacement for the dense slab.

    ``k/v (L, n_pages, page_size, Hkv, Dh)``; ``pos (n_pages, page_size)``
    global positions with the ``PAD_POS`` sentinel for unwritten/unowned
    slots; ``block_tables (max_batch, slot_pages)`` int32 page ids with the
    ``n_pages`` sentinel for unmapped entries; ``len (max_batch,)`` filled
    lengths.  Physical memory is ``n_pages * page_size`` tokens total —
    typically far below the dense ``max_batch * max_len`` — while each slot's
    *logical* capacity is ``slot_pages * page_size``.

    Under an active ``pctx`` mesh the *page* dimension shards over the SP
    axes (pages stripe across the ring, ``n_pages`` must divide the SP
    degree), ``pos`` alongside it; block tables and lengths replicate.  Each
    device then holds ``n_pages / P`` pages and the per-step gathers
    re-establish the sequence-sharded view the serving plans consume.
    """
    dtype = jnp.dtype(dtype)
    state = {
        "k": jnp.zeros((n_layers, n_pages, page_size, n_kv_heads, head_dim), dtype),
        "v": jnp.zeros((n_layers, n_pages, page_size, n_kv_heads, head_dim), dtype),
        "pos": jnp.full((n_pages, page_size), PAD_POS, jnp.int32),
        "block_tables": jnp.full((max_batch, slot_pages), n_pages, jnp.int32),
        "len": jnp.zeros((max_batch,), jnp.int32),
    }
    if pctx is not None and getattr(pctx, "active", False):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        if n_pages % pctx.sp_degree:
            raise ValueError(
                f"paged pool: n_pages={n_pages} must be a multiple of the SP "
                f"degree {pctx.sp_degree} so pages stripe evenly across the "
                "ring"
            )
        seq = pctx.seq_spec()
        specs = {
            "k": P(None, seq, None, None, None),
            "v": P(None, seq, None, None, None),
            "pos": P(seq, None),
            "block_tables": P(),
            "len": P(),
        }
        state = {
            name: jax.device_put(x, NamedSharding(pctx.mesh, specs[name]))
            for name, x in state.items()
        }
    return state


# ---------------------------------------------------------------------------
# page-table index arithmetic (pure JAX, shared by the paged model steps)
# ---------------------------------------------------------------------------


def view_indices(block_tables, page_size: int, lengths=None):
    """Flat token indices of each slot's gathered view.

    ``block_tables (B, W)`` -> ``(B, W * page_size)`` indices into the
    flattened ``n_pages * page_size`` token pool.  Unmapped entries (the
    ``n_pages`` sentinel) map past the pool end, where gathers fill.

    ``lengths (B,)`` additionally clamps the view to the pages each slot
    *actually uses*: page-slot ``j >= ceil(length / page_size)`` is forced
    out-of-pool, so its gather fills (K/V -> 0, positions -> ``PAD_POS``)
    even when the table still maps a page there.  That makes the clamp a
    correctness guard, not just a bandwidth saving: a stale mapping beyond
    the used length (e.g. a page kept mapped across a length rollback) can
    never leak another lifetime's K/V into the view.  Shapes stay static —
    the clamp is a mask, never a width change — so the engine keeps its one
    compiled step.
    """
    offs = jnp.arange(page_size, dtype=block_tables.dtype)
    flat = block_tables[:, :, None] * page_size + offs  # (B, W, page_size)
    if lengths is not None:
        used_pages = (lengths.astype(jnp.int32) + page_size - 1) // page_size
        slot = jnp.arange(block_tables.shape[1], dtype=jnp.int32)
        live = slot[None, :] < used_pages[:, None]  # (B, W)
        flat = jnp.where(live[:, :, None], flat, PAD_POS)
    return flat.reshape(block_tables.shape[0], -1)


def write_coords(block_tables, logical_slots, valid, n_pages: int, page_size: int):
    """Physical ``(page, offset)`` for logical cache ``logical_slots``.

    ``logical_slots`` is ``(B,)`` (decode) or ``(B, C)`` (a prefill chunk);
    ``valid`` the same shape (False rows/tokens get the ``n_pages`` drop
    sentinel).  Unmapped table entries also resolve to the sentinel, so a
    write can never land on a page the slot does not own.
    """
    W = block_tables.shape[1]
    tbl_raw = logical_slots // page_size
    tbl = jnp.clip(tbl_raw, 0, W - 1)
    if logical_slots.ndim == 1:
        page = block_tables[jnp.arange(block_tables.shape[0]), tbl]
    else:
        page = block_tables[jnp.arange(block_tables.shape[0])[:, None], tbl]
    # A slot past the table end (engine retires before this can happen) must
    # drop, not silently alias the clipped last page.
    ok = jnp.logical_and(valid, jnp.logical_and(tbl_raw < W, page < n_pages))
    page = jnp.where(ok, page, n_pages)
    return page, logical_slots % page_size


def gather_pages(pool, flat_view, layer):
    """Gather layer ``layer`` of ``pool (L, n_pages, page_size, ...)`` into
    per-slot views.

    ``flat_view (B, V)`` from :func:`view_indices` -> ``(B, V, ...)``.  The
    layer is read whole first and the view gathered from that read: the TPU
    compiler stages it in VMEM, where the gather's row reads are cheap; the
    same gather addressed into the stacked pool in HBM is far slower.  The
    pool itself is only read, so a caller that carries it updates it in
    place.  Out-of-pool indices (unmapped pages) fill with zeros — harmless
    because their positions fill with ``PAD_POS`` and the kernel masks on
    position.
    """
    layer_pool = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    flat_pool = layer_pool.reshape((-1,) + pool.shape[3:])
    return jnp.take(flat_pool, flat_view, axis=0, mode="fill", fill_value=0)


def gather_positions(pos_pool, flat_view):
    """Gather the position pool into per-slot views; unmapped -> PAD_POS."""
    return jnp.take(
        pos_pool.reshape(-1), flat_view, axis=0, mode="fill", fill_value=PAD_POS
    )


# ---------------------------------------------------------------------------
# byte accounting (benchmarks / docs worked example)
# ---------------------------------------------------------------------------


def dense_cache_bytes(cfg, max_batch: int, max_len: int) -> int:
    """Bytes the dense slab pins for its whole life: worst case, always."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (
        2 * cfg.n_layers * max_batch * max_len * cfg.n_kv_heads * cfg.head_dim
        * itemsize
    )


def paged_cache_bytes(cfg, n_pages: int, page_size: int) -> int:
    """Bytes ``n_pages`` pool pages hold (evaluate at the allocator's
    ``high_water`` for the achieved footprint, at the pool size for the cap)."""
    itemsize = jnp.dtype(cfg.dtype).itemsize
    return (
        2 * cfg.n_layers * n_pages * page_size * cfg.n_kv_heads * cfg.head_dim
        * itemsize
    )
