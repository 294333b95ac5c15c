"""Host-side page-pool bookkeeping for the continuous-batching scheduler.

The device state is ONE shared pool per layer (``models.attention.
init_paged_pool``); this class owns the free list, per-page refcounts, the
per-slot block tables and lengths, and the admission-time zeroing. The
leak-freedom contract lives at the ``alloc`` boundary: a slot's *fresh*
pages are zeroed in-kernel (``kernels/paged_attention`` ``paged_reset``)
before the slot's table row is published, so no read path ever observes a
previous tenant's K/V — recycling is safe by construction, not by
cache-lifetime discipline (the serving analogue of the paper's R2 state
isolation).

For a family whose pool also holds recurrent state (the hybrid family: one
Mamba2 state slot per sequence per layer beside the attention pages), the
contract covers the state too: ``alloc`` zeroes the slot's state on the
device (``Model.reset_slot_state``) in the same reset that zeroes its fresh
pages, before the slot is published, so a recycled slot's first prefill
starts from zero, never from the previous tenant's state. Prefix sharing and
speculative rollback would need state snapshots and are refused for such a
family (``ContinuousServer``).

Prefix sharing rides on two additions, both scoped so the R2 analogue
survives intact:

* **Per-page refcounts.** A page may appear in several slots' tables at
  once (read-only prompt-prefix pages); ``release`` decrements and only
  returns a page to the free list at zero, so a shared page can never be
  recycled — and hence never re-zeroed or rewritten — while any reader
  still maps it.
* **A per-tenant prefix index.** Full prompt pages are keyed by a chained
  SHA-256 over their token content, *with the tenant id baked into the
  lookup key*: a request can only ever be handed pages whose content was
  written under its own tenant. Cross-tenant sharing is impossible at the
  data-structure level, not by scheduler politeness — the adversarial test
  probes exactly this (identical prompt, different tenant, must get fresh
  zeroed pages and bitwise fresh-cache logits). The index holds its own
  refcount on each entry, so prompt pages of *recently finished* requests
  stay shareable until pool pressure evicts them (LRU).

Copy-on-write is by construction rather than by fault: sharing is page
granular, a sharer's write cursor starts at the shared-page boundary, and
every page past that boundary is a fresh zeroed page allocated at
admission — so no write can ever land on a shared page.

Speculative decoding adds a parallel *draft* pool (same page-id space, same
tables/lengths/refcounts — only the K/V arrays differ, sized for the draft
model): admission zeroing and rejected-tail ``rollback`` are applied to
both pools in lockstep, so the draft cache inherits every isolation
property of the target cache for free.
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

from repro.kernels.paged_attention import ops as paged_ops


class PagePool:
    """Refcounted free-list allocator over a device page pool + per-slot
    block tables, with an optional same-tenant prefix index and an optional
    parallel draft pool.

    ``tables`` rows are padded with the slot's own first page (the reset is
    idempotent over duplicates), so a short request never holds a reserved
    sentinel page and the table array stays rectangular for the one compiled
    graph."""

    def __init__(self, model, *, n_slots: int, n_pages: int, page_size: int,
                 pages_per_slot: int, draft_model=None,
                 prefix_index: bool = False):
        if model.init_paged_cache is None:
            raise ValueError(
                f"{model.cfg.name} ({model.cfg.family}) has no paged serving "
                f"path; continuous batching needs a transformer-family model")
        self.page_size = page_size
        self.n_pages = n_pages
        self.pages = model.init_paged_cache(n_pages, page_size,
                                            n_slots=n_slots)
        # zeroes a slot's recurrent state; None when the pool is pages only
        self._reset_state = model.reset_slot_state
        # draft pool: same page ids, draft-sized K/V. Shared-prefix pages are
        # populated for BOTH pools during the original request's prefill, so
        # a sharer admitted later finds its draft cache warm too.
        self.draft_pages = (None if draft_model is None else
                            draft_model.init_paged_cache(n_pages, page_size))
        self.free: list[int] = list(range(n_pages - 1, -1, -1))
        self.refcount = np.zeros((n_pages,), np.int32)
        self.tables = np.zeros((n_slots, pages_per_slot), np.int32)
        self.lengths = np.zeros((n_slots,), np.int32)
        self._owned: list[list[int]] = [[] for _ in range(n_slots)]
        self._shared: list[list[int]] = [[] for _ in range(n_slots)]
        # (tenant, chained sha256 of page tokens) -> page id, LRU-ordered.
        # The tenant id in the key IS the cross-tenant barrier.
        self.prefix_index_enabled = prefix_index
        self._prefix_index: OrderedDict[tuple, int] = OrderedDict()
        # slot -> [pages hashed so far, running digest] for incremental
        # registration across prefill chunks
        self._reg: dict[int, list] = {}

    @property
    def free_pages(self) -> int:
        return len(self.free)

    @property
    def has_state(self) -> bool:
        """True when the pool holds a recurrent state slot per sequence."""
        return self._reset_state is not None

    # ------------------------------------------------------------- reset glue
    def _reset_rows(self, row: np.ndarray, slot: int) -> None:
        """Zero ``row``'s pages in-kernel in the target pool and (when
        present) the draft pool, and ``slot``'s recurrent state where the
        pool holds one. Pools are consumed and rebound."""
        k, v = paged_ops.paged_reset(self.pages["k_pages"],
                                     self.pages["v_pages"], row)
        self.pages = {**self.pages, "k_pages": k, "v_pages": v}
        if self._reset_state is not None:
            self.pages = self._reset_state(self.pages, slot)
        if self.draft_pages is not None:
            self.draft_pages = dict(zip(
                ("k_pages", "v_pages"),
                paged_ops.paged_reset(self.draft_pages["k_pages"],
                                      self.draft_pages["v_pages"], row)))

    # ----------------------------------------------------------------- alloc
    def alloc(self, slot: int, n: int, shared: Sequence[int] = ()) -> bool:
        """Claim ``n`` pages for ``slot``: map the ``shared`` prefix pages
        read-only (refcount bump, NO zeroing — their content is the point)
        and zero ``n - len(shared)`` fresh pages in-kernel. False when the
        pool can't satisfy the claim even after LRU-evicting idle index
        entries (caller retries next step).

        ``shared`` must come from ``prefix_lookup`` in the same scheduler
        step (no yield between lookup and alloc), so the entries still hold
        their index refcount and cannot have been recycled in between."""
        shared = list(shared)
        fresh_n = n - len(shared)
        assert fresh_n >= 1, "a slot needs at least one writable fresh page"
        if n > self.tables.shape[1]:
            return False
        if fresh_n > len(self.free) and not self._evict(fresh_n):
            return False
        assert not self._owned[slot], f"slot {slot} already holds pages"
        fresh = [self.free.pop() for _ in range(fresh_n)]
        pages = shared + fresh
        width = self.tables.shape[1]
        row = np.full((width,), pages[0], np.int32)
        row[:n] = pages
        # zero BEFORE publishing the table row — but only the FRESH pages:
        # shared pages carry the prefix K/V the sharer is here for, and
        # zeroing them would corrupt every other reader. The reset row stays
        # full-width (padded with fresh[0]; idempotent duplicates) so one
        # compiled reset graph serves every allocation shape.
        reset_row = np.full((width,), fresh[0], np.int32)
        reset_row[:fresh_n] = fresh
        self._reset_rows(reset_row, slot)
        for p in shared:
            self.refcount[p] += 1
        self.refcount[fresh] = 1
        self.tables[slot] = row
        # the write cursor starts at the shared boundary: everything before
        # it is read-only by construction (the COW rule, enforced by where
        # fresh pages begin rather than by trapping writes)
        self.lengths[slot] = len(shared) * self.page_size
        self._owned[slot] = pages
        self._shared[slot] = shared
        return True

    def _evict(self, fresh_n: int) -> bool:
        """LRU-evict idle prefix-index entries (refcount 1 = held only by
        the index) until ``fresh_n`` pages are free. Entries still mapped by
        a live slot are skipped (rotated to MRU). True on success."""
        for _ in range(len(self._prefix_index)):
            if fresh_n <= len(self.free):
                break
            key, page = next(iter(self._prefix_index.items()))
            if self.refcount[page] == 1:
                del self._prefix_index[key]
                self.refcount[page] = 0
                self.free.append(page)
            else:
                self._prefix_index.move_to_end(key)
        return fresh_n <= len(self.free)

    # --------------------------------------------------------------- release
    def release(self, slot: int) -> None:
        """Drop the slot's references; pages return to the free list only at
        refcount zero. Prompt pages registered in the prefix index keep the
        index's own reference, so a recently-finished request's prefix stays
        shareable (until LRU eviction under pressure). Freed page *contents*
        stay on device until the next tenant's admission zeroes them — which
        is exactly what the adversarial recycling test probes."""
        for p in self._owned[slot]:
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free.append(p)
        self._owned[slot] = []
        self._shared[slot] = []
        self._reg.pop(slot, None)
        self.tables[slot] = 0
        self.lengths[slot] = 0

    # ---------------------------------------------------------- prefix index
    def _page_digest(self, digest: bytes, tokens) -> bytes:
        chunk = np.ascontiguousarray(np.asarray(tokens, np.int32))
        return hashlib.sha256(digest + chunk.tobytes()).digest()

    def prefix_lookup(self, tenant: Optional[str], prompt) -> list[int]:
        """Longest run of full prompt pages already cached *for this
        tenant*, in order. Capped one page short of both the prompt and the
        table width, so the admitted request always has at least one fresh
        page and at least one prompt token left to prefill (the first
        generated token needs a real query position)."""
        if not self.prefix_index_enabled:
            return []
        P = self.page_size
        cap = min((len(prompt) - 1) // P, self.tables.shape[1] - 1)
        digest, hit = b"", []
        for j in range(cap):
            digest = self._page_digest(digest, prompt[j * P:(j + 1) * P])
            page = self._prefix_index.get((tenant, digest))
            if page is None:
                break
            self._prefix_index.move_to_end((tenant, digest))
            hit.append(page)
        return hit

    def register_prefix(self, slot: int, tenant: Optional[str], prompt,
                        n_done: int) -> None:
        """Publish the slot's fully-prefilled full prompt pages into the
        tenant's prefix index (incremental across chunks: the chained digest
        is carried per slot). Idempotent; existing keys are refreshed to MRU
        but never re-pointed, so concurrent identical prompts converge on
        one canonical page per prefix."""
        if not self.prefix_index_enabled:
            return
        P = self.page_size
        max_j = min(int(n_done), len(prompt)) // P
        st = self._reg.setdefault(slot, [0, b""])
        while st[0] < max_j:
            j = st[0]
            st[1] = self._page_digest(st[1], prompt[j * P:(j + 1) * P])
            key = (tenant, st[1])
            if key in self._prefix_index:
                self._prefix_index.move_to_end(key)
            else:
                page = self._owned[slot][j]
                self._prefix_index[key] = page
                self.refcount[page] += 1
            st[0] += 1

    # -------------------------------------------------------------- rollback
    def rollback(self, slot: int, start: int, end: int) -> None:
        """Zero logical token positions ``[start, end)`` of the slot's
        sequence in-kernel, in both pools (the speculative rejected-tail
        eraser). The range must lie past the shared prefix — rejected
        speculation starts at the verified length, which is always past the
        prompt, let alone the shared span — so shared pages are untouchable
        here by construction (and asserted)."""
        if end <= start:
            return
        assert start >= len(self._shared[slot]) * self.page_size
        assert end <= len(self._owned[slot]) * self.page_size
        row = self.tables[slot]
        k, v = paged_ops.paged_rollback(self.pages["k_pages"],
                                        self.pages["v_pages"], row, start, end)
        self.pages = {**self.pages, "k_pages": k, "v_pages": v}
        if self.draft_pages is not None:
            self.draft_pages = dict(zip(
                ("k_pages", "v_pages"),
                paged_ops.paged_rollback(self.draft_pages["k_pages"],
                                         self.draft_pages["v_pages"], row,
                                         start, end)))

    # ---------------------------------------------------------------- probes
    def slot_pages(self, slot: int) -> list[int]:
        """Physical page ids currently owned by ``slot`` (for tests/probes)."""
        return list(self._owned[slot])

    def slot_shared_pages(self, slot: int) -> list[int]:
        """The read-only shared-prefix subset of ``slot_pages`` (probes)."""
        return list(self._shared[slot])

    def check_invariants(self) -> None:
        """Refcount accounting must balance exactly: every page's refcount
        equals its number of slot owners plus its index membership; the free
        list is exactly the refcount-zero pages, without duplicates."""
        expect = np.zeros((self.n_pages,), np.int32)
        for owned in self._owned:
            for p in owned:
                expect[p] += 1
        for p in self._prefix_index.values():
            expect[p] += 1
        assert np.array_equal(self.refcount, expect), \
            (self.refcount.tolist(), expect.tolist())
        assert len(set(self.free)) == len(self.free), "duplicate free pages"
        assert sorted(self.free) == sorted(np.flatnonzero(expect == 0).tolist())
