"""Host-side page allocator for the paged KV cache.

Reservation policy: a request is admitted only when every page it can ever
need — ``ceil(min(prompt + max_tokens, S_max) / page_size)`` — is available,
so a running request can never hit pool exhaustion mid-generation (no
preemption/swap machinery needed; admission control is the backpressure,
exactly where the gateway's fallback chain expects it: an overloaded local
engine returns an error tuple and the router falls back — SURVEY.md §5
"failure detection"). Physical page 0 is the trash page for masked scatter
writes (ops/paged_attention.py) and is never allocated.

Cross-request sharing (ISSUE 6): pages are REFCOUNTED at group
granularity (group = one superpage run when packing is on, else one
page). The radix prefix cache (engine/prefix_cache.py) retains resident
groups past their slot's release and hands them back to later requests
as ``shared_pages`` at :meth:`allocate` — a group returns to its free
list only when the last holder (slots mapping it + the cache pin) lets
go, so an in-flight request can never lose a page to eviction.

Single-threaded by design: called only from the engine's event-loop thread
(admission/release), mirroring the reference's single-asyncio-process
concurrency model (SURVEY.md §5 "race detection").
"""
from __future__ import annotations

from typing import Iterable

import numpy as np


class PageAllocator:
    """The host page table, the free lists and the group refcounts."""

    def __init__(self, num_pages: int, page_size: int, batch: int,
                 max_seq: int, pages_per_block: int = 1):
        if num_pages < 2:
            raise ValueError("need at least 2 pages (the trash page is "
                             "reserved)")
        self.page_size = page_size
        self.num_pages = num_pages
        self.pages_per_slot = (max_seq + page_size - 1) // page_size
        # SUPERPAGE PACKING (pages_per_block > 1): allocation happens in
        # aligned runs of `pages_per_block` contiguous physical pages, and
        # every aligned group of logical pages maps onto one such run —
        # the invariant the multi-page Pallas kernels' gather-free index
        # maps rely on (ops/paged_attention.py _check_pages_per_block).
        # Superpage 0 (which contains trash page 0) is never allocated,
        # so the trash-group read of a dead iteration only ever sees
        # trash bytes. Costs up to ppb-1 pages of internal fragmentation
        # per slot (pages_needed rounds up to whole runs).
        self.pages_per_block = max(1, pages_per_block)
        if self.pages_per_block > 1:
            if num_pages % self.pages_per_block:
                raise ValueError(
                    f"num_pages {num_pages} not divisible by "
                    f"pages_per_block {self.pages_per_block}")
            if self.pages_per_slot % self.pages_per_block:
                raise ValueError(
                    f"pages_per_slot {self.pages_per_slot} not divisible "
                    f"by pages_per_block {self.pages_per_block} (table "
                    f"rows must split into whole runs)")
        # The free list, excluding the trash page (physical id 0). LIFO:
        # recently-freed pages are likely still warm. Packed pools instead
        # keep a LIFO of free SUPERPAGE ids (group 0, the trash group,
        # excluded).
        if self.pages_per_block > 1:
            self._free: list[int] = []
            self._free_sp: list[int] = list(
                range(num_pages // self.pages_per_block - 1, 0, -1))
        else:
            self._free = list(range(num_pages - 1, 0, -1))
            self._free_sp = []
        # [B, NP] physical page per (slot, logical page); 0 = unallocated
        # (0 is the trash page, never a real mapping).
        self.table = np.zeros((batch, self.pages_per_slot), np.int32)
        self._held: dict[int, list[int]] = {}
        # Group refcounts (group id = page // group_pages): how many
        # holders — slots mapping the group plus the prefix cache's pin —
        # currently keep it alive. Free groups are absent from the dict.
        self.group_pages = max(1, self.pages_per_block)
        self._ref: dict[int, int] = {}
        # Slots running the SLIDING-WINDOW RING (allocate(..., ring_pages)):
        # they hold a fixed set of physical pages whose table mappings
        # rotate forward as the window slides (ensure_mapped) — steady-
        # state footprint O(window), not O(context).
        self._ring_slots: set[int] = set()

    @property
    def free_pages(self) -> int:
        if self.pages_per_block > 1:
            return len(self._free_sp) * self.pages_per_block
        return len(self._free)

    def pages_needed(self, total_tokens: int, ring_pages: int = 0) -> int:
        need = (min(total_tokens, self.pages_per_slot * self.page_size)
                + self.page_size - 1) // self.page_size
        need = min(need, ring_pages) if ring_pages else need
        if self.pages_per_block > 1:
            # Whole superpage runs only — the packing invariant's price.
            b = self.pages_per_block
            need = -(-need // b) * b
        return need

    def can_admit(self, total_tokens: int, ring_pages: int = 0,
                  shared_pages: int = 0) -> bool:
        """``shared_pages``: pages of the request's prefix already resident
        (prefix-cache hit) — only the tail needs fresh groups."""
        need = self.pages_needed(total_tokens, ring_pages)
        fresh = need - shared_pages
        if fresh <= 0:
            return True
        if self.pages_per_block > 1:
            return fresh // self.pages_per_block <= len(self._free_sp)
        return fresh <= len(self._free)

    def fresh_shortfall(self, total_tokens: int, ring_pages: int = 0,
                        shared_pages: int = 0) -> int:
        """How many pages short the free pool is of admitting this request
        — what the engine asks the prefix cache to evict under pressure."""
        need = self.pages_needed(total_tokens, ring_pages) - shared_pages
        return max(0, need - self.free_pages)

    def _groups_of(self, pages: Iterable[int]) -> list[int]:
        """Distinct group ids of ``pages``, first-occurrence order."""
        return list(dict.fromkeys(p // self.group_pages for p in pages))

    def allocate(self, slot: int, total_tokens: int,
                 ring_pages: int = 0,
                 shared_pages: Iterable[int] = ()) -> bool:
        """Reserve a slot's pages for its lifetime. False if insufficient.

        ``ring_pages`` (sliding-window models): hold at
        most that many pages — the whole-lifetime guarantee still stands
        because :meth:`ensure_mapped` recycles the slot's own dead pages
        instead of allocating, so the holding never grows.

        ``shared_pages`` (prefix-cache hit): physical pages of the
        request's resident prompt prefix, in logical order, whole groups
        only. They map into the slot's leading table rows with their
        refcount bumped instead of popping the free lists — the matched
        span's KV is served without allocation or prefill."""
        if slot in self._held:
            raise ValueError(f"slot {slot} already holds pages")
        if ring_pages and self.pages_per_block > 1:
            # Ring rotation remaps one page at a time, which would break
            # the aligned-run invariant; the engine disables packing on
            # SWA-ring builds, so this is a misuse guard.
            raise ValueError("ring reservation is incompatible with "
                             "superpage packing")
        shared = list(shared_pages)
        if shared:
            if ring_pages:
                raise ValueError("prefix sharing is non-ring only "
                                 "(engine gates the cache)")
            if len(shared) % self.group_pages:
                raise ValueError("shared prefix must be whole groups")
        need = self.pages_needed(total_tokens, ring_pages)
        if len(shared) > need:
            raise ValueError(f"shared prefix ({len(shared)} pages) exceeds "
                             f"the reservation ({need})")
        if not self.can_admit(total_tokens, ring_pages, len(shared)):
            return False
        fresh_n = need - len(shared)
        if self.pages_per_block > 1:
            ppb = self.pages_per_block
            sps = [self._free_sp.pop() for _ in range(fresh_n // ppb)]
            # Logical group g → superpage sps[g]: pt[slot, g·ppb + i] =
            # sps[g]·ppb + i, aligned and contiguous per run.
            fresh = [sp * ppb + i for sp in sps for i in range(ppb)]
        else:
            fresh = [self._free.pop() for _ in range(fresh_n)]
        for g in self._groups_of(shared):
            if g not in self._ref:
                raise ValueError(f"shared group {g} is not live")
            self._ref[g] += 1
        for g in self._groups_of(fresh):
            self._ref[g] = 1
        pages = shared + fresh
        self._held[slot] = pages
        self.table[slot, :] = 0
        self.table[slot, :need] = pages
        if ring_pages and need < self.pages_needed(total_tokens):
            self._ring_slots.add(slot)
        return True

    def retain(self, pages: Iterable[int]) -> None:
        """The prefix cache adopts/pins currently-live groups (insert-on-
        release runs BEFORE the slot's release, so the pages survive it)."""
        groups = self._groups_of(pages)
        for g in groups:
            if g not in self._ref:
                raise ValueError(f"cannot retain group {g}: not live")
        for g in groups:
            self._ref[g] += 1

    def drop(self, pages: Iterable[int]) -> None:
        """Release one reference on each group (cache eviction); groups
        whose count reaches zero return to the free lists."""
        self._deref(pages)

    def _deref(self, pages: Iterable[int]) -> None:
        for g in self._groups_of(pages):
            n = self._ref.get(g, 0) - 1
            if n > 0:
                self._ref[g] = n
                continue
            if n < 0:
                raise ValueError(f"group {g} over-freed")
            del self._ref[g]
            if self.pages_per_block > 1:
                self._free_sp.append(g)
            else:
                self._free.append(g)

    def ensure_mapped(self, slot: int, last_logical: int,
                      dead_before: int) -> int:
        """Ring-mode slots: extend the mapping through ``last_logical`` by
        recycling the slot's OLDEST mapped pages, which must lie strictly
        below ``dead_before`` (logical pages wholly below the attention
        window's floor — the windowed kernels' index-map clamp guarantees
        they are never read again, and the recycled page's stale contents
        are fully overwritten as positions advance through it). Returns
        the number of pages re-targeted: non-zero when the table row
        changed (callers flip the device-table dirty bit). No-op for
        whole-lifetime slots."""
        if slot not in self._ring_slots:
            return 0
        row = self.table[slot]
        last_logical = min(last_logical, self.pages_per_slot - 1)
        nz = np.nonzero(row)[0]
        hi = int(nz[-1])
        oldest_i = 0
        for j in range(hi + 1, last_logical + 1):
            old = int(nz[oldest_i])
            if old >= dead_before:
                raise RuntimeError(
                    f"SWA page ring exhausted for slot {slot}: need logical "
                    f"page {j} but the oldest mapping ({old}) is still "
                    f"inside the live window (< {dead_before} required) — "
                    f"ring sized too small for window + in-flight margin")
            row[j] = row[old]
            row[old] = 0
            oldest_i += 1
        return oldest_i

    def transfer(self, src_slot: int, dst_slot: int) -> list[int]:
        """Move ``src_slot``'s entire holding to ``dst_slot`` — the
        disaggregated prefill→decode KV handoff (ISSUE 13). Zero-copy by
        construction: the new owner retains every group FIRST, the table
        row is copied, then the old owner releases — net refcounts are
        unchanged and never dip through zero mid-transfer, so no page
        touches a free list and the same physical ids stay mapped (the
        device cache is untouched; callers only re-upload the page
        table). Returns the transferred page list so the engine can
        assert page-id identity across the handoff."""
        if dst_slot in self._held:
            raise ValueError(f"slot {dst_slot} already holds pages")
        if src_slot in self._ring_slots:
            # Ring rows rotate their mappings in place; handing one off
            # would need dst to inherit rotation state. The engine gates
            # disagg off SWA-ring builds, so this is a misuse guard.
            raise ValueError("cannot transfer a ring-mode slot")
        pages = self._held.get(src_slot)
        if pages is None:
            raise ValueError(f"slot {src_slot} holds no pages")
        for g in self._groups_of(pages):
            self._ref[g] += 1
        self.table[dst_slot, :] = self.table[src_slot, :]
        self._held[dst_slot] = pages
        self.release(src_slot)
        return pages

    def release(self, slot: int) -> None:
        pages = self._held.pop(slot, None)
        if pages:
            self._deref(pages)
        self._ring_slots.discard(slot)
        self.table[slot, :] = 0

    def check_invariants(self, pinned: Iterable[int] = ()) -> None:
        """Test hook: every non-trash group is either free or refcounted by
        exactly its holders (slots mapping it + the cache pin, passed as
        the pinned page list); table rows agree with holdings; packed
        holdings are aligned whole runs; no group is lost or
        double-freed."""
        held = [p for pages in self._held.values() for p in pages]
        if self.pages_per_block > 1:
            ppb = self.pages_per_block
            free = [sp * ppb + i for sp in self._free_sp for i in range(ppb)]
            trash = set(range(ppb))          # the whole trash group
            assert 0 not in self._free_sp, "trash superpage leaked"
            assert len(self._free_sp) == len(set(self._free_sp)), \
                "superpage double-freed"
            for slot, pages in self._held.items():
                assert len(pages) % ppb == 0, "partial superpage held"
                for g in range(len(pages) // ppb):
                    run = pages[g * ppb:(g + 1) * ppb]
                    assert run[0] % ppb == 0, "unaligned superpage run"
                    assert run == list(range(run[0], run[0] + ppb)), \
                        "non-contiguous superpage run"
        else:
            free = list(self._free)
            trash = {0}
        # Refcount truth: each live group's count equals its holders.
        expect: dict[int, int] = {}
        for pages in self._held.values():
            for g in self._groups_of(pages):
                expect[g] = expect.get(g, 0) + 1
        for g in self._groups_of(pinned):
            expect[g] = expect.get(g, 0) + 1
        assert expect == self._ref, \
            f"refcount drift: expected {expect}, have {self._ref}"
        free_groups = set(self._groups_of(free))
        assert not (free_groups & set(self._ref)), "group both free and live"
        assert not (trash & set(held + free)), "trash page leaked"
        n_groups = self.num_pages // self.group_pages
        assert len(free_groups) + len(self._ref) == n_groups - 1, \
            "group lost"
        for slot, pages in self._held.items():
            row = self.table[slot]
            if slot in self._ring_slots:
                # Ring rows rotate mappings forward; the held SET is the
                # invariant, not the positions.
                assert sorted(int(p) for p in row[row != 0]) == \
                    sorted(pages), "ring table/holding mismatch"
                continue
            assert list(row[:len(pages)]) == pages, "table/holding mismatch"
            assert (row[len(pages):] == 0).all()


class CacheGroup:
    """The softmax layers of a model that keep the same KV: how many they
    are (``layers``, stacked in ONE page pool), their window (0: the whole
    context), the pages a slot holds when the ring runs (``ring_pages``,
    0: the whole context's), their allocator with its host page table,
    and whether that table changed since its last upload (``dirty``).
    ``kind`` is the pool's shape — "kv": a K and a V pool ``[layers,
    pages, KV, page, Dh]`` (ops/paged_attention.py), and under a learned
    indexer a third side on the same table, the index keys ``[layers,
    pages, W, page]`` (ops/sparse_attention.py); "latent": ONE pool
    ``[layers, pages, latent_width, page]`` (ops/latent_attention.py) —
    and ``token_bytes`` what a token keeps in one of its layers. Pages,
    tables and admission are the same for both. ``readers``: the layers
    that READ the pool a decode step, ``chunk_readers``: those whose
    prefill chunks attend it (both default to ``layers``; a pool that
    other layers read too has more of the first, and one that is written
    a chunk at a time but attended from one row none of the second)."""

    def __init__(self, layers: int, window: int, ring_pages: int,
                 allocator: PageAllocator, kind: str = "kv",
                 token_bytes: int = 0, readers: int | None = None,
                 chunk_readers: int | None = None):
        if kind not in ("kv", "latent"):
            raise ValueError(f"unknown cache group kind {kind!r}")
        if kind == "latent" and (window or ring_pages):
            raise ValueError("a latent cache group keeps the whole context: "
                             "no window, no ring")
        self.kind = kind
        self.token_bytes = token_bytes
        self.layers = layers
        self.readers = layers if readers is None else readers
        self.chunk_readers = (layers if chunk_readers is None
                              else chunk_readers)
        self.window = window
        self.ring_pages = ring_pages
        self.allocator = allocator
        self.recycled = 0           # pages ensure_mapped re-targeted
        self.dirty = True

    @property
    def pages_per_slot(self) -> int:
        """The most pages one slot ever holds here."""
        return self.ring_pages or self.allocator.pages_per_slot

    def stats(self) -> dict[str, int]:
        a = self.allocator
        return {"kind": self.kind, "layers": self.layers,
                "window": self.window, "token_bytes": self.token_bytes,
                "pages": a.num_pages - a.pages_per_block,
                "pages_free": a.free_pages,
                "pages_per_slot": self.pages_per_slot}


class CacheGroups:
    """Every cache group of an engine behind the admission calls of ONE
    allocator: a request is admitted into every group or into none, and
    leaves them all. Mistral is one windowed group, a model without a
    window one global group, a model that mixes both has one of each
    (``ModelConfig.cache_groups``, in the order of a period: the ring
    comes first where the windowed layers do). ``shared_pages`` (a
    prefix-cache hit) belong to the first group: the engine builds no
    prefix cache beside several groups."""

    def __init__(self, groups: list[CacheGroup]):
        self.groups = list(groups)

    def __iter__(self):
        return iter(self.groups)

    def __len__(self) -> int:
        return len(self.groups)

    @property
    def whole_context(self) -> CacheGroup:
        """The group that keeps the whole context (window 0; a latent
        group is one), else — a model of windowed layers only — the first.
        Found by its window, never by its place: a model whose windowed
        layers come first in a period has the ring as group 0."""
        return next((g for g in self.groups if not g.window),
                    self.groups[0])

    def can_admit(self, total_tokens: int, shared_pages: int = 0) -> bool:
        return all(g.allocator.can_admit(total_tokens, g.ring_pages,
                                         shared_pages if i == 0 else 0)
                   for i, g in enumerate(self.groups))

    def fresh_shortfall(self, total_tokens: int,
                        shared_pages: int = 0) -> int:
        return max(g.allocator.fresh_shortfall(
            total_tokens, g.ring_pages, shared_pages if i == 0 else 0)
            for i, g in enumerate(self.groups))

    def allocate(self, slot: int, total_tokens: int,
                 shared_pages: Iterable[int] = ()) -> bool:
        """Reserve the slot's pages in every group, or in none."""
        shared = list(shared_pages)
        if not self.can_admit(total_tokens, len(shared)):
            return False
        for i, g in enumerate(self.groups):
            g.allocator.allocate(slot, total_tokens, g.ring_pages,
                                 shared if i == 0 else ())
            g.dirty = True
        return True

    def release(self, slot: int) -> None:
        for g in self.groups:
            g.allocator.release(slot)
            g.dirty = True

    def rotate(self, slot: int, last_pos: int, floor_pos: int) -> None:
        """WINDOWED groups only: map the slot's pages through position
        ``last_pos`` by recycling its pages wholly below the window of
        position ``floor_pos`` (``PageAllocator.ensure_mapped``). A global
        group's table is never touched."""
        for g in self.groups:
            if not g.ring_pages:
                continue
            page = g.allocator.page_size
            n = g.allocator.ensure_mapped(
                slot, last_pos // page,
                max(0, floor_pos - g.window + 1) // page)
            if n:
                g.recycled += n
                g.dirty = True

    def check_invariants(self) -> None:
        for g in self.groups:
            g.allocator.check_invariants()
