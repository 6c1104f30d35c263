"""Paged KV cache attention as Pallas TPU kernels (ragged paged attention).

The dense per-slot cache (models/llama.py ``KVCache``) reserves ``S_max``
tokens of HBM for every slot; the paged layout allocates fixed-size pages
from a global pool only as sequences grow, so HBM holds the *actual* token
count and the same memory serves more concurrent slots (cf. PAPERS.md
"Ragged Paged Attention" — re-derived here, not copied). No reference
counterpart: the reference proxies HTTP and has no KV cache at all
(SURVEY.md §2b "Serving scheduler" row).

Layout:
* ``k_pages``/``v_pages``: ``[L, P, KV, page, Dh]`` — ONE global page pool
  stacked over the layers that hold one, head-major within a page (an int8
  pool adds the per-token scales ``[L, P, KV, 1, page]``). **Physical page
  0 is the trash page** of every layer: writes of inactive slots and of
  out-of-range positions are redirected there, so masked writes need no
  branching. The allocator (engine/paged.py) never hands page 0 out. The
  reference path, a mesh and the speculative ``.verify`` see one layer of
  it, ``[P, KV, page, Dh]``, as the layer scan's slice.
* ``page_table``: ``[B, NP]`` int32 — slot's logical page j → physical
  page. Unallocated entries are 0 (trash) and are never read: reads are
  bounded by ``n_valid``.

The step programs leave the pool where it lies (decode: PR 30; prefill:
PR 34). The kernels take the whole stacked pool in HBM and the layer's
index as a prefetched scalar (``.decode_at``, ``.prefill_at``), so the
layer scan hands them no slice — a slice is a copy of a layer's whole
side, a cost of the pool's CAPACITY paid every layer of every step or
chunk. New tokens go in through kernels whose pool operands are their
outputs, under the scope ``kv.paged_insert``: a decode step's after the
layer scan (:func:`paged_insert_in_place`, ``L × B`` tiles), a prefill
chunk's inside it, where the pool is the scan's CARRY
(:func:`paged_insert_chunk_in_place`, whole tiles copied HBM to HBM and
only a ragged first or last tile patched). The pool the programs carry
keeps the default layout — the XLA scatters they replace
(:func:`paged_insert_all`, :func:`paged_insert_kv`: still the write of the
reference path, of a mesh and of ``.verify``, and the tests' oracle) made
it take a layout of their own liking, which every slice was then re-laid
from.

Two attention kernels, one shape: neither has a page axis in its grid. A
grid step has a fixed price (~0.3–0.4 us on a v5e when it moves nothing,
~0.7 when it attends one page for one head) and a kernel that steps through
the page TABLE pays it for every entry, live or dead: the decode kernel
paid ``B × KV × NP`` steps a call — 2 048 for some tens of live ones, 415
us against a byte floor of 21 (PERF.md, PR 27) — and the prefill kernel
``B × H × T/128 × NP``, 4 096 for one 512-token Mistral row, each ONE query
head's 128 rows against ONE page fetched 16 times over (2.2–2.3 ms a layer
call, 7% of its roofline: PERF.md, PR 37). So both leave the pools in HBM
and WALK: a program copies a block — a run of pages for as many of their
KV heads as fit a VMEM budget — into one of two buffers while it attends
the one before, from the first live block to the last and no further. The
decode kernel's program walks each slot's live blocks for one query
position; the prefill kernel's program (one a row and group of KV heads)
walks, for each row-block of query positions (512 rows a folded head), the
blocks between
the window's floor for its first query and the diagonal of its last, with
a KV head's whole query group folded into the rows that meet each page
(``G × bt`` rows a dot, the heads a batch dimension), and builds a mask
only on the pages a mask can change (the diagonal's and the floor's). The
cost is the live tokens' bytes (decode) or the visible pairs' arithmetic
(prefill) plus a few microseconds a program — the ragged property, by
construction and not by elision.

The adapter :func:`make_paged_attention_fn` is built INSIDE the engine's
jitted step (closing over the traced page table), so ``llama.forward``
needs no signature change: a ``PagedKVCache`` pytree scans over layers
exactly like the dense cache — and stays OUT of the scanned inputs where
the provider carries ``.decode_at`` / ``.prefill_at``.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.config import ModelConfig

NEG_INF = -1e30


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


class PagedKVCache(NamedTuple):
    """k, v: [L, P, KV, page, Dh] — global page pool per layer. Scans over
    the leading layer dim in llama.forward exactly like the dense KVCache.
    With ``kv_quant="int8"`` each of k/v is the ``{"q": int8, "s": f32
    [L, P, KV, 1, page]}`` dict (per-token-per-head scales; the unit dim
    before the token axis is the Mosaic-legal, relayout-free rank the
    kernels consume — models/llama.py KVCache convention)."""
    k: Any
    v: Any

    @classmethod
    def create(cls, config: ModelConfig, num_pages: int, page_size: int,
               dtype=jnp.bfloat16, kv_quant: str = "") -> "PagedKVCache":
        shape = (config.n_layers, num_pages, config.n_kv_heads, page_size,
                 config.head_dim)
        if kv_quant == "int8":
            def qz():
                return {"q": jnp.zeros(shape, jnp.int8),
                        "s": jnp.zeros(shape[:-2] + (1, shape[-2]),
                                       jnp.float32)}
            return cls(k=qz(), v=qz())
        return cls(k=jnp.zeros(shape, dtype=dtype),
                   v=jnp.zeros(shape, dtype=dtype))

    @property
    def page_size(self) -> int:
        k = self.k["q"] if isinstance(self.k, dict) else self.k
        return k.shape[3]


def _insert_positions(page_table: jax.Array, lengths: jax.Array,
                      active: jax.Array | None, T: int, page: int):
    """(phys, off), each [B, T] int32: where token t of slot b lands —
    logical position ``lengths + t`` through the table. Inactive slots and
    positions past the table's reach name trash page 0."""
    NP = page_table.shape[1]
    pos = lengths[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]  # [B,T]
    logical = jnp.clip(pos // page, 0, NP - 1)
    phys = jnp.take_along_axis(page_table, logical, axis=1)           # [B,T]
    ok = (pos // page) < NP
    if active is not None:
        ok = ok & active[:, None]
    return jnp.where(ok, phys, 0), pos % page


def paged_insert_kv(layer_k, layer_v,
                    k_new: jax.Array, v_new: jax.Array,
                    page_table: jax.Array, lengths: jax.Array,
                    active: jax.Array | None):
    """Scatter new tokens into the page pool at logical positions
    ``[lengths, lengths+T)`` per slot.

    layer_k/v: [P, KV, page, Dh] (or the int8 ``{"q","s"}`` dict — new
    tokens quantize at write time); k_new/v_new: [B, T, KV, Dh];
    page_table: [B, NP]; lengths: [B]. Inactive slots and positions past
    the table's reach land on trash page 0 (one scatter, no branches).
    """
    quant = isinstance(layer_k, dict)
    P, KV, page, Dh = (layer_k["q"] if quant else layer_k).shape
    B, T = k_new.shape[:2]
    flat_page, flat_off = (x.reshape(-1) for x in _insert_positions(  # [B*T]
        page_table, lengths, active, T, page))

    # [P, KV, page(, Dh)] scattered at (page, :, offset(, :)) per token.
    # In-bounds by construction (phys from the table or trash page 0;
    # off = pos % page) — the mode hint drops XLA's per-element clamping.
    def scatter(pool, new):
        return pool.at[flat_page, :, flat_off].set(
            new.astype(pool.dtype), mode="promise_in_bounds")

    def scatter_s(pool, new):
        # Scale pool [P, KV, 1, page]: same token positions, through the
        # unit dim.
        return pool.at[flat_page, :, 0, flat_off].set(
            new.astype(pool.dtype), mode="promise_in_bounds")

    if quant:
        from ..models.llama import quantize_kv
        kq, ks = quantize_kv(k_new)                  # [B,T,KV,Dh], [B,T,KV]
        vq, vs = quantize_kv(v_new)
        return (
            {"q": scatter(layer_k["q"], kq.reshape(B * T, KV, Dh)),
             "s": scatter_s(layer_k["s"], ks.reshape(B * T, KV))},
            {"q": scatter(layer_v["q"], vq.reshape(B * T, KV, Dh)),
             "s": scatter_s(layer_v["s"], vs.reshape(B * T, KV))},
        )
    layer_k = scatter(layer_k, k_new.reshape(B * T, KV, Dh))
    layer_v = scatter(layer_v, v_new.reshape(B * T, KV, Dh))
    return layer_k, layer_v


def paged_insert_all(pool_k, pool_v,
                     k_news: jax.Array, v_news: jax.Array,
                     page_table: jax.Array, lengths: jax.Array,
                     active: jax.Array | None):
    """Insert every layer's new tokens into the page pool with a single
    scatter (the paged half of the deferred-insert protocol —
    models/llama.py ``insert_kv_stacked`` is the dense twin).

    pool_k/v: [L, P, KV, page, Dh] (or the int8 ``{"q","s"}`` dict);
    k_news/v_news: [L, B, T, KV, Dh] (the layer scan's stacked ys, always
    bf16/fp32 — quantization happens here at write time); lengths: [B] —
    the first token's logical position (token t lands at lengths + t:
    T = 1 is the decode step, T = k+1 the speculative verify, whose
    rejected tail lands in the undefined zone past the advanced lengths
    exactly like the dense twin). Masked/overflow writes land on trash
    page 0 as usual.
    """
    quant = isinstance(pool_k, dict)
    page = (pool_k["q"] if quant else pool_k).shape[3]
    L, B, T = k_news.shape[:3]
    phys, off = (x.reshape(-1) for x in _insert_positions(   # [B*T] each
        page_table, lengths, active, T, page))

    # Advanced indices (phys, off) are separated by slices, so the indexed
    # result is [B*T, L, KV(, Dh)] — the [L, B, T, ...] new tokens
    # transpose to match. In-bounds by construction (see paged_insert_kv).
    def scatter(pool, news):
        new = news.transpose(1, 2, 0, 3, 4).reshape(
            B * T, L, *news.shape[3:]).astype(pool.dtype)
        return pool.at[:, phys, :, off].set(new, mode="promise_in_bounds")

    def scatter_s(pool, news):
        # Scale pool [L, P, KV, 1, page]: through the unit dim.
        new = news.transpose(1, 2, 0, 3).reshape(
            B * T, L, news.shape[3]).astype(pool.dtype)
        return pool.at[:, phys, :, 0, off].set(new,
                                               mode="promise_in_bounds")

    if quant:
        from ..models.llama import quantize_kv
        kq, ks = quantize_kv(k_news)      # [L,B,T,KV,Dh], [L,B,T,KV]
        vq, vs = quantize_kv(v_news)
        return (
            {"q": scatter(pool_k["q"], kq),
             "s": scatter_s(pool_k["s"], ks)},
            {"q": scatter(pool_v["q"], vq),
             "s": scatter_s(pool_v["s"], vs)},
        )
    return (scatter(pool_k, k_news), scatter(pool_v, v_news))


# ---------------------------------------------------------------------------
# Write kernel: the new tokens of every layer into the pool, in place
# ---------------------------------------------------------------------------

# Rows of a page the write kernel reads, changes and writes back around a
# token's row: a whole packed tile of the narrowest pool dtype (int8: 32
# rows share a tile's sublane words), so every copy is tile-aligned.
_INSERT_TILE_ROWS = 32
# What a program of the write kernel may take of VMEM for its slots.
_INSERT_VMEM_BYTES = 4 * 2 ** 20


def _wide(dtype):
    """The 32-bit type a write kernel selects in: the packed dtypes (int8,
    bf16) have no select of their own on every chip generation, and both
    widenings are exact."""
    return jnp.int32 if jnp.issubdtype(dtype, jnp.integer) else jnp.float32


def _pool_sides(pool_k, pool_v, k_new, v_new):
    """(new values, pool sides) as the write kernels take them, K then V,
    an int8 side as its values followed by its float32 scales — quantised
    here, by ``quantize_kv``, as the XLA scatters do."""
    if isinstance(pool_k, dict):
        from ..models.llama import quantize_kv
        (knq, kns), (vnq, vns) = quantize_kv(k_new), quantize_kv(v_new)
        return ((knq, kns.astype(jnp.float32), vnq, vns.astype(jnp.float32)),
                (pool_k["q"], pool_k["s"], pool_v["q"], pool_v["s"]))
    return ((k_new.astype(pool_k.dtype), v_new.astype(pool_k.dtype)),
            (pool_k, pool_v))


def _pool_of(sides):
    """(pool_k, pool_v) back from a write kernel's output sides."""
    if len(sides) == 4:
        return ({"q": sides[0], "s": sides[1]}, {"q": sides[2], "s": sides[3]})
    return sides[0], sides[1]


def _paged_insert_kernel(phys_ref, off_ref, *refs, T: int, rows: int,
                         quant: bool):
    """Program ``(layer, chunk of slots)``: for each token ``t`` in turn,
    copy the ``rows``-row tile around every slot's target row (and, int8,
    the page's scale plane) from the pool in HBM, put the new row in, and
    copy it back — the copies of a round started together and waited for
    once. Slots own disjoint pages, so a round's tiles are disjoint; a
    slot's OWN tokens share tiles, hence one round a token. The trash
    page takes every masked row (several may race there: it is never
    read). ``refs``: the new values ``[1, bc, T, KV, 1, Dh]`` (int8: each
    followed by its scales ``[1, bc, T, KV, 1, 1]``) for K then V, the
    pool sides in HBM (unused: they ARE the outputs), the output pool
    sides, a VMEM buffer per side, and the DMA semaphores
    ``[side, slot]``."""
    n = 4 if quant else 2
    news, pools = refs[:n], refs[2 * n:3 * n]
    bufs, sem = refs[3 * n:4 * n], refs[4 * n]
    layer, chunk = pl.program_id(0), pl.program_id(1)
    bc = news[0].shape[1]

    def target(b, t):
        row = (chunk * bc + b) * T + t
        return phys_ref[row], off_ref[row]

    def copies(b, t, back: bool):
        phys, off = target(b, t)
        start = pl.multiple_of(off // rows * rows, rows)
        out = []
        for side, (pool, buf) in enumerate(zip(pools, bufs)):
            if quant and side % 2:                  # a scale plane
                hbm = pool.at[layer, phys]
            else:
                hbm = pool.at[layer, phys, :, pl.ds(start, rows), :]
            src, dst = (buf.at[b], hbm) if back else (hbm, buf.at[b])
            out.append(pltpu.make_async_copy(src, dst, sem.at[side, b]))
        return out

    def put(buf, b, new, at, axis):
        wide = _wide(buf.dtype)
        old = buf[b].astype(wide)
        hit = jax.lax.broadcasted_iota(jnp.int32, old.shape, axis) == at
        buf[b] = jnp.where(hit, new.astype(wide), old).astype(buf.dtype)

    def puts(b, t):
        _, off = target(b, t)
        for side, (new, buf) in enumerate(zip(news, bufs)):
            if quant and side % 2:
                put(buf, b, new[0, b, t], off, 2)              # [KV,1,page]
            else:
                put(buf, b, new[0, b, t], off % rows, 1)       # [KV,rows,Dh]

    def each_slot(step):
        # A loop, not bc unrolled copies of the body: the kernel is traced
        # and lowered once a decode program, and set-up pays for its size.
        jax.lax.fori_loop(0, bc, lambda b, carry: (step(b), carry)[1], 0)

    def move(t, back: bool):
        """Every slot's copies of round ``t``: started together, then
        waited for."""
        def start(b):
            for c in copies(b, t, back):
                c.start()

        def wait(b):
            for c in copies(b, t, back):
                c.wait()
        each_slot(start)
        each_slot(wait)

    for t in range(T):
        move(t, back=False)
        each_slot(lambda b: puts(b, t))
        move(t, back=True)


def paged_insert_in_place(pool_k, pool_v, k_news: jax.Array,
                          v_news: jax.Array, page_table: jax.Array,
                          lengths: jax.Array, active: jax.Array | None, *,
                          interpret: bool | None = None):
    """:func:`paged_insert_all` as a Pallas call whose pool operands ARE
    its outputs (``input_output_aliases``): the pool stays where it lies,
    in the layout the decode kernel reads, and a step writes ``L × B × T``
    rows of it. The XLA scatter it stands in for made the burst's carried
    pool take a layout of the scatter's liking, which every layer of
    every step then paid to undo (PERF.md, PR 30). Same arguments, same
    positions (:func:`_insert_positions`), the same bytes in the pool off
    the trash page: quantisation stays outside, by ``quantize_kv``."""
    quant = isinstance(pool_k, dict)
    kq = pool_k["q"] if quant else pool_k
    L, _, KV, page, Dh = kq.shape
    B, T = k_news.shape[1:3]
    rows = min(_INSERT_TILE_ROWS, page)
    phys, off = _insert_positions(page_table, lengths, active, T, page)

    news, pools = _pool_sides(pool_k, pool_v, k_news, v_news)
    # Values [L, B, T, KV, 1, Dh], scales [L, B, T, KV, 1, 1].
    news = tuple(x[..., None, :] if x.ndim == 5 else x[..., None, None]
                 for x in news)
    # Slots a program holds: as many as keep its VMEM — per slot and side
    # a tile, the new rows (a one-row block pads to a whole 4 KiB tile a
    # head, and the pipeline holds two) and, int8, the same again for a
    # scale plane and the new scales — within the budget.
    row_pad = 2 * T * 4096 * -(-Dh // 128)
    per_slot = 2 * KV * (rows * Dh * kq.dtype.itemsize + row_pad
                         + (8 * page * 4 + 2 * T * 4096 if quant else 0))
    bc = max(d for d in range(1, B + 1)
             if B % d == 0 and (d == 1 or d * per_slot <= _INSERT_VMEM_BYTES))

    def new_spec(x):
        return pl.BlockSpec((1, bc, *x.shape[2:]),
                            lambda l, c, phys, off: (l, c, 0, 0, 0, 0))

    tile = pltpu.VMEM((bc, KV, rows, Dh), kq.dtype)
    plane = pltpu.VMEM((bc, KV, 1, page), jnp.float32)
    buffers = [tile, plane, tile, plane] if quant else [tile, tile]

    out = pl.pallas_call(
        functools.partial(_paged_insert_kernel, T=T, rows=rows, quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L, B // bc),
            in_specs=[*map(new_spec, news),
                      *[pl.BlockSpec(memory_space=pl.ANY)] * len(pools)],
            out_specs=[pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            scratch_shapes=[*buffers,
                            pltpu.SemaphoreType.DMA((len(pools), bc))],
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        # Operand i of the call (the two position vectors included) is
        # output i - 2 - len(news).
        input_output_aliases={2 + len(news) + i: i
                              for i in range(len(pools))},
        interpret=_interpret_default() if interpret is None else interpret,
    )(phys.reshape(-1).astype(jnp.int32), off.reshape(-1).astype(jnp.int32),
      *news, *pools)
    return _pool_of(out)


def _paged_insert_chunk_kernel(layer_ref, start_ref, tile_phys_ref,
                               tile_off_ref, page_phys_ref, *refs, T: int,
                               rows: int, page: int, n_tiles: int,
                               n_pages: int, quant: bool):
    """Program ``slot``: the slot's ``T`` new rows, which lie in the pool
    at ``[start, start + T)`` of its logical sequence, go in as WHOLE
    ``rows``-row tiles — one copy a tile and side from the new values to
    the pool, HBM to HBM, all started and then all waited for. Only a
    tile the run covers in part (its first, its last) and, int8, a
    page's scale plane are read, changed and written back. The new
    values arrive cut into the pool's own tiles (``tile j`` holds the
    rows of absolute tile ``start // rows + j``: the caller shifted them),
    so no copy is misaligned whatever ``start`` is. ``refs``: the new
    values ``[K, n_tiles, KV, rows, Dh]`` (int8: each followed by its
    scale planes ``[K, n_pages, KV, 1, page]``) for K then V, the pool
    sides in HBM (unused: they ARE the outputs), the output pool sides,
    per side a VMEM pair (the pool's piece, the new one) and the DMA
    semaphores ``[side, whole tiles | patches]``."""
    n = 4 if quant else 2
    news, pools = refs[:n], refs[2 * n:3 * n]
    bufs, sem = refs[3 * n:4 * n], refs[4 * n]
    slot = pl.program_id(0)
    layer, start = layer_ref[0], start_ref[slot]
    sides = range(n)
    planes = [side for side in sides if quant and side % 2]
    values = [side for side in sides if side not in planes]

    def tile_rows(j):
        """(first, covered wholly, covered in part) for tile ``j``: the
        index among the new rows of the tile's first row."""
        first = j * rows - start % rows
        whole = (first >= 0) & (first + rows <= T)
        return first, whole, (first < T) & jnp.logical_not(whole)

    def tile_in_pool(side, j):
        at = slot * n_tiles + j
        off = pl.multiple_of(tile_off_ref[at], rows)
        return pools[side].at[layer, tile_phys_ref[at], :,
                              pl.ds(off, rows), :]

    def whole_tile(side, j):
        return pltpu.make_async_copy(news[side].at[slot, j],
                                     tile_in_pool(side, j), sem.at[side, 0])

    def patch(piece, first, axis):
        """Read the pool's pieces and the new ones, put the new rows
        (index ``first + i`` within ``[0, T)``) over the old, write back.
        ``piece``: side -> (the pool's piece, the new one)."""
        def both(run):
            for side, (old, new) in piece.items():
                run(pltpu.make_async_copy(old, bufs[side].at[0],
                                          sem.at[side, 1]))
                run(pltpu.make_async_copy(new, bufs[side].at[1],
                                          sem.at[side, 1]))
        both(lambda c: c.start())
        both(lambda c: c.wait())
        for side in piece:
            buf = bufs[side]
            wide = _wide(buf.dtype)
            old, new = buf[0].astype(wide), buf[1].astype(wide)
            i = first + jax.lax.broadcasted_iota(jnp.int32, old.shape, axis)
            buf[0] = jnp.where((i >= 0) & (i < T), new, old
                               ).astype(buf.dtype)
        back = [pltpu.make_async_copy(bufs[side].at[0], old, sem.at[side, 1])
                for side, (old, _) in piece.items()]
        for c in back:
            c.start()
        for c in back:
            c.wait()

    def each(count, step):
        # Loops, not unrolled copies of the body: the kernel is traced and
        # lowered once a prefill bucket, and set-up pays for its size.
        jax.lax.fori_loop(0, count, lambda i, carry: (step(i), carry)[1], 0)

    def send(j):
        first, whole, part = tile_rows(j)

        @pl.when(whole)
        def _whole():
            for side in values:
                whole_tile(side, j).start()

        @pl.when(part)
        def _part():
            patch({side: (tile_in_pool(side, j), news[side].at[slot, j])
                   for side in values}, first, 1)

    def plane(i):
        first = i * page - start % page

        @pl.when(first < T)
        def _plane():
            phys = page_phys_ref[slot * n_pages + i]
            patch({side: (pools[side].at[layer, phys],
                          news[side].at[slot, i]) for side in planes},
                  first, 2)

    def settle(j):
        @pl.when(tile_rows(j)[1])
        def _whole():
            for side in values:
                whole_tile(side, j).wait()

    each(n_tiles, send)
    if planes:
        each(n_pages, plane)
    each(n_tiles, settle)


def paged_insert_chunk_in_place(pool_k, pool_v, k_new: jax.Array,
                                v_new: jax.Array, page_table: jax.Array,
                                lengths: jax.Array,
                                active: jax.Array | None, *,
                                layer: jax.Array | int = 0,
                                interpret: bool | None = None):
    """:func:`paged_insert_kv` on ONE layer of the stacked pool, as a
    Pallas call whose pool operands ARE its outputs
    (``input_output_aliases``): the prefill layer scan carries the pool
    and a chunk's rows go into layer ``layer`` (a traced scalar) where
    the pool lies. :func:`paged_insert_in_place` is general in ``T`` only
    by one read-modify-write round a token; this moves whole tiles
    (:func:`_paged_insert_chunk_kernel`).

    pool_k/v: [L, P, KV, page, Dh] (or the int8 ``{"q","s"}`` dicts);
    k_new/v_new: [K, T, KV, Dh]; page_table: [K, NP]; lengths: [K] — any
    start, any ``T``. Same positions (:func:`_insert_positions`: inactive
    rows, unmapped pages and positions past the table's reach land on
    trash page 0) and the same bytes in the pool off the trash page:
    quantisation stays outside, by ``quantize_kv``. Outside the kernel
    too, in XLA on the chunk's own rows: each slot's rows are shifted to
    where they sit in their first tile (first page, for the scales) and
    laid head-major, so that the kernel copies tiles and never rows."""
    quant = isinstance(pool_k, dict)
    kq = pool_k["q"] if quant else pool_k
    KV, page, Dh = kq.shape[2:]
    K, T = k_new.shape[:2]
    rows = min(_INSERT_TILE_ROWS, page)
    if page % rows:
        raise ValueError(f"page size {page} is not a multiple of {rows}")
    n_tiles, n_pages = -(-T // rows) + 1, -(-T // page) + 1
    phys, off = _insert_positions(page_table, lengths, active, T, page)
    lengths = lengths.astype(jnp.int32)

    def pieces(x, size, count):
        """x [K, T, ...] -> [K, count, size, ...]: slot b's row t at row
        ``lengths[b] % size + t`` of its ``count`` pieces, zeros around."""
        blank = jnp.zeros((count * size, *x.shape[2:]), x.dtype)
        return jnp.stack([
            jax.lax.dynamic_update_slice_in_dim(blank, x[b],
                                                lengths[b] % size, axis=0)
            for b in range(K)]).reshape(K, count, size, *x.shape[2:])

    def piece_of(x, size, count):
        """x [K, T] of the rows -> [K * count] of the pieces (a piece's
        rows share a page; a piece past the run is never looked at)."""
        t = jnp.arange(count, dtype=jnp.int32)[None, :] * size \
            - (lengths % size)[:, None]
        return jnp.take_along_axis(x, jnp.clip(t, 0, T - 1),
                                   axis=1).reshape(-1).astype(jnp.int32)

    def tiles(x):                              # -> [K, n_tiles, KV, rows, Dh]
        return pieces(x, rows, n_tiles).transpose(0, 1, 3, 2, 4)

    def scale_planes(x):                       # -> [K, n_pages, KV, 1, page]
        return pieces(x, page, n_pages
                      ).transpose(0, 1, 3, 2)[:, :, :, None, :]

    news, pools = _pool_sides(pool_k, pool_v, k_new, v_new)
    news = tuple(tiles(x) if x.ndim == 4 else scale_planes(x) for x in news)
    tile = pltpu.VMEM((2, KV, rows, Dh), kq.dtype)
    plane = pltpu.VMEM((2, KV, 1, page), jnp.float32)
    buffers = [tile, plane, tile, plane] if quant else [tile, tile]
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), lengths,
               piece_of(phys, rows, n_tiles),
               piece_of(off, rows, n_tiles) // rows * rows,
               piece_of(phys, page, n_pages))

    out = pl.pallas_call(
        functools.partial(_paged_insert_chunk_kernel, T=T, rows=rows,
                          page=page, n_tiles=n_tiles, n_pages=n_pages,
                          quant=quant),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(K,),
            in_specs=[hbm] * (len(news) + len(pools)),
            out_specs=[hbm] * len(pools),
            scratch_shapes=[*buffers,
                            pltpu.SemaphoreType.DMA((len(pools), 2))],
        ),
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype) for p in pools],
        input_output_aliases={len(scalars) + len(news) + i: i
                              for i in range(len(pools))},
        interpret=_interpret_default() if interpret is None else interpret,
    )(*scalars, *news, *pools)
    return _pool_of(out)


# ---------------------------------------------------------------------------
# Decode kernel: q [B, KV, G, Dh] vs pages [P, KV, page, Dh]
# ---------------------------------------------------------------------------

# What the decode kernel's two K + V (+ scale) buffers may take of VMEM.
# A buffer holds one block: a run of pages for as many of their KV heads
# as fit (int8, page 256, Dh 128, 8 heads: 1.25 MiB for both buffers of
# both sides — all of them). A quarter of the 16 MiB a v5e kernel may
# use, so the q/out blocks and the body's temporaries (the heads' K and
# V converted for the dots) have room.
_DECODE_KV_VMEM_BYTES = 4 * 2 ** 20


def _kv_block_bytes(page: int, Dh: int, itemsize: int, quant: bool,
                    ppb: int) -> int:
    """VMEM bytes of ONE KV head's side of a block of ``ppb`` pages: the
    values and, int8, the f32 scale planes, whose unit dim pads to 8
    sublanes."""
    return ppb * (page * Dh * itemsize + (8 * page * 4 if quant else 0))


def _walk_buffers(k_pages, v_pages, ppb: int, heads: int):
    """(pool operands, VMEM buffer pairs) of a kernel that walks blocks
    ``(ppb, heads, page, Dh)`` of the stacked pool: K, V — int8: K, its
    scale plane, V, its scale plane. Scales are STORED [L, P, KV, 1,
    page], so a block's scale plane is the same slice of the pool as its
    values (the unit dim keeps a block's trailing two dims ``(1, page)``
    legal under the TPU's (8, 128) tiling: rank 3 would put a block of 1
    on the KV dim, which Mosaic refuses and interpret mode never sees)."""
    quant = isinstance(k_pages, dict)
    kq = k_pages["q"] if quant else k_pages
    page, Dh = kq.shape[3:]
    kv_buf = pltpu.VMEM((2, ppb, heads, page, Dh), kq.dtype)
    if not quant:
        return (k_pages, v_pages), [kv_buf, kv_buf]
    s_buf = pltpu.VMEM((2, ppb, heads, 1, page), jnp.float32)
    return ((k_pages["q"], k_pages["s"], v_pages["q"], v_pages["s"]),
            [kv_buf, s_buf, kv_buf, s_buf])


def _decode_heads_per_block(KV: int, page: int, Dh: int, itemsize: int,
                            quant: bool, ppb: int) -> int:
    """How many of a page's KV heads one block of the paged decode kernel
    holds — pure shape arithmetic over what the call sees: the largest
    divisor of the local ``KV`` whose K and V buffers ``(ppb, heads, page,
    Dh)``, two of each, fit ``_DECODE_KV_VMEM_BYTES`` (an int8 pool adds
    its f32 scale planes, whose unit dim pads to 8 sublanes in VMEM). A
    block too large for the budget still holds one head."""
    # K and V, two buffers each.
    per_head = 2 * 2 * _kv_block_bytes(page, Dh, itemsize, quant, ppb)
    return max([d for d in range(1, KV + 1)
                if KV % d == 0 and d * per_head <= _DECODE_KV_VMEM_BYTES],
               default=1)


def _decode_live_blocks(n_valid, bs: int, window: int, n_table_blocks: int):
    """(first, last) live BLOCK (run of ``bs`` tokens) for a query at
    position ``n_valid``: the blocks that hold stale keys ``p`` with
    ``n_valid - p < window`` (``ceil((window - 1) / bs) + 1`` of them at
    most, however the window is aligned). ``last`` is clamped into the
    table, so a fresh slot (nothing stale, only the self column counts)
    still names a block."""
    last = jnp.clip((n_valid + bs - 1) // bs - 1, 0, n_table_blocks - 1)
    if window:
        first = jnp.minimum(jnp.maximum(n_valid - (window - 1), 0) // bs,
                            last)
    else:
        first = 0
    return first, last


# The two halves of one online-softmax update, either side of the mask.
# ``inline=True`` as jnp's own functions have it: the traced jaxpr is
# CACHED by shapes and replayed into the kernel being traced, so the
# kernel's jaxpr is what calling the body would give, eqn for eqn — but
# a kernel that attends on two paths (masked and not), and every program
# after the first with a block shape, does not run this Python again.
# Set-up traces the prefill kernel once a (bucket, rows) program: the 16
# of a chat cell warm up 1.3 s sooner for it on the chip's host, where
# the kernel's trace is what a warm set-up pays (PERF.md, PR 37).

@functools.partial(jax.jit, inline=True)
def _scaled_scores(q, k, ks):
    if k.dtype == jnp.int8:
        k = k.astype(q.dtype)
    else:
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [heads, G, page]
    scores *= q.shape[-1] ** -0.5
    if ks is not None:
        scores = scores * ks
    return scores


@functools.partial(jax.jit, inline=True)
def _softmax_update(scores, v, vs, m, l, acc):
    m_new = jnp.maximum(m, jnp.max(scores, axis=2, keepdims=True))
    alpha = jnp.exp(m - m_new)
    e = jnp.exp(scores - m_new)
    l = alpha * l + jnp.sum(e, axis=2, keepdims=True)
    p = e if vs is None else e * vs
    acc = acc * alpha + jax.lax.dot_general(
        p, v.astype(jnp.float32), (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)        # [heads, G, Dh]
    return m_new, l, acc


def _attend_heads(q, k, v, ks, vs, mask, m, l, acc):
    """One online-softmax update of EVERY folded head against one page,
    the heads the leading batch dimension of both dots (``q`` [heads, G, Dh], ``k``/``v``
    [heads, page, Dh], int8 scales ``ks``/``vs`` [heads, 1, page] or None,
    state ``m``/``l`` [heads, G, 1] and ``acc`` [heads, G, Dh]; the
    prefill kernel's rows are ``G x bt``; ``mask`` None where every score
    is visible, which is what an all-true mask selects). Per head
    the operations and their order are attend_block's — int8 K cast to
    q's dtype for one native MXU pass, the K scale on the scores after the
    QK dot, the V scale on the probabilities after ``l`` accumulates —
    so a head's result does not depend on how many heads share the call.
    On the chip the batched form is what pays: eight heads' dots issued
    as one op ran 3.6x faster than eight unrolled (PERF.md, PR 27)."""
    scores = _scaled_scores(q, k, ks)
    if mask is not None:
        scores = mask(scores)
    return _softmax_update(scores, v, vs, m, l, acc)


def _paged_decode_kernel(pt_ref, nvalid_ref, layer_ref, q_ref, kn_ref,
                         vn_ref, *refs, page: int, window: int,
                         pages_per_block: int, n_table_blocks: int):
    """One program per group of folded heads walks EVERY slot's live
    blocks, and only those: the pools stay in HBM and each block is copied
    into one of two VMEM buffers while the block before it is attended.
    The walk is one sequence over (slot, block) — a slot's last block
    prefetches the next slot's first — so the copy engine idles only on
    the call's very first block. ``refs``: the LAYER-STACKED pool sides in
    HBM (K, V; int8: K, its scale plane, V, its scale plane), of which
    only layer ``layer_ref[0]`` is read, the output block, a VMEM buffer
    pair per pool side in the same order, and the DMA semaphores
    ``[buffer, side]``."""
    n_sides = (len(refs) - 2) // 2       # K, V (int8: + their scale planes)
    pools, o_ref = refs[:n_sides], refs[n_sides]
    bufs, sem = refs[n_sides + 1:-1], refs[-1]
    if n_sides == 4:
        k_buf, ks_buf, v_buf, vs_buf = bufs
    else:
        (k_buf, v_buf), ks_buf, vs_buf = bufs, None, None
    hb = pl.program_id(0)
    B, heads = q_ref.shape[0], q_ref.shape[1]
    ppb = pages_per_block
    layer = layer_ref[0]

    def live(b):
        n_valid = nvalid_ref[b]
        first, last = _decode_live_blocks(n_valid, ppb * page, window,
                                          n_table_blocks)
        return n_valid, first, last - first + 1        # >= 1 block

    def copies(b, blk, buf):
        # Gather-free: ONE table lookup per block. The packed-table
        # promise makes a run's ppb physical pages contiguous from its
        # first, so one copy per pool side moves the whole run for the
        # folded heads.
        p0 = pt_ref[b, blk * ppb]
        return [pltpu.make_async_copy(
            pool.at[layer, pl.ds(p0, ppb), pl.ds(hb * heads, heads)],
            vmem.at[buf], sem.at[buf, side])
            for side, (pool, vmem) in enumerate(zip(pools, bufs))]

    for c in copies(0, live(0)[1], 0):
        c.start()

    def slot(b, walked):
        n_valid, first, n_blocks = live(b)
        # Sliding window: the query at position n_valid sees stale keys p
        # with n_valid - p < window, i.e. p >= w0.
        w0 = jnp.maximum(n_valid - (window - 1), 0) if window else 0
        q = q_ref[b]                                   # [heads, G, Dh]
        # The SELF column, per head:
        # m = q·k_new, l = 1, acc = v_new — the current token's K/V never
        # touched HBM (deferred-insert decode protocol).
        qf = q.astype(jnp.float32)
        m = jax.lax.dot_general(
            qf, kn_ref[b].astype(jnp.float32),
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)        # [heads, G, 1]
        m *= q.shape[-1] ** -0.5
        state = (m, jnp.ones_like(m),
                 jnp.broadcast_to(vn_ref[b].astype(jnp.float32), qf.shape))

        def block(i, state):
            buf = (walked + i) % 2
            # Start the NEXT block of the walk — this slot's, or the next
            # slot's first — into the other buffer, then wait for this one.
            ends = i == n_blocks - 1
            nb = jnp.minimum(jnp.where(ends, b + 1, b), B - 1)
            nblk = jnp.where(ends, live(nb)[1], first + i + 1)

            @pl.when(jnp.logical_not(ends & (b == B - 1)))
            def _prefetch():
                for c in copies(nb, nblk, 1 - buf):
                    c.start()
            for c in copies(b, first + i, buf):
                c.wait()
            # Per-page attends over the block's sub-pages, unrolled
            # (pages_per_block is compile-time): for each head the SAME
            # online-softmax updates in the SAME page order as a per-page,
            # per-head kernel, so any pages_per_block and any head fold
            # give a head the same result — only what one copy carries
            # changes. A page with nothing visible (a fresh slot's one
            # block; a run's tail) is skipped, not masked: it may hold
            # anything.
            for sub in range(ppb):
                lp = (first + i) * ppb + sub           # logical page
                visible = lp * page < n_valid
                if window:
                    visible = visible & ((lp + 1) * page > w0)

                def attend(state, sub=sub, lp=lp):
                    def mask(scores):
                        pos = lp * page + jax.lax.broadcasted_iota(
                            jnp.int32, scores.shape, 2)
                        ok = pos < n_valid
                        if window:
                            ok = ok & (pos >= w0)
                        return jnp.where(ok, scores, NEG_INF)
                    return _attend_heads(
                        q, k_buf[buf, sub], v_buf[buf, sub],
                        None if ks_buf is None else ks_buf[buf, sub],
                        None if vs_buf is None else vs_buf[buf, sub],
                        mask, *state)
                state = jax.lax.cond(visible, attend, lambda s: s, state)
            return state

        _, l, acc = jax.lax.fori_loop(0, n_blocks, block, state)
        o_ref[b] = (acc / l).astype(o_ref.dtype)       # l >= 1 (self column)
        return walked + n_blocks

    jax.lax.fori_loop(0, B, slot, 0)


def _check_pages_per_block(ppb: int, NP: int, P: int) -> None:
    """Static geometry gate for the multi-page kernels: the table width and
    the pool's page count must both split into whole runs. The SEMANTIC
    requirement — every aligned group of ``ppb`` logical pages maps to an
    aligned contiguous run of physical pages (``pt[b, g·ppb+i] ==
    pt[b, g·ppb] + i`` with ``pt[b, g·ppb] % ppb == 0``) — is the
    caller's promise; the engine's superpage-packing allocator
    (engine/paged.py ``pages_per_block``) is the one producer that
    guarantees it, and the engine falls back to per-page blocks whenever
    it can't (SWA ring, non-divisible geometry)."""
    if ppb < 1:
        raise ValueError(f"pages_per_block must be >= 1, got {ppb}")
    if ppb > 1 and (NP % ppb or P % ppb):
        raise ValueError(
            f"pages_per_block={ppb} needs the page-table width ({NP}) and "
            f"the pool's page count ({P}) divisible by it")


def paged_decode_attention(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, k_pages, v_pages,
                           page_table: jax.Array,
                           n_stale: jax.Array, *,
                           layer: jax.Array | int = 0,
                           window: int = 0,
                           pages_per_block: int = 1,
                           interpret: bool | None = None) -> jax.Array:
    """Ragged single-token attention over the STALE page pool plus the new
    token (self column folded into the online-softmax init).

    q: [B, H, Dh] (RoPE applied); k_new/v_new: [B, KV, Dh];
    k_pages/v_pages: the layer-stacked pool ``[L, P, KV, page, Dh]`` (or
    the int8 ``{"q","s"}`` dicts) of which ``layer`` (a traced scalar: the
    layer scan's index) is read WHERE IT LIES — the pool operands stay in
    HBM and a block's copy names the layer, so a scan over layers hands
    the kernel no slice of the pool (a slice is a copy of a layer's whole
    side, paid for by the pool's CAPACITY every layer and step: PERF.md,
    PR 30). A rank-4 side ``[P, KV, page, Dh]`` is one layer (a free
    reshape, layer 0). page_table: [B, NP]; n_stale: [B] int32 (the
    query's position; 0 for a fresh slot). Returns [B, H*Dh].

    One Pallas call, grid ``(KV // heads,)``: a program holds every slot's
    q / k_new / v_new / out rows for ``heads`` KV heads in VMEM and walks
    the slots' LIVE blocks — for each slot the ``pages_per_block``-page
    runs between the first stale key in the window (``window``: the
    mistral family's bound; 0 = full) and the last stale key — copying
    each block ``(ppb, heads, page, Dh)`` from the HBM pool while it
    attends the one before (:func:`_paged_decode_kernel`). ``heads`` is as
    many of the local KV heads as fit the kernel's VMEM budget
    (:func:`_decode_heads_per_block` — a function of the shapes and dtypes
    seen here, nothing configured). The call's time is the live blocks'
    bytes plus a few microseconds a slot; nothing is paid per table entry
    or per dead block. ``pages_per_block`` > 1 requires a PACKED table
    (see :func:`_check_pages_per_block`). Numerics do not depend on
    pages_per_block or on the head fold (per-page attends in page order,
    the heads a batch dimension): bit-for-bit where the backend emits the
    same dot for any batch, as the chip does.
    """
    B, H, Dh = q.shape
    quant = isinstance(k_pages, dict)
    if (k_pages["q"] if quant else k_pages).ndim == 4:
        k_pages, v_pages = jax.tree.map(lambda x: x[None],
                                        (k_pages, v_pages))
    kq = k_pages["q"] if quant else k_pages
    KV, page = kq.shape[2], kq.shape[3]
    NP = page_table.shape[1]
    ppb = pages_per_block
    _check_pages_per_block(ppb, NP, kq.shape[1])
    G = H // KV
    heads = _decode_heads_per_block(KV, page, Dh, kq.dtype.itemsize, quant,
                                    ppb)

    def rows(width):
        return pl.BlockSpec((B, heads, width, Dh),
                            lambda hb, pt, nv, layer: (0, hb, 0, 0))

    kv_operands, buffers = _walk_buffers(k_pages, v_pages, ppb, heads)

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, page=page, window=window,
                          pages_per_block=ppb, n_table_blocks=NP // ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(KV // heads,),
            in_specs=[rows(G), rows(1), rows(1),
                      *[pl.BlockSpec(memory_space=pl.ANY)] * len(kv_operands)],
            out_specs=rows(G),
            scratch_shapes=[*buffers,
                            pltpu.SemaphoreType.DMA((2, len(kv_operands)))],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(page_table.astype(jnp.int32), n_stale.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.reshape(B, KV, G, Dh), k_new[:, :, None, :], v_new[:, :, None, :],
      *kv_operands)
    return out.reshape(B, H * Dh)


# ---------------------------------------------------------------------------
# Prefill kernel: q [B, T, H, Dh] vs pages, causal from per-slot start
# ---------------------------------------------------------------------------

# The paged prefill kernel's block: the query rows of ONE folded KV head
# (``G x bt``) and of all a program's heads that meet a page in one pass,
# and what the program may take of VMEM by :func:`_prefill_vmem_bytes`'
# count, which is generous (a v5e kernel is lent 16 MiB unless it asks:
# the call asks for ``_PREFILL_VMEM_LIMIT_BYTES``). A program holds ONE
# row-block's q and out (and the next in flight: the grid's last axis is
# the row-block, so the pipeline moves them), its pages and its state —
# nothing by the row, so the count does not grow with ``T`` and the fold
# at 16 query heads a KV head gets two heads like every other (a whole
# row's q and out would be 8 MiB a head there; PERF.md, PR 48).
# From two sweeps on the chip over row-blocks of 32-512 positions and 1-8
# heads at the served folds of 4, 7 and 8 (PERF.md, PR 37): one KV head a
# program is 1.4-1.6x slower than two at any row-block (inferred: a lone
# head's dots and vector passes wait for each other, a second head's fill
# the gaps); two heads of ~512 rows are within a tenth of the best shape
# found at every fold; and the compiler unrolls a pass into one
# instruction a vector register, so a kernel's COMPILE follows the block
# (3.2-3.9 s at 4 096 rows against 0.3-0.5 at 1 024: a checkout's first
# set-up, once a prefill program). A warm set-up does not see the block:
# there the kernel costs its Python trace (see _scaled_scores).
_PREFILL_HEAD_ROWS = 512
_PREFILL_BLOCK_ROWS = 1024
_PREFILL_VMEM_BYTES = 16 * 2 ** 20
_PREFILL_VMEM_LIMIT_BYTES = 32 * 2 ** 20


def _prefill_vmem_bytes(bt: int, heads: int, G: int, page: int, Dh: int,
                        q_itemsize: int, kv_itemsize: int, quant: bool,
                        ppb: int) -> int:
    """What a program of the paged prefill kernel holds in VMEM with
    ``bt`` query positions a row-block and ``heads`` KV heads folded:
    the row-block's state and temporaries, its q and out blocks (two
    buffers each — this row-block's and the next one's, in flight) and
    the pages' buffers. Nothing is held by the row, so the chunk's
    length is no argument."""
    rows = heads * G * bt
    # Scores, their exponentials and the scaled probabilities (float32
    # [rows, page] each); the accumulator; m and l (a lane-padded column
    # each).
    body = rows * (3 * page * 4 + Dh * 4 + 2 * 128 * 4)
    q_and_out = 2 * 2 * rows * Dh * q_itemsize
    # K and V, two buffers each, and a page of each converted for the dots.
    pages = heads * (2 * 2 * _kv_block_bytes(page, Dh, kv_itemsize, quant,
                                             ppb)
                     + page * Dh * (q_itemsize + 4))
    return body + q_and_out + pages


def prefill_block_shape(T: int, G: int, KV: int, page: int, Dh: int,
                        q_itemsize: int, kv_itemsize: int, quant: bool,
                        ppb: int, block_t: int | None = None
                        ) -> tuple[int, int]:
    """(query positions a row-block, KV heads a program) of the paged
    prefill kernel — pure shape arithmetic over what the call sees. A
    block is ``heads x G x bt`` query rows against one copy of each
    head's page. ``bt`` is the largest power-of-two divisor of ``T``
    that keeps a head's ``G x bt`` rows within ``_PREFILL_HEAD_ROWS`` (a
    caller's ``block_t`` is taken as given), ``heads`` the largest
    divisor of the local ``KV`` that keeps the block within
    ``_PREFILL_BLOCK_ROWS`` — the small buckets fold every head — and
    both give way, ``bt`` down to eight positions and ``heads`` to one,
    until the program fits ``_PREFILL_VMEM_BYTES``."""
    def fits(bt, heads):
        return _prefill_vmem_bytes(bt, heads, G, page, Dh, q_itemsize,
                                   kv_itemsize, quant, ppb
                                   ) <= _PREFILL_VMEM_BYTES
    if block_t is None:
        block_t = T & -T
        while block_t > 8 and not (G * block_t <= _PREFILL_HEAD_ROWS
                                   and fits(block_t, 1)):
            block_t //= 2
    heads = max([d for d in range(1, KV + 1)
                 if KV % d == 0 and fits(block_t, d)
                 and d * G * block_t <= _PREFILL_BLOCK_ROWS], default=1)
    return block_t, heads


def _prefill_live_blocks(first_q, bt: int, bs: int, window: int,
                         n_table_blocks: int, xp=jnp):
    """(first, last) live BLOCK (run of ``bs`` tokens) for the ``bt``
    queries from position ``first_q``: causal upper bound (the block of
    the last query's own key, clamped into the table), window lower bound
    (the block of the first key the FIRST query sees) — every key any of
    the queries can see lies between them, and ``first <= last`` always.
    ``xp``: ``jnp`` in the kernel, ``numpy`` where the engine counts the
    walk on the host (:func:`prefill_pages_walked`)."""
    last = xp.minimum((first_q + bt - 1) // bs, n_table_blocks - 1)
    if window:
        first = xp.minimum(xp.maximum(first_q - (window - 1), 0) // bs, last)
    else:
        first = last * 0
    return first, last


def prefill_pages_walked(starts, T: int, bt: int, page: int, window: int,
                         n_table_pages: int, ppb: int = 1) -> tuple[int, int]:
    """(pages walked, table entries) of one paged prefill call over rows
    that start at ``starts``: summed over rows and their ``T // bt``
    row-blocks, the pages between the first and the last live block of
    :func:`_prefill_live_blocks` — what the kernel copies and attends a
    KV head — and the table's width, which is what a grid with a page
    axis stepped through. Host integer arithmetic (numpy)."""
    import numpy as np
    first_q = (np.asarray(starts, np.int64)[:, None]
               + np.arange(T // bt, dtype=np.int64)[None, :] * bt)
    first, last = _prefill_live_blocks(first_q, bt, ppb * page, window,
                                       n_table_pages // ppb, xp=np)
    return int((last - first + 1).sum()) * ppb, first_q.size * n_table_pages


def _paged_prefill_kernel(pt_ref, start_ref, layer_ref, q_ref, *refs,
                          block_t: int, page: int, window: int,
                          pages_per_block: int, n_table_blocks: int,
                          has_keep: bool = False):
    """Program ``(row b, group of folded KV heads, row-block t)``: walk the
    blocks of pages the row-block's ``block_t`` queries can see
    (:func:`_prefill_live_blocks`) and only those. The pools stay in HBM;
    a block — a run of pages for the program's heads — is copied into one
    of two VMEM buffers while the block before it is attended, and the
    walk is one sequence over (row-block, block): the grid runs a row's
    row-blocks in turn, a row-block's last block prefetches the next
    row-block's first, and how many blocks the row has walked (which
    buffer the next one lands in) is carried in SMEM from one row-block
    to the next. ``q_ref``/``o_ref``: ``[1, 1, heads, G * block_t, Dh]``,
    THIS row-block's — the pipeline fetches the next one's q and writes
    the last one's out meanwhile — a head's ``G`` query heads folded into
    the rows (row ``g * block_t + i`` is query head ``g`` at position
    ``first_q + i``). ``refs``: the LAYER-STACKED pool sides in HBM (K, V;
    int8: K, its scale plane, V, its scale plane), of which only layer
    ``layer_ref[0]`` is read, the output block, a VMEM buffer pair per
    pool side in the same order, the online-softmax state (m, l, acc),
    the walk's count and the DMA semaphores ``[buffer, side]``. Under
    ``has_keep`` the first of ``refs`` is the row-block's SELECTION, int8
    ``[1, 1, block_t, table positions]``: a score stays where it is not 0
    and nowhere else (ops/sparse_attention.py: it holds the causal bound),
    so every visible page is masked by it."""
    if has_keep:
        keep_ref, refs = refs[0], refs[1:]
    n_sides = (len(refs) - 6) // 2       # K, V (int8: + their scale planes)
    pools, o_ref = refs[:n_sides], refs[n_sides]
    bufs = refs[n_sides + 1:2 * n_sides + 1]
    m_ref, l_ref, acc_ref, walked_ref, sem = refs[2 * n_sides + 1:]
    if n_sides == 4:
        k_buf, ks_buf, v_buf, vs_buf = bufs
    else:
        (k_buf, v_buf), ks_buf, vs_buf = bufs, None, None
    b, hb, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_row_blocks, heads = pl.num_programs(2), q_ref.shape[2]
    bt, ppb = block_t, pages_per_block
    layer, start = layer_ref[0], start_ref[b]

    def live(t):
        first_q = start + t * bt
        first, last = _prefill_live_blocks(first_q, bt, ppb * page, window,
                                           n_table_blocks)
        return first_q, first, last - first + 1        # >= 1 block

    def copies(blk, buf):
        # One table lookup a block: the packed-table promise makes a
        # run's ppb physical pages contiguous from its first (see
        # _paged_decode_kernel).
        p0 = pt_ref[b, blk * ppb]
        return [pltpu.make_async_copy(
            pool.at[layer, pl.ds(p0, ppb), pl.ds(hb * heads, heads)],
            vmem.at[buf], sem.at[buf, side])
            for side, (pool, vmem) in enumerate(zip(pools, bufs))]

    first_q, first, n_blocks = live(t)
    last_q = first_q + (bt - 1)

    @pl.when(t == 0)
    def _first_of_the_row():
        walked_ref[0] = 0
        for c in copies(first, 0):
            c.start()
    walked = walked_ref[0]
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(buf, sub, mask):
        m, l, acc = _attend_heads(
            q_ref[0, 0], k_buf[buf, sub], v_buf[buf, sub],
            None if ks_buf is None else ks_buf[buf, sub],
            None if vs_buf is None else vs_buf[buf, sub],
            mask, m_ref[...], l_ref[...], acc_ref[...])
        m_ref[...], l_ref[...], acc_ref[...] = m, l, acc

    def block(i, carry):
        buf = (walked + i) % 2
        # Start the NEXT block of the walk — this row-block's, or the
        # next one's first — into the other buffer, then wait for this
        # one.
        ends = i == n_blocks - 1
        nblk = jnp.where(
            ends, live(jnp.minimum(t + 1, n_row_blocks - 1))[1],
            first + i + 1)

        @pl.when(jnp.logical_not(ends & (t == n_row_blocks - 1)))
        def _prefetch():
            for c in copies(nblk, 1 - buf):
                c.start()
        for c in copies(first + i, buf):
            c.wait()
        # Per-page attends over the block's sub-pages, unrolled
        # (pages_per_block is compile-time), in ascending logical order:
        # a row's updates are the per-page, per-head kernel's whatever
        # the block shape. A page none of the queries sees (a run's
        # tail, a run's head below the window) is skipped.
        for sub in range(ppb):
            lo = ((first + i) * ppb + sub) * page      # the page's first key
            hi = lo + (page - 1)                       # ... and its last
            visible = lo <= last_q
            # Below the diagonal of EVERY row (and inside every row's
            # window): the mask would select every score, so none is
            # built — only the diagonal pages and the window's floor
            # pages pay for iota, compare and select.
            whole = hi <= first_q
            if window:
                visible = visible & (hi > first_q - window)
                whole = whole & (lo > last_q - window)

            def mask(scores, lo=lo):
                row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
                q_pos = first_q + (row & (bt - 1) if bt & (bt - 1) == 0
                                   else row % bt)
                s_pos = lo + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 2)
                ok = s_pos <= q_pos
                if window:
                    ok = ok & (s_pos > q_pos - window)
                return jnp.where(ok, scores, NEG_INF)

            if has_keep:
                def selected(scores, lo=lo):
                    kept = keep_ref[0, 0, :, pl.ds(pl.multiple_of(lo, page),
                                                   page)].astype(jnp.int32)
                    kept = jnp.concatenate(
                        [kept] * (scores.shape[1] // bt), axis=0)
                    return jnp.where((kept != 0)[None], scores, NEG_INF)

                @pl.when(visible)
                def _kept(sub=sub, selected=selected):
                    attend(buf, sub, selected)
                continue

            @pl.when(visible & whole)
            def _whole(sub=sub):
                attend(buf, sub, None)

            @pl.when(visible & jnp.logical_not(whole))
            def _edge(sub=sub, mask=mask):
                attend(buf, sub, mask)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)
    l = l_ref[...]
    o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                   ).astype(o_ref.dtype)
    walked_ref[0] = walked + n_blocks


def paged_prefill_attention(q: jax.Array, k_pages, v_pages,
                            page_table: jax.Array,
                            start: jax.Array, *,
                            layer: jax.Array | int = 0,
                            block_t: int | None = None,
                            window: int = 0,
                            pages_per_block: int = 1,
                            keep: jax.Array | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Causal chunk attention over the page pool (keys already inserted).

    q: [B, T, H, Dh] at absolute positions ``start + t``;
    k_pages/v_pages: the layer-stacked pool ``[L, P, KV, page, Dh]`` (or
    the int8 ``{"q","s"}`` dicts) of which ``layer`` (a traced scalar: the
    layer scan's index) is read WHERE IT LIES — the pool operands stay in
    HBM and a block's copy names the layer, so a scan over layers hands
    the kernel no slice of the pool (:func:`paged_decode_attention` says
    what a slice costs). A rank-4 side ``[P, KV, page, Dh]`` is one layer
    (a free reshape, layer 0). page_table: [B, NP]; start: [B].
    ``window``: sliding-window bound (0 = full causal). Returns
    [B, T, H*Dh].

    One Pallas call, grid ``(B, KV // heads, T // bt)`` — no page axis and
    no query-head axis. A program holds ONE row-block's q and out for
    ``heads`` KV heads (the next row-block's in flight), each head's
    ``G = H // KV`` query heads folded into the rows of the block, and
    for its ``bt`` query positions walks
    the LIVE blocks of pages (:func:`_prefill_live_blocks`: up to the
    last query's own key, from the first key the first query's window
    holds), copying each block ``(ppb, heads, page, Dh)`` from the HBM
    pool while it attends the one before (:func:`_paged_prefill_kernel`):
    a page is fetched once a KV head a row-block and meets ``G x bt``
    query rows in one dot, and only the pages on a row-block's diagonal
    or at its window's floor are masked. ``bt`` and ``heads`` are
    arithmetic over the shapes and dtypes seen here
    (:func:`prefill_block_shape`); ``block_t`` overrides ``bt`` (the
    tests' way to compare block shapes). The call's time is the visible
    (query, key) pairs' arithmetic — the float32 probabilities-times-V
    dot first, then the vector passes over the score tile — plus a few
    microseconds a program; nothing is paid per table entry or per dead
    page. ``pages_per_block`` > 1 requires a PACKED table (see
    :func:`_check_pages_per_block`). A row's result does not depend on
    ``bt``, ``heads`` or ``pages_per_block`` (per-page updates in page
    order; a page a row sees nothing of leaves its state as it was).

    ``keep`` (bool [B, T, NP * page]; None: every visible key): the keys
    each query attends, a selection that holds the causal bound and at
    least one key a query (ops/sparse_attention.py). An OPTIONAL operand:
    a call without it lowers to the text it lowered to before there was
    one. With it the walk is the same and every visible page is masked by
    the selection's int8 block ``[bt, NP * page]`` of the row-block.
    """
    B, T, H, Dh = q.shape
    quant = isinstance(k_pages, dict)
    if (k_pages["q"] if quant else k_pages).ndim == 4:
        k_pages, v_pages = jax.tree.map(lambda x: x[None],
                                        (k_pages, v_pages))
    kq = k_pages["q"] if quant else k_pages
    KV, page = kq.shape[2], kq.shape[3]
    NP = page_table.shape[1]
    ppb = pages_per_block
    _check_pages_per_block(ppb, NP, kq.shape[1])
    G = H // KV
    if block_t is not None:
        block_t = min(block_t, T)
        if T % block_t:
            raise ValueError(f"T={T} not a multiple of block_t={block_t}")
    bt, heads = prefill_block_shape(T, G, KV, page, Dh, q.dtype.itemsize,
                                    kq.dtype.itemsize, quant, ppb, block_t)
    nT, rows = T // bt, G * bt
    # [B, T, H, Dh] -> [B, nT, KV, G * bt, Dh]: a KV head's query heads
    # side by side in a block's rows.
    qb = q.reshape(B, nT, bt, KV, G, Dh).transpose(0, 1, 3, 4, 2, 5
                                                   ).reshape(B, nT, KV, rows, Dh)

    q_spec = pl.BlockSpec((1, 1, heads, rows, Dh),
                          lambda b, hb, t, pt, st, layer: (b, t, hb, 0, 0))
    kv_operands, buffers = _walk_buffers(k_pages, v_pages, ppb, heads)
    kept, kept_specs, kept_arg = (), [], {}
    if keep is not None:
        if window or ppb != 1:
            raise ValueError("a selection masks a whole-context walk of "
                             "single pages")
        kept = (keep.astype(jnp.int8).reshape(B, nT, bt, NP * page),)
        kept_specs = [pl.BlockSpec(
            (1, 1, bt, NP * page),
            lambda b, hb, t, pt, st, layer: (b, t, 0, 0))]
        kept_arg = {"has_keep": True}

    out = pl.pallas_call(
        functools.partial(_paged_prefill_kernel, block_t=bt, page=page,
                          window=window, pages_per_block=ppb,
                          n_table_blocks=NP // ppb, **kept_arg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV // heads, nT),
            in_specs=[q_spec, *kept_specs,
                      *[pl.BlockSpec(memory_space=pl.ANY)] * len(kv_operands)],
            out_specs=q_spec,
            scratch_shapes=[*buffers,
                            pltpu.VMEM((heads, rows, 1), jnp.float32),
                            pltpu.VMEM((heads, rows, 1), jnp.float32),
                            pltpu.VMEM((heads, rows, Dh), jnp.float32),
                            pltpu.SMEM((1,), jnp.int32),
                            pltpu.SemaphoreType.DMA((2, len(kv_operands)))],
        ),
        out_shape=jax.ShapeDtypeStruct(qb.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM_LIMIT_BYTES),
        interpret=_interpret_default() if interpret is None else interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qb, *kept, *kv_operands)
    return out.reshape(B, nT, KV, G, bt, Dh).transpose(0, 1, 4, 2, 3, 5
                                                       ).reshape(B, T, H * Dh)


# ---------------------------------------------------------------------------
# Reference jnp path (CPU tests / non-TPU backends) + attention_fn adapter
# ---------------------------------------------------------------------------

def gather_pages(layer_pages, page_table: jax.Array, max_seq: int):
    """Materialize the dense [B, KV, S(, Dh)] view from the pool —
    reference path only. Dict pools gather per leaf; the rank-4
    [P, KV, 1, page] scale plane gathers through its squeezed rank-3
    view and comes back rank-4 [B, KV, 1, S] (the dense stored form)."""
    if isinstance(layer_pages, dict):
        s = gather_pages(layer_pages["s"][:, :, 0, :], page_table, max_seq)
        return {"q": gather_pages(layer_pages["q"], page_table, max_seq),
                "s": s[:, :, None, :]}
    KV, page = layer_pages.shape[1], layer_pages.shape[2]
    NP = page_table.shape[1]
    n_pages = min(NP, (max_seq + page - 1) // page)
    picked = layer_pages[page_table[:, :n_pages]]     # [B, n, KV, page(,Dh)]
    picked = jnp.moveaxis(picked, 1, 2)               # [B, KV, n, page(,Dh)]
    seq = picked.reshape(page_table.shape[0], KV, n_pages * page,
                         *picked.shape[4:])
    return seq[:, :, :max_seq]


def dequant_gathered(d, dtype):
    """Gathered pool dict → dense float view (reference paths only; the
    Pallas kernels consume the int8 pool + scales directly). The gathered
    scale is rank-4 [B, KV, 1, S] (gather_pages owns that form); swapping
    its trailing dims broadcasts it against the [B, KV, S, Dh] values.
    THE one copy of the int8-KV dequant — the per-mesh adapters share it."""
    if isinstance(d, dict):
        return d["q"].astype(dtype) * jnp.swapaxes(
            d["s"], -1, -2).astype(dtype)
    return d


def _paged_reference_core(q, dense_k, dense_v, lengths, active, T,
                          window: int = 0):
    """Dense attention over a gathered view WITHOUT re-inserting."""
    B, H = q.shape[0], q.shape[2]
    KV, S = dense_k.shape[1], dense_k.shape[2]
    Dh = q.shape[3]
    group = H // KV
    k_all = jnp.repeat(dense_k, group, axis=1)
    v_all = jnp.repeat(dense_v, group, axis=1)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bthd,bhsd->bhts", qf, k_all.astype(jnp.float32))
    scores = scores / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    q_pos = lengths[:, None] + jnp.arange(T)[None, :]
    s_idx = jnp.arange(S)[None, None, :]
    visible = s_idx <= q_pos[:, :, None]
    if window:
        # HF Mistral semantics: key s visible to query i iff i - s < window.
        visible = visible & (s_idx > q_pos[:, :, None] - window)
    if active is not None:
        visible = visible & active[:, None, None]
    scores = jnp.where(visible[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhts,bhsd->bthd", probs, v_all.astype(jnp.float32))
    return out.reshape(B, T, H * Dh).astype(q.dtype)


def pool_in_place(impl: str, mesh=None) -> bool:
    """Whether :func:`make_paged_attention_fn` builds the paths that leave
    the page pool where it lies — ``.decode_at`` and ``.prefill_at``
    reading the stacked pool by layer, ``.insert_all`` and ``.prefill_at``
    writing it through aliased operands. The kernels on one device do;
    the reference path gathers, and under a mesh the pool's per-layer
    slices stay (the write kernels have no shard_map wrapper yet). The
    speculative provider's ``.verify`` defers its insert and keeps the
    slices too: the forwards take it before ``.prefill_at``."""
    return impl == "pallas" and mesh is None


def make_paged_attention_fn(page_table: jax.Array, max_seq: int,
                            impl: str = "pallas",
                            block_t: int | None = None,
                            interpret: bool | None = None,
                            mesh=None, window: int = 0,
                            pages_per_block: int = 1,
                            spec: bool = False):
    """Build an ``attention_fn`` (llama.forward contract) over a paged cache.

    Constructed INSIDE the engine's jitted step function, closing over the
    traced ``page_table`` — so the model forward signature is unchanged and
    ``layer_k``/``layer_v`` are the per-layer page pools from the scanned
    ``PagedKVCache``. ``impl``: "pallas" (kernels) or "reference" (gather +
    dense jnp — exact but materializes [B, S]; CPU tests).
    ``pages_per_block``: multi-page kernel blocking (pallas impl only;
    the reference path gathers densely and ignores it) — requires the
    engine's superpage-packed allocator behind the table.

    With a multi-device ``mesh`` the kernels run under ``shard_map`` —
    pages are sharded on their KV-head dim over ``model``, the page table
    is replicated (it indexes the pool's unsharded page dim), and the
    insert scatter stays in XLA/GSPMD. The map is manual over EVERY mesh
    axis, not just ``model``: the chip's compiler refuses a Mosaic kernel
    under a partially-manual map, even when the other axes have size 1
    (interpret mode never reaches that check). The pool has no batch dim,
    so the operands are simply replicated along the other axes.
    """
    from jax.sharding import PartitionSpec as P

    msize = mesh.shape.get("model", 1) if mesh is not None else 1
    in_place = pool_in_place(impl, mesh)

    _dequant_dense = dequant_gathered

    def _pool_spec(side):
        """Per-leaf shard_map spec for a per-layer pool side: the int8
        scale plane is rank-4 [P, KV, 1, page] (head dim shards like the
        value's; the trailing (1, page) dims stay whole)."""
        val = P(None, "model", None, None)
        if isinstance(side, dict):
            return {"q": val, "s": P(None, "model", None, None)}
        return val

    def attention_fn(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        # Phase marker (ISSUE 8): trace-time metadata so captures name
        # the paged kernels inside the layer's attention scope.
        with jax.named_scope("attention.paged_prefill"):
            return _attention_fn(q, k_new, v_new, layer_k, layer_v,
                                 lengths, active)

    def _attention_fn(q, k_new, v_new, layer_k, layer_v, lengths,
                      active=None):
        B, T, H, Dh = q.shape
        quant = isinstance(layer_k, dict)
        KV = (layer_k["q"] if quant else layer_k).shape[1]
        layer_k, layer_v = paged_insert_kv(layer_k, layer_v, k_new, v_new,
                                           page_table, lengths, active)
        if impl == "reference":
            dense_k = _dequant_dense(
                gather_pages(layer_k, page_table, max_seq), q.dtype)
            dense_v = _dequant_dense(
                gather_pages(layer_v, page_table, max_seq), q.dtype)
            out = _paged_reference_core(q, dense_k, dense_v, lengths,
                                        active, T, window=window)
            return out, layer_k, layer_v
        shard = msize > 1 and KV % msize == 0 and H % msize == 0
        pool = _pool_spec(layer_k)
        if shard:
            f = shard_map(
                lambda q_, k_, v_, pt_, st_: paged_prefill_attention(
                    q_, k_, v_, pt_, st_, block_t=block_t, window=window,
                    pages_per_block=pages_per_block, interpret=interpret),
                mesh=mesh,
                in_specs=(P(None, None, "model", None), pool, pool,
                          P(None, None), P(None)),
                out_specs=P(None, None, "model"), check_vma=False)
            out = f(q, layer_k, layer_v, page_table, lengths)
        else:
            out = paged_prefill_attention(
                q, layer_k, layer_v, page_table, lengths,
                block_t=block_t, window=window,
                pages_per_block=pages_per_block, interpret=interpret)
        return out, layer_k, layer_v

    def prefill_at(q, k_new, v_new, pool_k, pool_v, layer, lengths,
                   active=None):
        """``attention_fn`` over the layer-STACKED pool, which the layer
        scan carries: the chunk's rows are written into layer ``layer``
        (the scan's traced index) where the pool lies, and the written
        pool is attended at that index — insert-then-attend, the chunk's
        own keys read back as the bytes that were written, so the pool
        and every token are the sliced path's. Returns (attn, pool_k,
        pool_v), the pool whole."""
        # The write keeps the scope PR 30's carries, outside
        # ``attention.paged_prefill``: ONE attention custom call a layer.
        with jax.named_scope("kv.paged_insert"):
            pool_k, pool_v = paged_insert_chunk_in_place(
                pool_k, pool_v, k_new, v_new, page_table, lengths, active,
                layer=layer, interpret=interpret)
        with jax.named_scope("attention.paged_prefill"):
            out = paged_prefill_attention(
                q, pool_k, pool_v, page_table, lengths, layer=layer,
                block_t=block_t, window=window,
                pages_per_block=pages_per_block, interpret=interpret)
        return out, pool_k, pool_v

    def decode(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        """Deferred-decode: stale pool + self column, no insert."""
        with jax.named_scope("attention.paged_decode"):
            return _decode(q, k_new, v_new, layer_k, layer_v, lengths,
                           active)

    def decode_at(q, k_new, v_new, pool_k, pool_v, layer, lengths,
                  active=None):
        """:func:`decode` over the layer-STACKED pool: layer ``layer`` (the
        layer scan's traced index) is read where it lies, so the scan
        keeps the pool out of its inputs and slices nothing."""
        with jax.named_scope("attention.paged_decode"):
            return _decode(q, k_new, v_new, pool_k, pool_v, lengths,
                           active, layer=layer)

    def _decode(q, k_new, v_new, layer_k, layer_v, lengths, active=None,
                layer=0):
        B, T, H, Dh = q.shape
        quant = isinstance(layer_k, dict)
        KV = (layer_k["q"] if quant else layer_k).shape[-3]
        n_stale = lengths if active is None else jnp.where(active, lengths, 0)
        if impl == "reference":
            # dense_decode_attention is dict-aware: the gathered int8
            # view + scales pass through un-dequantized.
            from ..models.llama import dense_decode_attention
            dense_k = gather_pages(layer_k, page_table, max_seq)
            dense_v = gather_pages(layer_v, page_table, max_seq)
            return dense_decode_attention(q, k_new, v_new, dense_k, dense_v,
                                          n_stale, None, window=window)
        shard = msize > 1 and KV % msize == 0 and H % msize == 0
        pool = _pool_spec(layer_k)
        if shard:
            f = shard_map(
                lambda q_, kn_, vn_, k_, v_, pt_, nv_: paged_decode_attention(
                    q_, kn_, vn_, k_, v_, pt_, nv_, window=window,
                    pages_per_block=pages_per_block, interpret=interpret),
                mesh=mesh,
                in_specs=(P(None, "model", None), P(None, "model", None),
                          P(None, "model", None), pool, pool,
                          P(None, None), P(None)),
                out_specs=P(None, "model"), check_vma=False)
            out = f(q[:, 0], k_new[:, 0], v_new[:, 0], layer_k, layer_v,
                    page_table, n_stale)
        else:
            out = paged_decode_attention(
                q[:, 0], k_new[:, 0], v_new[:, 0], layer_k, layer_v,
                page_table, n_stale, layer=layer, window=window,
                pages_per_block=pages_per_block, interpret=interpret)
        return out[:, None, :]

    def verify(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        """Deferred speculative verify: T = k+1 draft tokens attend the
        STALE pool (gathered to a dense per-slot view) plus the causal
        self-block, no pool write inside the layer scan — the insert
        happens once via ``insert_all`` (T-generalized). Two wins over
        the chunk path it replaces: (1) exact-greedy parity under int8 —
        dense_verify_attention's mixed-precision self-block reads
        off-diagonal drafts quantize→dequantized and the diagonal at
        full precision, matching what plain decode sees, where the chunk
        path reads even the SELF token quantized; (2) no per-layer pool
        scatters through the spec burst scan (2·L serialized scatters
        per verify step — the same cost insert_kv_stacked's dense twin
        eliminates). The gather materializes [B, KV, max_seq, Dh] —
        bounded by CONTEXT, not pool capacity, i.e. the same bytes one
        decode step's attention streams anyway, amortized over k+1
        positions."""
        with jax.named_scope("attention.paged_verify"):
            from ..models.llama import dense_verify_attention
            n_stale = (lengths if active is None
                       else jnp.where(active, lengths, 0))
            dense_k = gather_pages(layer_k, page_table, max_seq)
            dense_v = gather_pages(layer_v, page_table, max_seq)
            return dense_verify_attention(q, k_new, v_new, dense_k,
                                          dense_v, n_stale, None,
                                          window=window)

    def insert_all(pool_k, pool_v, k_news, v_news, lengths, active):
        if in_place:
            # A scope of its own, and none of benchmark/xplane.py SCOPES:
            # ``attention.paged_decode`` must keep ONE custom call a paged
            # layer a step (step.decode_ms counts steps by them).
            with jax.named_scope("kv.paged_insert"):
                return paged_insert_in_place(
                    pool_k, pool_v, k_news, v_news, page_table, lengths,
                    active, interpret=interpret)
        return paged_insert_all(pool_k, pool_v, k_news, v_news,
                                page_table, lengths, active)

    def write_at(k_new, v_new, pool_k, pool_v, layer, lengths, active=None):
        """A chunk's rows into layer ``layer`` of the stacked pool and
        NOTHING attended: a layer whose K/V other layers read (a cross
        decoder's full-context layer, models/sambay.py) writes every row
        of a chunk and attends from one. Returns the pool whole."""
        with jax.named_scope("kv.paged_insert"):
            if in_place:
                return paged_insert_chunk_in_place(
                    pool_k, pool_v, k_new, v_new, page_table, lengths,
                    active, layer=layer, interpret=interpret)
            side = jax.tree.map(lambda a: a[layer], (pool_k, pool_v))
            side = paged_insert_kv(*side, k_new, v_new, page_table, lengths,
                                   active)
            return jax.tree.map(lambda a, new: a.at[layer].set(new),
                                (pool_k, pool_v), side)

    attention_fn.decode = decode
    attention_fn.insert_all = insert_all
    attention_fn.write_at = write_at
    if in_place:
        attention_fn.decode_at = decode_at
        attention_fn.prefill_at = prefill_at
    if spec:
        # Spec-only provider: a `.verify` on the SHARED provider would
        # reroute every prefill chunk (T > 1) through the deferred path;
        # the engine builds a dedicated instance for spec bursts.
        attention_fn.verify = verify
    return attention_fn
