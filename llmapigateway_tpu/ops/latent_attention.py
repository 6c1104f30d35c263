"""Latent attention over ONE page pool: the cache of a latent-attention
(MLA) layer, its in-place row write and the kernel that attends it.

A latent layer caches, per token, the normed key/value latent ``c`` (``r``
numbers) and ONE rotated key ``k_r`` (``d_r`` numbers) that all heads share
— ``W = r + d_r`` numbers where the expanded K and V of ``H`` heads would
be ``H (d_nope + d_r + d_v)``. In the ABSORBED form of the attention the
up-projection of the keys is multiplied into the queries and that of the
values into the outputs, so every head attends the SAME ``W``-wide key
whose first ``r`` numbers are also the value: multi-query attention over
one key head, and each cached byte is read once.

Layout: ``[L, P, W, page]`` — a page is stored TRANSPOSED, the token axis
in the lanes. ``W`` = 320 is no multiple of the 128 lanes a tile has, so
token-major pages ``[page, W]`` would be padded to 384 columns in HBM and
in every copy (a fifth of the pool and of the bandwidth); transposed, 320
sublane rows by a 256-lane page tile exactly, the score product
``q [rows, W] @ page [W, page]`` needs no transpose and the value product
contracts the lanes of both operands (``p [rows, page] · page[:r]``).
Physical page 0 is the trash page and ``page_table`` ``[B, NP]`` maps a
slot's logical pages, as in ops/paged_attention.py, whose positions
(:func:`_insert_positions`) and allocator (engine/paged.py) this shares.

The step programs carry the pool through their layer scan and leave it
where it lies (PR 30/34's protocol): :func:`latent_insert_in_place` writes
a call's new rows into layer ``layer`` through aliased operands, then
:func:`latent_paged_attention` reads that layer's pages by index —
insert-then-attend, for a prefill chunk and for a decode step alike (a
decode step is a chunk of one token: 32 query rows a slot).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _paged
from .paged_attention import NEG_INF, _insert_positions

# Lanes a write moves at a time: a whole lane tile where the page has one.
_INSERT_LANES = 128
# What the chip's compiler gives a kernel's scratch unasked, and the room
# the write asks for beside its patch buffers where they pass it.
_DEFAULT_VMEM_BYTES = 16 * 2 ** 20
_INSERT_VMEM_ROOM = 4 * 2 ** 20
# Query rows (positions x heads) a program of the attention kernel holds,
# the pages it copies a step (all in flight together, into one of two
# buffers), and what it may take of VMEM: q and out blocks twice over, the
# score tile three times in float32, the accumulator, m and l.
_BLOCK_ROWS = 2048
_PAGES_PER_STEP = 4
_VMEM_LIMIT_BYTES = 48 * 2 ** 20


def create_latent_pool(n_layers: int, num_pages: int, page_size: int,
                       width: int, dtype=jnp.bfloat16) -> jax.Array:
    """The zeroed pool ``[L, P, W, page]``."""
    return jnp.zeros((n_layers, num_pages, width, page_size), dtype)


# ---------------------------------------------------------------------------
# The write
# ---------------------------------------------------------------------------

def latent_insert(pool: jax.Array, new: jax.Array, page_table: jax.Array,
                  lengths: jax.Array, active: jax.Array | None, *,
                  layer: jax.Array | int = 0) -> jax.Array:
    """XLA scatter of ``new`` [K, T, W] at positions ``lengths + t`` of
    layer ``layer``: the reference path's write and the tests' oracle."""
    page = pool.shape[-1]
    phys, off = _insert_positions(page_table, lengths, active, new.shape[1],
                                  page)
    return pool.at[layer, phys, :, off].set(new.astype(pool.dtype))


def _latent_insert_kernel(layer_ref, start_ref, phys_ref, off_ref, new_ref,
                          _pool_in, pool_ref, buf, sem, *, K: int, T: int,
                          cols: int, n_tiles: int):
    """ONE program for the call's ``K`` slots: slot ``b``'s ``T`` new
    columns go into its pages as tiles of ``cols`` lanes. A tile the run
    covers wholly is copied HBM to HBM from the new values, which arrive
    cut into the pool's own tiles (``tile j`` holds the columns of
    absolute tile ``start // cols + j``); a tile covered in part — a
    slot's first, its last; a decode step's only one — is read, patched
    under a lane mask and written back. Every copy of a phase is started
    before any is waited for: a decode step's ``K`` read-patch-write
    rounds cost three copy latencies, not ``3 K``. ``buf``: [K, 2
    (first | last partial tile), 2 (pool's | new), W, cols]."""
    layer = layer_ref[0]

    def tile_cols(b, j):
        first = j * cols - start_ref[b] % cols      # the tile's first new col
        whole = (first >= 0) & (first + cols <= T)
        return first, whole, (first < T) & jnp.logical_not(whole)

    def in_pool(b, j):
        at = b * n_tiles + j
        off = pl.multiple_of(off_ref[at], cols)
        return pool_ref.at[layer, phys_ref[at], :, pl.ds(off, cols)]

    def whole_tile(b, j):
        return pltpu.make_async_copy(new_ref.at[b, j], in_pool(b, j),
                                     sem.at[b, 2, 0])

    def reads(b, j):
        which = jnp.minimum(j, 1)
        return [pltpu.make_async_copy(in_pool(b, j), buf.at[b, which, 0],
                                      sem.at[b, which, 0]),
                pltpu.make_async_copy(new_ref.at[b, j], buf.at[b, which, 1],
                                      sem.at[b, which, 1])]

    def back(b, j):
        which = jnp.minimum(j, 1)
        return pltpu.make_async_copy(buf.at[b, which, 0], in_pool(b, j),
                                     sem.at[b, which, 0])

    def each_tile(whole_fn, part_fn):
        def step(i, carry):
            b, j = i // n_tiles, i % n_tiles
            first, whole, part = tile_cols(b, j)
            if whole_fn is not None:
                pl.when(whole)(lambda: whole_fn(b, j))
            if part_fn is not None:
                pl.when(part)(lambda: part_fn(b, j, first))
            return carry
        jax.lax.fori_loop(0, K * n_tiles, step, 0)

    def start_reads(b, j, first):
        for c in reads(b, j):
            c.start()

    def patch(b, j, first):
        for c in reads(b, j):
            c.wait()
        which = jnp.minimum(j, 1)
        old = buf[b, which, 0].astype(jnp.float32)
        col = first + jax.lax.broadcasted_iota(jnp.int32, old.shape, 1)
        buf[b, which, 0] = jnp.where(
            (col >= 0) & (col < T), buf[b, which, 1].astype(jnp.float32),
            old).astype(buf.dtype)
        back(b, j).start()

    each_tile(lambda b, j: whole_tile(b, j).start(), start_reads)
    each_tile(None, patch)
    each_tile(lambda b, j: whole_tile(b, j).wait(),
              lambda b, j, first: back(b, j).wait())


def latent_insert_in_place(pool: jax.Array, new: jax.Array,
                           page_table: jax.Array, lengths: jax.Array,
                           active: jax.Array | None, *,
                           layer: jax.Array | int = 0,
                           interpret: bool | None = None) -> jax.Array:
    """:func:`latent_insert` as a Pallas call whose pool operand IS its
    output (``input_output_aliases``): the pool stays where it lies and a
    call writes ``K x T`` columns of one layer. Any start, any ``T``; the
    same positions and the same bytes off the trash page. Outside the
    kernel, in XLA on the call's own rows, each slot's rows are transposed
    and shifted to where they sit in their first tile, so that the kernel
    copies tiles and never columns."""
    _, _, W, page = pool.shape
    K, T = new.shape[:2]
    cols = min(_INSERT_LANES, page)
    if page % cols:
        raise ValueError(f"page size {page} is not a multiple of {cols}")
    n_tiles = -(-T // cols) + 1
    phys, off = _insert_positions(page_table, lengths, active, T, page)
    lengths = lengths.astype(jnp.int32)
    blank = jnp.zeros((W, n_tiles * cols), pool.dtype)
    new_t = jnp.swapaxes(new.astype(pool.dtype), 1, 2)          # [K, W, T]
    tiles = jnp.stack([
        jax.lax.dynamic_update_slice_in_dim(blank, new_t[b],
                                            lengths[b] % cols, axis=1)
        for b in range(K)]).reshape(K, W, n_tiles, cols).transpose(0, 2, 1, 3)

    def tile_of(x):
        """x [K, T] of the rows -> [K * n_tiles] of the tiles (a tile's
        columns share a page; a tile past the run is never looked at)."""
        t = jnp.arange(n_tiles, dtype=jnp.int32)[None, :] * cols \
            - (lengths % cols)[:, None]
        return jnp.take_along_axis(x, jnp.clip(t, 0, T - 1),
                                   axis=1).reshape(-1).astype(jnp.int32)

    scalars = (jnp.asarray(layer, jnp.int32).reshape(1), lengths,
               tile_of(phys), tile_of(off) // cols * cols)
    buf_bytes = K * 4 * W * cols * pool.dtype.itemsize
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_latent_insert_kernel, K=K, T=T, cols=cols,
                          n_tiles=n_tiles),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(1,),
            in_specs=[hbm, hbm], out_specs=hbm,
            scratch_shapes=[pltpu.VMEM((K, 2, 2, W, cols), pool.dtype),
                            pltpu.SemaphoreType.DMA((K, 3, 2))]),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={len(scalars) + 1: 0},
        # The patch buffers of a wide row and many slots (32 slots of 576:
        # 18 MiB) pass the compiler's default of 16 MiB a kernel.
        compiler_params=(pltpu.CompilerParams(
            vmem_limit_bytes=buf_bytes + _INSERT_VMEM_ROOM)
            if buf_bytes + _INSERT_VMEM_ROOM > _DEFAULT_VMEM_BYTES else None),
        interpret=_paged._interpret_default() if interpret is None else interpret,
    )(*scalars, tiles, pool)


# ---------------------------------------------------------------------------
# The attention kernel (absorbed form)
# ---------------------------------------------------------------------------

def _latent_attention_kernel(pt_ref, start_ref, layer_ref, q_ref, pool_ref,
                             o_ref, buf, m_ref, l_ref, acc_ref, sem, *,
                             block_t: int, heads: int, page: int, ppb: int,
                             value_width: int, n_table_pages: int):
    """Program ``(row b, row-block t)``: ``block_t`` query positions of all
    ``heads`` heads (row ``i * heads + h`` is head ``h`` at position
    ``first_q + i``) walk the pages up to the last query's own key, and
    only those. The pool stays in HBM; a step copies ``ppb`` pages into
    one of two VMEM buffers while the step before is attended. A page is
    ``[W, page]``: the score dot reads it as it lies and the value dot
    contracts its lanes with the probabilities' — the page's first
    ``value_width`` rows are the value. Only a page on the row-block's
    diagonal is masked."""
    b, t = pl.program_id(0), pl.program_id(1)
    bt = block_t
    layer = layer_ref[0]
    first_q = start_ref[b] + t * bt
    last_q = first_q + (bt - 1)
    n_live = jnp.minimum(last_q // page + 1, n_table_pages)
    n_steps = (n_live + ppb - 1) // ppb

    def copy(step, sub, slot):
        lp = jnp.minimum(step * ppb + sub, n_table_pages - 1)
        return pltpu.make_async_copy(pool_ref.at[layer, pt_ref[b, lp]],
                                     buf.at[slot, sub], sem.at[slot, sub])

    def start(step, slot):
        for sub in range(ppb):
            @pl.when(step * ppb + sub < n_live)
            def _start(sub=sub):
                copy(step, sub, slot).start()

    start(0, 0)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def attend(slot, sub, lo, masked: bool):
        q = q_ref[0, 0]                                     # [rows, W]
        keys = buf[slot, sub]                               # [W, page]
        scores = jnp.dot(q, keys, preferred_element_type=jnp.float32)
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 0)
            q_pos = first_q + (row // heads)
            s_pos = lo + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(s_pos <= q_pos, scores, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        e = jnp.exp(scores - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(e, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            e.astype(keys.dtype), keys[:value_width],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_steps)
        def _prefetch():
            start(i + 1, 1 - slot)
        for sub in range(ppb):
            lp = i * ppb + sub
            lo = lp * page
            live = lp < n_live
            # Below the diagonal of EVERY row: no mask is built.
            whole = lo + (page - 1) <= first_q

            @pl.when(live)
            def _wait(sub=sub):
                copy(i, sub, slot).wait()

            @pl.when(live & whole)
            def _whole(sub=sub, lo=lo):
                attend(slot, sub, lo, False)

            @pl.when(live & jnp.logical_not(whole))
            def _edge(sub=sub, lo=lo):
                attend(slot, sub, lo, True)
        return carry

    jax.lax.fori_loop(0, n_steps, step, 0)
    l = l_ref[...]
    o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                   ).astype(o_ref.dtype)


def latent_block_t(T: int, heads: int) -> int:
    """Query positions a row-block: the largest power-of-two divisor of
    ``T`` whose rows (``x heads``) stay within ``_BLOCK_ROWS``."""
    bt = T & -T
    while bt > 1 and bt * heads > _BLOCK_ROWS:
        bt //= 2
    return bt


def latent_paged_attention(q: jax.Array, pool: jax.Array,
                           page_table: jax.Array, start: jax.Array, *,
                           value_width: int, layer: jax.Array | int = 0,
                           block_t: int | None = None,
                           pages_per_step: int = _PAGES_PER_STEP,
                           interpret: bool | None = None) -> jax.Array:
    """Causal attention of absorbed queries over the latent pool (keys
    already inserted).

    q: [K, T, H, W] at absolute positions ``start + t``, the softmax scale
    already multiplied in; pool: ``[L, P, W, page]`` of which ``layer`` (a
    traced scalar: the layer scan's index) is read WHERE IT LIES;
    page_table: [K, NP]; start: [K]. Returns [K, T, H, value_width]: per
    head the softmax-weighted sum of the attended tokens' first
    ``value_width`` numbers.

    One Pallas call, grid ``(K, T // bt)``: a program's ``bt x H`` query
    rows meet each page in one bfloat16 dot with float32 accumulation, the
    probabilities are rounded to the pool's dtype for the value dot (as the
    pool itself is), and the online-softmax state is float32. The call's
    time is the visible (query, key) pairs' arithmetic where rows are many
    (a chunk) and the live pages' bytes where they are few (a decode
    step); nothing is paid per table entry or dead page."""
    K, T, H, W = q.shape
    page = pool.shape[-1]
    if pool.shape[-2] != W:
        raise ValueError(f"queries of width {W} over a pool of "
                         f"{pool.shape[-2]}")
    NP = page_table.shape[1]
    bt = latent_block_t(T, H) if block_t is None else min(block_t, T)
    if T % bt:
        raise ValueError(f"T={T} not a multiple of block_t={bt}")
    nT, rows, ppb = T // bt, bt * H, pages_per_step
    q_spec = pl.BlockSpec((1, 1, rows, W),
                          lambda b, t, pt, st, layer: (b, t, 0, 0))
    o_spec = pl.BlockSpec((1, 1, rows, value_width),
                          lambda b, t, pt, st, layer: (b, t, 0, 0))
    out = pl.pallas_call(
        functools.partial(_latent_attention_kernel, block_t=bt, heads=H,
                          page=page, ppb=ppb, value_width=value_width,
                          n_table_pages=NP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(K, nT),
            in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=o_spec,
            scratch_shapes=[pltpu.VMEM((2, ppb, W, page), pool.dtype),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, value_width), jnp.float32),
                            pltpu.SemaphoreType.DMA((2, ppb))]),
        out_shape=jax.ShapeDtypeStruct((K, nT, rows, value_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_paged._interpret_default() if interpret is None else interpret,
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(pool.dtype).reshape(K, nT, rows, W), pool)
    return out.reshape(K, T, H, value_width)


# ---------------------------------------------------------------------------
# Reference jnp path + the provider
# ---------------------------------------------------------------------------

def gather_latent(pool: jax.Array, page_table: jax.Array, max_seq: int, *,
                  layer: jax.Array | int = 0) -> jax.Array:
    """The dense ``[K, S, W]`` view of each row's pages — reference path
    only (it materialises the context)."""
    page = pool.shape[-1]
    n_pages = min(page_table.shape[1], -(-max_seq // page))
    picked = pool[layer][page_table[:, :n_pages]]       # [K, n, W, page]
    seq = jnp.swapaxes(picked, 2, 3).reshape(
        page_table.shape[0], n_pages * page, pool.shape[-2])
    return seq[:, :max_seq]


def causal_softmax(scores: jax.Array, start: jax.Array) -> jax.Array:
    """scores [K, H, T, S] float32 -> probabilities: key ``s`` visible to
    the query at ``start + t`` iff ``s <= start + t``."""
    T, S = scores.shape[2:]
    q_pos = start[:, None] + jnp.arange(T)[None, :]
    seen = jnp.arange(S)[None, None, :] <= q_pos[:, :, None]
    return jax.nn.softmax(jnp.where(seen[:, None], scores, NEG_INF), axis=-1)


def latent_attention_reference(q: jax.Array, dense: jax.Array,
                               start: jax.Array, value_width: int
                               ) -> jax.Array:
    """:func:`latent_paged_attention` in plain float32 ``jax.numpy`` over
    the gathered view ``dense`` [K, S, W]."""
    dense = dense.astype(jnp.float32)
    scores = jnp.einsum("kthw,ksw->khts", q.astype(jnp.float32), dense)
    probs = causal_softmax(scores, start)
    return jnp.einsum("khts,ksv->kthv", probs,
                      dense[..., :value_width]).astype(q.dtype)


class LatentAttention:
    """What a latent layer is handed for its cache (models/mla.py), built
    INSIDE the engine's jitted step over the traced page table, as
    ``make_paged_attention_fn``'s provider is. ``impl`` "pallas": the
    in-place write and the absorbed kernel; "reference": an XLA scatter
    and the gathered dense view, over which the layer attends in the
    EXPANDED form. Both insert, then attend."""

    def __init__(self, page_table: jax.Array, max_seq: int,
                 impl: str = "pallas", interpret: bool | None = None):
        self.page_table, self.max_seq = page_table, max_seq
        self.impl, self.interpret = impl, interpret

    @property
    def absorbed(self) -> bool:
        return self.impl == "pallas"

    def write(self, pool, new, layer, lengths, active=None):
        with jax.named_scope("kv.latent_insert"):
            if self.impl == "pallas":
                return latent_insert_in_place(
                    pool, new, self.page_table, lengths, active, layer=layer,
                    interpret=self.interpret)
            return latent_insert(pool, new, self.page_table, lengths, active,
                                 layer=layer)

    def attend(self, q, pool, layer, lengths, value_width: int):
        """Absorbed queries [K, T, H, W] over the written pool."""
        with jax.named_scope("attention.latent"):
            return latent_paged_attention(
                q, pool, self.page_table, lengths, value_width=value_width,
                layer=layer, interpret=self.interpret)

    def gather(self, pool, layer):
        return gather_latent(pool, self.page_table, self.max_seq, layer=layer)
