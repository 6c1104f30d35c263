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
# buffers), and what it may take of VMEM.
#
# The online-softmax UPDATE (running max, ``alpha``, ``exp``, row sum, the
# accumulator rescaled) is made once over a SPAN of pages, not once a
# page: its lane reductions, its ``[rows, 1]`` state and the accumulator's
# read-multiply-write cost by the ROWS, so an update over 1,024 keys pays
# a quarter a key of what four updates over 256 do, and one basic block
# holds the span's score products beside the vector work. A step whose
# ``ppb`` pages are all live and wholly below the row-block's diagonal is
# attended span by span without a branch between its pages; the step that
# holds the diagonal or a dead page (a program's last) keeps one update a
# page, the diagonal's masked.
#
# The span is the most pages of a step, a power of two, whose update
# fits (:func:`update_span`): the span's score tiles ``[rows, page]`` side
# by side, twice in float32 (scores, exponentials) and once in the pool's
# dtype (the probabilities the value dot takes), beside what a program
# holds anyway — the q and out blocks twice over (the pipeline's two
# buffers), the float32 accumulator ``[rows, value_width]``, m and l (a
# lane tile a row each), the two page buffers. At 2,048 rows, 256-key
# pages, 4 pages a step: 20 MiB of tiles + 10 MiB at 32 heads x 320 / 256,
# + 17 MiB at 64 heads x 576 / 512, of the 48 the call asks for — the
# whole step, 1,024 keys an update, at both.
#
# The kernel's CODE is as scarce as its memory: at 2,048 rows one update
# is thousands of instructions (every tile is unrolled), and a program
# whose bodies together outgrow the core's instruction memory runs ALL of
# them slower — the parent's eight copies of a page's update (four pages
# x masked or not) beside the whole step's block read 2.55 ms where the
# eight alone read 1.54 and the block with the page's two bodies in a LOOP
# reads 1.06 (v5e, one 512-token row at 8k, PERF.md PR 57). Hence the edge
# step's ``fori_loop``; a new body here is measured, not assumed free.
_BLOCK_ROWS = 2048
_PAGES_PER_STEP = 4
_VMEM_LIMIT_BYTES = 48 * 2 ** 20
# Left to the compiler of the limit: its own temporaries (the value
# operand's transpose, the mask's iotas on the edge step).
_VMEM_ROOM_BYTES = 6 * 2 ** 20


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

def _softmax_update(scores, m, l):
    """One online-softmax update of the float32 score tiles ``scores``
    (each [rows, page], side by side) under the running max ``m`` and sum
    ``l`` [rows, 1]: the tiles' exponentials, the factor that rescales
    what was accumulated under ``m``, the new ``m`` and ``l``. The tiles
    are combined element-wise first: ONE lane reduction a row for the max
    and one for the sum, however many tiles."""
    m_new = jnp.maximum(m, jnp.max(functools.reduce(jnp.maximum, scores),
                                   axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    e = [jnp.exp(s - m_new) for s in scores]
    return e, alpha, m_new, alpha * l + jnp.sum(
        functools.reduce(jnp.add, e), axis=1, keepdims=True)


def _latent_attention_kernel(pt_ref, start_ref, layer_ref, q_ref, pool_ref,
                             o_ref, buf, m_ref, l_ref, acc_ref, sem, *,
                             block_t: int, heads: int, page: int, ppb: int,
                             span: int, value_width: int,
                             n_table_pages: int):
    """Program ``(row b, row-block t)``: ``block_t`` query positions of all
    ``heads`` heads (row ``i * heads + h`` is head ``h`` at position
    ``first_q + i``) walk the pages up to the last query's own key, and
    only those. The pool stays in HBM; a step copies ``ppb`` pages into
    one of two VMEM buffers while the step before is attended. A page is
    ``[W, page]``: the score dot reads it as it lies and the value dot
    contracts its lanes with the probabilities' — the page's first
    ``value_width`` rows are the value. A step of whole pages is ``ppb //
    span`` updates in one straight line; the step on the row-block's
    diagonal is a loop of one update a page, and only the diagonal's page
    is masked."""
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

    def attend(slot, subs, lo=None):
        """One update over the step's pages ``subs``; ``lo``: the first
        key's position where the (one) page is masked."""
        q = q_ref[0, 0]                                     # [rows, W]
        keys = [buf[slot, sub] for sub in subs]             # [W, page] each
        scores = [jnp.dot(q, k, preferred_element_type=jnp.float32)
                  for k in keys]
        if lo is not None:
            row = jax.lax.broadcasted_iota(jnp.int32, scores[0].shape, 0)
            q_pos = first_q + (row // heads)
            s_pos = lo + jax.lax.broadcasted_iota(jnp.int32,
                                                  scores[0].shape, 1)
            scores = [jnp.where(s_pos <= q_pos, scores[0], NEG_INF)]
        e, alpha, m_ref[...], l_ref[...] = _softmax_update(
            scores, m_ref[...], l_ref[...])
        acc_ref[...] = acc_ref[...] * alpha + functools.reduce(jnp.add, [
            jax.lax.dot_general(
                p.astype(k.dtype), k[:value_width], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            for p, k in zip(e, keys)])

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_steps)
        def _prefetch():
            start(i + 1, 1 - slot)
        # Every page live and below the diagonal of EVERY row.
        whole_step = step_is_whole(i, first_q, n_live, page, ppb)

        @pl.when(whole_step)
        def _whole_step():
            for sub in range(ppb):
                copy(i, sub, slot).wait()
            for sub in range(0, ppb, span):
                attend(slot, range(sub, sub + span))

        def edge_page(sub, carry):
            lp = i * ppb + sub
            lo = lp * page
            live = lp < n_live
            whole = lo + (page - 1) <= first_q      # no mask is built

            @pl.when(live)
            def _wait():
                copy(i, sub, slot).wait()

            @pl.when(live & whole)
            def _whole():
                attend(slot, [sub])

            @pl.when(live & jnp.logical_not(whole))
            def _edge():
                attend(slot, [sub], lo)
            return carry

        # A LOOP over the step's pages, not ``ppb`` copies of its two
        # bodies: the kernel's code is scarce (header).
        @pl.when(jnp.logical_not(whole_step))
        def _edge_step():
            jax.lax.fori_loop(0, ppb, edge_page, 0)
        return carry

    jax.lax.fori_loop(0, n_steps, step, 0)
    l = l_ref[...]
    o_ref[0, 0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
                   ).astype(o_ref.dtype)


def step_is_whole(i, first_q, n_live, page: int, ppb: int):
    """Whether step ``i`` of a program whose first query stands at
    ``first_q`` and that walks ``n_live`` pages holds ``ppb`` live pages
    that all lie wholly at or below ``first_q``: the kernel's branch
    (traced scalars) and the host's count (integers) alike."""
    return ((i + 1) * ppb <= n_live) & ((i + 1) * ppb * page - 1 <= first_q)


def latent_steps_walked(starts, T: int, bt: int, page: int,
                        n_table_pages: int,
                        ppb: int = _PAGES_PER_STEP) -> tuple[int, int]:
    """(steps, whole steps) of ONE layer's call over rows that start at
    ``starts`` with ``T`` new tokens each, row-blocks of ``bt`` positions:
    the steps every program of :func:`latent_paged_attention` walks and
    those of them it attends as one straight line (``step_is_whole``) —
    the kernel's own arithmetic on the host, in closed form."""
    steps = whole = 0
    for s in starts:
        for first_q in range(int(s), int(s) + T, bt):
            n_live = min((first_q + bt - 1) // page + 1, n_table_pages)
            steps += -(-n_live // ppb)
            whole += min(n_live // ppb, (first_q + 1) // (ppb * page))
    return steps, whole


def update_span(rows: int, ppb: int, page: int, width: int,
                value_width: int, itemsize: int) -> int:
    """Pages one softmax update of a whole step spans: the largest power
    of two dividing ``ppb`` whose score tiles fit ``_VMEM_LIMIT_BYTES``
    beside what a program holds anyway (the header's account)."""
    held = (2 * rows * (width + value_width) * itemsize    # q, out: twice
            + rows * value_width * 4                       # accumulator
            + 2 * rows * 128 * 4                           # m, l
            + 2 * ppb * width * page * itemsize)           # page buffers
    span = ppb & -ppb
    while span > 1 and held + rows * span * page * (8 + itemsize) \
            > _VMEM_LIMIT_BYTES - _VMEM_ROOM_BYTES:
        span //= 2
    return span


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
    pool itself is), and the online-softmax state is float32, updated once
    over the pages of a whole step (:func:`update_span`). The call's
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
    span = update_span(rows, ppb, page, W, value_width, pool.dtype.itemsize)
    q_spec = pl.BlockSpec((1, 1, rows, W),
                          lambda b, t, pt, st, layer: (b, t, 0, 0))
    o_spec = pl.BlockSpec((1, 1, rows, value_width),
                          lambda b, t, pt, st, layer: (b, t, 0, 0))
    out = pl.pallas_call(
        functools.partial(_latent_attention_kernel, block_t=bt, heads=H,
                          page=page, ppb=ppb, span=span,
                          value_width=value_width, n_table_pages=NP),
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
