"""Learned sparse attention over the page pool: a lightning indexer's
scores, the exact top-``k`` selection, and attention over the selected
rows only.

A softmax layer with an indexer (``ModelConfig.idx_topk`` > 0) caches, per
token, ONE index key of ``W`` numbers beside its K and V rows. A query at
position ``t`` has ``J`` index queries ``qI_j`` and weights ``w_j`` and
scores every cached position ``s <= t``::

    I(t, s) = sum_j w_j relu(qI_j . kI_s)

and attends the ``k`` positions of largest ``I`` only (float32 compare,
ties to the lower position; every position while ``t < k``). One selection
a query, shared by all heads.

Layout. The index keys are a THIRD side of the group's page pool, on the
same page table and allocator as K and V: ``[L, P, W, page]``, a page
stored TRANSPOSED with the token axis in the lanes, as the latent pool is
(ops/latent_attention.py) — ``W`` = 64 is half a lane tile, so token-major
pages ``[page, W]`` would be padded to 128 columns in HBM and in every
copy (twice the side), and transposed the score product ``qI [rows, W] @
page [W, page]`` reads a page as it lies. Its in-place write IS the latent
pool's (:func:`latent_insert_in_place`); K and V go in through the chunk
write of ops/paged_attention.py. Insert, then attend, in both step
programs: a call's own keys are read back as the bytes that were written.

Two forms of the attention, one selection:

* DECODE (one query a slot): ONE kernel over the slots' LIVE index-key
  pages (:func:`decode_select`) — the scores (``W`` numbers a cached
  token, not its K/V rows), the exact ``k``-th largest a slot by the chunk
  kernel's counting passes with the slots as the rows of one program, and
  the selection as 32-bit words a table position, 1 where the query
  attends: no scores over the table's dead entries, no sort, no list —
  and ONE kernel that walks the slot's live K and V pages and keeps a
  score where those words say so (:func:`selected_decode_attention`). The
  walk reads every live page, not ``k`` rows: this layout gives up nothing
  smaller than a tile of 8 tokens of a head, XLA's row-by-row gather of
  the selected rows (:func:`gathered_decode_attention`, the plain form the
  kernel is held to and what the "reference" provider attends by) ran at
  11 ns a 256-byte row, and whole pages stream at 600-665 GB/s (PERF.md,
  PR 52). The plain form of the selection is a LIST: scores over every
  table position, ``lax.top_k``'s sort of a slot's one row
  (:func:`top_positions`), and that list as the same words
  (:func:`selection_words`) — what the kernel is held to bit for bit, what
  ``SparseAttention.select`` still answers one query with (the benchmark's
  reference unpacks it) and what was served until PR 53, at 0.33 ms a
  layer on the chip where the read it fed took 0.5 and the kernel takes
  0.05 (PERF.md, PR 53).
* PREFILL (a chunk of queries a row): ONE kernel over the row's live
  index-key pages (:func:`index_select`) — the scores, the exact ``k``-th
  largest a query by BISECTION on the float's bits (32 counting passes, no
  sort), the selection as an int8 mask — and the dense page walk with a
  score kept only where the mask says so (``keep``, an optional operand of
  ``paged_prefill_attention``).

The selection is the same SET in both: ``lax.top_k``'s, ties to the lower
position. The plain form of it is :func:`top_positions` (a decode step's
list) and :func:`top_mask` (that list as a mask: what the "reference"
provider attends a chunk by and what the chunk kernel is held to).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _paged
from .latent_attention import (create_latent_pool, gather_latent,
                               latent_insert, latent_insert_in_place)
from .paged_attention import (NEG_INF, _decode_heads_per_block,
                              _decode_live_blocks, _walk_buffers,
                              gather_pages, paged_insert_chunk_in_place,
                              paged_insert_kv, paged_prefill_attention)

_INT_MIN = -2 ** 31


def create_index_pool(n_layers: int, num_pages: int, page_size: int,
                      width: int, dtype=jnp.bfloat16) -> jax.Array:
    """The zeroed index-key side ``[L, P, W, page]``."""
    return create_latent_pool(n_layers, num_pages, page_size, width, dtype)


# ---------------------------------------------------------------------------
# Scores and the selection
# ---------------------------------------------------------------------------

def index_scores(qi: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """qi [B, T, J, W], w [B, T, J] float32 (the scale factors multiplied
    in), keys [B, S, W] -> ``I`` [B, T, S] float32. The products run in
    the keys' dtype with float32 accumulation, all heads in one product
    (2 MB a row at 16 heads of 32,768 keys and one query)."""
    s = jnp.einsum("btjw,bsw->btjs", qi.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32)
    return jnp.sum(w[..., None] * jnp.maximum(s, 0.0), axis=2)


def sortable(x: jax.Array) -> jax.Array:
    """float32 -> int32 whose signed order is the floats' (the two zeros
    one key, as a float compare has them; no NaN comes in)."""
    x = x.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x),
                                        jnp.int32)
    return jnp.where(bits < 0, bits ^ 0x7FFFFFFF, bits)


def top_positions(scores: jax.Array, seen: jax.Array, k: int
                  ) -> tuple[jax.Array, jax.Array]:
    """scores float32 [..., S], seen bool [..., S] -> (the ``k`` selected
    positions int32 [..., k], how many of them are real [...]): the seen
    positions of largest score through ``lax.top_k`` (the lower index of
    equal scores first; a sort, 0.29 ms at 8 x 32,768 on the chip). The
    plain form of a decode step's selection: while decode GATHERED its rows
    it needed this list, and a bisection with a sort-free compaction of its
    mask into one took 0.81; since the read takes a mask (PR 52) the served
    step compacts nothing and sorts nothing (:func:`decode_select`). A row
    that sees fewer than ``k`` lists them first; the rest of its places name
    unseen positions and are not real."""
    scores = jnp.where(scores == 0.0, 0.0, scores)      # -0.0 IS 0.0
    k = min(k, scores.shape[-1])
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    total = jnp.minimum(jnp.sum(seen, axis=-1, dtype=jnp.int32), k)
    return idx.astype(jnp.int32), total


def top_mask(scores: jax.Array, seen: jax.Array, k: int) -> jax.Array:
    """:func:`top_positions`' set as ``keep`` bool [..., S]: the plain form
    of a chunk's selection (every seen position of a row that sees no more
    than ``k``)."""
    idx, total = top_positions(scores, seen, k)
    rows, S = idx.reshape(-1, idx.shape[-1]), scores.shape[-1]
    real = jnp.arange(rows.shape[-1]) < total.reshape(-1, 1)
    keep = jnp.zeros((rows.shape[0], S), bool).at[
        jnp.arange(rows.shape[0])[:, None], rows].set(real)
    return keep.reshape(scores.shape)


# ---------------------------------------------------------------------------
# A chunk's scores and selection as ONE kernel over the live pages
# ---------------------------------------------------------------------------

# Query positions a program of the chunk kernel holds (their int32 keys over
# every table position stay in VMEM: 4 MiB at 32 x 32,768), the pages it
# copies a step, and what it may take of VMEM.
_SELECT_BLOCK_T = 32
_SELECT_PAGES_PER_STEP = 4
_SELECT_VMEM_LIMIT_BYTES = 48 * 2 ** 20


def _positions(block, rows: int, width: int) -> jax.Array:
    """int32 [rows, width]: the positions of block ``block`` of ``width``."""
    return block * width + jax.lax.broadcasted_iota(jnp.int32, (rows, width),
                                                    1)


def _kth_by_counting(keys_at, n_blocks, rows: int, width: int, topk: int
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """The exact top-``topk`` of ``rows`` rows of sortable int32 keys with no
    sort, for BOTH kernels: ``keys_at(i)`` [rows, width] is block ``i`` of
    ``n_blocks`` (the least int32 where a row cannot see). Returns, [rows, 1]
    each: the ``topk``-th largest key bit by bit, from the sign down (32
    counting passes: the largest ``x`` that at least ``topk`` keys reach;
    the least int32 where a row sees fewer), the places left for the keys AT
    that value, and among those the position of the last one there is room
    for (20 more passes: positions fit 15 bits and more; 20 covers a 1M
    context)."""
    def count(pred):
        """[rows, 1]: how many positions of each row ``pred(keys of a
        block, the block)`` holds for."""
        def body(i, acc):
            return acc + pred(keys_at(i), i).astype(jnp.int32)
        acc = jax.lax.fori_loop(0, n_blocks, body,
                                jnp.zeros((rows, width), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def reach(x):
        return count(lambda keys, i: keys >= x) >= topk

    zero = jnp.zeros((rows, 1), jnp.int32)
    kth = jnp.where(reach(zero), zero, _INT_MIN)

    def bit(i, x):
        cand = x | jnp.left_shift(jnp.int32(1), 30 - i)
        return jnp.where(reach(cand), cand, x)
    kth = jax.lax.fori_loop(0, 31, bit, kth)
    room = topk - count(lambda keys, i: keys > kth)

    def place(i, x):
        cand = x + jnp.left_shift(jnp.int32(1), 19 - i)
        short = count(lambda keys, i: (keys == kth)
                      & (_positions(i, rows, width) < cand)) < room
        return jnp.where(short, cand, x)
    return kth, room, jax.lax.fori_loop(0, 20, place, zero)


def _kept(keys, block, kth, room, last) -> jax.Array:
    """bool: the keys of block ``block`` that :func:`_kth_by_counting`'s
    three numbers select — every key above the k-th, and of those AT it the
    seen ones up to the last place."""
    return (keys > kth) | ((keys == kth)
                           & (_positions(block, *keys.shape) <= last)
                           & (room > 0) & (keys > _INT_MIN))


def _index_select_kernel(pt_ref, start_ref, layer_ref, q_ref, w_ref,
                         pool_ref, keep_ref, buf, keys_ref, sem, *,
                         block_t: int, heads: int, page: int, ppb: int,
                         topk: int, n_table_pages: int):
    """Program ``(row b, row-block t)``: ``block_t`` query positions, all
    ``heads`` index heads of each (row ``j * block_t + i`` is head ``j`` at
    position ``first_q + i``), walk the row's index-key pages up to the last
    query's own — ``ppb`` pages a step into one of two VMEM buffers while
    the step before is scored — and leave each position's score as a
    sortable int32 key in ``keys_ref`` [block_t, positions] (the least
    int32 where the query cannot see). Then, over the LIVE pages alone: the
    ``topk``-th largest key a query bit by bit, from the sign down (32
    counting passes: the largest ``x`` that at least ``topk`` keys reach),
    among the keys AT that value the position of the last one there is room
    for (20 more passes), and the selection as int8."""
    b, t = pl.program_id(0), pl.program_id(1)
    bt = block_t
    layer = layer_ref[0]
    first_q = start_ref[b] + t * bt
    n_live = jnp.minimum((first_q + bt - 1) // page + 1, n_table_pages)
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

    def columns(lp):
        return pl.ds(pl.multiple_of(lp * page, page), page)

    def position(lp):
        return _positions(lp, bt, page)

    q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bt, page), 0)

    def score(slot, sub, lp):
        s = jnp.dot(q_ref[0, 0], buf[slot, sub],
                    preferred_element_type=jnp.float32)     # [heads*bt, page]
        s = jnp.maximum(s, 0.0) * w_ref[0, 0]
        acc = s[0:bt]
        for j in range(1, heads):
            acc = acc + s[j * bt:(j + 1) * bt]
        keys_ref[:, columns(lp)] = jnp.where(position(lp) <= q_pos,
                                             sortable(acc), _INT_MIN)

    start(0, 0)

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_steps)
        def _prefetch():
            start(i + 1, 1 - slot)
        for sub in range(ppb):
            lp = i * ppb + sub

            @pl.when(lp < n_live)
            def _score(sub=sub, lp=lp):
                copy(i, sub, slot).wait()
                score(slot, sub, lp)
        return carry
    jax.lax.fori_loop(0, n_steps, step, 0)

    kth, room, last = _kth_by_counting(
        lambda lp: keys_ref[:, columns(lp)], n_live, bt, page, topk)

    def keep(lp, carry):
        kept = _kept(keys_ref[:, columns(lp)], lp, kth, room, last)
        keep_ref[0, :, columns(lp)] = kept.astype(jnp.int8)
        return carry
    jax.lax.fori_loop(0, n_live, keep, 0)

    def dead(lp, carry):
        keep_ref[0, :, columns(lp)] = jnp.zeros((bt, page), jnp.int8)
        return carry
    jax.lax.fori_loop(n_live, n_table_pages, dead, 0)


def index_select(qi: jax.Array, w: jax.Array, pool_i: jax.Array,
                 page_table: jax.Array, start: jax.Array, *,
                 layer: jax.Array | int, topk: int,
                 interpret: bool | None = None) -> jax.Array:
    """A chunk's selection in one Pallas call: index queries qi [B, T, J,
    W] with weights w [B, T, J] at positions ``start + t`` over layer
    ``layer`` of the index-key side ``[L, P, W, page]``, read through
    page_table [B, NP] -> ``keep`` int8 [B, T, NP * page], 1 where the
    query attends (:func:`top_mask` of :func:`index_scores`, the same set).
    Grid ``(B, T // bt)``; a program's work follows its LIVE pages — the
    scores' product, the 67 counting passes over its queries' keys in VMEM
    — and nothing is paid for the table's dead entries but their zeros."""
    B, T, J, W = qi.shape
    page, NP = pool_i.shape[-1], page_table.shape[1]
    bt = min(_SELECT_BLOCK_T, T)
    if T % bt:
        raise ValueError(f"T={T} is not whole row-blocks of {bt}")
    nT, ppb = T // bt, _SELECT_PAGES_PER_STEP
    qb = qi.astype(pool_i.dtype).reshape(B, nT, bt, J, W).transpose(
        0, 1, 3, 2, 4).reshape(B, nT, J * bt, W)
    wb = w.astype(jnp.float32).reshape(B, nT, bt, J).transpose(
        0, 1, 3, 2).reshape(B, nT, J * bt, 1)
    return pl.pallas_call(
        functools.partial(_index_select_kernel, block_t=bt, heads=J,
                          page=page, ppb=ppb, topk=topk, n_table_pages=NP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, nT),
            in_specs=[pl.BlockSpec((1, 1, J * bt, W),
                                   lambda b, t, pt, st, ly: (b, t, 0, 0)),
                      pl.BlockSpec((1, 1, J * bt, 1),
                                   lambda b, t, pt, st, ly: (b, t, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, bt, NP * page),
                                   lambda b, t, pt, st, ly: (b, t, 0)),
            scratch_shapes=[pltpu.VMEM((2, ppb, W, page), pool_i.dtype),
                            pltpu.VMEM((bt, NP * page), jnp.int32),
                            pltpu.SemaphoreType.DMA((2, ppb))]),
        out_shape=jax.ShapeDtypeStruct((B, T, NP * page), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_SELECT_VMEM_LIMIT_BYTES),
        interpret=(_paged._interpret_default() if interpret is None
                   else interpret),
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qb, wb, pool_i)


# ---------------------------------------------------------------------------
# A decode step's scores and selection as ONE kernel over the live pages
# ---------------------------------------------------------------------------

# Pages the decode kernel copies a step (its copies in flight: a page of the
# index side is 32 KB, and one step ahead of 4 / 8 / 16 of them walked 8
# slots of 20k keys in 0.089 / 0.065 / 0.055 ms on the chip, PERF.md PR 53),
# and never more than an eighth of a slot's table: a slot's last step copies
# its last page again in every place past it.
_DECODE_SELECT_PAGES_PER_STEP = 16


def _decode_select_kernel(pt_ref, start_ref, layer_ref, q_ref, w_ref,
                          pool_ref, keep_ref, buf, keys_ref, sem,
                          *, heads: int, page: int, ppb: int, topk: int,
                          n_table_pages: int):
    """ONE program, the SLOTS its rows: walks every slot's live index-key
    pages in one sequence over (slot, step) — ``ppb`` pages a step side by
    side in one of two VMEM buffers while the step before is scored, a
    slot's last step prefetching the next slot's first; the walk is bound
    by its copies in flight, so a step is many pages and holds no branch —
    and scores a step as the chunk kernel scores a page (one product in the
    pool's dtype, float32 accumulation, ``relu``, times ``w``, the heads
    summed in their order), leaving slot ``b``'s sortable int32 keys in row
    ``b`` of ``keys_ref`` [slots, positions] (the least int32 where it
    cannot see). Then the chunk kernel's counting passes over the LONGEST
    slot's live blocks, all slots a pass (:func:`_kth_by_counting`), and
    the selection as int32 words, a row of ``keep_ref`` [slots, table
    pages, page] a live page; every dead page reads 0."""
    B = q_ref.shape[0]
    width = ppb * page
    layer = layer_ref[0]

    def n_live(b):
        return jnp.minimum(start_ref[b] // page + 1, n_table_pages)

    def n_steps(b):
        return (n_live(b) + ppb - 1) // ppb

    def block(i):
        return pl.ds(pl.multiple_of(i * width, width), width)

    def copies(b, step, slot):
        # A step's pages past the slot's last are its last page again: every
        # step starts and waits for ``ppb`` copies, no branch among them.
        last = n_live(b) - 1
        return [pltpu.make_async_copy(
            pool_ref.at[layer, pt_ref[b, jnp.minimum(step * ppb + sub,
                                                     last)]],
            buf.at[slot, :, pl.ds(sub * page, page)], sem.at[slot, sub])
            for sub in range(ppb)]

    def start(b, step, slot):
        for c in copies(b, step, slot):
            c.start()

    # The longest slot's blocks are what a counting pass reads of EVERY
    # slot: a shorter one's rows read the least int32 past its own steps.
    n_blocks = n_steps(0)
    for b in range(1, B):
        n_blocks = jnp.maximum(n_blocks, n_steps(b))

    def unseen(i, carry):
        keys_ref[:, block(i)] = jnp.full((B, width), _INT_MIN, jnp.int32)
        return carry
    jax.lax.fori_loop(0, n_blocks, unseen, 0)

    start(0, 0, 0)

    def slot_walk(b, walked):
        steps = n_steps(b)

        def step(i, carry):
            slot = (walked + i) % 2
            ends = i == steps - 1
            nb = jnp.minimum(jnp.where(ends, b + 1, b), B - 1)

            @pl.when(jnp.logical_not(ends & (b == B - 1)))
            def _prefetch():
                start(nb, jnp.where(ends, 0, i + 1), 1 - slot)
            for c in copies(b, i, slot):
                c.wait()
            s = jnp.dot(q_ref[b], buf[slot],
                        preferred_element_type=jnp.float32)  # [heads, width]
            s = jnp.maximum(s, 0.0) * w_ref[b]
            acc = s[0:1]
            for j in range(1, heads):
                acc = acc + s[j:j + 1]
            keys_ref[pl.ds(b, 1), block(i)] = jnp.where(
                _positions(i, 1, width) <= start_ref[b], sortable(acc),
                _INT_MIN)
            return carry
        jax.lax.fori_loop(0, steps, step, 0)
        return walked + steps
    jax.lax.fori_loop(0, B, slot_walk, 0)

    kth, room, last = _kth_by_counting(lambda i: keys_ref[:, block(i)],
                                       n_blocks, B, width, topk)

    def words(i, carry):
        kept = _kept(keys_ref[:, block(i)], i, kth, room, last)
        keys_ref[:, block(i)] = kept.astype(jnp.int32)
        return carry
    jax.lax.fori_loop(0, n_blocks, words, 0)

    keep_ref[...] = jnp.zeros(keep_ref.shape, jnp.int32)

    def live_pages(b, carry):
        def live_page(lp, carry):
            keep_ref[b, pl.ds(lp, 1), :] = keys_ref[
                pl.ds(b, 1), pl.ds(pl.multiple_of(lp * page, page), page)]
            return carry
        return jax.lax.fori_loop(0, n_live(b), live_page, carry)
    jax.lax.fori_loop(0, B, live_pages, 0)


def decode_select(qi: jax.Array, w: jax.Array, pool_i: jax.Array,
                  page_table: jax.Array, start: jax.Array, *,
                  layer: jax.Array | int, topk: int,
                  interpret: bool | None = None) -> jax.Array:
    """A decode step's selection in one Pallas call: ONE index query a slot
    qi [B, J, W] with weights w [B, J] at position ``start`` over layer
    ``layer`` of the index-key side ``[L, P, W, page]``, read through
    page_table [B, NP] -> int32 words [B, NP, page], 1 where the query
    attends and 0 at every other place, dead pages among them: exactly
    ``selection_words(*top_positions(index_scores(...), seen, topk), NP,
    page)``, what :func:`selected_decode_attention` takes as ``keep``. The
    query's own key is in the pool (insert, then select): a slot's
    ``start // page + 1`` live pages are read and nothing of the table's
    dead entries; a slot at ``start`` 0 keeps position 0 alone. No sort, no
    list: the chunk kernel's scores and counting passes with the slots as
    the rows of one program."""
    B, J, W = qi.shape
    page, NP = pool_i.shape[-1], page_table.shape[1]
    ppb = min(_DECODE_SELECT_PAGES_PER_STEP, max(1, NP // 8))
    positions = -(-NP // ppb) * ppb * page      # whole steps of pages
    return pl.pallas_call(
        functools.partial(_decode_select_kernel, heads=J, page=page, ppb=ppb,
                          topk=topk, n_table_pages=NP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(1,),
            in_specs=[pl.BlockSpec((B, J, W), lambda i, pt, st, ly: (0, 0, 0)),
                      pl.BlockSpec((B, J, 1), lambda i, pt, st, ly: (0, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((B, NP, page),
                                   lambda i, pt, st, ly: (0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, W, ppb * page), pool_i.dtype),
                            pltpu.VMEM((B, positions), jnp.int32),
                            pltpu.SemaphoreType.DMA((2, ppb))]),
        out_shape=jax.ShapeDtypeStruct((B, NP, page), jnp.int32),
        interpret=(_paged._interpret_default() if interpret is None
                   else interpret),
    )(page_table.astype(jnp.int32), start.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), qi.astype(pool_i.dtype),
      w.astype(jnp.float32)[..., None], pool_i)


# ---------------------------------------------------------------------------
# The two forms of the attention
# ---------------------------------------------------------------------------

def gathered_decode_attention(q: jax.Array, pool_k: jax.Array,
                              pool_v: jax.Array, layer, phys: jax.Array,
                              offset: jax.Array, total: jax.Array
                              ) -> jax.Array:
    """The plain form of :func:`selected_decode_attention` (what it is
    held to, and what the "reference" provider attends by): one query a
    slot over its SELECTED rows, gathered. q [B, H, Dh]; the stacked
    pool sides [L, P, KV, page, Dh]; phys, offset [B, k]: where each
    selected token lies in layer ``layer``; total [B]: how many of the
    ``k`` places are real. The rows are gathered token by token — row
    ``((layer P + phys) KV + head) page + offset`` of the pool seen as
    ``[L P KV page, Dh]``, a view that moves nothing — and attended in
    float32. Returns [B, H * Dh] in q's dtype."""
    L, P, KV, page, Dh = pool_k.shape
    B, H, _ = q.shape
    G, k = H // KV, phys.shape[1]
    row = (((jnp.asarray(layer, jnp.int32) * P + phys)[:, :, None] * KV
            + jnp.arange(KV, dtype=jnp.int32)) * page + offset[:, :, None])
    keys = jnp.take(pool_k.reshape(-1, Dh), row, axis=0)    # [B, k, KV, Dh]
    vals = jnp.take(pool_v.reshape(-1, Dh), row, axis=0)
    qg = q.reshape(B, KV, G, Dh)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, keys.astype(q.dtype),
                        preferred_element_type=jnp.float32) * Dh ** -0.5
    real = jnp.arange(k)[None, :] < total[:, None]
    scores = jnp.where(real[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs.astype(vals.dtype), vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H * Dh).astype(q.dtype)


def selection_words(positions: jax.Array, total: jax.Array, n_pages: int,
                    page: int) -> jax.Array:
    """:func:`top_positions`' list as the mask the decode kernel reads:
    int32 ``[B, n_pages, page]``, 1 at the ``total`` real places of a row's
    list and 0 everywhere else — whatever the unreal places name. 32-bit
    words, so that a page's row is one row of the resident block read at a
    dynamic index. No scatter: a place is a (page, offset) pair, and the
    product of the places' two one-hot rows, summed over the list, counts
    the places that name each word (ONE matrix product a slot, ``[n_pages,
    k] @ [k, page]``, exact in any float: XLA's scatter of the 16,384
    places took 0.15 ms a layer on the chip and files under no scope)."""
    k = positions.shape[1]
    real = jnp.arange(k)[None, :] < total[:, None]
    in_page = (positions // page)[:, :, None] == jnp.arange(n_pages)
    at_offset = (positions % page)[:, :, None] == jnp.arange(page)
    named = jnp.einsum("bjp,bjo->bpo",
                       (in_page & real[:, :, None]).astype(jnp.bfloat16),
                       at_offset.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    return (named > 0.0).astype(jnp.int32)


def _selected_decode_kernel(pt_ref, nkeys_ref, layer_ref, q_ref, keep_ref,
                            k_pool, v_pool, o_ref, k_buf, v_buf, sem, *,
                            page: int, n_table_pages: int):
    """One program per group of folded KV heads walks EVERY slot's live
    pages as the paged decode kernel does (ops/paged_attention.py
    ``_paged_decode_kernel``: one sequence over (slot, page), two VMEM
    buffers a side, a slot's last page prefetching the next slot's first)
    and keeps a score where ``keep_ref`` [B, table pages, page] says so.
    There is no self column — the query's own key is in the pool — so the
    state starts empty, and a page with no kept key leaves it untouched:
    its probabilities are ``where(kept, exp(s - m), 0)``."""
    hb = pl.program_id(0)
    B, heads, G, Dh = q_ref.shape
    layer = layer_ref[0]

    def n_live(b):
        return _decode_live_blocks(nkeys_ref[b], page, 0,
                                   n_table_pages)[1] + 1      # >= 1 page

    def copies(b, lp, buf):
        phys = pt_ref[b, lp]
        return [pltpu.make_async_copy(
            pool.at[layer, pl.ds(phys, 1), pl.ds(hb * heads, heads)],
            vmem.at[buf], sem.at[buf, side])
            for side, (pool, vmem) in enumerate(((k_pool, k_buf),
                                                 (v_pool, v_buf)))]

    for c in copies(0, 0, 0):
        c.start()

    def slot(b, walked):
        n_pages = n_live(b)
        q = q_ref[b]                                    # [heads, G, Dh]

        def one_page(lp, state):
            m, l, acc = state
            buf = (walked + lp) % 2
            ends = lp == n_pages - 1
            nb = jnp.minimum(jnp.where(ends, b + 1, b), B - 1)
            nlp = jnp.where(ends, 0, lp + 1)

            @pl.when(jnp.logical_not(ends & (b == B - 1)))
            def _prefetch():
                for c in copies(nb, nlp, 1 - buf):
                    c.start()
            for c in copies(b, lp, buf):
                c.wait()
            k, v = k_buf[buf, 0], v_buf[buf, 0]         # [heads, page, Dh]
            # ONE product in the operands' dtype, float32 accumulation.
            s = jax.lax.dot_general(
                q, k.astype(q.dtype), (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * Dh ** -0.5
            kept = jnp.broadcast_to(
                (keep_ref[b, pl.ds(lp, 1), :] != 0)[None], s.shape)
            m_new = jnp.maximum(m, jnp.max(
                jnp.where(kept, s, NEG_INF), axis=2, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(kept, jnp.exp(s - m_new), 0.0)
            l = alpha * l + jnp.sum(p, axis=2, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)     # [heads, G, Dh]
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, n_pages, one_page,
            (jnp.full((heads, G, 1), NEG_INF, jnp.float32),
             jnp.zeros((heads, G, 1), jnp.float32),
             jnp.zeros((heads, G, Dh), jnp.float32)))
        o_ref[b] = (acc / jnp.where(l > 0.0, l, 1.0)).astype(o_ref.dtype)
        return walked + n_pages

    jax.lax.fori_loop(0, B, slot, 0)


def selected_decode_attention(q: jax.Array, pool_k: jax.Array,
                              pool_v: jax.Array, page_table: jax.Array,
                              n_keys: jax.Array, keep: jax.Array, *,
                              layer: jax.Array | int,
                              interpret: bool | None = None) -> jax.Array:
    """One query a slot over its SELECTED keys, read at stream speed: ONE
    Pallas call walks each slot's live pages of layer ``layer`` of the
    stacked pool sides [L, P, KV, page, Dh] where they lie — whole pages,
    all the KV heads that fit a block (:func:`_decode_heads_per_block`: 4
    of 4 at bfloat16), a page a copy a side (no packed-table promise is
    handed here) — and attends the keys ``keep`` marks. q [B, H, Dh];
    page_table [B, NP]; n_keys [B]: the keys a slot holds, the query's own
    among them (its pages ``ceil(n_keys / page)`` are walked); keep int32
    [B, NP, page], nonzero where the query attends
    (:func:`selection_words`). The numbers are
    :func:`gathered_decode_attention`'s: q . K one product in q's dtype
    with float32 accumulation, a float32 softmax over the kept scores (here
    online, a page at a time), the probabilities rounded to the pool's
    dtype before the V product. Returns [B, H * Dh] in q's dtype.

    The chip cannot copy less than 8 tokens of a head out of this layout
    (a tile), so a read bounded by ``k`` is not to be had from it; whole
    pages stream at the paged decode kernel's rate, which beats XLA's
    row-by-row gather while a slot holds under ~60k keys (PERF.md, PR 52)."""
    B, H, Dh = q.shape
    KV, page = pool_k.shape[2], pool_k.shape[3]
    NP, G = page_table.shape[1], H // KV
    heads = _decode_heads_per_block(KV, page, Dh, pool_k.dtype.itemsize,
                                    False, 1)
    pools, buffers = _walk_buffers(pool_k, pool_v, 1, heads)
    out = pl.pallas_call(
        functools.partial(_selected_decode_kernel, page=page,
                          n_table_pages=NP),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(KV // heads,),
            in_specs=[pl.BlockSpec((B, heads, G, Dh),
                                   lambda hb, pt, nk, ly: (0, hb, 0, 0)),
                      pl.BlockSpec((B, NP, page),
                                   lambda hb, pt, nk, ly: (0, 0, 0)),
                      *[pl.BlockSpec(memory_space=pl.ANY)] * len(pools)],
            out_specs=pl.BlockSpec((B, heads, G, Dh),
                                   lambda hb, pt, nk, ly: (0, hb, 0, 0)),
            scratch_shapes=[*buffers,
                            pltpu.SemaphoreType.DMA((2, len(pools)))]),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=(_paged._interpret_default() if interpret is None
                   else interpret),
    )(page_table.astype(jnp.int32), n_keys.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(B, KV, G, Dh),
      keep.astype(jnp.int32), *pools)
    return out.reshape(B, H * Dh)


def masked_attention_reference(q: jax.Array, dense_k: jax.Array,
                               dense_v: jax.Array, keep: jax.Array
                               ) -> jax.Array:
    """Plain float32 attention over a gathered view with the selection as
    its mask. q [B, T, H, Dh], dense_k/v [B, KV, S, Dh], keep bool
    [B, T, S] -> [B, T, H * Dh] in q's dtype."""
    B, T, H, Dh = q.shape
    KV = dense_k.shape[1]
    qg = q.astype(jnp.float32).reshape(B, T, KV, H // KV, Dh)
    scores = jnp.einsum("bthgd,bhsd->bhgts", qg,
                        dense_k.astype(jnp.float32)) * Dh ** -0.5
    scores = jnp.where(keep[:, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgts,bhsd->bthgd", probs, dense_v.astype(jnp.float32))
    return out.reshape(B, T, H * Dh).astype(q.dtype)


class SparseAttention:
    """What a softmax layer with an indexer is handed for its cache
    (models/hybrid.py), built INSIDE the engine's jitted step over the
    traced page table as the other groups' providers are. ``pool`` is the
    group's three stacked sides ``(K, V, index keys)``, which both step
    programs carry through their layer scan. ``impl`` "pallas": the
    in-place writes, a selection kernel and a read kernel in each step
    program (a decode step's mask words and the walk under them, a chunk's
    mask and the masked page walk); "reference": XLA scatters, the sorted
    list and the gathered rows of a decode step and a gathered dense view
    of a chunk (CPU tests). Both insert, then attend, over the same
    selection."""

    def __init__(self, page_table: jax.Array, max_seq: int, topk: int,
                 impl: str = "pallas", interpret: bool | None = None):
        self.page_table, self.max_seq, self.topk = page_table, max_seq, topk
        self.impl, self.interpret = impl, interpret

    def write(self, pool, k_new, v_new, ki_new, layer, lengths, active=None):
        """k_new, v_new [B, T, KV, Dh], ki_new [B, T, W] into layer
        ``layer`` at positions ``lengths + t`` -> the pool."""
        pool_k, pool_v, pool_i = pool
        table = self.page_table
        if self.impl == "pallas":
            with jax.named_scope("kv.paged_insert"):
                pool_k, pool_v = paged_insert_chunk_in_place(
                    pool_k, pool_v, k_new, v_new, table, lengths, active,
                    layer=layer, interpret=self.interpret)
                pool_i = latent_insert_in_place(
                    pool_i, ki_new, table, lengths, active, layer=layer,
                    interpret=self.interpret)
            return pool_k, pool_v, pool_i
        layer_k, layer_v = paged_insert_kv(
            pool_k[layer], pool_v[layer], k_new, v_new, table, lengths,
            active)
        return (pool_k.at[layer].set(layer_k), pool_v.at[layer].set(layer_v),
                latent_insert(pool_i, ki_new, table, lengths, active,
                              layer=layer))

    def scores(self, qi, w, pool_i, layer):
        """``I`` [B, T, S] of index queries qi [B, T, J, W] with weights w
        [B, T, J] over the index keys of layer ``layer`` at every table
        entry's positions (``S`` of them)."""
        S = self.page_table.shape[1] * pool_i.shape[-1]
        return index_scores(qi, w, gather_latent(
            pool_i, self.page_table, S, layer=layer))

    def select(self, qi, w, pool_i, layer, start):
        """The queries at positions ``start + t`` over the WRITTEN index
        keys -> what each attends. One query a row: :func:`top_positions`'
        (positions [B, k], how many are real [B]) of :meth:`scores` — the
        PLAIN list form for either provider (the "reference" provider's
        decode step, and what the served kernel, :meth:`select_words`, is
        held to). A chunk: ``keep`` bool [B, T, S] (a position the query
        cannot see is never kept) — one kernel over the live pages
        (:func:`index_select`); the plain :func:`top_mask` of :meth:`scores`
        for the "reference" provider and a chunk that is not whole
        row-blocks. The same set in each."""
        T = qi.shape[1]
        if self.impl == "pallas" and T >= 8 and T % min(
                _SELECT_BLOCK_T, T) == 0:
            return index_select(qi, w, pool_i, self.page_table, start,
                                layer=layer, topk=self.topk,
                                interpret=self.interpret).astype(bool)
        scores = self.scores(qi, w, pool_i, layer)
        q_pos = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
        seen = jnp.arange(scores.shape[-1], dtype=jnp.int32)[None, None, :] \
            <= q_pos[:, :, None]
        if T == 1:
            return top_positions(scores[:, 0], seen[:, 0], self.topk)
        return top_mask(scores, seen, self.topk)

    def select_words(self, qi, w, pool_i, layer, start):
        """What a DECODE step of the "pallas" provider selects by: ONE
        query a row, qi [B, 1, J, W] with weights w [B, 1, J] at position
        ``start``, over the WRITTEN index keys -> :meth:`select`'s set as
        the read kernel's mask, int32 words [B, NP, page] — one kernel over
        the slots' live pages (:func:`decode_select`), no scores over the
        table, no sort, no list."""
        with jax.named_scope("attention.index_decode"):
            return decode_select(qi[:, 0], w[:, 0], pool_i, self.page_table,
                                 start, layer=layer, topk=self.topk,
                                 interpret=self.interpret)

    def attend(self, q, pool, layer, start, selected):
        """q [B, T, H, Dh] (rotated) at positions ``start + t`` over what
        :meth:`select` or :meth:`select_words` gave, in the WRITTEN pool ->
        [B, T, H * Dh]. One query a row: the walk of the row's live pages
        (its own key's among them: ``start + 1`` keys) under the selection's
        words — :meth:`select_words`' as they are, a ``(positions, total)``
        list through :func:`selection_words` — where the plain form gathers
        the listed rows. A chunk: the dense page walk, masked by ``keep``."""
        pool_k, pool_v, pool_i = pool
        if q.shape[1] == 1:
            page = pool_i.shape[-1]
            with jax.named_scope("attention.sparse_decode"):
                if self.impl == "pallas":
                    keep = selection_words(
                        *selected, self.page_table.shape[1], page) \
                        if isinstance(selected, tuple) else selected
                    return selected_decode_attention(
                        q[:, 0], pool_k, pool_v, self.page_table, start + 1,
                        keep, layer=layer, interpret=self.interpret)[:, None]
                positions, total = selected
                phys = jnp.take_along_axis(self.page_table,
                                           positions // page, axis=1)
                return gathered_decode_attention(
                    q[:, 0], pool_k, pool_v, layer, phys, positions % page,
                    total)[:, None]
        if self.impl == "pallas":
            with jax.named_scope("attention.paged_prefill"):
                return paged_prefill_attention(
                    q, pool_k, pool_v, self.page_table, start, layer=layer,
                    keep=selected, interpret=self.interpret)
        S = selected.shape[-1]
        dense_k = gather_pages(pool_k[layer], self.page_table, S)
        dense_v = gather_pages(pool_v[layer], self.page_table, S)
        return masked_attention_reference(q, dense_k, dense_v, selected)
