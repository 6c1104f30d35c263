"""The grouped expert product's live-tile loop as ONE Pallas kernel.

``models/hybrid.py`` ``experts_grouped`` lays the routed rows out expert by
expert in tiles (``grouped_layout``) and hands this kernel the layout, each
row's routing weight, the call's rows (int8 with their scales, quantised
once a call, or plain) and the period-stacked expert matrices. The kernel
has no grid: ONE program loops over the LIVE tiles (``meta[0]``, read from
scalar memory), so a dead tile of the layout's static bound costs nothing
and fetches nothing. The call's rows and its float32 result are resident in
fast memory for the whole kernel. A tile

* receives its rows BY INDEX from the resident rows. The chip reads a
  single row of a tiled buffer only in 32-bit words, so the rows are
  packed four int8 (two bfloat16) to a word (``pack_rows``), a row's
  float32 scale riding in 128 more lanes, and unpacked by shifts once a
  tile (the padding rows read the zero row ``N``);
* reads its expert's matrices IN PLACE from the stack, ``[period, expert]``
  of it, in blocks of the expert width ``F`` (``width_blocks``: as few as
  fit the fast memory twice beside the resident rows and result). Gate/up
  blocks and down blocks are two streams of two buffers each, and a buffer
  is refilled with the block two ahead OF ITS STREAM the moment its
  product is done — across tiles, so the next tile's weights stream while
  this one computes;
* keeps the gate product, the up product, the activation, the hidden
  rows' re-quantisation and the down product in fast memory, with the
  numbers of ``quant.mm_q8`` / ``quant._dynamic_int8``: int32
  accumulation, float32 rescale by row and column scales, results rounded
  to the rows' dtype, the hidden rows quantised per row over the WHOLE
  width (so the down product waits for the last gate/up block);
* adds its result rows, each times its routing weight, in float32 onto
  its tokens' rows of the result — a row at a time, padding rows skipped:
  no scatter, no sorted-order buffer in HBM, no gather after the kernel.

Set-up cost is part of the design (PERF.md section 6, PR 43): the body
has no Python loop over blocks, rows or tiles (``fori_loop`` only), and
``experts_grouped`` calls it through one module-level jitted function, so a
program traces it once a distinct row count and lowers it once, however
many expert layers its period body unrolls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..models.llama import gated_hidden

# What the kernel may ask of a v5e's 128 MiB of fast memory, and what of
# that is neither the resident rows and result nor the streamed blocks: the
# tile's rows, hidden rows, accumulator and result, the products' int32 and
# float32 temporaries.
VMEM_LIMIT = 100 * 2 ** 20
FIXED_BYTES = 20 * 2 ** 20


def width_blocks(D: int, F: int, itemsize: int, budget: int) -> int:
    """Into how many blocks the expert width ``F`` is cut: the fewest for
    which two gate blocks, two up blocks and two down blocks fit
    ``budget``, a block a whole number of 128 lanes wide (one block where
    no such cut exists: a toy width)."""
    for n in range(1, F // 128 + 1):
        if F % (128 * n) == 0 and 6 * D * (F // n) * itemsize <= budget:
            return n
    return 1


def mosaic_can_take(D: int, F: int, tile: int) -> bool:
    """The shapes the chip's compiler takes: whole lanes of 128 in both
    widths and whole packed int8 sublanes of 32 in a tile. Anything else
    (the tests' toy presets) runs the same kernel interpreted."""
    return D % 128 == 0 and F % 128 == 0 and tile % 32 == 0


def pack_rows(x: jax.Array, scale: jax.Array | None = None) -> jax.Array:
    """x [R, D] -> int32 [R, C (+128)], ``C = D * itemsize / 4``: the
    form a tile can gather rows of (the chip reads a single row of a
    tiled buffer only in 32-bit words). Word ``c`` of a row holds the
    row's elements ``c, C + c, 2C + c, ...`` lowest byte first, so that
    plane ``b`` of the words is the row's columns ``[bC, (b+1)C)``
    (``_unpack_rows``). ``scale`` [R, 1] float32 rides in 128 more lanes."""
    R, D = x.shape
    per = 4 // x.dtype.itemsize
    words = jax.lax.bitcast_convert_type(
        x.reshape(R, per, D // per).swapaxes(1, 2), jnp.int32
    ) if per > 1 else jax.lax.bitcast_convert_type(x, jnp.int32)
    if scale is None:
        return words
    bits = jax.lax.bitcast_convert_type(scale, jnp.int32)
    return jnp.concatenate([words, jnp.broadcast_to(bits, (R, 128))], axis=1)


def _unpack_rows(words: jax.Array, dtype) -> jax.Array:
    """``pack_rows``'s words [R, C] back to [R, D] of ``dtype``."""
    per = 4 // jnp.dtype(dtype).itemsize
    if per == 1:
        return jax.lax.bitcast_convert_type(words, dtype)
    bits = 32 // per
    planes = []
    for b in range(per):
        high = jnp.left_shift(words, 32 - bits * (b + 1))   # plane b on top
        if jnp.issubdtype(dtype, jnp.integer):
            planes.append(jnp.right_shift(high, 32 - bits).astype(dtype))
        else:       # a 16-bit float is the top half of the float32 it equals
            planes.append(jax.lax.bitcast_convert_type(
                jnp.bitwise_and(high, -(1 << 16)), jnp.float32).astype(dtype))
    return jnp.concatenate(planes, axis=1)


def _gated(act: str, gate: jax.Array, up: jax.Array,
           limit: float = 0.0) -> jax.Array:
    """``act(gate) * up`` in the rows' dtype (``llama.gated_hidden``, with
    its clamp where ``limit`` > 0), computed in float32 and rounded once
    (the chip has no 16-bit vector unit and its compiler takes no 16-bit
    logistic; XLA keeps the same excess precision inside a fusion)."""
    return gated_hidden(act, gate.astype(jnp.float32),
                        up.astype(jnp.float32), limit).astype(gate.dtype)


def _kernel(meta, tile_expert, row_token, row_weight, x32, *refs, tile: int,
            n_blocks: int, act: str, limit: float, quantized: bool,
            x_dtype):
    """meta int32 [2]: (live tiles, period); tile_expert [n_tiles];
    row_token, row_weight [n_tiles * tile]: the token a row holds (the
    result's row count: a padding row) and its weight; x32: the call's
    rows and a zero row as ``pack_rows`` packs them (with their scales if
    ``quantized``), resident in fast memory. ``refs``: the matrices (each
    int8 [P, E, din, dout] and its scales [P, E, 1, dout], or plain), the
    result float32 [N, D] (resident too), then the scratch of
    ``grouped_experts``."""
    n_w = 6 if quantized else 3
    mats, out, refs = refs[:n_w], refs[n_w], refs[n_w + 1:]
    if quantized:
        (wg, sg, wu, su, wd, sd) = mats
        rows_v, gu_v, sgu_v, dn_v, sdn_v, h_v, acc_v, y_v, sems = refs
    else:
        (wg, wu, wd) = mats
        rows_v, gu_v, dn_v, h_v, acc_v, y_v, sems = refs
    live, period = meta[0], meta[1]
    N, D = out.shape
    Fb = gu_v.shape[-1]
    C = rows_v.shape[-1] - (128 if quantized else 0)
    dtype = h_v.dtype
    UP, DOWN, SCALE = range(3)              # the semaphores' first index

    def cols(j):
        return pl.ds(pl.multiple_of(j * Fb, Fb), Fb)

    def gather_rows(i):
        """Tile ``i``'s rows, by index, into the tile buffer."""
        def one(r, _):
            rows_v[pl.ds(r, 1), :] = x32[pl.ds(row_token[i * tile + r], 1), :]
        jax.lax.fori_loop(0, tile, one, None)

    def add_rows(i):
        """Tile ``i``'s results, weighted, onto their tokens' rows."""
        def one(r, _):
            t = row_token[i * tile + r]

            @pl.when(t < N)
            def _():
                out[pl.ds(t, 1), :] += (row_weight[i * tile + r]
                                        * y_v[pl.ds(r, 1), :])
        jax.lax.fori_loop(0, tile, one, None)

    def scale_copies(i, slot):
        """What a tile needs once beside its blocks: the down product's
        column scales."""
        if not quantized:
            return []
        return [pltpu.make_async_copy(
            sd.at[period, tile_expert[i]], sdn_v.at[slot],
            sems.at[SCALE, slot])]

    def up_copies(q, slot):
        """Gate/up block ``q`` of the stream (tile ``q // n_blocks``)."""
        e, j = tile_expert[q // n_blocks], q % n_blocks
        got = [pltpu.make_async_copy(w.at[period, e, :, cols(j)],
                                     gu_v.at[slot, n], sems.at[UP, slot])
               for n, w in enumerate((wg, wu))]
        if quantized:
            got += [pltpu.make_async_copy(
                s.at[period, e, :, cols(j)], sgu_v.at[slot, n],
                sems.at[UP, slot]) for n, s in enumerate((sg, su))]
        return got

    def down_copies(q, slot):
        e, j = tile_expert[q // n_blocks], q % n_blocks
        return [pltpu.make_async_copy(wd.at[period, e, cols(j)],
                                      dn_v.at[slot], sems.at[DOWN, slot])]

    def start_if_live(copies_of, q):
        """Start block ``q`` of a stream into its buffer if its tile runs."""
        @pl.when(q // n_blocks < live)
        def _():
            for c in copies_of(q, q % 2):
                c.start()

    for q in range(2):
        start_if_live(up_copies, q)
        start_if_live(down_copies, q)

    @pl.when(live > 0)
    def _():
        for c in scale_copies(0, 0):
            c.start()

    def clear(b, _):
        out[pl.ds(pl.multiple_of(b * 8, 8), 8), :] = jnp.zeros((8, D),
                                                               jnp.float32)
    jax.lax.fori_loop(0, N // 8, clear, None)

    def one_tile(i, _):
        at = i % 2

        @pl.when(i + 1 < live)
        def _():
            for c in scale_copies(i + 1, 1 - at):
                c.start()
        gather_rows(i)
        x = _unpack_rows(rows_v[:, :C], x_dtype)
        xs = (jax.lax.bitcast_convert_type(rows_v[:, C:], jnp.float32)[:, :1]
              if quantized else None)

        def up(j, amax):
            q = i * n_blocks + j
            slot = q % 2
            for c in up_copies(q, slot):
                c.wait()
            if quantized:
                def product(n):
                    acc = jax.lax.dot_general(
                        x, gu_v[slot, n], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.int32)
                    return (acc.astype(jnp.float32) * xs
                            * sgu_v[slot, n]).astype(dtype)
            else:
                def product(n):
                    return jnp.dot(x, gu_v[slot, n],
                                   preferred_element_type=jnp.float32
                                   ).astype(dtype)
            hidden = _gated(act, product(0), product(1), limit)
            start_if_live(up_copies, q + 2)
            h_v[j] = hidden
            return jnp.maximum(amax, jnp.max(
                jnp.abs(hidden.astype(jnp.float32)), axis=-1, keepdims=True))
        amax = jax.lax.fori_loop(0, n_blocks, up,
                                 jnp.zeros((tile, 1), jnp.float32))
        # ``quant._dynamic_int8`` of the hidden rows, over the whole width.
        hs = jnp.maximum(amax, 1e-30) / 127.0

        def down(j, _):
            q = i * n_blocks + j
            slot = q % 2
            for c in down_copies(q, slot):
                c.wait()
            if quantized:
                hq = jnp.clip(jnp.round(h_v[j].astype(jnp.float32) / hs),
                              -127, 127).astype(jnp.int8)
                part = jax.lax.dot_general(
                    hq, dn_v[slot], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
            else:
                part = jnp.dot(h_v[j], dn_v[slot],
                               preferred_element_type=jnp.float32)

            @pl.when(j == 0)
            def _():
                acc_v[...] = part

            @pl.when(j > 0)
            def _():
                acc_v[...] += part
            start_if_live(down_copies, q + 2)
        jax.lax.fori_loop(0, n_blocks, down, None)

        for c in scale_copies(i, at):
            c.wait()
        if quantized:
            y = acc_v[...].astype(jnp.float32) * hs * sdn_v[at]
        else:
            y = acc_v[...]
        # The expert's result in the rows' dtype, as ``mm`` returns it.
        y_v[...] = y.astype(dtype).astype(jnp.float32)
        add_rows(i)
    jax.lax.fori_loop(0, live, one_tile, None)


def resident_bytes(N: int, D: int, itemsize: int) -> int:
    """What a call of ``N`` rows keeps in fast memory for the whole
    kernel: its packed rows (a zero row and 128 lanes of scale more) and
    its float32 result (whole sublanes of 8 rows)."""
    return (N + 1) * (D * itemsize + 512) + -(-N // 8) * 8 * D * 4


def rows_that_fit(D: int, F: int, w_itemsize: int, x_itemsize: int) -> int:
    """The most rows a call may have so that six blocks of at least a
    quarter of the expert width still fit beside them."""
    least = 6 * D * max(F // 4, min(F, 128)) * w_itemsize
    a_row = resident_bytes(8, D, x_itemsize) - resident_bytes(0, D, x_itemsize)
    return max(8, (VMEM_LIMIT - FIXED_BYTES - least) // a_row * 8)


def grouped_experts(meta: jax.Array, tile_expert: jax.Array,
                    row_token: jax.Array, row_weight: jax.Array, src: tuple,
                    mats: tuple, *, tile: int, act: str, dtype,
                    limit: float = 0.0, interpret: bool | None = None
                    ) -> jax.Array:
    """The held experts' weighted results, summed a token: float32 [N, D].
    ``meta`` int32 [2]: the live tiles and the period to read;
    ``tile_expert`` [n_tiles]; ``row_token``, ``row_weight``
    [n_tiles * tile] (``GroupedLayout``, and each row's routing weight);
    ``src``: the call's ``N`` rows and a zero row, (int8 [N+1, D], float32
    scales [N+1, 1]) for int8 matrices or (plain [N+1, D],); ``mats``:
    gate, up, down over periods and experts, each ([P, E, din, dout] int8,
    [P, E, dout] float32) flattened in that order, or plain
    [P, E, din, dout]; ``dtype``: what the rows were before they were
    quantised — an expert's hidden rows and result are rounded to it;
    ``limit``: the gated product's clamp (0: none)."""
    quantized = len(src) == 2
    wg = mats[0]
    D, F = wg.shape[-2:]
    N = src[0].shape[0] - 1
    x32 = pack_rows(*src)
    if quantized:   # a row of scales an expert: a matrix the chip can slice
        mats = tuple(a if n % 2 == 0 else a[:, :, None, :]
                     for n, a in enumerate(mats))
    n_blocks = width_blocks(
        D, F, wg.dtype.itemsize, VMEM_LIMIT - FIXED_BYTES
        - resident_bytes(N, D, src[0].dtype.itemsize))
    Fb = F // n_blocks
    if interpret is None:
        interpret = (jax.default_backend() != "tpu"
                     or not mosaic_can_take(D, F, tile))
    scratch = [pltpu.VMEM((tile, x32.shape[1]), jnp.int32),
               pltpu.VMEM((2, 2, D, Fb), wg.dtype)]
    if quantized:
        scratch.append(pltpu.VMEM((2, 2, 1, Fb), jnp.float32))
    scratch.append(pltpu.VMEM((2, Fb, D), wg.dtype))
    if quantized:
        scratch.append(pltpu.VMEM((2, 1, D), jnp.float32))
    scratch += [pltpu.VMEM((n_blocks, tile, Fb), dtype),
                pltpu.VMEM((tile, D), jnp.int32 if quantized
                           else jnp.float32),
                pltpu.VMEM((tile, D), jnp.float32),
                pltpu.SemaphoreType.DMA((3, 2))]
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, tile=tile, n_blocks=n_blocks, act=act,
                          limit=limit,
                          quantized=quantized, x_dtype=src[0].dtype),
        out_shape=jax.ShapeDtypeStruct((-(-N // 8) * 8, D), jnp.float32),
        in_specs=([smem] * 4 + [vmem]
                  + [pl.BlockSpec(memory_space=pl.ANY)] * len(mats)),
        out_specs=vmem,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        name="grouped_experts",
        interpret=interpret,
    )(meta, tile_expert, row_token, row_weight, x32, *mats)
    return out[:N]
