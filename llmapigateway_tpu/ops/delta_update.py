"""The delta rule's one-token update as ONE in-place Pallas kernel.

A decode step of a linear-attention layer (``models/hybrid.py``) moves
nothing that matters but its float32 state: ``B x H`` tiles of ``[dk, dv]``
(32 x 64 x 64 KB a layer at the published widths). The mathematics needs
one read and one write of a tile::

    s_dec = s * a[:, None]                  a = e^{log_a}, a decay a channel
    r_k   = sum_c s_dec[c] k[c]             r_q likewise with q
    u     = b (v - r_k)
    o     = r_q + u (k . q)
    s_new = s_dec + k[:, None] u[None, :]

and this kernel does exactly that: the state block is the STACKED block of a
cache entry, ``[layers, B, H, dk, dv]``, which is the kernel's operand AND
its result (``input_output_aliases``); a grid step takes one row's block of
``heads_per_step`` heads of layer ``at`` (a scalar the block specs read),
holds each tile in registers between its one load and its one store, and
writes it where it came from. A row whose ``keep`` is False is written back
as it was read: bit-identical. The other layers of the stack are never
touched. Everything is float32, multiply-and-sum in the order of
``hybrid.delta_step`` (the plain form this kernel is held to).

What lies ALONG a tile's rows (``a``, ``k``, ``q``: a number a channel)
reaches the kernel transposed — channels down the sublanes, one column a
head — so that a tile broadcasts a column across its lanes and nothing is
transposed on the chip; what lies along its columns (``v``, and ``b`` and
``k . q`` spread over them) arrives as rows. Both are packed outside, in
XLA, on the call's own few hundred KB.

Off the chip, and at widths the chip's compiler would refuse
(:func:`mosaic_can_take`: the tests' toy presets), the same kernel runs
interpreted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import paged_attention as _paged

# A grid step moves this much of the state each way: well past the ~0.35 us
# a step costs (at one 64 KB tile a step the steps would cost more than the
# stream), and twice over, in and out, inside the compiler's default 16 MiB.
STEP_BYTES = 2 ** 20


def heads_per_step(H: int, dk: int, dv: int) -> int:
    """The heads of one row a grid step takes: the most that divide ``H``
    and keep the step's block within ``STEP_BYTES``."""
    fit = max(1, STEP_BYTES // (dk * dv * 4))
    return max(d for d in range(1, H + 1) if H % d == 0 and d <= fit)


def mosaic_can_take(H: int, dk: int, dv: int) -> bool:
    """The shapes the chip's compiler takes: whole lanes of 128 across a
    tile, whole sublanes of 8 down it, and a step's heads whole sublanes of
    the row-vectors' blocks (or all of them)."""
    hb = heads_per_step(H, dk, dv)
    return dv % 128 == 0 and dk % 8 == 0 and (hb % 8 == 0 or hb == H)


def _kernel(at_ref, keep_ref, cols_ref, rows_ref, s_ref, o_ref, out_ref, *,
            hb: int):
    """Program ``(row b, head block j)``. cols [1, 1, dk, 3 hb]: column
    ``n hb + i`` is head ``i``'s ``(a, k, q)[n]``; rows [1, 3, hb, dv]:
    ``(v, b, k . q)``, the last two spread over ``dv``; s, out
    [1, 1, hb, dk, dv]: the same bytes of the stack; o [1, hb, dv]."""
    del at_ref                              # the block specs read it
    kept = keep_ref[pl.program_id(0)] != 0
    cols = cols_ref[0, 0]
    for i in range(hb):
        a, k, q = (cols[:, n * hb + i:n * hb + i + 1] for n in range(3))
        v, b, kq = (rows_ref[0, n, pl.ds(i, 1), :] for n in range(3))
        s = s_ref[0, 0, i]
        s_dec = s * a
        r_k = jnp.sum(s_dec * k, axis=0, keepdims=True)
        r_q = jnp.sum(s_dec * q, axis=0, keepdims=True)
        u = b * (v - r_k)
        o_ref[0, pl.ds(i, 1), :] = r_q + u * kq
        out_ref[0, 0, i] = jnp.where(kept, s_dec + k * u, s)


def delta_update(state: jax.Array, at: jax.Array | int, q: jax.Array,
                 k: jax.Array, v: jax.Array, log_a: jax.Array,
                 beta: jax.Array, keep: jax.Array, *,
                 interpret: bool | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """One token of every row, on layer ``at`` of a stacked state block.
    ``state`` [layers, B, H, dk, dv] float32; q, k [B, H, dk] (normalised,
    q scaled), v [B, H, dv], ``log_a`` [B, H, dk] (a decay a channel) or
    [B, H, 1] (ONE a head, spread over its channels here), beta [B, H],
    keep [B] bool. Returns (o [B, H, dv], the block): the block IS the
    operand, layer ``at`` updated where ``keep``, every other byte as it
    was."""
    _, B, H, dk, dv = state.shape
    hb = heads_per_step(H, dk, dv)
    f32 = jnp.float32
    a = jnp.broadcast_to(jnp.exp(log_a.astype(f32)), (B, H, dk))
    # [B, H, 3, dk] -> [B, H / hb, dk, 3 hb]
    cols = jnp.stack([a, k.astype(f32), q.astype(f32)], axis=2).reshape(
        B, H // hb, hb, 3, dk).transpose(0, 1, 4, 3, 2).reshape(
        B, H // hb, dk, 3 * hb)
    spread = lambda x: jnp.broadcast_to(x.astype(f32)[..., None], (B, H, dv))
    rows = jnp.stack([v.astype(f32), spread(beta),
                      spread(jnp.sum(k * q, axis=-1))], axis=1)
    if interpret is None:
        interpret = (_paged._interpret_default()
                     or not mosaic_can_take(H, dk, dv))
    block = pl.BlockSpec((1, 1, hb, dk, dv),
                         lambda b, j, at, keep: (at[0], b, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_kernel, hb=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // hb),
            in_specs=[
                pl.BlockSpec((1, 1, dk, 3 * hb),
                             lambda b, j, at, keep: (b, j, 0, 0)),
                pl.BlockSpec((1, 3, hb, dv),
                             lambda b, j, at, keep: (b, 0, j, 0)),
                block],
            out_specs=[pl.BlockSpec((1, hb, dv),
                                    lambda b, j, at, keep: (b, j, 0)),
                       block]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # Operand 4 (after the two scalar vectors, cols and rows) is
        # result 1.
        input_output_aliases={4: 1},
        # The block twice over, in and out, and room for the rest.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=4 * hb * dk * dv * 4 + 8 * 2 ** 20),
        name="delta_update",
        interpret=interpret,
    )(jnp.asarray(at, jnp.int32).reshape(1), keep.astype(jnp.int32), cols,
      rows, state)
    return o, state
