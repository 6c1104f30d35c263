"""Ring attention: causal self-attention with the sequence axis sharded
over the mesh, exchanging K/V blocks around the ring via ``ppermute``.

Long-context prefill support (SURVEY.md §2b "Sequence/Context Parallelism"
row, §5 "long-context"): a prompt longer than one chip's HBM/FLOP budget is
sharded ``[B, T/n, ...]`` per chip; each chip keeps its query block resident
and sees every K/V block exactly once as blocks rotate n-1 hops around the
ring (neighbor exchange — on TPU this rides ICI, overlapping each hop with
the current block's compute; cf. the blockwise-attention papers in
PAPERS.md, re-derived). Online softmax (m/l/acc running triple) makes the
result exact, not approximate.

The reference has no counterpart — sequence length is the upstream
vendor's problem there (SURVEY.md §5). Here it is a first-class op usable
standalone (tested against dense attention on a virtual CPU mesh) and as
the prefill attention for a sequence-sharded engine.

No reference-repo code involved; collective structure is textbook ring
parallelism expressed with ``shard_map`` + ``jax.lax.ppermute``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _block_attn_accum(q, k, v, q_off, k_off, m, l, acc, *, causal: bool):
    """One K/V block's contribution under online softmax.

    q: [B, Tq, H, Dh]; k/v: [B, Tk, KV, Dh]; q_off/k_off: scalar global
    offsets of the blocks; m/l: [B, H, Tq, 1]; acc: [B, H, Tq, Dh].
    Fully-masked entries contribute exactly zero (explicit mask multiply —
    the classic exp(0)=1 hazard when a block is entirely invisible).
    """
    B, Tq, H, Dh = q.shape
    KV = k.shape[2]
    group = H // KV
    kh = jnp.repeat(k, group, axis=2)          # [B, Tk, H, Dh]
    vh = jnp.repeat(v, group, axis=2)

    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kh.astype(jnp.float32))
    scores *= Dh ** -0.5                        # [B, H, Tq, Tk]

    if causal:
        q_pos = q_off + jnp.arange(Tq)[:, None]         # [Tq, 1]
        k_pos = k_off + jnp.arange(k.shape[1])[None, :]  # [1, Tk]
        mask = (k_pos <= q_pos)[None, None]              # [1, 1, Tq, Tk]
        scores = jnp.where(mask, scores, NEG_INF)
    else:
        mask = jnp.ones((1, 1, Tq, k.shape[1]), bool)

    m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new) * mask          # zero where invisible
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = alpha * acc + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vh.astype(jnp.float32))
    return m_new, l_new, acc_new


def _ring_body(q, k, v, *, axis: str, causal: bool):
    """Per-shard ring loop (runs inside shard_map, manual over `axis`)."""
    B, Tl, H, Dh = q.shape
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    q_off = idx * Tl

    m = jnp.full((B, H, Tl, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, Tl, 1), jnp.float32)
    acc = jnp.zeros((B, H, Tl, Dh), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(s, carry):
        k_blk, v_blk, m, l, acc = carry
        # At step s this shard holds the block that started on shard idx-s.
        owner = (idx - s) % n
        m, l, acc = _block_attn_accum(
            q, k_blk, v_blk, q_off, owner * Tl, m, l, acc, causal=causal)
        # Rotate for the next step (skipped result on the last iteration is
        # harmless; keeping the permute inside the loop lets XLA overlap it
        # with this step's compute).
        k_nxt = jax.lax.ppermute(k_blk, axis, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis, perm)
        return k_nxt, v_nxt, m, l, acc

    _, _, m, l, acc = jax.lax.fori_loop(0, n, step, (k, v, m, l, acc))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)   # [B, Tl, H, Dh]


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis: str = "seq", causal: bool = True) -> jax.Array:
    """Exact causal attention with sequence sharded on ``axis``.

    q: [B, T, H, Dh]; k/v: [B, T, KV, Dh] (GQA OK) — T sharded over
    ``axis``; every other dim replicated or GSPMD-managed. Returns
    [B, T, H, Dh] with the same sequence sharding.
    """
    n = mesh.shape[axis]
    if q.shape[1] % n:
        raise ValueError(f"sequence {q.shape[1]} not divisible by "
                         f"{axis}={n}")
    body = functools.partial(_ring_body, axis=axis, causal=causal)
    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        axis_names={axis}, check_vma=False)
    return f(q, k, v)
