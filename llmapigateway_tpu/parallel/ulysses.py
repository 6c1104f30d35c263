"""Ulysses-style sequence parallelism: all-to-all head↔sequence resharding.

The alternative to ring attention when heads ≥ chips (SURVEY.md §2b
"Ulysses-style attention" row): instead of rotating K/V blocks n-1 hops,
ONE ``all_to_all`` converts the sharding from sequence-split (each chip has
``T/n`` tokens of every head) to head-split (each chip has every token of
``H/n`` heads), plain full-sequence attention runs locally per head group,
and a second ``all_to_all`` restores sequence sharding. Two collectives
total — cheaper than a ring when the sequence is long but heads divide
evenly; not applicable when KV heads < chips (ring handles that case).

No reference counterpart (the reference has no parallelism of any kind —
SURVEY.md §2b); pattern follows the public DeepSpeed-Ulysses idea,
expressed TPU-natively with ``shard_map`` + ``jax.lax.all_to_all``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _dense_causal(q, k, v, *, causal: bool):
    """Plain attention, local shapes [B, T, h, Dh] / [B, T, kv, Dh]."""
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    group = H // KV
    kh = jnp.repeat(k, group, axis=2)
    vh = jnp.repeat(v, group, axis=2)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kh.astype(jnp.float32))
    scores *= Dh ** -0.5
    if causal:
        q_pos = jnp.arange(T)[:, None]
        k_pos = jnp.arange(T)[None, :]
        scores = jnp.where((k_pos <= q_pos)[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, vh.astype(jnp.float32))
    return out.astype(q.dtype)


def _ulysses_body(q, k, v, *, axis: str, causal: bool):
    """Inside shard_map: local q [B, T/n, H, Dh] → attention → same shape."""
    # seq-sharded → head-sharded: split heads (axis 2) across the group,
    # gather sequence (axis 1).
    qh = jax.lax.all_to_all(q, axis, split_axis=2, concat_axis=1, tiled=True)
    kh = jax.lax.all_to_all(k, axis, split_axis=2, concat_axis=1, tiled=True)
    vh = jax.lax.all_to_all(v, axis, split_axis=2, concat_axis=1, tiled=True)
    out = _dense_causal(qh, kh, vh, causal=causal)     # [B, T, H/n, Dh]
    # head-sharded → seq-sharded.
    return jax.lax.all_to_all(out, axis, split_axis=1, concat_axis=2,
                              tiled=True)


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                      axis: str = "seq", causal: bool = True) -> jax.Array:
    """Exact attention with sequence sharded on ``axis`` via all-to-all.

    q: [B, T, H, Dh]; k/v: [B, T, KV, Dh], T sharded over ``axis``.
    Requires H % n == 0 and KV % n == 0 (n = mesh axis size) — use
    :func:`..parallel.ring_attention.ring_attention` otherwise.
    """
    n = mesh.shape[axis]
    H, KV = q.shape[2], k.shape[2]
    if q.shape[1] % n:
        raise ValueError(f"sequence {q.shape[1]} not divisible by {axis}={n}")
    if H % n or KV % n:
        raise ValueError(
            f"Ulysses needs heads divisible by the mesh axis (H={H}, "
            f"KV={KV}, {axis}={n}); use ring_attention for KV < chips")
    body = functools.partial(_ulysses_body, axis=axis, causal=causal)
    f = shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis, None, None),) * 3,
        out_specs=P(None, axis, None, None),
        axis_names={axis}, check_vma=False)
    return f(q, k, v)
