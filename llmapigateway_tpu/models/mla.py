"""Latent attention (MLA): ONE attention in two forms that must agree.

Per token, with ``x' = norm(x)`` (``norm``: the config's ``norm_kind``, for
the block's input and both bottlenecks)::

    c_q = rmsnorm(x' W_qa)                    [q_lora_rank]
    q_h = c_q W_qb -> [q_nope_h | q_rope_h]   per head
    [c_kv | k_r] = x' W_kva;  c = rmsnorm(c_kv)
    [k_nope_h | v_h] = c W_kvb                per head; k_r is shared
    score_h(t, s) = a_t sigma (q_nope_h(t) . k_nope_h(s)
                               + rope(q_rope_h)(t) . rope(k_r)(s))
    out = concat_h(sum_s p_h(t, s) v_h(s)) W_o
          (under ``attn_gate``: the concatenation times sigmoid(x' W_g),
          per element, before W_o)

Rotary on ``q_rope`` and ``k_r`` only (YaRN by parts where the config says
so, pairs ``(2i, 2i+1)`` under ``rope_interleave``); ``sigma`` is
``(d_nope + d_rope)^-1/2`` times YaRN's softmax scale, ``a_t`` the
position-scaled query factor ``1 + beta ln(1 + floor(t / original))``.

What is cached is ``[c | rope(k_r)]`` — ``latent_width`` numbers a token
(ops/latent_attention.py). The EXPANDED form rebuilds each visible key
and value from the cached latent through ``W_kvb``; the ABSORBED form
multiplies ``W_kvb``'s key half into the queries (``q~_h = q_nope_h
W_uk,h^T``) and its value half into the outputs (``(sum_s p c(s))
W_uv,h``), so all heads attend one ``latent_width``-wide key whose first
``kv_lora_rank`` numbers are also the value. The absorbed form serves both
step programs through the kernel (a decode step and a prefill chunk: see
``mla_block``); the expanded form is the reference path's.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .llama import block_norm, rope_tables
from .quant import mm

Params = dict[str, Any]
# Stored int8 under quant: the four projections a token's activations
# meet. ``wkvb`` stays in the compute dtype: the absorbed form multiplies
# it into queries and outputs a head at a time.
QUANT_KEYS = frozenset({"wqa", "wqb", "wkva", "wo", "wgate"})


def init_layer(c: ModelConfig, keys, dense: Callable, dtype) -> Params:
    """One latent layer's attention weights but for the block's own norm,
    which the caller draws with the other layers' (``dense(key, *shape,
    scale=, name=)`` draws a matrix and quantises it where its name says so):
      wqa [D, rq], q_norm [rq], wqb [rq, H (dn + dr)],
      wkva [D, r + dr], kv_norm [r], wkvb [r, H, dn + dv],
      wo [H dv, D] (scaled as every projection into the residual),
      wgate [D, H dv] under ``attn_gate``. A norm's weight is drawn at
      gain 1: ones, or zeros where the gain is ``2 sigmoid(w)``."""
    D, H = c.d_model, c.n_heads
    dn, dr, dv = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
    back = (2 * c.n_layers) ** -0.5
    unit = jnp.zeros if c.norm_kind == "rms_2sigmoid" else jnp.ones
    gate = {"wgate": dense(jax.random.fold_in(keys[3], 1), D, H * dv,
                           name="wgate")} if c.attn_gate else {}
    return {**gate,
            "wqa": dense(keys[0], D, c.q_lora_rank, name="wqa"),
            "q_norm": unit((c.q_lora_rank,), dtype),
            "wqb": dense(keys[1], c.q_lora_rank, H * (dn + dr), name="wqb"),
            "wkva": dense(keys[2], D, c.kv_lora_rank + dr, name="wkva"),
            "kv_norm": unit((c.kv_lora_rank,), dtype),
            "wkvb": dense(keys[3], c.kv_lora_rank, H * (dn + dv)
                          ).reshape(c.kv_lora_rank, H, dn + dv),
            "wo": dense(keys[4], H * dv, D, scale=back, name="wo")}


def softmax_scale(c: ModelConfig) -> float:
    """``sigma``: the head's inverse root width times YaRN's softmax scale."""
    scale = (c.qk_nope_head_dim + c.qk_rope_head_dim) ** -0.5
    if c.rope_scaling is not None:
        scale *= c.rope_scaling.softmax_mscale
    return scale


def query_scale(positions: jax.Array, c: ModelConfig) -> jax.Array:
    """``a_t sigma`` [B, T] float32 for queries at ``positions``."""
    scale = jnp.full(positions.shape, softmax_scale(c), jnp.float32)
    if c.query_scale_beta and c.rope_scaling is not None:
        turns = positions // c.rope_scaling.original_max_seq
        scale = scale * (1.0 + c.query_scale_beta
                         * jnp.log1p(turns.astype(jnp.float32)))
    return scale


def rotate(x: jax.Array, cos: jax.Array, sin: jax.Array,
           interleave: bool) -> jax.Array:
    """x [B, T, N, d] float32 rotated by cos/sin [B, T, d/2]; pairs
    ``(2i, 2i+1)`` under ``interleave``, else ``(i, i + d/2)``. The result
    lies [first of each pair | second of each pair] either way: queries
    and keys share the order, which is all a dot product asks."""
    half = x.shape[-1] // 2
    a, b = (x[..., 0::2], x[..., 1::2]) if interleave else (
        x[..., :half], x[..., half:])
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def project(h: jax.Array, lp: Params, c: ModelConfig, lengths: jax.Array
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """h [B, T, D] (the NORMED block input) at positions ``lengths + t`` ->
    (q_nope [B, T, H, dn], q_rope [B, T, H, dr], both float32 with ``a_t
    sigma`` multiplied in; the token's cache row ``[c | rope(k_r)]``
    [B, T, latent_width] in h's dtype)."""
    B, T, _ = h.shape
    H, dn, dr = c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim
    q = mm(block_norm(mm(h, lp["wqa"]), lp["q_norm"], c), lp["wqb"])
    q = q.reshape(B, T, H, dn + dr).astype(jnp.float32)
    kva = mm(h, lp["wkva"])
    latent = block_norm(kva[..., :c.kv_lora_rank], lp["kv_norm"], c)
    positions = lengths[:, None] + jnp.arange(T)[None, :]
    cos, sin = rope_tables(positions, dr, c.rope_theta, c.rope_scaling)
    scale = query_scale(positions, c)[:, :, None, None]
    q_rope = rotate(q[..., dn:], cos, sin, c.rope_interleave) * scale
    k_rope = rotate(kva[..., None, c.kv_lora_rank:].astype(jnp.float32),
                    cos, sin, c.rope_interleave)[:, :, 0]
    row = jnp.concatenate([latent, k_rope.astype(h.dtype)], axis=-1)
    return q[..., :dn] * scale, q_rope, row


def absorb_queries(q_nope: jax.Array, q_rope: jax.Array, wkvb: jax.Array,
                   dtype) -> jax.Array:
    """-> [B, T, H, r + dr]: ``[q_nope_h W_uk,h^T | q_rope_h]``."""
    dn = q_nope.shape[-1]
    q_lat = jnp.einsum("bthn,chn->bthc", q_nope.astype(dtype),
                       wkvb[..., :dn].astype(dtype),
                       preferred_element_type=jnp.float32)
    return jnp.concatenate([q_lat, q_rope], axis=-1).astype(dtype)


def expand_values(out_latent: jax.Array, wkvb: jax.Array, dn: int
                  ) -> jax.Array:
    """[B, T, H, r] -> [B, T, H dv]: ``(sum_s p c(s)) W_uv,h``."""
    B, T = out_latent.shape[:2]
    out = jnp.einsum("bthc,chv->bthv", out_latent,
                     wkvb[..., dn:].astype(out_latent.dtype),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, T, -1).astype(out_latent.dtype)


def expanded_attention(q_nope: jax.Array, q_rope: jax.Array,
                       dense: jax.Array, wkvb: jax.Array,
                       lengths: jax.Array, c: ModelConfig) -> jax.Array:
    """The expanded form over the gathered cache rows ``dense`` [B, S,
    latent_width]: K and V of every cached token rebuilt through ``W_kvb``,
    float32 -> [B, T, H dv] float32."""
    from ..ops.latent_attention import causal_softmax
    r, dn = c.kv_lora_rank, c.qk_nope_head_dim
    dense = dense.astype(jnp.float32)
    kv = jnp.einsum("bsc,chx->bshx", dense[..., :r],
                    wkvb.astype(jnp.float32))
    scores = (jnp.einsum("bthn,bshn->bhts", q_nope, kv[..., :dn])
              + jnp.einsum("bthr,bsr->bhts", q_rope, dense[..., r:]))
    probs = causal_softmax(scores, lengths)
    out = jnp.einsum("bhts,bshv->bthv", probs, kv[..., dn:])
    return out.reshape(*out.shape[:2], -1)


def mla_block(x: jax.Array, lp: Params, c: ModelConfig, pool: jax.Array,
              layer: jax.Array, fn: Any, lengths: jax.Array,
              active: jax.Array | None) -> tuple[jax.Array, jax.Array]:
    """x [B, T, D] -> (the branch MLA(norm(x)), which the caller adds
    to the stream, the pool with the call's rows written into layer
    ``layer``). ``fn``: the group's
    ``ops.latent_attention.LatentAttention``. Insert, then attend: the
    call's own keys are read back as the bytes that were written. A row
    that is not ``active`` writes to the trash page and attends from
    position 0; what it returns is not looked at.

    Both step programs attend in the absorbed form where ``fn`` has the
    kernel: at chunk width it costs 576 multiply-adds a (query, key, head)
    where the expanded form costs 256 and a rebuild of every visible key
    and value a chunk, but it reads the pool's pages in place and needs no
    kernel of its own (PERF.md section 5 has both measured)."""
    dn = c.qk_nope_head_dim
    start = lengths if active is None else jnp.where(active, lengths, 0)
    h = block_norm(x, lp["norm"], c)
    q_nope, q_rope, row = project(h, lp, c, start)
    pool = fn.write(pool, row, layer, lengths, active)
    if fn.absorbed:
        q = absorb_queries(q_nope, q_rope, lp["wkvb"], x.dtype)
        out = expand_values(
            fn.attend(q, pool, layer, start, c.kv_lora_rank), lp["wkvb"], dn)
    else:
        out = expanded_attention(q_nope, q_rope, fn.gather(pool, layer),
                                 lp["wkvb"], start, c).astype(x.dtype)
    if c.attn_gate:
        gate = jax.nn.sigmoid(mm(h, lp["wgate"]).astype(jnp.float32))
        out = (out.astype(jnp.float32) * gate).astype(x.dtype)
    return mm(out, lp["wo"]), pool
