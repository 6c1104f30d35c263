"""Llama-family decoder as pure JAX functions.

TPU-first design decisions (not a port of any torch module structure):

* **Stacked layer parameters + ``lax.scan`` over layers** — one compiled
  layer body regardless of depth, keeping compile time flat for 80-layer
  models and letting GSPMD treat every layer identically.
* **One forward for prefill and decode** — tokens ``[B, T]`` with ``T`` the
  prefill chunk (or 1 for decode) against a fixed-shape KV cache, so XLA
  compiles exactly two programs (per bucket) and shapes never depend on data.
* **Pluggable attention** — the cache-attention inner op is an argument, so
  the reference jnp implementation and the Pallas paged kernel interchange
  without touching model code.
* bfloat16 params/activations by default (MXU-native), fp32 for RMSNorm
  accumulation, rotary tables, and logits.

Covers Llama 1/2/3 and TinyLlama (GQA via ``n_kv_heads``), and provides the
attention/norm blocks Mixtral reuses (models/mixtral.py).
"""
from __future__ import annotations

import math
from functools import lru_cache, partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .quant import head_matmul, mm

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16) -> Params:
    """Random-init params in the stacked-layer layout.

    Layout (leaf shapes; L = n_layers, D = d_model, H/KV = heads, Dh = head
    dim, F = d_ff, V = vocab):
      embed [V, D]; final_norm [D]; lm_head [V, D] (absent if tied)
      layers/{attn_norm [L,D], wq [L,D,H*Dh], wk [L,D,KV*Dh], wv [L,D,KV*Dh],
              wo [L,H*Dh,D], mlp_norm [L,D], wg [L,D,F], wu [L,D,F], wd [L,F,D]}
    """
    c = config
    keys = jax.random.split(key, 10)
    dh = c.head_dim

    def norm_init(*shape):
        return jnp.ones(shape, dtype=dtype)

    def dense_init(k, *shape):
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        scale = 1.0 / jnp.sqrt(fan_in)
        return (jax.random.normal(k, shape, dtype=jnp.float32) * scale).astype(dtype)

    params: Params = {
        "embed": dense_init(keys[0], c.vocab_size, c.d_model),
        "final_norm": norm_init(c.d_model),
        "layers": {
            "attn_norm": norm_init(c.n_layers, c.d_model),
            "wq": dense_init(keys[1], c.n_layers, c.d_model, c.n_heads * dh),
            "wk": dense_init(keys[2], c.n_layers, c.d_model, c.n_kv_heads * dh),
            "wv": dense_init(keys[3], c.n_layers, c.d_model, c.n_kv_heads * dh),
            "wo": dense_init(keys[4], c.n_layers, c.n_heads * dh, c.d_model),
            "mlp_norm": norm_init(c.n_layers, c.d_model),
            "wg": dense_init(keys[5], c.n_layers, c.d_model, c.d_ff),
            "wu": dense_init(keys[6], c.n_layers, c.d_model, c.d_ff),
            "wd": dense_init(keys[7], c.n_layers, c.d_ff, c.d_model),
        },
    }
    if c.attn_bias:
        # Qwen2-family QKV bias. Random (not zero) init so random-weight
        # tests exercise the bias path end to end.
        bkeys = jax.random.split(keys[9], 3)
        params["layers"]["bq"] = dense_init(
            bkeys[0], c.n_layers, c.n_heads * dh)
        params["layers"]["bk"] = dense_init(
            bkeys[1], c.n_layers, c.n_kv_heads * dh)
        params["layers"]["bv"] = dense_init(
            bkeys[2], c.n_layers, c.n_kv_heads * dh)
    if not c.tie_embeddings:
        params["lm_head"] = dense_init(keys[8], c.vocab_size, c.d_model)
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             offset: float = 0.0) -> jax.Array:
    """RMSNorm with fp32 accumulation (bf16 variance underflows).
    ``offset``: Gemma parameterizes the scale as ``(1 + w)`` (HF
    GemmaRMSNorm); llama/qwen2 use plain ``w`` (offset 0)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (offset + weight.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x: jax.Array, weight: jax.Array, eps: float,
               bias: jax.Array | None = None) -> jax.Array:
    """LayerNorm, float32 inside: ``w (x - mean) / sqrt(var + eps)`` over
    the last axis (``+ bias`` where the family's norm has one), in ``x``'s
    dtype."""
    xf = x.astype(jnp.float32)
    xc = xf - jnp.mean(xf, axis=-1, keepdims=True)
    normed = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True)
                                + eps)
    normed = normed * weight.astype(jnp.float32)
    if bias is not None:
        normed = normed + bias.astype(jnp.float32)
    return normed.astype(x.dtype)


def block_norm(x: jax.Array, weight: jax.Array, c: ModelConfig) -> jax.Array:
    """The period families' norm of ``ModelConfig.norm_kind`` (a block's,
    a bottleneck's or the head's input): "rms", "layernorm" (no bias), or
    "rms_2sigmoid" — an RMS norm whose gain is ``2 sigmoid(w)``, 1 at the
    raw weight 0."""
    if c.norm_kind == "layernorm":
        return layer_norm(x, weight, c.layer_norm_eps)
    if c.norm_kind == "rms_2sigmoid":
        return rms_norm(x, 2.0 * jax.nn.sigmoid(weight.astype(jnp.float32)),
                        c.rms_eps)
    return rms_norm(x, weight, c.rms_eps)


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                scaling=None) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables [..., head_dim/2] (fp32) for given absolute positions.
    ``scaling`` is an optional ``config.RopeScaling`` — without it a modern
    Llama-3.1-style checkpoint would silently load with wrong RoPE."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    if scaling is not None:
        freqs = _scale_rope_freqs(freqs, scaling, theta)
    angles = positions.astype(jnp.float32)[..., None] * freqs   # [..., half]
    if scaling is not None and scaling.table_mscale != 1.0:
        return (jnp.cos(angles) * scaling.table_mscale,
                jnp.sin(angles) * scaling.table_mscale)
    return jnp.cos(angles), jnp.sin(angles)


def _scale_rope_freqs(freqs: jax.Array, scaling,
                      theta: float = 10000.0) -> jax.Array:
    """Apply HF-convention rope_scaling to the inverse-frequency vector
    (matches transformers' _compute_llama3_parameters numerics; ``yarn``:
    its _compute_yarn_parameters, the correction range floored and ceiled)."""
    if scaling.rope_type == "linear":
        return freqs / scaling.factor
    if scaling.rope_type == "yarn":
        half = freqs.shape[0]

        def pair_turning(turns: float) -> float:
            """The (fractional) pair index that turns ``turns`` times
            inside the original context."""
            return (half * math.log(scaling.original_max_seq
                                    / (turns * 2.0 * math.pi))
                    / math.log(theta))
        low = max(math.floor(pair_turning(scaling.beta_fast)), 0)
        high = min(math.ceil(pair_turning(scaling.beta_slow)), half - 1)
        ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                        / max(high - low, 1e-3), 0.0, 1.0)
        return freqs / scaling.factor * ramp + freqs * (1.0 - ramp)
    # llama3: long wavelengths (beyond the original context's low-freq band)
    # are slowed by `factor`; short ones kept; the middle band interpolates.
    old_ctx = float(scaling.original_max_seq)
    low_wavelen = old_ctx / scaling.low_freq_factor
    high_wavelen = old_ctx / scaling.high_freq_factor
    wavelen = 2.0 * jnp.pi / freqs
    scaled = jnp.where(wavelen > low_wavelen, freqs / scaling.factor, freqs)
    smooth = (old_ctx / wavelen - scaling.low_freq_factor) / (
        scaling.high_freq_factor - scaling.low_freq_factor)
    smoothed = (1.0 - smooth) * freqs / scaling.factor + smooth * freqs
    is_medium = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
    return jnp.where(is_medium, smoothed, scaled)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x[..., :half], x[..., half:]) — HF llama convention.
    x: [B, T, N, Dh]; cos/sin: [B, T, half]."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out1 = xf1 * cos - xf2 * sin
    out2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([out1, out2], axis=-1).astype(x.dtype)


class KVCache(NamedTuple):
    """Dense per-slot KV cache, stacked over layers, **head-major**.

    k, v: [L, B, KV, S_max, Dh] — per-head sequence contiguous, which is
    the layout the Pallas kernels want (Mosaic blocks tile the last two
    dims: (seq_block, head_dim) = (8k, 128)-aligned) and gives the jnp
    path unit-stride reads per head too. ``lengths`` ([B], int32) — tokens
    already cached per slot — lives in the engine's batch state, not here,
    so the cache stays a plain pytree of arrays.

    With KV quantization (``kv_quant: "int8"``) each of k/v is instead the
    sub-dict ``{"q": int8 [L,B,KV,S,Dh], "s": f32 [L,B,KV,1,S]}`` —
    symmetric per-token-per-head scales, the same plain-or-quantized dict
    convention as weight quant (models/quant.py). Ordinary pytree leaves:
    the layer scan, GSPMD shardings, and row slicing all treat them
    uniformly. The scales carry a unit dim before the token axis: that is
    the rank the Pallas kernels' BlockSpecs need (trailing block dims
    ``(1, block)`` are legal under Mosaic's (8, 128) tiling rule for any
    KV — a ``[.., KV, S]`` layout would need an illegal KV-dim block of
    1), and storing it natively means NO per-call relayout of the scale
    tensors (which scales with CACHE CAPACITY, not live context — on a
    large paged pool the reshape alternative costs whole milliseconds per
    step). The jnp reference paths broadcast it for free.
    """
    k: Any
    v: Any

    @classmethod
    def create(cls, config: ModelConfig, batch: int, max_seq: int,
               dtype=jnp.bfloat16, kv_quant: str = "") -> "KVCache":
        shape = (config.n_layers, batch, config.n_kv_heads, max_seq,
                 config.head_dim)
        if kv_quant == "int8":
            def qz():
                return {"q": jnp.zeros(shape, jnp.int8),
                        "s": jnp.zeros(shape[:-2] + (1, shape[-2]),
                                       jnp.float32)}
            return cls(k=qz(), v=qz())
        return cls(k=jnp.zeros(shape, dtype=dtype),
                   v=jnp.zeros(shape, dtype=dtype))


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-token-per-head int8 over the LAST dim (Dh).
    x [..., Dh] → (int8 same shape, f32 scale [...])."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    s = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s


def insert_kv(layer_k, layer_v, k_new: jax.Array,
              v_new: jax.Array, lengths: jax.Array,
              active: jax.Array | None):
    """Insert new tokens at [lengths, lengths+T) per row of the head-major
    cache ([B, KV, S, Dh]; or its int8 ``{"q","s"}`` dict). T is static;
    offsets are data — per-row dynamic_update_slice through vmap (XLA
    lowers this efficiently on TPU). Rows with ``active=False`` are left
    untouched: their cache is owned by the prefill path. The ONE copy of
    this layout-sensitive invariant — both the jnp and the Pallas
    attention paths go through it.
    """
    # Inactive rows: instead of a full-cache `where` (which copies every
    # byte of the cache each step), route their write to the row TAIL via
    # offset clamping (dynamic_update_slice clamps start to S-T). Tail
    # positions are never visible before being rewritten: position p is only
    # attended once some step has length >= p, and that step (prefill chunk
    # or decode insert at offset p) writes p first.
    quant = isinstance(layer_k, dict)
    S = (layer_k["q"] if quant else layer_k).shape[2]
    if active is not None:
        lengths = jnp.where(active, lengths, S)

    def insert(cache_row, new_row, offset):
        # cache_row [KV, S, Dh]; new_row [T, KV, Dh] → [KV, T, Dh]
        return jax.lax.dynamic_update_slice(
            cache_row, new_row.transpose(1, 0, 2).astype(cache_row.dtype),
            (0, offset, 0))

    def insert_s(scale_row, new_row, offset):
        # scale_row [KV, 1, S]; new_row [T, KV] → [KV, 1, T]
        return jax.lax.dynamic_update_slice(
            scale_row, new_row.T[:, None, :].astype(scale_row.dtype),
            (0, 0, offset))

    if quant:
        kq, ks = quantize_kv(k_new)                  # [B,T,KV,Dh], [B,T,KV]
        vq, vs = quantize_kv(v_new)
        return (
            {"q": jax.vmap(insert)(layer_k["q"], kq, lengths),
             "s": jax.vmap(insert_s)(layer_k["s"], ks, lengths)},
            {"q": jax.vmap(insert)(layer_v["q"], vq, lengths),
             "s": jax.vmap(insert_s)(layer_v["s"], vs, lengths)},
        )
    inserted_k = jax.vmap(insert)(layer_k, k_new, lengths)
    inserted_v = jax.vmap(insert)(layer_v, v_new, lengths)
    return inserted_k, inserted_v


def insert_kv_stacked(cache_k, cache_v,
                      k_news: jax.Array, v_news: jax.Array,
                      lengths: jax.Array,
                      active: jax.Array | None):
    """Insert every layer's new tokens into the FULL stacked cache with one
    scatter — the deferred-decode half of :func:`insert_kv`.

    cache_k/v: [L, B, KV, S, Dh] (or the int8 ``{"q","s"}`` dict);
    k_news/v_news: [L, B, T, KV, Dh] (the layer scan's stacked ys, always
    bf16/fp32 — quantization happens here at write time); lengths: [B].
    One vmap(dynamic_update_slice) over B for ALL layers costs ~40× less
    than a per-layer insert inside the scan: the per-layer form lowers to
    2·L serialized TPU scatters per step (~2 ms/step at L=22), the stacked
    form to one (~0.1 ms). Inactive
    rows reuse insert_kv's clamp-to-tail trick (see there for the
    visibility argument)."""
    quant = isinstance(cache_k, dict)
    S = (cache_k["q"] if quant else cache_k).shape[3]
    if active is not None:
        lengths = jnp.where(active, lengths, S)

    def ins(ck, new, off):
        # ck [L, KV, S, Dh]; new [L, T, KV, Dh] → [L, KV, T, Dh]
        return jax.lax.dynamic_update_slice(
            ck, new.transpose(0, 2, 1, 3).astype(ck.dtype), (0, 0, off, 0))

    def ins_s(cs, new, off):
        # cs [L, KV, 1, S]; new [L, T, KV] → [L, KV, 1, T]
        return jax.lax.dynamic_update_slice(
            cs, new.transpose(0, 2, 1)[:, :, None, :].astype(cs.dtype),
            (0, 0, 0, off))

    if quant:
        kq, ks = quantize_kv(k_news)          # [L,B,T,KV,Dh], [L,B,T,KV]
        vq, vs = quantize_kv(v_news)
        vb = partial(jax.vmap, in_axes=(1, 1, 0), out_axes=1)
        return (
            {"q": vb(ins)(cache_k["q"], kq, lengths),
             "s": vb(ins_s)(cache_k["s"], ks, lengths)},
            {"q": vb(ins)(cache_v["q"], vq, lengths),
             "s": vb(ins_s)(cache_v["s"], vs, lengths)},
        )
    new_k = jax.vmap(ins, in_axes=(1, 1, 0), out_axes=1)(
        cache_k, k_news, lengths)
    new_v = jax.vmap(ins, in_axes=(1, 1, 0), out_axes=1)(
        cache_v, v_news, lengths)
    return new_k, new_v


def dense_decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           layer_k: jax.Array, layer_v: jax.Array,
                           lengths: jax.Array,
                           active: jax.Array | None = None,
                           window: int = 0) -> jax.Array:
    """Deferred-insert decode attention: one query token against the STALE
    cache prefix ``[0, lengths)`` plus the new token itself (self-column).

    Mathematically identical to insert-then-attend over ``[0, lengths]``,
    but the cache write is deferred so the layer scan never copies cache
    blocks through its ys (see :func:`insert_kv_stacked`). The two-piece
    softmax is computed explicitly (no [S+1] concat) so every S-reduction
    stays a clean sharded reduction under GSPMD for sharded caches.

    q [B,1,H,Dh]; k_new/v_new [B,1,KV,Dh]; layer_k/v [B,KV,S,Dh] (stale;
    or the int8 ``{"q","s"}`` dict — scales fold into scores/probs).
    Returns out [B, 1, H*Dh]; writes nothing.
    """
    B, T, H, Dh = q.shape
    KV = k_new.shape[2]
    lk, ks, lv, vs = _kv_dequant_views(layer_k, layer_v, q.dtype)
    S = lk.shape[2]
    G = H // KV
    scale = Dh ** -0.5

    qg = q[:, 0].reshape(B, KV, G, Dh)
    kn = k_new[:, 0]                                    # [B, KV, Dh]
    vn = v_new[:, 0].astype(jnp.float32)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, lk,
                        preferred_element_type=jnp.float32) * scale
    if ks is not None:
        scores = scores * ks          # [B,KV,1,S] broadcasts over G
    self_s = jnp.einsum("bkgd,bkd->bkg", qg, kn,
                        preferred_element_type=jnp.float32) * scale

    visible = jnp.arange(S)[None, :] < lengths[:, None]            # [B, S]
    if window:
        # Sliding window (HF Mistral semantics): the query at position
        # `lengths` sees keys j with lengths - j < window; the self
        # column is always in-window.
        visible = visible & (jnp.arange(S)[None, :]
                             > (lengths - window)[:, None])
    if active is not None:
        visible = visible & active[:, None]
    scores = jnp.where(visible[:, None, None, :], scores, -1e30)

    m = jnp.maximum(jnp.max(scores, axis=-1), self_s)              # [B,KV,G]
    p = jnp.exp(scores - m[..., None])                             # [B,KV,G,S]
    p_self = jnp.exp(self_s - m)                                   # [B,KV,G]
    l = jnp.sum(p, axis=-1) + p_self
    if vs is not None:
        p = p * vs                    # [B,KV,1,S] broadcasts over G
    out = jnp.einsum("bkgs,bksd->bkgd", p.astype(lv.dtype), lv,
                     preferred_element_type=jnp.float32)
    out = (out + p_self[..., None] * vn[:, :, None, :]) / l[..., None]
    return out.reshape(B, 1, H * Dh).astype(q.dtype)


def _kv_dequant_views(layer_k, layer_v, dtype):
    """(k, ks, v, vs) from a plain or int8-quantized cache layer. The
    per-token scale factors OUT of the Dh contraction — scores multiply by
    ``ks`` after the QK dot, probs by ``vs`` before the PV dot — so no
    dequantized [S, Dh] copy ever materializes. Scales come back in their
    stored rank-4 form ([B, KV, 1, S] — the unit dim broadcasts over G in
    the [B, KV, G, S] score layout for free)."""
    if isinstance(layer_k, dict):
        return (layer_k["q"].astype(dtype), layer_k["s"],
                layer_v["q"].astype(dtype), layer_v["s"])
    return layer_k, None, layer_v, None


def dense_verify_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                           layer_k: jax.Array, layer_v: jax.Array,
                           lengths: jax.Array,
                           active: jax.Array | None = None,
                           window: int = 0) -> jax.Array:
    """Deferred-insert BLOCK attention: T new tokens attend the STALE cache
    prefix ``[0, lengths)`` plus a causal self-block of themselves — the
    T>1 generalization of :func:`dense_decode_attention` (T=1 self-column).

    Mathematically identical to insert-then-attend over ``[0, lengths+T)``,
    but with no cache write inside the layer scan: the speculative verify
    step (engine/speculative.py, T = k+1) otherwise pays the chunk path's
    per-layer serialized scatters every step. Two-piece online softmax,
    clean S-reductions under GSPMD (same rationale as the decode twin).

    q [B,T,H,Dh]; k_new/v_new [B,T,KV,Dh]; layer_k/v [B,KV,S,Dh] (stale).
    Returns out [B, T, H*Dh]; writes nothing. ``window``: sliding-window
    bound (0 = full causal).
    """
    B, T, H, Dh = q.shape
    KV = k_new.shape[2]
    lk, ks, lv, vs = _kv_dequant_views(layer_k, layer_v, q.dtype)
    S = lk.shape[2]
    G = H // KV
    scale = Dh ** -0.5

    qg = q.reshape(B, T, KV, G, Dh).transpose(0, 2, 3, 1, 4)  # [B,KV,G,T,Dh]
    kn = k_new.transpose(0, 2, 1, 3)                          # [B,KV,T,Dh]
    vn = v_new.transpose(0, 2, 1, 3).astype(jnp.float32)
    scores = jnp.einsum("bkgtd,bksd->bkgts", qg, lk,
                        preferred_element_type=jnp.float32) * scale
    if ks is not None:
        scores = scores * ks[:, :, :, None, :]    # [B,KV,1,1,S]
    self_s = jnp.einsum("bkgtd,bkud->bkgtu", qg, kn,
                        preferred_element_type=jnp.float32) * scale
    if ks is not None:
        # Quantized cache: MIXED-PRECISION self-block. Plain decode sees a
        # drafted token u two different ways — full precision in its own
        # step's self-column (u == t), quantize→dequantize from the cache
        # in every LATER step (u < t, inserted by insert_kv_stacked). For
        # greedy parity with spec off, the verify block must reproduce
        # that split exactly: off-diagonal entries use the SAME
        # quantize_kv the insert path will apply to these k_new/v_new
        # (bitwise-identical q and s), with the same op order as the
        # stale path ((dot · scale) · s; probs · s before the PV dot,
        # cast to the cache view dtype). The diagonal stays full
        # precision, matching the decode self-column.
        knq, kns = quantize_kv(k_new)             # [B,T,KV,Dh], [B,T,KV]
        knq = knq.transpose(0, 2, 1, 3).astype(q.dtype)     # [B,KV,U,Dh]
        kns = kns.transpose(0, 2, 1)                        # [B,KV,U]
        self_sq = jnp.einsum("bkgtd,bkud->bkgtu", qg, knq,
                             preferred_element_type=jnp.float32) * scale
        self_sq = self_sq * kns[:, :, None, None, :]
        diag = jnp.eye(T, dtype=bool)[None, None, None]   # [1,1,1,T,U]
        self_s = jnp.where(diag, self_s, self_sq)

    visible = jnp.arange(S)[None, :] < lengths[:, None]            # [B, S]
    if window:
        # Query t sits at position lengths + t: stale key j visible iff
        # (lengths + t) - j < window — a per-(B, T) bound.
        q_pos = lengths[:, None] + jnp.arange(T)[None, :]          # [B, T]
        in_win = (jnp.arange(S)[None, None, :]
                  > (q_pos - window)[:, :, None])                  # [B, T, S]
        vis_ts = visible[:, None, :] & in_win
        if active is not None:
            vis_ts = vis_ts & active[:, None, None]
        scores = jnp.where(vis_ts[:, None, None, :, :], scores, -1e30)
    else:
        if active is not None:
            visible = visible & active[:, None]
        scores = jnp.where(visible[:, None, None, None, :], scores, -1e30)
    # Self-block: new token u is visible to query t iff u <= t (the query
    # itself is always visible, so the softmax denominator is >= 1).
    causal = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])    # [T, T]
    if window:
        # Within-block window: u visible to t iff t - u < window.
        causal = causal & (jnp.arange(T)[None, :]
                           > jnp.arange(T)[:, None] - window)
    self_s = jnp.where(causal[None, None, None], self_s, -1e30)

    m = jnp.maximum(jnp.max(scores, axis=-1), jnp.max(self_s, axis=-1))
    p = jnp.exp(scores - m[..., None])                      # [B,KV,G,T,S]
    p_self = jnp.exp(self_s - m[..., None])                 # [B,KV,G,T,T]
    l = jnp.sum(p, axis=-1) + jnp.sum(p_self, axis=-1)
    if vs is not None:
        p = p * vs[:, :, :, None, :]              # [B,KV,1,1,S]
    out = jnp.einsum("bkgts,bksd->bkgtd", p.astype(lv.dtype), lv,
                     preferred_element_type=jnp.float32)
    if vs is not None:
        # Mixed-precision PV to match: off-diagonal drafted values go
        # through the same qdq + dtype cast as the stale path; the
        # diagonal uses the full-precision fp32 value like the decode
        # self-column. Masking by multiply is exact (×1.0 / ×0.0).
        vnq, vns = quantize_kv(v_new)             # [B,T,KV,Dh], [B,T,KV]
        vnq = vnq.transpose(0, 2, 1, 3).astype(q.dtype)     # [B,KV,U,Dh]
        vns = vns.transpose(0, 2, 1)                        # [B,KV,U]
        diag_f = jnp.eye(T, dtype=jnp.float32)[None, None, None]
        p_off = p_self * (1.0 - diag_f) * vns[:, :, None, None, :]
        out = out + jnp.einsum("bkgtu,bkud->bkgtd",
                               p_off.astype(vnq.dtype), vnq,
                               preferred_element_type=jnp.float32)
        out = out + jnp.einsum("bkgtu,bkud->bkgtd", p_self * diag_f, vn)
    else:
        out = out + jnp.einsum("bkgtu,bkud->bkgtd", p_self, vn)
    out = out / l[..., None]
    # [B,KV,G,T,Dh] → [B,T,H*Dh]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, H * Dh)
    return out.astype(q.dtype)


def dense_cache_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                          layer_k: jax.Array, layer_v: jax.Array,
                          lengths: jax.Array,
                          active: jax.Array | None = None,
                          window: int = 0
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Reference cache attention (pure jnp; the Pallas paged kernel replaces
    this on TPU — ops/paged_attention.py).

    q:      [B, T, H, Dh] (RoPE already applied)
    k_new:  [B, T, KV, Dh], v_new same — new tokens to insert at `lengths`.
    layer_k/v: [B, KV, S, Dh] — this layer's cache (head-major).
    lengths: [B] int32 — tokens already cached (insert offset).
    window: sliding-window bound (0 = full causal; HF Mistral semantics —
    query at position i sees keys j with i - j < window).
    Returns (attn_out [B, T, H*Dh], updated layer_k, layer_v).
    """
    B, T, H, Dh = q.shape
    KV = k_new.shape[2]

    layer_k, layer_v = insert_kv(layer_k, layer_v, k_new, v_new,
                                 lengths, active)
    lk, ks, lv, vs = _kv_dequant_views(layer_k, layer_v, q.dtype)
    S = lk.shape[2]

    # GQA WITHOUT materializing repeated KV: group the query heads
    # [B,T,H,Dh] → [B,KV,G,T,Dh] and contract each group against its single
    # KV head. bf16 reads + fp32 MXU accumulation (preferred_element_type)
    # — no fp32 copy of the cache, no 8× `repeat` traffic.
    group = H // KV
    qg = q.reshape(B, T, KV, group, Dh).transpose(0, 2, 3, 1, 4)
    scores = jnp.einsum("bkgtd,bksd->bkgts", qg, lk,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(Dh, jnp.float32))
    if ks is not None:
        scores = scores * ks[:, :, :, None, :]    # [B,KV,1,1,S]

    # Mask: key position s is visible to query t iff s <= lengths + t
    # (and, with a sliding window, within `window` of it).
    q_pos = lengths[:, None] + jnp.arange(T)[None, :]          # [B, T]
    s_idx = jnp.arange(S)[None, None, :]                        # [1, 1, S]
    visible = s_idx <= q_pos[:, :, None]                        # [B, T, S]
    if window:
        visible = visible & (s_idx > q_pos[:, :, None] - window)
    if active is not None:
        visible = visible & active[:, None, None]
    scores = jnp.where(visible[:, None, None, :, :], scores, -1e30)

    probs = jax.nn.softmax(scores, axis=-1)
    if vs is not None:
        probs = probs * vs[:, :, :, None, :]      # [B,KV,1,1,S]
    out = jnp.einsum("bkgts,bksd->bkgtd", probs.astype(lv.dtype),
                     lv, preferred_element_type=jnp.float32)
    # [B,KV,G,T,Dh] → [B,T,H*Dh]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, T, H * Dh)
    return out.astype(q.dtype), layer_k, layer_v


# The default attention provider supports the deferred-decode protocol
# (forward() docstring): decode steps attend the stale cache + self-column
# and the cache write happens once per step via insert_kv_stacked.
dense_cache_attention.decode = dense_decode_attention
dense_cache_attention.insert_all = insert_kv_stacked


@lru_cache(maxsize=8)
def windowed_dense_attention(window: int):
    """The default dense provider with a sliding-window bound threaded
    through every path (chunk, deferred decode, spec verify) —
    ``forward`` swaps it in for ``config.sliding_window`` models
    (mistral family). Memoized so the provider identity is stable."""
    def fn(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        return dense_cache_attention(q, k_new, v_new, layer_k, layer_v,
                                     lengths, active, window=window)
    fn.decode = partial(dense_decode_attention, window=window)
    # No ``.verify`` here: that attribute reroutes EVERY T>1 call (prefill
    # chunks included) through the deferred block path — the spec engine
    # adds its windowed verify via _spec_verify_attention_fn instead.
    fn.insert_all = insert_kv_stacked
    return fn


_GATE_ACTS = {
    "silu": jax.nn.silu,                                      # llama/qwen2
    "gelu_tanh": partial(jax.nn.gelu, approximate=True),      # gemma GeGLU
    "relu": jax.nn.relu,                    # ReGLU experts (models/hybrid.py)
}


def gated_hidden(act: str, gate: jax.Array, up: jax.Array,
                 limit: float = 0.0) -> jax.Array:
    """``act(gate) * up``, the hidden rows of a gated MLP. ``limit`` > 0
    (``ModelConfig.swiglu_limit``): ``act(min(gate, L)) * clip(up, -L,
    L)``. 0 is a trace-time branch: the product as it always was."""
    if limit:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return _GATE_ACTS[act](gate) * up


def swiglu_mlp(x: jax.Array, wg: jax.Array, wu: jax.Array,
               wd: jax.Array, act: str = "silu", limit: float = 0.0
               ) -> jax.Array:
    """Gated MLP (SwiGLU for llama/qwen2, GeGLU for gemma via ``act``;
    clamped where ``limit`` says so, ``gated_hidden``). Each weight is a
    plain array or an int8 ``{"q","s"}`` dict (models/quant.py) — ``mm``
    dispatches."""
    return mm(gated_hidden(act, mm(x, wg), mm(x, wu), limit), wd)


def qkv_proj(h: jax.Array, lp: dict, config: ModelConfig
             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Q/K/V projections with the optional qwen2-family bias, RoPE NOT yet
    applied. THE one copy of this block (the bias was once added to only
    one of two copies, silently forking the model): the layer scan calls
    it, and so would any other block. ``"bq" in lp`` is
    static at trace time. h [B, T, D] → q [B,T,H,Dh], k/v [B,T,KV,Dh]."""
    c = config
    B, T = h.shape[0], h.shape[1]
    dh = c.head_dim
    qp, kp, vp = mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])
    if "bq" in lp:
        qp, kp, vp = qp + lp["bq"], kp + lp["bk"], vp + lp["bv"]
    return (qp.reshape(B, T, c.n_heads, dh),
            kp.reshape(B, T, c.n_kv_heads, dh),
            vp.reshape(B, T, c.n_kv_heads, dh))


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: Params, config: ModelConfig, tokens: jax.Array,
            lengths: jax.Array, cache: KVCache,
            active: jax.Array | None = None,
            attention_fn: Callable = dense_cache_attention,
            mlp_fn: Callable | None = None,
            ) -> tuple[jax.Array, KVCache]:
    """One forward pass over new tokens (prefill chunk or single decode step).

    tokens:  [B, T] int32 — new token ids.
    lengths: [B] int32 — tokens already in the cache per slot.
    active:  [B] bool — mask for live batch slots (padding slots compute but
             can't corrupt anything; their cache rows are reset on admit).
    Returns (logits [B, T, V] fp32, updated cache).
    """
    c = config
    B, T = tokens.shape
    dh = c.head_dim
    if c.sliding_window and attention_fn is dense_cache_attention:
        # Mistral-family sliding window, threaded through the default
        # dense provider. Explicit providers carry the window themselves
        # (the engine builds each cache group's paged provider with it).
        attention_fn = windowed_dense_attention(c.sliding_window)

    x = jnp.take(params["embed"], tokens, axis=0)   # [B, T, D]
    if c.scale_embed:
        # Gemma scales embeddings by sqrt(D) *in the model dtype* (HF casts
        # the normalizer to hidden-state dtype — match its rounding).
        x = x * jnp.asarray(c.d_model ** 0.5, x.dtype)

    positions = lengths[:, None] + jnp.arange(T)[None, :]       # [B, T]
    cos, sin = rope_tables(positions, dh, c.rope_theta, c.rope_scaling)

    layer_params = params["layers"]
    custom_mlp = mlp_fn

    # Deferred-insert protocol: an attention_fn may carry a ``.decode``
    # (T=1: stale-cache + self-column attention, NO cache write), a
    # ``.verify`` (T>1 twin with a causal self-block — the speculative
    # verify path), and an ``.insert_all`` (one stacked insert for every
    # layer's new tokens). This keeps the full-extent cache OUT of the
    # layer scan's ys — the per-layer functional cache update costs
    # ~2 ms/step in serialized scatters at L=22;
    # the deferred form stacks only the tiny [L,B,T,KV,Dh] new tokens and
    # inserts once. Providers WITHOUT ``.verify`` (the prefill chunk path,
    # Pallas causal kernels) keep insert-then-attend for T>1.
    decode_attend = getattr(attention_fn, "decode", None) if T == 1 else \
        getattr(attention_fn, "verify", None)
    # A provider with ``.decode_at`` reads the layer-STACKED cache at a
    # layer's index (ops/paged_attention.py): the cache then stays out of
    # the scanned inputs too — a scanned slice of it is a COPY of a layer's
    # whole side, every layer of every step. ``.prefill_at`` is the chunk
    # path's twin (insert-then-attend, so only where there is no
    # ``.verify``): the stacked cache is the scan's CARRY, written in place
    # and attended at the layer's index, and never among the ys.
    decode_at = getattr(attention_fn, "decode_at", None) if T == 1 else None
    prefill_at = getattr(attention_fn, "prefill_at", None) \
        if T > 1 and decode_attend is None else None
    by_index = decode_at is not None or prefill_at is not None

    # Phase markers (ISSUE 8): named_scope is trace-time op metadata —
    # zero runtime cost — so profiler captures segment each layer into
    # its attention and MLP halves in Perfetto. "decode" = the deferred-
    # insert path (T=1 decode and the speculative verify), "prefill" =
    # the insert-then-attend chunk path.
    scope = "decode" if decode_attend is not None else "prefill"

    def layer_step(carry, scanned):
        x, pool = carry           # pool: the stacked cache, under prefill_at
        lp, at = scanned          # at: the layer's index, or its (K, V) slice
        # Attention block
        with jax.named_scope(f"{scope}.attention"):
            h = rms_norm(x, lp["attn_norm"], c.rms_eps, c.rms_offset)
            q, k, v = qkv_proj(h, lp, c)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            ys = (k, v)                       # stacked for insert_all below
            if prefill_at is not None:
                attn, pool_k, pool_v = prefill_at(q, k, v, *pool, at,
                                                  lengths, active)
                pool, ys = (pool_k, pool_v), None
            elif decode_at is not None:
                attn = decode_at(q, k, v, cache.k, cache.v, at, lengths,
                                 active)
            elif decode_attend is not None:
                attn = decode_attend(q, k, v, *at, lengths, active)
            else:
                attn, layer_k, layer_v = attention_fn(q, k, v, *at, lengths,
                                                      active)
                ys = (layer_k, layer_v)       # the written slices
            x = x + mm(attn, lp["wo"])
        # MLP block
        with jax.named_scope(f"{scope}.mlp"):
            h = rms_norm(x, lp["mlp_norm"], c.rms_eps, c.rms_offset)
            if custom_mlp is not None:
                x = x + custom_mlp(h, lp)
            else:
                x = x + swiglu_mlp(h, lp["wg"], lp["wu"], lp["wd"], c.act)
        return (x, pool), ys

    (x, pool), ys = jax.lax.scan(
        layer_step,
        (x, (cache.k, cache.v) if prefill_at is not None else None),
        (layer_params, jnp.arange(c.n_layers) if by_index
         else (cache.k, cache.v)))
    if prefill_at is not None:
        new_k, new_v = pool
    elif decode_attend is not None:
        new_k, new_v = attention_fn.insert_all(
            cache.k, cache.v, *ys, lengths, active)
    else:
        new_k, new_v = ys

    x = rms_norm(x, params["final_norm"], c.rms_eps, c.rms_offset)
    head = _select_head(params, c)
    # bf16 (or int8) reads of the [V, D] head with MXU accumulation — an
    # explicit astype would materialize a fp32 copy of the vocab matrix.
    logits = head_matmul(x, head)
    return logits, KVCache(k=new_k, v=new_v)


def _select_head(params: Params, c: ModelConfig):
    """The LM head weight: ``lm_head`` (untied), or for tied-embedding
    models the int8 head copy ``lm_head_q8`` when quantized (models/
    quant.py quantize_tree) else the embed table itself."""
    if c.tie_embeddings:
        return params["lm_head_q8"] if "lm_head_q8" in params \
            else params["embed"]
    return params["lm_head"]
