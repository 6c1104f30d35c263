"""Flash attention over the serving KV cache, as Pallas TPU kernels.

Two kernels cover the two compiled serving programs (engine/engine.py):

* :func:`flash_decode_attention` — one query token per slot against the
  whole cache. Grid ``(B, KV, S/BS)``; each program block holds one slot's
  one KV head's key/value block in VMEM. GQA is handled *inside* the
  kernel (queries arrive grouped ``[B, KV, G, Dh]``), so cache reads are
  never expanded ``G×`` the way the jnp path's ``jnp.repeat`` does — at
  serving batch sizes decode attention is pure HBM bandwidth, making this
  the kernel that sets the tok/s ceiling. Sequence blocks past the slot's
  live length contribute nothing: their compute is skipped with ``pl.when``
  AND their HBM→VMEM copies are elided by clamping the K/V block index maps
  to the last live block (the pipeline skips the DMA when the next block
  index equals the current one), so slots early in their generation truly
  don't pay ``S_max`` bandwidth (ragged attention).
* :func:`flash_prefill_attention` — a prompt chunk of ``T`` queries against
  the cache prefix plus itself. Grid ``(B, H, T/TB, S/BS)`` with online
  softmax over the S blocks; causally-invisible key blocks are skipped
  entirely, and per-element causal masking handles the block diagonal.
  Nothing ``[T, S]``-shaped ever hits HBM (the jnp path materializes
  ``[B, H, T, S]`` scores).

Both kernels accumulate in fp32 scratch (``m``/``l``/``acc`` — the classic
online-softmax triple) and run in interpret mode off-TPU, so the same code
path is exercised by the CPU test suite (tests/test_ops_attention.py
compares against models/llama.py's reference jnp attention).

The :func:`make_cache_attention_fn` wrapper adapts these to the model's
``attention_fn`` contract (llama.py:132 ``dense_cache_attention``): cache
insertion stays in XLA (dynamic_update_slice lowers well), the kernels do
the bandwidth-heavy read.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Decode kernel: q [B, KV, G, Dh] vs cache [B, KV, S, Dh], ragged by n_valid
# ---------------------------------------------------------------------------

def self_column_init(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref) -> None:
    """Initialize a decode kernel's online-softmax state from the SELF
    column (the new token attending itself): m = q·k_new, l = 1,
    acc = v_new. The cache is STALE — the current token's K/V never
    touched HBM; its contribution lives entirely in registers (the
    deferred-insert decode protocol, models/llama.py forward()). Shared by
    the dense and paged decode kernels."""
    q = q_ref[0, 0].astype(jnp.float32)            # [G, Dh]
    kn = kn_ref[0, 0].astype(jnp.float32)          # [1, Dh]
    vn = vn_ref[0, 0].astype(jnp.float32)          # [1, Dh]
    self_s = jax.lax.dot_general(
        q, kn, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # [G, 1]
    self_s *= q.shape[-1] ** -0.5
    m_ref[:] = jnp.broadcast_to(self_s, m_ref.shape)
    l_ref[:] = jnp.ones_like(l_ref)
    acc_ref[:] = jnp.broadcast_to(vn, acc_ref.shape)


def attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask,
                 ks_ref=None, vs_ref=None, sub: int = 0) -> None:
    """One online-softmax block update — THE shared compute of every flash
    kernel here and in ops/paged_attention.py (dense/paged × decode/prefill
    × bf16/int8-KV). ``mask(scores)`` applies the caller's visibility rule;
    ``ks_ref``/``vs_ref`` are the optional int8-KV per-token scale blocks
    ``[1, 1, 1, BS]`` (rank-4: the unit dim before the token axis keeps the
    block's trailing two dims ``(1, BS)`` legal under the TPU (8, 128)
    tiling rule — a ``(1, BS)`` block of a rank-3 ``[B, KV, S]`` array
    would put a block of 1 on the KV dim, which real Mosaic lowering
    rejects; interpret mode never catches this): the scale factors out of
    the Dh contraction, so scores
    multiply by ``ks`` after the QK dot and probs by ``vs`` before the PV
    dot (after ``l`` accumulates — the softmax denominator is unscaled),
    and no dequantized [BS, Dh] block is ever built.

    ``sub`` (static) selects the K/V/scale sub-block along the leading
    block dim: the multi-page paged kernels fetch ``pages_per_block``
    physical pages in ONE ``(ppb, 1, page, Dh)`` block and attend them
    per-page (ops/paged_attention.py), so each call here stays the exact
    per-page update — only the DMA granularity grows."""
    q = q_ref[0, 0]                                # [rows, Dh]
    k = k_ref[sub, 0]                              # [BS, Dh] (bf16 or int8)
    v = v_ref[sub, 0].astype(jnp.float32)
    if k.dtype == jnp.int8:
        # int8-KV QK dot (the worst_kernel() pick on the int8 ladder —
        # decode.d*.greedy sat at ~0.4 of the HBM roof): dequant is fused
        # into the dot as a cast to q's NATIVE dtype. Every int8 value is
        # exact in bf16 (8 mantissa bits ≥ the 7 magnitude bits of ±127),
        # so scores are bit-identical to the old `.astype(float32)` pair —
        # but the MXU now runs one native low-precision pass with fp32
        # accumulation instead of the multi-pass fp32×fp32 matmul the
        # explicit upcast forced.
        k = k.astype(q.dtype)
    else:
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # [rows, BS]
    scores *= q.shape[-1] ** -0.5
    if ks_ref is not None:
        scores = scores * ks_ref[sub, 0]
    scores = mask(scores)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    e = jnp.exp(scores - m_new)                    # [rows, BS]
    l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(e, axis=1, keepdims=True)
    p = e if vs_ref is None else e * vs_ref[sub, 0]
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # [rows, Dh]
    m_ref[:, :1] = m_new


def unpack_kv_refs(refs):
    """(k, ks, v, vs, o, m, l, acc) from a kernel's trailing refs. Without
    int8-KV the scale refs are absent (arity 6) and come back None — THE
    one copy of this arity contract, shared by all four flash kernels
    (dense/paged × decode/prefill)."""
    if len(refs) == 8:
        return refs
    k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    return k_ref, None, v_ref, None, o_ref, m_ref, l_ref, acc_ref


def _decode_kernel(nvalid_ref, q_ref, kn_ref, vn_ref, *refs, block_s: int,
                   window: int = 0):
    k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = \
        unpack_kv_refs(refs)
    b = pl.program_id(0)
    s = pl.program_id(2)
    n_sb = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        self_column_init(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref)

    n_valid = nvalid_ref[b]
    # Sliding window: the query (at position n_valid) sees stale keys j
    # with n_valid - j < window, i.e. j >= w0. Blocks entirely below w0
    # skip compute (and their DMA is elided by the index-map clamp).
    w0 = jnp.maximum(n_valid - (window - 1), 0) if window else 0
    live = s * block_s < n_valid
    if window:
        live = live & ((s + 1) * block_s > w0)

    @pl.when(live)
    def _block():
        def mask(scores):
            s_global = s * block_s + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            ok = s_global < n_valid
            if window:
                ok = ok & (s_global >= w0)
            return jnp.where(ok, scores, NEG_INF)
        attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask,
                     ks_ref, vs_ref)

    @pl.when(s == n_sb - 1)
    def _out():
        l = l_ref[:, :1]                               # >= 1 (self column)
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def flash_decode_attention(q: jax.Array, k_new: jax.Array,
                           v_new: jax.Array, layer_k, layer_v,
                           n_stale: jax.Array,
                           *, block_s: int = 128,
                           window: int = 0,
                           interpret: bool | None = None) -> jax.Array:
    """Ragged single-token attention over a STALE cache plus the new token.

    q: [B, H, Dh] (RoPE applied); k_new/v_new: [B, KV, Dh] — the current
    token's key/value (NOT yet in the cache; folded in as the online
    softmax's initial state); layer_k/v: [B, KV, S, Dh] (head-major), or
    the int8 ``{"q","s"}`` dicts (models/llama.py kv_quant layout — the
    kernel gains per-token scale blocks, see :func:`attend_block`);
    n_stale: [B] int32 — visible stale prefix per slot (the query's
    position; 0 for a fresh slot). ``window``: sliding-window bound
    (mistral family; 0 = full) — out-of-window leading blocks skip both
    compute and DMA. Returns [B, H * Dh] in q.dtype.
    """
    B, H, Dh = q.shape
    quant = isinstance(layer_k, dict)
    kq = layer_k["q"] if quant else layer_k
    KV, S = kq.shape[1], kq.shape[2]
    G = H // KV
    block_s = min(block_s, S)
    if S % block_s:
        raise ValueError(f"cache extent {S} not a multiple of block {block_s}")
    qg = q.reshape(B, KV, G, Dh)
    grid = (B, KV, S // block_s)

    def _live_range(nv_b):
        """(first, last) live block for a slot — iterations outside re-
        reference a live block so the pipeline elides their DMA (pl.when
        already skips their compute). max() guards n_stale == 0 (fresh
        slot: all cache blocks dead, only the self column counts)."""
        last = jnp.maximum((nv_b + block_s - 1) // block_s - 1, 0)
        if window:
            first = jnp.maximum(nv_b - (window - 1), 0) // block_s
            first = jnp.minimum(first, last)
        else:
            first = 0
        return first, last

    def kv_index(b, h, s, nv):
        first, last = _live_range(nv[b])
        return b, h, jnp.clip(s, first, last), 0

    def scale_index(b, h, s, nv):
        first, last = _live_range(nv[b])
        return b, h, 0, jnp.clip(s, first, last)

    # Scales are STORED rank-4 [B, KV, 1, S] (models/llama.py KVCache) so
    # the block's trailing dims are (1, block_s) — legal under the TPU
    # (8, 128) tiling rule for any KV (a (1, block_s) block of a
    # [B, KV, S] layout would block the KV dim at 1, which real Mosaic
    # lowering rejects; see attend_block) — and no per-call relayout of
    # the scale tensor is needed.
    kv_spec = pl.BlockSpec((1, 1, block_s, Dh), kv_index)
    s_spec = pl.BlockSpec((1, 1, 1, block_s), scale_index)
    if quant:
        kv_operands = (layer_k["q"], layer_k["s"],
                       layer_v["q"], layer_v["s"])
        kv_specs = [kv_spec, s_spec, kv_spec, s_spec]
    else:
        kv_operands = (layer_k, layer_v)
        kv_specs = [kv_spec, kv_spec]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_s=block_s, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, nv: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, Dh), lambda b, h, s, nv: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 1, Dh), lambda b, h, s, nv: (b, h, 0, 0)),
                *kv_specs,
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dh),
                                   lambda b, h, s, nv: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 128), jnp.float32),      # m
                pltpu.VMEM((G, 128), jnp.float32),      # l
                pltpu.VMEM((G, Dh), jnp.float32),       # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(n_stale.astype(jnp.int32), qg, k_new[:, :, None, :],
      v_new[:, :, None, :], *kv_operands)
    return out.reshape(B, H * Dh)


# ---------------------------------------------------------------------------
# Prefill kernel: q [B, T, H, Dh] vs cache [B, KV, S, Dh], causal from start
# ---------------------------------------------------------------------------

def _prefill_kernel(start_ref, q_ref, *refs, block_t: int, block_s: int,
                    window: int = 0):
    k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = \
        unpack_kv_refs(refs)
    b = pl.program_id(0)
    t = pl.program_id(2)
    s = pl.program_id(3)
    n_sb = pl.num_programs(3)

    @pl.when(s == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    start = start_ref[b]
    # Query block t covers absolute positions [start + t*TB, start + t*TB +
    # TB); key block s is (partially) visible iff its first key position is
    # <= the block's last query position (and, with a sliding window, its
    # last key position within `window` of the block's FIRST query).
    first_q_pos = start + t * block_t
    last_q_pos = first_q_pos + (block_t - 1)
    live = s * block_s <= last_q_pos
    if window:
        live = live & ((s + 1) * block_s - 1 > first_q_pos - window)

    @pl.when(live)
    def _block():
        def mask(scores):
            q_pos = start + t * block_t + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 0)
            s_pos = s * block_s + jax.lax.broadcasted_iota(
                jnp.int32, scores.shape, 1)
            ok = s_pos <= q_pos
            if window:
                ok = ok & (s_pos > q_pos - window)
            return jnp.where(ok, scores, NEG_INF)
        attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask,
                     ks_ref, vs_ref)

    @pl.when(s == n_sb - 1)
    def _out():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
                       ).astype(o_ref.dtype)


def flash_prefill_attention(q: jax.Array, layer_k, layer_v,
                            start: jax.Array,
                            *, block_t: int = 128, block_s: int = 128,
                            window: int = 0,
                            interpret: bool | None = None) -> jax.Array:
    """Causal chunk attention over an (already updated) cache.

    q: [B, T, H, Dh] — the chunk's queries at absolute positions
    ``start + t``; layer_k/v: [B, KV, S, Dh] (head-major) with the chunk's
    keys already inserted at ``[start, start+T)``, or the int8 ``{"q","s"}``
    dicts (kv_quant layout); start: [B] int32. ``window``: sliding-window
    bound (0 = full causal) — out-of-window key blocks skip compute and
    their DMA is elided.
    Returns [B, T, H * Dh] in q.dtype.
    """
    B, T, H, Dh = q.shape
    quant = isinstance(layer_k, dict)
    kq = layer_k["q"] if quant else layer_k
    KV, S = kq.shape[1], kq.shape[2]
    G = H // KV
    block_t = min(block_t, T)
    block_s = min(block_s, S)
    if T % block_t or S % block_s:
        raise ValueError(f"T={T} / S={S} not multiples of blocks "
                         f"{block_t}/{block_s}")
    qh = q.transpose(0, 2, 1, 3)                 # [B, H, T, Dh]
    grid = (B, H, T // block_t, S // block_s)

    def _live_range(st_b, t):
        # Clamp to the causally-visible (and in-window) key-block range
        # for query block t — out-of-range iterations repeat a live block
        # index so their HBM→VMEM copy is elided (compute already skipped
        # by pl.when).
        last = (st_b + t * block_t + (block_t - 1)) // block_s
        if window:
            first_q = st_b + t * block_t
            first = jnp.maximum(first_q - (window - 1), 0) // block_s
            first = jnp.minimum(first, last)
        else:
            first = 0
        return first, last

    def kv_index(b, h, t, s, st):
        first, last = _live_range(st[b], t)
        return b, h // G, jnp.clip(s, first, last), 0

    def scale_index(b, h, t, s, st):
        first, last = _live_range(st[b], t)
        return b, h // G, 0, jnp.clip(s, first, last)

    # Stored rank-4 [B, KV, 1, S] scale layout — see flash_decode_attention.
    kv_spec = pl.BlockSpec((1, 1, block_s, Dh), kv_index)
    s_spec = pl.BlockSpec((1, 1, 1, block_s), scale_index)
    if quant:
        kv_operands = (layer_k["q"], layer_k["s"],
                       layer_v["q"], layer_v["s"])
        kv_specs = [kv_spec, s_spec, kv_spec, s_spec]
    else:
        kv_operands = (layer_k, layer_v)
        kv_specs = [kv_spec, kv_spec]

    out = pl.pallas_call(
        functools.partial(_prefill_kernel, block_t=block_t, block_s=block_s,
                          window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, block_t, Dh),
                             lambda b, h, t, s, st: (b, h, t, 0)),
                *kv_specs,
            ],
            out_specs=pl.BlockSpec((1, 1, block_t, Dh),
                                   lambda b, h, t, s, st: (b, h, t, 0)),
            scratch_shapes=[
                pltpu.VMEM((block_t, 128), jnp.float32),   # m
                pltpu.VMEM((block_t, 128), jnp.float32),   # l
                pltpu.VMEM((block_t, Dh), jnp.float32),    # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, T, Dh), q.dtype),
        interpret=_interpret_default() if interpret is None else interpret,
    )(start.astype(jnp.int32), qh, *kv_operands)
    return out.transpose(0, 2, 1, 3).reshape(B, T, H * Dh)


# ---------------------------------------------------------------------------
# attention_fn adapter (llama.forward contract)
# ---------------------------------------------------------------------------


def _auto_block(n: int, cap: int) -> int:
    """Largest power-of-two divisor of n, capped — shapes are static at
    trace time, so each distinct (T, S) picks its own legal blocking (the
    final prefill bucket can be a non-power-of-two after the cache-extent
    clamp in engine._prefill_one_chunk)."""
    b = n & (-n)
    return min(b, cap)


def make_cache_attention_fn(block_s: int | None = None,
                            block_t: int | None = None,
                            interpret: bool | None = None,
                            window: int = 0):
    """Build an ``attention_fn`` (llama.py forward contract) backed by the
    flash kernels. Prefill chunks (T>1): insert in XLA, attend with the
    causal kernel. Decode (T==1): the deferred protocol — ``.decode``
    attends the stale cache + self column in the ragged GQA kernel and
    ``.insert_all`` (models/llama.py insert_kv_stacked) writes every
    layer's token once per step, outside the layer scan.
    ``block_s``/``block_t`` default to auto (largest pow2 divisor ≤128)."""
    def attention_fn(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        B, T, H, Dh = q.shape
        quant = isinstance(layer_k, dict)
        S = (layer_k["q"] if quant else layer_k).shape[2]
        from ..models.llama import insert_kv
        bs = block_s if block_s is not None else _auto_block(S, 128)
        layer_k, layer_v = insert_kv(layer_k, layer_v, k_new, v_new,
                                     lengths, active)
        bt = block_t if block_t is not None else _auto_block(T, 128)
        out = flash_prefill_attention(
            q, layer_k, layer_v, lengths,
            block_t=bt, block_s=bs, window=window, interpret=interpret)
        return out, layer_k, layer_v

    def decode(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        quant = isinstance(layer_k, dict)
        S = (layer_k["q"] if quant else layer_k).shape[2]
        # Decode blocks default wider than prefill (256 vs 128): the grid
        # is (B, KV, S/bs) programs whose per-program work is one small
        # matmul — at bs=128 the launch/DMA overhead of 256 tiny programs
        # dominates; bs=256 measured fastest on v5e (a block-size sweep:
        # 3.0 ms/step vs 3.3 at 128, 4.1 at 512 for TinyLlama).
        bs = block_s if block_s is not None else _auto_block(S, 256)
        n_stale = lengths if active is None else jnp.where(active, lengths, 0)
        out = flash_decode_attention(
            q[:, 0], k_new[:, 0], v_new[:, 0], layer_k, layer_v,
            n_stale, block_s=bs, window=window, interpret=interpret)
        return out[:, None, :]

    from ..models.llama import insert_kv_stacked
    attention_fn.decode = decode
    attention_fn.insert_all = insert_kv_stacked
    return attention_fn


def make_sharded_cache_attention_fn(mesh, block_s: int | None = None,
                                    block_t: int | None = None,
                                    interpret: bool | None = None,
                                    window: int = 0):
    """Mesh-aware ``attention_fn``: the flash kernels under ``shard_map``.

    ``pallas_call`` has no GSPMD partitioning rule, so invoking the kernels
    inside ``jit`` on mesh-sharded arrays would force XLA to gather the full
    KV cache onto every chip. Attention is embarrassingly parallel over
    batch (``data`` axis) and KV heads (``model`` axis — cache_sharding's
    layout), so the specs shard over exactly the axes the shapes allow:
    ``model`` when heads divide, ``data`` when the batch divides (prefill
    runs a single slot's [1, ...] row, so its batch stays replicated).
    The map itself is manual over EVERY mesh axis: the chip's compiler
    refuses a Mosaic kernel under a partially-manual map.
    Falls back to the unsharded fn when nothing divides (e.g. 1-chip mesh).
    """
    from jax.sharding import PartitionSpec as P

    # The window bound threads straight through: positions are absolute
    # per slot, untouched by batch (data) or head (model) sharding.
    base = make_cache_attention_fn(block_s, block_t, interpret,
                                   window=window)

    def _axes(q, layer_k):
        B, _, H, _ = q.shape
        KV = (layer_k["q"] if isinstance(layer_k, dict) else layer_k).shape[1]
        msize = mesh.shape.get("model", 1)
        dsize = mesh.shape.get("data", 1)
        model = "model" if (msize > 1 and KV % msize == 0 and H % msize == 0) \
            else None
        data = "data" if (dsize > 1 and B % dsize == 0) else None
        return model, data

    def _cache_spec(side, data, model):
        """Per-leaf spec: an int8 {"q","s"} cache leaf carries a 4-D
        [B, KV, S, Dh] value + 4-D [B, KV, 1, S] scale plane (batch and
        head dims shard identically; the scale's trailing (1, S) dims
        stay whole)."""
        val = P(data, model, None, None)
        if isinstance(side, dict):
            return {"q": val, "s": P(data, model, None, None)}
        return val

    def attention_fn(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        model, data = _axes(q, layer_k)
        if not (model or data):
            return base(q, k_new, v_new, layer_k, layer_v, lengths, active)

        head = P(data, None, model, None)       # q / k_new / v_new
        cache = _cache_spec(layer_k, data, model)
        slot = P(data)                          # lengths / active
        # `active=None` means "all slots live" — materialize it so the
        # shard_map signature is static.
        act = active if active is not None \
            else jnp.ones((q.shape[0],), bool)
        f = shard_map(
            lambda q_, kn, vn, lk, lv, ln, ac:
                base(q_, kn, vn, lk, lv, ln, ac),
            mesh=mesh,
            in_specs=(head, head, head, cache, cache, slot, slot),
            out_specs=(P(data, None, model), cache, cache),
            check_vma=False)
        return f(q, k_new, v_new, layer_k, layer_v, lengths, act)

    def decode(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        model, data = _axes(q, layer_k)
        if not (model or data):
            return base.decode(q, k_new, v_new, layer_k, layer_v, lengths,
                               active)
        head = P(data, None, model, None)
        cache = _cache_spec(layer_k, data, model)
        slot = P(data)
        act = active if active is not None \
            else jnp.ones((q.shape[0],), bool)
        f = shard_map(
            lambda q_, kn, vn, lk, lv, ln, ac:
                base.decode(q_, kn, vn, lk, lv, ln, ac),
            mesh=mesh,
            in_specs=(head, head, head, cache, cache, slot, slot),
            out_specs=P(data, None, model), check_vma=False)
        return f(q, k_new, v_new, layer_k, layer_v, lengths, act)

    from ..models.llama import insert_kv_stacked
    attention_fn.decode = decode
    # The stacked insert stays in GSPMD land: dynamic_update_slice with
    # replicated offsets partitions cleanly over the cache's data/model
    # sharded dims, and it runs ONCE per step outside the layer scan.
    attention_fn.insert_all = insert_kv_stacked
    return attention_fn
