"""The CROSS-DECODER family ("phi4flash": Phi-4-mini-flash-reasoning, the
SambaY architecture of arXiv:2507.06607): a decoder whose lower half is
pairs of [selective scan, attention inside a window], whose upper half is
pairs of [a gate on ONE layer's memory, attention over ONE layer's K/V], and
between them the pair that makes both — a selective scan that also hands
its gated output up the stack, and the only attention layer that keeps the
whole context. A module of its own beside ``hybrid.py``: that forward is ONE
scanned period of one shape behind leading layers, with an expert layer at
every depth; this one is THREE scanned runs of different shapes, dense MLPs,
and a prefill that leaves most of a prompt's rows half-way up the stack.
Only the cache type (``hybrid.HybridCache``) and the provider protocol are
shared.

Layer ``l`` of ``n_layers`` = 4 ``n_self_pairs`` (D model, E = ``ssm_inner``
channels, N = ``ssm_state``, R = ``ssm_dt_rank``)::

    x <- x + mixer_l(LN(x));  x <- x + MLP_l(LN(x))        LN: weight AND bias
    MLP(u) = (v * SiLU(g)) W_2,  [g | v] = u W_1           gate first, no bias

    even l <= n/2       Mamba-1:  [x | z] = u W_in;  c_t = SiLU(conv(x)_t + b_c)
                        [d_t | B_t | C_t] = c_t W_x;  D_t = softplus(d_t W_D + b_D)
                        h_t = exp(D_t A) * h_{t-1} + (D_t c_t) (x) B_t
                        y_t = h_t C_t + D_skip c_t;  m_t = y_t SiLU(z_t)
                        out m_t W_out.   l = n/2 also hands m_t up: the MEMORY.
    odd l < n/2         differential attention in ``sliding_window``
    l = n/2 + 1         the same over the whole context; its K/V is the one
                        full-context cache
    even l > n/2        (SiLU(u W_1) * m_t) W_2 on the memory at the SAME t
    odd l > n/2 + 1     q = u W_q + b_q only; K and V are layer n/2+1's

Differential attention, SERVED FOLDED (``ModelConfig.served``): the
published query heads (2i, 2i+1) and K/V heads (2j, 2j+1) pair, a pair's two
softmax maps both read ``[V_2j | V_2j+1]``, and ``o_i = RMSNorm(a1_i - lam
a2_i) (1 - lam_init)``. With ``K'_j = [K_2j | K_2j+1]`` (one head of twice
the width: the same bytes) and ``q'_2i = [q_2i | 0]``, ``q'_2i+1 = [0 |
q_2i+1]`` that is plain grouped-query attention — ``n_heads`` queries over
``n_heads / 4`` K/V heads — followed by the subtraction and the norm: the
paged kernels run as they are (twice the QK products, no extra byte). The
kernels scale by ``head_dim^-1/2`` of the FOLDED width, so the queries carry
the missing ``sqrt 2``.

A prompt's rows stop at the full layer's K/V. Nothing above layer n/2 at
position t is read at any other position but that layer's ``K_t, V_t``: a
prefill chunk runs the lower pairs, the memory layer, the full layer's norm,
K/V projection and page write over ALL its rows, and everything from that
layer's attention up — the cross pairs, the final norm, the head — over
each row's LAST real position only, as a decode step's upper half on a
fresh memory (the providers' decode form: the stale pool plus the row's own
K/V). That is the architecture's published prefill, not an option.

TPU-first decisions:

* The state block is ``[layers, slots, N, E]`` float32 — the channels in the
  lanes. Channel-major ``[E, N]`` would pad N = 16 to a 128-lane tile: eight
  times the bytes.
* The recurrence is an XLA scan over tokens, ``SCAN_UNROLL`` of them a trip,
  with ``exp(D_t A)`` formed in the step: materialised for a chunk it is
  [T, N, E] float32, 168 MB a row at the published widths. A diagonal state
  per (channel, state number) has no matrix form (the decay depends on
  channel, state number, source AND target token), so the chunk form is the
  recurrence itself, unrolled (PERF.md section 7: a kernel that keeps ``h``
  in fast memory is the open item).
* Decode updates the stacked state block and conv tails on the scans' carry,
  a layer's rows written at its index; prefill gathers its slots' rows,
  scans them in and out, and scatters them back (as ``hybrid.forward``).
"""
from __future__ import annotations

import math
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .hybrid import N_COUNTERS, HybridCache, _conv_silu
from .llama import _select_head, layer_norm, rms_norm
from .quant import head_matmul, mm, quantize_array, weight_bits

Params = dict[str, Any]

SCAN_UNROLL = 16        # tokens a trip of the chunk form's scan
ATTN_OUT = 0.6          # the draw of W_o over (2 n_layers)^-1/2: init_params
# Stored int8 under quant (contraction axis second to last): the big
# projections. NOT ``w_x``, ``w_dt``, the conv, the biases, the lambdas or
# the norms (small), nor ``a_log`` and ``d_skip`` (float32: they decide the
# recurrence as a router decides routing).
QUANT_KEYS = frozenset({"w_in", "w_out", "wqkv", "wq", "wo", "g1", "g2",
                        "w1", "w2"})


def lambda_init(layer: int) -> float:
    """The differential weight's fixed part at depth ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _folded(config: ModelConfig) -> ModelConfig:
    """This module takes the config the engine SERVES (``config.served()``,
    applied once by whoever builds the programs): the folded heads."""
    if not (config.cross_decoder and config.head_dim_override):
        raise ValueError("models/sambay.py takes a cross decoder's served() "
                         "config: K/V head pairs folded")
    return config


def create_cache(config: ModelConfig, num_pages: tuple[int, ...],
                 page_size: int, batch: int, dtype=jnp.bfloat16,
                 kv_quant: str = "") -> HybridCache:
    """A pool a cache group — the ring's ``n_self_pairs`` layers, the ONE
    full-context layer — beside ONE state block [scans, B, N, E] float32
    and ONE conv tail [scans, B, taps-1, E] (the memory layer's the last)."""
    from dataclasses import replace
    from ..ops.paged_attention import PagedKVCache
    c = _folded(config)
    pools = [PagedKVCache.create(replace(c, n_layers=n, cross_decoder=False),
                                 pages, page_size, dtype, kv_quant)
             for n, pages in zip(c.group_layers, num_pages)]
    n = c.n_lin_layers
    return HybridCache(
        k=tuple(p.k for p in pools), v=tuple(p.v for p in pools),
        state=(jnp.zeros((n, batch, c.ssm_state, c.ssm_inner), jnp.float32),),
        conv=(jnp.zeros((n, batch, c.lin_conv_taps - 1, c.ssm_inner), dtype),),
        counters=jnp.zeros((N_COUNTERS,), jnp.int32))


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16, quant: str = "") -> Params:
    """Seeded random params; with ``quant`` every matrix of ``QUANT_KEYS``
    is quantised where it is drawn.

    Layout (S = ``n_self_pairs``, X = ``n_cross_pairs``; Q = ``n_heads *
    head_dim / 2`` query numbers, K = ``n_kv_heads * head_dim`` key numbers,
    dh the PUBLISHED head, half the folded one):
      embed [V, D]; lm_head_q8 (quant: the embedding's rows in int8);
      final_norm_w, final_norm_b [D]
      self/ssm, self/attn   stacked [S, ...];  mid/ssm, mid/attn  one layer;
      cross/gmu, cross/attn stacked [X, ...]
      ssm:  norm_w, norm_b [D]; w_in [D, 2E]; conv_w [taps, E]; conv_b [E];
            w_x [E, R+2N]; w_dt [R, E]; dt_bias [E]; a_log [N, E] f32;
            d_skip [E] f32; w_out [E, D]; mlp
      attn: norm_w, norm_b; wqkv [D, Q+2K], bqkv (a cross layer: wq [D, Q],
            bq); lq1, lk1, lq2, lk2 [dh]; sub_norm [2 dh]; wo [Q, D]; bo [D];
            mlp
      gmu:  norm_w, norm_b; g1 [D, E]; g2 [E, D]; mlp
      mlp:  norm_w, norm_b; w1 [D, 2F] (gate first); w2 [F, D]
    The stream is drawn at unit scale and every projection back into it at
    ``(2 n_layers)^-1/2`` (models/hybrid.py says why). The scan's sizes
    follow the Mamba convention — ``A = -(1..N)`` on every channel, the step
    ``softplus(b_D)`` log-uniform in 1e-3..0.1, ``W_D`` uniform in ``+-
    R^-1/2`` — so that state both persists (a thousand tokens) and decays (a
    few) inside a long prompt; the B and C columns of ``w_x`` are drawn at 3
    times the usual scale so that what the STATE adds to ``y`` is as large
    as the skip term (at 1 it is a twentieth, and no comparison of served
    tokens would see a state that was lost). The q and k columns are drawn
    at ``qk_norm_draw`` (models/config.py: peaked attention, so that a
    comparison sees WHICH keys were read); every bias at N(0, 0.1^2) and the
    four lambda vectors at N(0, 0.2^2) — ``lam`` then moves +-0.45 about
    ``lam_init`` — so that a comparison sees them. A differential layer's
    ``sub_norm`` is drawn at ``1 / (1 - lam_init)``: what enters ``W_o`` then
    has unit size at every depth (at 1 the fixed ``1 - lam_init`` leaves a
    FIFTH of it from layer 9 up, and which keys a cross layer read, or under
    which ``lam``, moved a served logit by less than W8A8 does); ``W_o``
    itself is drawn at ``ATTN_OUT`` of the other projections' scale — an
    attention layer's branch is then as large as the MLP's beside it and no
    larger, for under int8 activations a peaked softmax is the noisiest
    mixer of the stack (PERF.md section 6, PR 54: at twice the scale the
    SOUND program read 0.17-0.25 against the reference); and ``g2`` writes
    back at twice the scale, so that the memory carries as much of a served
    token as the shared K/V. Under the TIED head the final norm's gain is
    random signs at ``D^-1/2`` (models/hybrid.py)."""
    c = _folded(config)
    D, F, E, N, R = c.d_model, c.d_ff, c.ssm_inner, c.ssm_state, c.ssm_dt_rank
    taps, dh = c.lin_conv_taps, c.head_dim // 2
    Q, K = c.n_heads * dh, c.n_kv_heads * c.head_dim
    back = (2 * c.n_layers) ** -0.5
    half = c.n_layers // 2

    def dense(k, *shape, scale=1.0, name="", columns=None):
        w = jax.random.normal(k, shape, jnp.float32) * (
            scale / math.sqrt(shape[-2]))
        if columns is not None:
            w = w * columns
        w = w.astype(dtype)
        if quant and name in QUANT_KEYS:
            return quantize_array(w, w.ndim - 2,
                                  bits=weight_bits(quant, f"layers.{name}"))
        return w

    def bias(k, n, scale=0.1):
        return (scale * jax.random.normal(k, (n,), jnp.float32)).astype(dtype)

    def norms(k):
        return {"norm_w": jnp.ones((D,), dtype), "norm_b": bias(k, D)}

    def mlp(k):
        ks = jax.random.split(k, 3)
        return {**norms(ks[0]), "w1": dense(ks[1], D, 2 * F, name="w1"),
                "w2": dense(ks[2], F, D, scale=back, name="w2")}

    def ssm(k):
        ks = jax.random.split(k, 9)
        step = jnp.exp(jax.random.uniform(ks[5], (E,), jnp.float32,
                                          math.log(1e-3), math.log(0.1)))
        wide = jnp.concatenate([jnp.ones((R,)), jnp.full((2 * N,), 3.0)])
        return {**norms(ks[0]),
                "w_in": dense(ks[1], D, 2 * E, name="w_in"),
                "conv_w": (jax.random.normal(ks[2], (taps, E), jnp.float32)
                           / math.sqrt(taps)).astype(dtype),
                "conv_b": bias(ks[3], E),
                "w_x": dense(ks[4], E, R + 2 * N, columns=wide),
                "w_dt": jax.random.uniform(
                    ks[6], (R, E), jnp.float32, -R ** -0.5,
                    R ** -0.5).astype(dtype),
                "dt_bias": jnp.log(jnp.expm1(step)).astype(dtype),
                "a_log": jnp.broadcast_to(jnp.log(jnp.arange(
                    1, N + 1, dtype=jnp.float32))[:, None], (N, E)),
                "d_skip": jnp.ones((E,), jnp.float32),
                "w_out": dense(ks[7], E, D, scale=back, name="w_out"),
                "mlp": mlp(ks[8])}

    def attn(k, cross=False):
        ks = jax.random.split(k, 10)
        draw = c.qk_norm_draw
        if cross:
            proj = {"wq": dense(ks[1], D, Q, scale=draw, name="wq"),
                    "bq": bias(ks[2], Q)}
        else:
            columns = jnp.concatenate([jnp.full((Q + K,), draw),
                                       jnp.ones((K,))])
            proj = {"wqkv": dense(ks[1], D, Q + 2 * K, name="wqkv",
                                  columns=columns),
                    "bqkv": bias(ks[2], Q + 2 * K)}
        lams = {name: bias(kl, dh, 0.2) for name, kl in zip(
            ("lq1", "lk1", "lq2", "lk2"), jax.random.split(ks[3], 4))}
        return {**norms(ks[0]), **proj, **lams,
                "sub_norm": jnp.ones((2 * dh,), dtype),
                "wo": dense(ks[4], Q, D, scale=ATTN_OUT * back, name="wo"),
                "bo": bias(ks[5], D, 0.1 * back), "mlp": mlp(ks[6])}

    def gmu(k):
        ks = jax.random.split(k, 4)
        return {**norms(ks[0]), "g1": dense(ks[1], D, E, name="g1"),
                "g2": dense(ks[2], E, D, scale=2 * back, name="g2"),
                "mlp": mlp(ks[3])}

    def pairs(first, second, k, n):
        def pair(kp):
            ka, kb = jax.random.split(kp)
            return first(ka), second(kb)
        return jax.lax.map(pair, jax.random.split(k, n))

    def unit(layers, *depths):
        """``sub_norm`` at ``1 / (1 - lam_init)`` of each layer's depth."""
        sub = layers["sub_norm"]
        gain = jnp.asarray([1.0 / (1.0 - lambda_init(l)) for l in depths])
        return {**layers, "sub_norm": (
            sub * gain.reshape(*sub.shape[:-1], 1)).astype(dtype)}

    k_embed, k_norm, k_self, k_mid, k_cross = jax.random.split(key, 5)
    embed = jax.random.normal(k_embed, (c.vocab_size, D),
                              jnp.float32).astype(dtype)
    signs = jnp.where(jax.random.bernoulli(k_norm, 0.5, (D,)), 1.0, -1.0)
    self_ssm, self_attn = pairs(ssm, attn, k_self, c.n_self_pairs)
    cross_gmu, cross_attn = pairs(
        gmu, lambda k: attn(k, cross=True), k_cross, c.n_cross_pairs)
    k_a, k_b = jax.random.split(k_mid)
    return {"embed": embed,
            **({"lm_head_q8": quantize_array(embed, 1)} if quant else {}),
            "final_norm_w": (signs * D ** -0.5).astype(dtype),
            "final_norm_b": bias(jax.random.fold_in(k_norm, 1), D,
                                 0.1 * D ** -0.5),
            "self": {"ssm": self_ssm,
                     "attn": unit(self_attn, *range(1, half, 2))},
            "mid": {"ssm": ssm(k_a), "attn": unit(attn(k_b), half + 1)},
            "cross": {"gmu": cross_gmu, "attn": unit(
                cross_attn, *range(half + 3, c.n_layers, 2))}}


# ---------------------------------------------------------------------------
# The pieces
# ---------------------------------------------------------------------------

def _norm(x, lp, c: ModelConfig):
    """A sub-block's LayerNorm, WITH weight and bias."""
    return layer_norm(x, lp["norm_w"], c.layer_norm_eps, lp["norm_b"])


def mlp_block(x: jax.Array, lp: Params, c: ModelConfig) -> jax.Array:
    """The MLP's branch of the stream ``x``: one fused gate-and-value
    product, the gate FIRST."""
    gv = mm(_norm(x, lp, c), lp["w1"])
    g, v = jnp.split(gv, 2, axis=-1)
    hidden = (jax.nn.silu(g.astype(jnp.float32))
              * v.astype(jnp.float32)).astype(x.dtype)
    return mm(hidden, lp["w2"])


def ssm_step(h, a, x_t, d_t, b_t, c_t):
    """ONE token of the recurrence — its DEFINITION, and the decode form.
    h [B, N, E] float32, a [N, E] (= -exp(a_log)), x_t and d_t [B, E], b_t
    and c_t [B, N] -> (h_t, the state's part of y_t [B, E])."""
    h = (jnp.exp(d_t[:, None, :] * a[None]) * h
         + (d_t * x_t)[:, None, :] * b_t[:, :, None])
    return h, jnp.sum(h * c_t[:, :, None], axis=1)


def selective_scan(x, delta, b, c_, a, h0, unroll: int = SCAN_UNROLL):
    """The chunk form: ``ssm_step`` over T tokens, ``unroll`` a trip. x,
    delta [B, T, E] float32, b, c_ [B, T, N], a [N, E], h0 [B, N, E] ->
    (the state's part of y [B, T, E], h_T). A token whose ``delta`` is 0
    leaves the state as it was (the caller zeroes a row's padding)."""
    def step(h, xs):
        return ssm_step(h, a, *xs)
    with jax.named_scope("ssm.scan"):
        h, y = jax.lax.scan(
            step, h0, tuple(jnp.moveaxis(v, 1, 0) for v in (x, delta, b, c_)),
            unroll=min(unroll, x.shape[1]))
        return jnp.moveaxis(y, 0, 1), h


def ssm_block(h, lp: Params, c: ModelConfig, s0, tail, n_valid, keep,
              at=None):
    """One selective-scan layer on normalised input ``h`` [B, T, D].
    PREFILL: ``s0`` [B, N, E] and ``tail`` [B, taps-1, E], the rows' state
    and last inputs on entry; tokens past ``n_valid`` [B] are padding and
    move neither. DECODE (``keep`` [B] bool, T = 1): ``s0`` and ``tail`` are
    the STACKED blocks [layers, B, ...], this layer the one at index ``at``,
    and the blocks come back with this layer's rows written where ``keep``.
    Returns (the branch [B, T, D], the memory ``m`` [B, T, E], state, tail).
    """
    B, T, _ = h.shape
    E, N, R = c.ssm_inner, c.ssm_state, c.ssm_dt_rank
    f32 = jnp.float32
    decoding = keep is not None
    with jax.named_scope("ssm.proj"):
        x, z = jnp.split(mm(h, lp["w_in"]), 2, axis=-1)
        tail_in = tail[at] if decoding else tail
        x_ext = jnp.concatenate([tail_in.astype(x.dtype), x], axis=1)
        xc = _conv_silu(x_ext, lp["conv_w"], lp["conv_b"])       # f32
        dbc = jnp.einsum("bte,er->btr", xc.astype(h.dtype), lp["w_x"],
                         preferred_element_type=f32)
        delta = jax.nn.softplus(
            jnp.einsum("btr,re->bte", dbc[..., :R].astype(h.dtype),
                       lp["w_dt"], preferred_element_type=f32)
            + lp["dt_bias"].astype(f32))
        bm, cm = dbc[..., R:R + N], dbc[..., R + N:]
        a = -jnp.exp(lp["a_log"].astype(f32))
    if decoding:
        with jax.named_scope("ssm.scan"):
            old = s0[at]
            new, y = ssm_step(old, a, xc[:, 0], delta[:, 0], bm[:, 0],
                              cm[:, 0])
            state = s0.at[at].set(jnp.where(keep[:, None, None], new, old))
            y = y[:, None]
        new_tail = tail.at[at].set(jnp.where(
            keep[:, None, None], x_ext[:, 1:].astype(tail.dtype), tail_in))
    else:
        live = jnp.arange(T)[None, :] < n_valid[:, None]
        y, state = selective_scan(xc, jnp.where(live[..., None], delta, 0.0),
                                  bm, cm, a, s0)
        new_tail = jax.vmap(lambda row, n: jax.lax.dynamic_slice_in_dim(
            row, n, c.lin_conv_taps - 1, axis=0))(
                x_ext, n_valid).astype(tail.dtype)
    with jax.named_scope("ssm.proj"):
        y = y + lp["d_skip"].astype(f32) * xc
        m = (y * jax.nn.silu(z.astype(f32))).astype(h.dtype)
        return mm(m, lp["w_out"]), m, state, new_tail


def gmu_block(h, lp: Params, memory: jax.Array) -> jax.Array:
    """The gated memory unit on normalised input ``h``: the memory of the
    same positions, gated element by element."""
    with jax.named_scope("gmu"):
        gate = jax.nn.silu(mm(h, lp["g1"]).astype(jnp.float32))
        return mm((gate * memory.astype(jnp.float32)).astype(h.dtype),
                  lp["g2"])


def fold_queries(q: jax.Array, c: ModelConfig) -> jax.Array:
    """q [B, T, H, dh] (the published heads) -> the served query heads [B,
    T, H, 2 dh]: an even head's numbers in the first half, an odd head's in
    the second, zeros in the other — and the ``sqrt 2`` between the
    published scale ``dh^-1/2`` and the kernels' ``(2 dh)^-1/2``."""
    zero = jnp.zeros_like(q)
    odd = (jnp.arange(q.shape[2]) % 2 == 1)[None, None, :, None]
    q = (q.astype(jnp.float32) * math.sqrt(2.0)).astype(q.dtype)
    return jnp.concatenate([jnp.where(odd, zero, q), jnp.where(odd, q, zero)],
                           axis=-1)


def diff_combine(attn: jax.Array, lp: Params, c: ModelConfig,
                 lam_init: jax.Array) -> jax.Array:
    """attn [B, T, H * 2 dh] (the served heads' outputs: head 2i is a1_i,
    head 2i+1 a2_i) -> [B, T, H/2 * 2 dh]: ``RMSNorm(a1 - lam a2) (1 -
    lam_init)`` a pair, float32 inside."""
    B, T, _ = attn.shape
    f32 = jnp.float32
    a = attn.astype(f32).reshape(B, T, c.n_heads // 2, 2, c.head_dim)
    lam = (jnp.exp(jnp.sum(lp["lq1"].astype(f32) * lp["lk1"].astype(f32)))
           - jnp.exp(jnp.sum(lp["lq2"].astype(f32) * lp["lk2"].astype(f32)))
           + lam_init)
    o = a[:, :, :, 0] - lam * a[:, :, :, 1]
    o = rms_norm(o, lp["sub_norm"], c.layer_norm_eps) * (1.0 - lam_init)
    return o.reshape(B, T, -1).astype(attn.dtype)


def qkv(h, lp: Params, c: ModelConfig):
    """The normalised input's served queries [B, T, H, 2 dh] and the
    layer's own K and V [B, T, KV, 2 dh] (a cross layer: None, None)."""
    B, T, _ = h.shape
    dh = c.head_dim // 2
    Q = c.n_heads * dh
    if "wq" in lp:
        q, k, v = mm(h, lp["wq"]) + lp["bq"], None, None
    else:
        x = mm(h, lp["wqkv"]) + lp["bqkv"]
        q = x[..., :Q]
        k, v = (t.reshape(B, T, c.n_kv_heads, c.head_dim)
                for t in jnp.split(x[..., Q:], 2, axis=-1))
    return fold_queries(q.reshape(B, T, c.n_heads, dh), c), k, v


def attn_out(attn, lp: Params, c: ModelConfig, lam_init) -> jax.Array:
    return mm(diff_combine(attn, lp, c, lam_init), lp["wo"]) + lp["bo"]


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: Params, config: ModelConfig, tokens: jax.Array,
            lengths: jax.Array, cache: HybridCache,
            active: jax.Array | None = None,
            attention_fn: tuple[Callable, Callable] | None = None, *,
            slots: jax.Array | None = None,
            n_valid: jax.Array | None = None,
            final: jax.Array | None = None
            ) -> tuple[jax.Array, HybridCache]:
    """One forward over new tokens in ``hybrid.forward``'s signature:
    ``attention_fn`` a provider a cache group, (the ring's, the full
    layer's), each over its group's page table.

    Decode (T == 1 and none of ``slots``, ``n_valid``, ``final``): row b IS
    slot b; a row that is not ``active`` leaves state, tail and pools as
    they were. Prefill: row i is slot ``slots[i]``, a row whose ``lengths``
    is 0 starts from ZERO state, ``n_valid`` [B] (default T) is its true
    token count. Returns (logits float32 [B, 1, V], the cache). A prefill
    call's logits are those of each row's LAST real position and of no
    other: the upper half never ran on the others. ``final`` [B] bool
    (default all): whether a row's chunk ENDS its prompt; a call none of
    whose rows does stops EVERY row at the full layer's K/V — the upper
    half does not run, and its logits are zeros (nobody reads a first token
    off a chunk that is not a prompt's last)."""
    c = _folded(config)
    B, T = tokens.shape
    if attention_fn is None or len(attention_fn) != 2:
        raise ValueError("the cross-decoder family serves from two page "
                         "pools: it needs a provider for the ring group and "
                         "one for the full-context layer")
    fn_w, fn_g = attention_fn
    decoding = (T == 1 and slots is None and n_valid is None
                and final is None)
    scope = "decode" if decoding else "prefill"
    S, X = c.n_self_pairs, c.n_cross_pairs
    half = 2 * S
    lam_self = jnp.asarray([lambda_init(2 * i + 1) for i in range(S)],
                           jnp.float32)
    lam_cross = jnp.asarray([lambda_init(half + 3 + 2 * i) for i in range(X)],
                            jnp.float32)
    lam_mid = lambda_init(half + 1)
    (pool_wk, pool_gk), (pool_wv, pool_gv) = cache.k, cache.v
    state, conv = cache.state[0], cache.conv[0]

    if decoding:
        keep = active if active is not None else jnp.ones((B,), bool)
        n_valid = jnp.ones((B,), jnp.int32)
        rows_s = rows_t = None
    else:
        keep = None
        if slots is None:
            slots = jnp.arange(B, dtype=jnp.int32)
        n_valid = (jnp.full((B,), T, jnp.int32) if n_valid is None
                   else n_valid.astype(jnp.int32))
        fresh = lengths == 0
        rows_s = jnp.where(fresh[:, None, None], 0.0, state[:, slots])
        rows_t = jnp.where(fresh[:, None, None], 0, conv[:, slots])

    def add_mlp(x, lp):
        with jax.named_scope(f"{scope}.mlp"), jax.named_scope("mlp.dense"):
            return x + mlp_block(x, lp["mlp"], c)

    def scan_layer(x, lp, s, tail, at):
        """-> (x past the layer, the memory, state, tail); decode: ``s``
        and ``tail`` the stacked blocks, ``at`` the layer's index."""
        with jax.named_scope(f"{scope}.ssm"):
            out, m, s, tail = ssm_block(_norm(x, lp, c), lp, c, s, tail,
                                        n_valid, keep, at)
        return add_mlp(x + out, lp), m, s, tail

    def attend_stale(fn, q, k, v, pool_k, pool_v, at, where):
        """The providers' decode form: the stale pool below ``where`` plus
        the row's own K/V."""
        if hasattr(fn, "decode_at"):
            return fn.decode_at(q, k, v, pool_k, pool_v, at, where, active)
        side = jax.tree.map(lambda a: a[at], (pool_k, pool_v))
        return fn.decode(q, k, v, *side, where, active)

    # -- the lower pairs: [scan, attention inside the window] ---------------
    in_place = hasattr(fn_w, "prefill_at")

    def self_pair(carry, scanned):
        x, s, tail, pool = carry
        (lp_s, lp_a, lam), at, rows = scanned
        if decoding:
            x, _, s, tail = scan_layer(x, lp_s, s, tail, at)
        else:
            x, _, rows_s_new, rows_t_new = scan_layer(x, lp_s, *rows, None)
        with jax.named_scope(f"{scope}.attention"), \
                jax.named_scope("attn.window"):
            q, k, v = qkv(_norm(x, lp_a, c), lp_a, c)
            new = (k, v)
            if decoding:
                attn = attend_stale(fn_w, q, k, v, pool_wk, pool_wv, at,
                                    lengths)
            elif in_place:
                attn, *pool = fn_w.prefill_at(q, k, v, *pool, at, lengths,
                                              active)
                pool, new = tuple(pool), None
            else:
                side = jax.tree.map(lambda a: a[at], (pool_wk, pool_wv))
                attn, *new = fn_w(q, k, v, *side, lengths, active)
                new = tuple(new)
            x = x + attn_out(attn, lp_a, c, lam)
        x = add_mlp(x, lp_a)
        if decoding:
            return (x, s, tail, pool), new
        return (x, s, tail, pool), (new, rows_s_new, rows_t_new)

    x = jnp.take(params["embed"], tokens, axis=0)               # [B, T, D]
    (x, state_d, conv_d, carried), ys = jax.lax.scan(
        self_pair,
        (x, state if decoding else None, conv if decoding else None,
         (pool_wk, pool_wv) if not decoding and in_place else None),
        ((params["self"]["ssm"], params["self"]["attn"], lam_self),
         jnp.arange(S), None if decoding else (rows_s[:S], rows_t[:S])))
    if decoding:
        pool_wk, pool_wv = fn_w.insert_all(pool_wk, pool_wv, *ys, lengths,
                                           active)
    else:
        new, low_s, low_t = ys
        pool_wk, pool_wv = carried if in_place else new

    # -- the pair between: the memory, and the one full-context K/V ---------
    mid_s, mid_a = params["mid"]["ssm"], params["mid"]["attn"]
    if decoding:
        x, memory, state, conv = scan_layer(x, mid_s, state_d, conv_d, S)
        last = lengths
    else:
        x, memory, top_s, top_t = scan_layer(x, mid_s, rows_s[S], rows_t[S],
                                             None)
        state = state.at[:, slots].set(
            jnp.concatenate([low_s, top_s[None]], axis=0))
        conv = conv.at[:, slots].set(
            jnp.concatenate([low_t, top_t[None]], axis=0))
        last = lengths + n_valid - 1
    with jax.named_scope(f"{scope}.attention"), jax.named_scope("attn.cross"):
        # The full layer's K/V over EVERY row; from here up, a prefill
        # call keeps each row's last real position only.
        q, k, v = qkv(_norm(x, mid_a, c), mid_a, c)
        if not decoding:
            pool_gk, pool_gv = fn_g.write_at(k, v, pool_gk, pool_gv, 0,
                                             lengths, active)
            at_last = (n_valid - 1)[:, None, None]
            x, memory = (jnp.take_along_axis(t, at_last, axis=1)
                         for t in (x, memory))
            q, k, v = (jnp.take_along_axis(t, at_last[..., None], axis=1)
                       for t in (q, k, v))

    def upper(x, memory, q, k, v):
        """From the full layer's attention up, on the rows given (a decode
        step's; a prefill call's last real positions) -> logits."""
        with jax.named_scope(f"{scope}.attention"), \
                jax.named_scope("attn.cross"):
            attn = attend_stale(fn_g, q, k, v, pool_gk, pool_gv, 0, last)
            x = x + attn_out(attn, mid_a, c, lam_mid)
        x = add_mlp(x, mid_a)

        # -- the upper pairs: [memory gate, attention over that K/V] --------
        def cross_pair(x, scanned):
            lp_g, lp_a, lam = scanned
            with jax.named_scope(f"{scope}.gmu"):
                x = x + gmu_block(_norm(x, lp_g, c), lp_g, memory)
            x = add_mlp(x, lp_g)
            with jax.named_scope(f"{scope}.attention"), \
                    jax.named_scope("attn.cross"):
                q, _, _ = qkv(_norm(x, lp_a, c), lp_a, c)
                attn = attend_stale(fn_g, q, k, v, pool_gk, pool_gv, 0, last)
                x = x + attn_out(attn, lp_a, c, lam)
            return add_mlp(x, lp_a), None

        x, _ = jax.lax.scan(cross_pair, x, (
            params["cross"]["gmu"], params["cross"]["attn"], lam_cross))
        x = layer_norm(x, params["final_norm_w"], c.layer_norm_eps,
                       params["final_norm_b"])
        return head_matmul(x, _select_head(params, c))

    if final is None:
        logits = upper(x, memory, q, k, v)
    else:
        logits = jax.lax.cond(
            jnp.any(final), upper,
            lambda *_: jnp.zeros((B, 1, c.vocab_size), jnp.float32),
            x, memory, q, k, v)
    if decoding:
        pool_gk, pool_gv = fn_g.insert_all(pool_gk, pool_gv, k[None], v[None],
                                           lengths, active)
    return logits, HybridCache(
        k=(pool_wk, pool_gk), v=(pool_wv, pool_gv), state=(state,),
        conv=(conv,), counters=cache.counters)
