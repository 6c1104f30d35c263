"""The PERIOD families: layers of several kinds in one forward, and an
expert layer that holds a share of its experts. ONE scanned body serves
five shapes of period (``ModelConfig``, one family each):

* "hybrid" (Solar-Open2): one gated NoPE softmax layer, then linear-attention
  layers — K/V pages beside recurrent state;
* "smallthinker": softmax layers ONLY, in two cache groups (a global NoPE
  layer, then rotary layers inside a window);
* "mistral4": ONE latent-attention layer — a latent pool, no state;
* "cohere2_moe" (Command A+): softmax layers in PARALLEL blocks, the ring
  the first cache group, a tied head;
* "gigachat3_5": one LATENT layer, then linear-attention layers of the
  gated-delta kind — a latent pool AND recurrent state in one slot —
  behind ``leading_dense`` layers of their own (a linear mixer and a dense
  MLP), every sub-block normed before and after.

The first of them, in full. A period of ``layer_period`` layers is one
softmax layer — grouped-query attention with no rotary embedding, its
output gated by ``sigmoid(W x)`` — followed by linear-attention layers: a
gated delta rule with a per-channel decay. Per head the linear layer keeps
a state ``S`` [dk, dv]::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

with ``q, k, v = SiLU(conv(W x))`` (a causal depthwise convolution over
time), ``q`` and ``k`` L2-normalised per head, ``b_t = 2 sigmoid(W_b x)``
and ``a_t = exp(-exp(A) softplus(W_f x + bias))``. Every layer's MLP is a
sparse expert layer: a sigmoid router over ALL ``n_experts``, the top-k
scores normalised to sum 1, plus shared experts. The layer is told which
experts it holds (``first_expert_held``, ``n_experts_held``) and computes
their part of the result only: what the absent experts would add is left
out (one of several chips that share a layer; on one chip there is no
exchange). No assignment to a held expert is ever dropped.

The same forward serves a period made of softmax layers ONLY
(``ModelConfig.softmax_positions``; SmallThinker): each position of a
period says whether it attends inside the sliding window and whether it
rotates q and k (``window_layout``, ``rope_layout``), the layers are
grouped by the KV they must keep (``cache_groups``: one page pool, page
table and provider a group), the router may score by softmax and read the
block's input, and the experts' gate may be a ReLU.

And it serves a period whose position 0 is a latent-attention layer
(``ModelConfig.is_mla``): the attention sub-block is ``models/mla.py``'s,
its cache group a latent pool (``HybridCache.k`` holds it, ``v`` is empty)
that BOTH step programs carry through the scan and write in place, and the
expert layer is the one above. The period is that layer ALONE
(Mistral-Small-4: a softmax router, no state), or that layer and
linear-attention layers behind it (GigaChat 3.5): then the pool is carried
while the state blocks and conv tails are gathered and scattered by slot as
in the first family. There the linear layer is ``lin_kind`` "gated_delta":
fewer key heads than value heads, ONE decay a value head (broadcast into
the same three forms of the rule), ``b = sigmoid``, the output gated by
``2 sigmoid(h W_z)`` at full width; ``leading_dense`` layers of that mixer
and a dense gated MLP run in FRONT of the periods, one scanned body over a
stacked tree of their own (``params["lead"]``; their state is the last
entry of the cache's tuples); the norm's gain is ``2 sigmoid(w)`` and a
sub-block's branch is normed again before it joins the stream
(``post_norm``); the router selects by score + bias and scales its weights
(``router_bias``, ``routed_scale``); every gated MLP is clamped
(``swiglu_limit``). Each of those is a trace-time branch: a family that
states none computes what it computed.

Those blocks are SEQUENTIAL: a norm, the attention (or linear) sub-block,
an add; another norm, the expert layer, another add. A period of softmax
layers may instead be PARALLEL blocks (``ModelConfig.parallel_block``;
Command A+, family "cohere2_moe")::

    h  = norm(x)                 ONE norm a layer (a LayerNorm without bias
                                 where ``norm_kind`` says so), scope block.norm
    x' = x + A(h) + R(h) + S(h)  ONE add: attention, the routed experts held
                                 here, the shared experts

``A`` is the softmax sub-block without its norm (rotary on interleaved pairs
under ``rope_interleave``), ``R`` and ``S`` are ``moe_block`` handed the
normed input (``normed``), ``S`` the MEAN of the shared experts' outputs.
Either way a sub-block returns its BRANCH and ``period_step`` adds it to
the stream. That family's windowed layers come FIRST in a period, so its
first cache group is the ring and the global group the second; its head is
the embedding (``tie_embeddings``: no ``lm_head``; under quant an int8 copy
of the embedding's own rows, ``lm_head_q8``, as the llama family keeps it).

TPU-first decisions:

* ``lax.scan`` over PERIODS, one compiled body whatever the depth. Every
  layer of a period has its own tree stacked ``[P, ...]`` (the softmax
  layer, and a tuple of the linear ones), so that the body reads each
  weight through ONE dynamic slice of its leading axis, as
  ``llama.forward`` does: an inner scan over a period's linear layers
  hands the chip's compiler a sliced copy of a period's weights (the
  decode program compiled for a v5e at the published widths: 5.1 GB of
  temporaries with the inner scan, 2.0 GB without).
* The softmax layers use the ``attention_fn`` protocol of
  ``llama.forward`` on a page pool of ``n_layers / layer_period`` layers:
  both paged kernels serve them unchanged.
* The cache is the page pool PLUS a fixed block of recurrent state and a
  convolution tail per slot (``HybridCache``). Prefill gathers the rows of
  its slots, runs the block-parallel form over sub-chunks of 64 tokens and
  scatters the rows back; decode reads and writes the whole block once a
  step, IN PLACE: the stacked blocks ride the scans' carry and each
  linear layer's one-token update is ONE Pallas kernel whose state
  operand is its result (``ops/delta_update.py``). State is float32.
* The expert layer has two exact forms: every held expert on every token
  (decode, small calls: the weights stream from memory either way), and a
  grouped product over the assignments sorted by expert, tile by tile,
  whose trip count is the number of live tiles: ONE Pallas kernel
  (``ops/grouped_experts.py``) over rows quantised once a call, which adds
  each tile's weighted results onto its tokens' rows of a float32 result
  that stays in fast memory.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from . import mla
from .config import ModelConfig
from .llama import (_select_head, apply_rope, block_norm, gated_hidden,
                    layer_norm, rms_norm, rope_tables, swiglu_mlp)
from ..ops.delta_update import delta_update
from ..ops.grouped_experts import grouped_experts, rows_that_fit
from .quant import (_dynamic_int8, head_matmul, is_quantized, mm,
                    moe_mm_batched, quantize_array, weight_bits)

Params = dict[str, Any]
HIGHEST = jax.lax.Precision.HIGHEST

KDA_CHUNK = 64          # tokens per sub-chunk of the block-parallel form
KDA_BLOCK = 16          # tokens per block inside a sub-chunk (exact decays)
DENSE_MAX_TOKENS = 64   # calls up to this size run every held expert
GROUP_TILE = 128        # rows per tile of the grouped expert product
N_COUNTERS = 5          # HybridCache.counters, moe_block's counted vector
EXPERT_KEYS = ("wg", "wu", "wd")    # the routed experts' matrices
# Stored int8 under quant (contraction axis second to last): the projections
# of both layer kinds, the softmax gate, routed and shared experts. NOT the
# router and its bias, the rank-r gate pairs, W_a, W_b, the conv taps, A,
# the decay's bias or the norms: small, and they decide routing and decay.
QUANT_KEYS = frozenset({"wq", "wk", "wv", "wz", "wo", "wgate", "sg", "su",
                        "sd", *EXPERT_KEYS})


class HybridCache(NamedTuple):
    """``k``, ``v``: the page pools of the softmax layers, a tuple over
    ``ModelConfig.cache_groups``, each as ``PagedKVCache``'s ([layers of
    the group, pages of the group, KV, page, Dh], or its int8 dict; a
    group's layer ``j`` of period ``p`` lies at ``p * n + j``). A latent
    layer's group is ONE pool (``ops/latent_attention.py``: [layers,
    pages, latent_width, page]) in ``k``, and ``v`` is ``()``.
    ``state`` and ``conv``: the linear layers' recurrent state and the
    last inputs of their convolutions, one fixed block per slot — a tuple
    over a period's linear layers of [P, B, H, dk, dv] float32 and of
    [P, B, taps-1, ``lin_conv_width``], and LAST, where the family has
    ``leading_dense`` layers, theirs stacked the same way ([leading, B,
    ...]). A family may hold a latent pool AND state. ``counters``
    int32 [``N_COUNTERS``], running totals that wrap: of the DECODE steps
    so far, the routed assignments (all; landing on a held expert) and,
    summed over layers, the held experts that at least one active row was
    assigned to; then, of every call that took the grouped expert product
    (a prefill chunk), the tiles it ran and the rows they held.
    ``index``: where the softmax layers have an indexer
    (``ModelConfig.is_sparse``), the THIRD side of each group's pool — one
    index key a token, [layers of the group, pages of the group,
    ``idx_head_dim``, page] (ops/sparse_attention.py) on the group's own
    page table — else ``()``."""
    k: Any
    v: Any
    state: tuple[jax.Array, ...]
    conv: tuple[jax.Array, ...]
    counters: jax.Array
    index: tuple[jax.Array, ...] = ()

    @classmethod
    def create(cls, config: ModelConfig, num_pages: int | tuple[int, ...],
               page_size: int, batch: int, dtype=jnp.bfloat16,
               kv_quant: str = "") -> "HybridCache":
        """``num_pages``: the pages of each cache group's pool (one
        number: of every group's)."""
        from dataclasses import replace
        from ..ops.paged_attention import PagedKVCache
        c = config
        if isinstance(num_pages, int):
            num_pages = (num_pages,) * len(c.cache_groups)
        if c.cross_decoder:     # its own layout of the same type
            from .sambay import create_cache
            return create_cache(c, num_pages, page_size, batch, dtype,
                                kv_quant)
        periods = c.n_periods
        if c.is_mla:
            from ..ops.latent_attention import create_latent_pool
            k, v = (create_latent_pool(c.n_kv_layers, num_pages[0], page_size,
                                       c.latent_width, dtype),), ()
        else:
            pools = [PagedKVCache.create(
                replace(c, n_layers=periods * len(positions)), pages,
                page_size, dtype, kv_quant)
                for (_, positions), pages in zip(c.cache_groups, num_pages)]
            k, v = tuple(p.k for p in pools), tuple(p.v for p in pools)
        index = ()
        if c.is_sparse:
            from ..ops.sparse_attention import create_index_pool
            index = tuple(create_index_pool(
                periods * len(positions), pages, page_size, c.idx_head_dim,
                dtype) for (_, positions), pages in zip(c.cache_groups,
                                                        num_pages))
        # A stack of blocks a linear position of a period, then the
        # leading layers' stack.
        stacks = [periods] * (c.layer_period - len(c.softmax_positions))
        stacks += [c.leading_dense] if c.leading_dense else []
        return cls(
            k=k, v=v,
            state=tuple(jnp.zeros((n, batch, c.lin_heads, c.lin_head_dim,
                                   c.lin_head_dim), jnp.float32)
                        for n in stacks),
            conv=tuple(jnp.zeros((n, batch, c.lin_conv_taps - 1,
                                  c.lin_conv_width), dtype) for n in stacks),
            counters=jnp.zeros((N_COUNTERS,), jnp.int32), index=index)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_params(config: ModelConfig, key: jax.Array,
                dtype: jnp.dtype = jnp.bfloat16, quant: str = "") -> Params:
    """Seeded random params. With ``quant`` every matrix of ``QUANT_KEYS``
    is quantised where it is drawn, an expert at a time, so that no
    full-precision copy of a period (6 GB at the published widths) ever
    exists.

    Layout (P periods of ``per`` layers; D model, H heads of Dh, KV heads;
    Hl linear heads of dk; r gate rank; E experts of which ``held`` live
    here, width F; Fs shared width):
      embed [V, D]; final_norm [D]; lm_head [V, D] (a tied head: none,
                   and under quant ``lm_head_q8``, the embedding's own
                   rows in int8)
      layers/attn/{norm [P,D], wq [P,D,H*Dh], wk, wv [P,D,KV*Dh],
                   wgate [P,D,H*Dh], wo [P,H*Dh,D], mlp/...}
                   (``wgate`` with ``attn_gate`` only; a period of several
                   softmax layers: a tuple of such trees, one a position)
      layers/lin/(per-1 trees of){norm [P,D], wq, wk, wv [P,D,Hl*dk],
                  conv [P,taps,3*Hl*dk], wf_down [P,D,r],
                  wf_up [P,r,Hl*dk], f_bias [P,Hl*dk], a_log [P,Hl],
                  wbeta [P,D,Hl], wg_down [P,D,r], wg_up [P,r,Hl*dk],
                  out_norm [P,dk], wo [P,Hl*dk,D], mlp/...}
      layers/attn with ``qk_norm``: + q_norm, k_norm [P,Dh]; with an
                   indexer (J index heads of W): + wqi [P,D,J*W],
                   wki [P,D,W], wwi [P,D,J], ki_norm, ki_bias [P,W]
      layers/attn of a latent layer: models/mla.py ``init_layer``'s tree,
                   stacked [P, ...], with its mlp/...
      layers/lin of ``lin_kind`` "gated_delta" (Hk key heads, Hv value
                  heads): {norm, wq, wk [P,D,Hk*dk], wv, wz [P,D,Hv*dk],
                  conv [P,taps,(2Hk+Hv)*dk], wa, wbeta [P,D,Hv],
                  dt_bias, a_log [P,Hv], out_norm [P,dk], wo, mlp/...}
      lead/{the linear layer's keys, mlp/{norm [L,D], wg, wu [L,D,d_ff],
            wd [L,d_ff,D]}} stacked over the ``leading_dense`` layers
      ``post_norm`` [.., D] beside every ``norm`` under
                  ``ModelConfig.post_norm``; mlp/router_bias [P,E] float32
                  under ``router_bias``
      .../mlp/{norm [P,D], router [P,D,E], wg, wu [P,held,D,F],
               wd [P,held,F,D], sg, su [P,D,Fs], sd [P,Fs,D]}
               (``sg``, ``su``, ``sd`` with shared experts only: the shared
               experts side by side, Fs = their number x F; no ``norm``
               in a parallel block, whose one norm is the layer's)
    The residual stream is drawn at unit scale and every projection back
    into it (``wo``, ``wd``, ``sd``) at ``(2 n_layers)^-1/2`` of the usual
    (the GPT-2 convention): a delta rule with b up to 2 and slow decays
    answers a relative change of its input with ~3 times that change of
    its output, and with branches as large as the stream a rounding error
    doubles every period — the random network would be chaotic, which no
    trained one is. The decay parameters are drawn so that the median per-channel decay
    lies in 0.9-0.999 (``a_log = log U(1,8)``, ``softplus(f_bias)`` log-
    uniform in 0.001-0.05, a small ``wf_up``): with unit-scale gates the
    layer would forget in a token and no comparison could see its state.
    A norm is drawn at gain 1 (raw weight 0 where the gain is ``2
    sigmoid(w)``); a POST norm at the gain ``(2 n_layers)^-1/2 / 2``, for
    it sets its branch's size whatever ``wo`` is drawn at — and HALF the
    convention's scale is the size a branch has in the families without
    one, where a unit input leaves a sigmoid gate or a SiLU-gated product
    at an rms of 0.54 before its ``wo`` (a post norm at the full scale
    doubles every branch, and with it what a rounded stream or a flipped
    eighth expert moves a logit by). A router's selection bias is drawn
    N(0, 0.1^2): enough to change a token's eighth expert. Where the family
    clamps its gated MLPs (``swiglu_limit`` L), their gate and up matrices
    are drawn at L / 1.25 of the usual scale when that is more than 1, so
    that the clamp bites at 1.25 standard deviations — a fifth of the up
    products, a tenth of the gates — and a comparison can see it (the post
    norm takes the scale out again; at 2.5 standard deviations, one product
    in a hundred, an un-clamped reference read like the sound one on the
    chip: PERF.md section 6, PR 46).
    Under a TIED head the final norm's gain is drawn as random SIGNS at
    ``D^-1/2``. The magnitude brings the unit-variance embedding's logits
    to the unit variance an untied head's rows are drawn for. The signs
    are there because the one matrix is read at both ends: the stream
    carries the input token's own row to the head, and under a gain of one
    sign that token's logit (``|row|^2 D^-1/2`` = 64 at D = 4096, the
    others of unit variance) would be the maximum at every position
    whatever the layers computed — a comparison of served tokens would
    compare nothing. Under random signs it is one more unit-variance
    number (a trained model's learned gain and moved stream do the same).
    """
    c = config
    if (not c.layer_period
            or (c.n_layers - c.leading_dense) % c.layer_period):
        raise ValueError("hybrid.init_params needs a layer_period and "
                         "whole periods of layers behind the leading ones")
    if c.lin_heads and not c.is_mla and (c.use_rope or not c.attn_gate):
        raise ValueError("the hybrid family's softmax layers carry no "
                         "rotary embedding and gate their output: use_rope "
                         "must be False, attn_gate True")
    if c.parallel_block and (c.lin_heads or c.is_mla):
        raise ValueError("a parallel block is a period of softmax layers: "
                         "no linear-attention and no latent layer has one")
    per, P = c.layer_period, c.n_periods
    n_soft = len(c.softmax_positions)
    D, dh, dk, Hl, r = (c.d_model, c.head_dim, c.lin_head_dim, c.lin_heads,
                        c.lin_gate_rank)
    Hk = c.lin_kheads
    F, held = c.d_ff_expert, c.experts_held
    Fs = c.n_shared_experts * F
    back = (2 * c.n_layers) ** -0.5     # projections into the residual
    two_sigmoid = c.norm_kind == "rms_2sigmoid"
    gated = max(1.0, c.swiglu_limit / 1.25)     # gate and up matrices

    def norms():
        """A sub-block's norm at gain 1 and, where it has one, its post
        norm at gain ``back / 2`` (as raw weights of ``2 sigmoid(w)``: 0,
        and the logit of a quarter of ``back``)."""
        gain = back / 2.0
        unit, post = (0.0, math.log(gain / (2.0 - gain))) if two_sigmoid \
            else (1.0, gain)
        out = {"norm": jnp.full((D,), unit, dtype)}
        if c.post_norm:
            out["post_norm"] = jnp.full((D,), post, dtype)
        return out

    def dense(k, *shape, scale=1.0, name=""):
        w = (jax.random.normal(k, shape, jnp.float32)
             * (scale / math.sqrt(shape[-2]))).astype(dtype)
        if quant and name in QUANT_KEYS | mla.QUANT_KEYS:
            return quantize_array(w, w.ndim - 2,
                                  bits=weight_bits(quant, f"layers.{name}"))
        return w

    def mlp(k):
        ks = jax.random.split(k, 7)

        def expert(ke):
            kg, ku, kd = jax.random.split(ke, 3)
            return {"wg": dense(kg, D, F, scale=gated, name="wg"),
                    "wu": dense(ku, D, F, scale=gated, name="wu"),
                    "wd": dense(kd, F, D, scale=back, name="wd")}
        out = jax.lax.map(expert, jax.random.split(ks[0], held))
        if not c.parallel_block:        # its one norm is the layer's
            out.update(norms())
        out.update(router=dense(ks[1], D, c.n_experts))
        if c.router_bias:
            out.update(router_bias=0.1 * jax.random.normal(
                ks[5], (c.n_experts,), jnp.float32))
        if Fs:
            out.update(sg=dense(ks[2], D, Fs, scale=gated, name="sg"),
                       su=dense(ks[3], D, Fs, scale=gated, name="su"),
                       sd=dense(ks[4], Fs, D, scale=back, name="sd"))
        return out

    def attn_layer(k):
        ks = jax.random.split(k, 6)
        if c.is_mla:
            return {**mla.init_layer(c, ks[:5], dense, dtype), **norms(),
                    "mlp": mlp(ks[5])}
        gate = {"wgate": dense(ks[3], D, c.n_heads * dh, name="wgate")
                } if c.attn_gate else {}
        if c.qk_norm:
            # Not ones: ``qk_norm_draw`` (models/config.py says why).
            gate.update(q_norm=jnp.full((dh,), c.qk_norm_draw, dtype),
                        k_norm=jnp.full((dh,), c.qk_norm_draw, dtype))
        if c.is_sparse:
            # The indexer, un-quantised (it decides the selection, as the
            # router decides the routing); the index key's LayerNorm has a
            # bias, drawn N(0, 0.1^2) so that a comparison can see it.
            ki = jax.random.split(jax.random.fold_in(k, 1), 4)
            J, W = c.idx_heads, c.idx_head_dim
            gate.update(wqi=dense(ki[0], D, J * W), wki=dense(ki[1], D, W),
                        wwi=dense(ki[2], D, J),
                        ki_norm=jnp.ones((W,), dtype),
                        ki_bias=(0.1 * jax.random.normal(
                            ki[3], (W,), jnp.float32)).astype(dtype))
        return {**norms(), **gate,
                "wq": dense(ks[0], D, c.n_heads * dh, name="wq"),
                "wk": dense(ks[1], D, c.n_kv_heads * dh, name="wk"),
                "wv": dense(ks[2], D, c.n_kv_heads * dh, name="wv"),
                "wo": dense(ks[4], c.n_heads * dh, D, scale=back, name="wo"),
                "mlp": mlp(ks[5])}

    def lin_layer(k, mlp=mlp):
        ks = jax.random.split(k, 13)
        per_head = c.lin_kind == "gated_delta"     # one decay a value head
        step = jnp.exp(jax.random.uniform(
            ks[8], (Hl if per_head else Hl * dk,), jnp.float32,
            math.log(1e-3), math.log(5e-2)))
        shared = {**norms(),
                  "wq": dense(ks[0], D, Hk * dk, name="wq"),
                  "wk": dense(ks[1], D, Hk * dk, name="wk"),
                  "wv": dense(ks[2], D, Hl * dk, name="wv"),
                  "conv": jax.random.normal(
                      ks[3], (c.lin_conv_taps, c.lin_conv_width), jnp.float32)
                  / math.sqrt(c.lin_conv_taps),
                  "a_log": jnp.log(jax.random.uniform(
                      ks[7], (Hl,), jnp.float32, 1.0, 8.0)),
                  "wbeta": dense(ks[6], D, Hl),
                  "out_norm": jnp.ones((dk,), dtype),
                  "wo": dense(ks[11], Hl * dk, D, scale=back, name="wo"),
                  "mlp": mlp(ks[12])}
        if per_head:
            return {**shared,
                    "wz": dense(ks[9], D, Hl * dk, name="wz"),
                    "wa": dense(ks[4], D, Hl, scale=0.5),
                    # softplus(dt_bias) = step
                    "dt_bias": jnp.log(jnp.expm1(step))}
        return {**shared,
                "wf_down": dense(ks[4], D, r),
                "wf_up": dense(ks[5], r, Hl * dk, scale=0.5),
                # softplus(f_bias) = step
                "f_bias": jnp.log(jnp.expm1(step)),
                "wg_down": dense(ks[9], D, r),
                "wg_up": dense(ks[10], r, Hl * dk)}

    def dense_mlp(k):
        kg, ku, kd = jax.random.split(k, 3)
        return {**norms(), "wg": dense(kg, D, c.d_ff, scale=gated, name="wg"),
                "wu": dense(ku, D, c.d_ff, scale=gated, name="wu"),
                "wd": dense(kd, c.d_ff, D, scale=back, name="wd")}

    def period(k):
        ks = list(jax.random.split(k, per))
        soft = tuple(map(attn_layer, ks[:n_soft]))
        return {"attn": soft if n_soft > 1 else soft[0],
                "lin": tuple(map(lin_layer, ks[n_soft:]))}

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    tied = c.tie_embeddings
    head = None if tied else (
        jax.random.normal(k_head, (c.vocab_size, D), jnp.float32)
        / math.sqrt(D)).astype(dtype)
    embed = jax.random.normal(k_embed, (c.vocab_size, D),
                              jnp.float32).astype(dtype)
    if tied:
        # ONE matrix: the head is the embedding; under quant the head's
        # product reads an int8 copy of the embedding's own rows.
        signs = jnp.where(jax.random.bernoulli(k_head, 0.5, (D,)), 1.0, -1.0)
        final_norm = (signs * D ** -0.5).astype(dtype)
        top = {"lm_head_q8": quantize_array(embed, 1)} if quant else {}
    else:
        final_norm = jnp.ones((D,), dtype)
        top = {"lm_head": (quantize_array(
            head, 1, bits=weight_bits(quant, "lm_head")) if quant else head)}
    if two_sigmoid:
        final_norm = jnp.zeros((D,), dtype)
    if c.leading_dense:
        top["lead"] = jax.lax.map(
            partial(lin_layer, mlp=dense_mlp), jax.random.split(
                jax.random.fold_in(k_layers, 1), c.leading_dense))
    return {"embed": embed, "final_norm": final_norm, **top,
            "layers": jax.lax.map(period, jax.random.split(k_layers, P))}


# ---------------------------------------------------------------------------
# The linear layer
# ---------------------------------------------------------------------------

def delta_step(q, k, v, log_a, beta, s):
    """One token, in plain jnp — the DEFINITION of the rule's step: q, k
    [B,H,dk], log_a [B,H,dk] or [B,H,1], v [B,H,dv], beta [B,H], s
    [B,H,dk,dv] -> (o [B,H,dv], s_new). Reductions are multiply-and-sum in
    float32; both reductions over the decayed state share its one read:
    ``o = S_new^T q = S_dec^T q + u (k . q)``."""
    with jax.named_scope("kda.decode_update"):
        s_dec = s * jnp.exp(log_a)[..., None]
        r_k = jnp.sum(s_dec * k[..., None], axis=-2)
        r_q = jnp.sum(s_dec * q[..., None], axis=-2)
        u = beta[..., None] * (v - r_k)
        o = r_q + u * jnp.sum(k * q, axis=-1, keepdims=True)
        return o, s_dec + k[..., None] * u[..., None, :]


def kda_recurrent(q, k, v, log_a, beta, s0):
    """The recurrence token by token (``delta_step`` scanned) — what
    ``kda_chunked`` and ``kda_decode_update`` are held to, and what
    ``kda_chunked`` runs for a call that is not whole sub-chunks.
    q, k [B,T,H,dk] (normalised, q scaled), v [B,T,H,dv], log_a [B,T,H,dk]
    (<= 0; [B,T,H,1]: ONE decay a head, broadcast over its channels in
    all three forms), beta [B,T,H], s0 [B,H,dk,dv]; all float32.
    Returns (o [B,T,H,dv], s_T)."""
    def step(s, x):
        o, s = delta_step(*x, s)
        return s, o
    xs = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (q, k, v, log_a, beta))
    s, o = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(o, 0, 1), s


@jax.jit
def _state_update(state, at, q, k, v, log_a, beta, keep):
    """``delta_step`` on layer ``at`` of a STACKED state block [layers, B,
    H, dk, dv], where ``keep`` [B]: ONE kernel that reads and writes the
    block in place (``ops/delta_update.py``). One jitted function, so a
    decode program traces and lowers it once however many linear layers
    its scans' bodies unroll."""
    with jax.named_scope("kda.decode_update"):
        return delta_update(state, at, q, k, v, log_a, beta, keep)


def kda_decode_update(q, k, v, log_a, beta, s):
    """``delta_step`` (same arguments and result) as the decode programs
    run it: the kernel, on ``s`` as a stack of one layer."""
    o, stack = _state_update(s[None], 0, q, k, v, log_a, beta,
                             jnp.ones(s.shape[:1], bool))
    return o, stack[0]


def _unit_lower_inverse(a: jax.Array) -> jax.Array:
    """(I + A)^-1 for strictly lower-triangular A [..., n, n], by forward
    substitution in float32 (n is a block: 16 rows, unrolled)."""
    n = a.shape[-1]
    x = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    for t in range(1, n):
        row = x[..., t, :] - jnp.sum(a[..., t, :, None] * x, axis=-2)
        x = x.at[..., t, :].set(row)
    return x


def kda_chunked(q, k, v, log_a, beta, s0, chunk: int = KDA_CHUNK,
                block: int = KDA_BLOCK):
    """The block-parallel form of :func:`kda_recurrent` (same arguments and
    result), over sub-chunks of ``chunk`` tokens that carry ``S``.

    With ``g`` the cumulative log-decay inside a sub-chunk, ``A[t,s] = b_t
    sum_c k_t k_s e^{g_t-g_s}`` (s < t) and ``N[t,s] = sum_c q_t k_s
    e^{g_t-g_s}`` (s <= t), the delta values solve ``(I + A) U = b (V -
    (e^g K) S_0)``, the outputs are ``(e^g Q) S_0 + N U`` and the state
    leaves as ``e^{g_C} S_0 + (e^{g_C-g} K)^T U``. Every decay is the
    exponential of a DIFFERENCE of cumulative logs that is <= 0 — never a
    quotient of cumulative products, which underflows with a fast decay:
    across blocks of ``block`` tokens the difference is split at the
    t-block's first cumulative log (both factors <= 1, so the products are
    two matrix products); inside a block it is formed per channel."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    bl = min(block, C)
    n, nb = T // C, C // bl
    if T % C or C % bl:
        return kda_recurrent(q, k, v, log_a, beta, s0)

    def sub(x):                     # [B,T,H,..] -> [n,B,H,C,..]
        x = x.reshape(B, n, C, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    dot = partial(jnp.einsum, precision=HIGHEST)
    # s lies in a block before t's: [nb, bl, C]
    earlier = jnp.broadcast_to(
        jnp.arange(C)[None, None, :] < (jnp.arange(nb) * bl)[:, None, None],
        (nb, bl, C))
    upto = jnp.tril(jnp.ones((bl, bl), bool))           # s <= t in a block
    before = jnp.tril(jnp.ones((bl, bl), bool), -1)     # s < t

    def step(s, x):
        with jax.named_scope("kda.prefill_chunk"):
            qc, kc, vc, lc, bc = x                      # [B,H,C,..]
            g = jnp.cumsum(lc, axis=2)                  # [B,H,C,dk or 1]
            gb = g.reshape(B, H, nb, bl, g.shape[-1])
            qb, kb = (a.reshape(B, H, nb, bl, dk) for a in (qc, kc))
            bb = bc.reshape(B, H, nb, bl)
            # Across blocks: split at ref_i = g just before block i.
            ref = jnp.concatenate(
                [jnp.zeros_like(gb[:, :, :1, -1]), gb[:, :, :-1, -1]], 2)
            e_t = jnp.exp(gb - ref[:, :, :, None, :])           # <= 1
            ks = kc[:, :, None] * jnp.exp(jnp.minimum(
                ref[:, :, :, None, :] - g[:, :, None, :, :], 0.0))
            a_x = jnp.where(earlier, bb[..., None] * dot(
                "bhitc,bhisc->bhits", kb * e_t, ks), 0.0)   # [B,H,nb,bl,C]
            n_x = jnp.where(earlier, dot(
                "bhitc,bhisc->bhits", qb * e_t, ks), 0.0)
            # Inside a block: the decay per channel.
            kk = kb[:, :, :, None, :, :] * jnp.exp(jnp.minimum(
                gb[:, :, :, :, None, :] - gb[:, :, :, None, :, :], 0.0))
            a_d = jnp.where(before, bb[..., None] * jnp.sum(
                kb[:, :, :, :, None, :] * kk, -1), 0.0)     # [B,H,nb,bl,bl]
            n_d = jnp.where(upto, jnp.sum(
                qb[:, :, :, :, None, :] * kk, -1), 0.0)
            decay = jnp.exp(g)
            w = bc[..., None] * (vc - dot("bhtc,bhcv->bhtv", kc * decay, s))
            # (I + A) U = W block by block: the diagonal blocks inverted by
            # forward substitution, the blocks below them matrix products.
            inv = _unit_lower_inverse(a_d)
            wb = w.reshape(B, H, nb, bl, dv)
            ab = a_x.reshape(B, H, nb, bl, nb, bl)
            us: list[jax.Array] = []
            for i in range(nb):
                rhs = wb[:, :, i]
                for j in range(i):
                    rhs = rhs - dot("bhts,bhsv->bhtv", ab[:, :, i, :, j],
                                    us[j])
                us.append(dot("bhts,bhsv->bhtv", inv[:, :, i], rhs))
            ub = jnp.stack(us, axis=2)                  # [B,H,nb,bl,dv]
            u = ub.reshape(B, H, C, dv)
            o = (dot("bhtc,bhcv->bhtv", qc * decay, s)
                 + (dot("bhits,bhsv->bhitv", n_x, u)
                    + dot("bhits,bhisv->bhitv", n_d, ub)
                    ).reshape(B, H, C, dv))
            g_end = g[:, :, -1:, :]
            s = (jnp.exp(g_end[:, :, 0])[..., None] * s
                 + dot("bhtc,bhtv->bhcv", kc * jnp.exp(g_end - g), u))
            return s, o

    s, o = jax.lax.scan(step, s0, tuple(map(sub, (q, k, v, log_a, beta))))
    # [n,B,H,C,dv] -> [B,T,H,dv]
    return jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3).reshape(B, T, H, dv), s


def _conv_silu(x_ext: jax.Array, taps: jax.Array,
               bias: jax.Array | None = None) -> jax.Array:
    """Causal depthwise convolution (and its ``bias`` [C], where the layer
    has one) then SiLU. x_ext [B, T+taps-1, C] (the tail of the previous
    inputs first), taps [taps, C] -> [B, T, C] f32."""
    n = taps.shape[0]
    T = x_ext.shape[1] - (n - 1)
    xf = x_ext.astype(jnp.float32)
    y = sum(taps[j].astype(jnp.float32) * xf[:, j:j + T] for j in range(n))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y)


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def linear_block(h, lp, c: ModelConfig, s0, tail, n_valid, keep, at=None):
    """One linear-attention layer on normalised input ``h`` [B,T,D].
    ``tail`` [B,taps-1,``lin_conv_width``]: the rows' last inputs on entry.
    PREFILL: ``s0`` [B,H,dk,dv], the rows' state on entry; ``n_valid``
    [B]: tokens past it are padding and move neither the state nor the
    tail (b = 0, a = 1, the tail taken at the true length). DECODE
    (``keep`` [B] bool): ``s0`` is the STACKED block [layers,B,H,dk,dv],
    this layer the one at index ``at``, and the block is what comes back,
    written in place; rows that are False leave with the state and the
    tail they came with. Returns (out [B,T,D], state, tail).

    ``lin_kind`` "kda": H key heads, a decay a channel through a low-rank
    pair, b in (0, 2), the output gated by sigmoid(low-rank pair).
    "gated_delta": ``lin_kheads`` key heads, key head ``j`` serving value
    heads ``j H/Hk ..``; ONE decay a value head (``log_a`` [B,T,H,1],
    broadcast inside the same three forms of the rule), b in (0, 1), the
    output gated by ``2 sigmoid(h W_z)`` at full width."""
    B, T, _ = h.shape
    H, Hk, dk = c.lin_heads, c.lin_kheads, c.lin_head_dim
    taps = c.lin_conv_taps
    f32 = jnp.float32
    x = jnp.concatenate([mm(h, lp["wq"]), mm(h, lp["wk"]), mm(h, lp["wv"])],
                        axis=-1)                    # [B,T,(2Hk+H)*dk]
    x_ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    qkv = _conv_silu(x_ext, lp["conv"])
    q, k, v = (a.reshape(B, T, -1, dk) for a in jnp.split(
        qkv, (Hk * dk, 2 * Hk * dk), axis=-1))
    q, k = _l2norm(q) * dk ** -0.5, _l2norm(k)
    if Hk != H:
        q, k = (jnp.repeat(a, H // Hk, axis=2) for a in (q, k))
    low = partial(jnp.einsum, preferred_element_type=f32)
    rate = -jnp.exp(lp["a_log"].astype(f32))[:, None]               # [H,1]
    if c.lin_kind == "gated_delta":
        z = low("btd,dh->bth", h, lp["wa"]) + lp["dt_bias"].astype(f32)
        log_a = rate * jax.nn.softplus(z)[..., None]                # [B,T,H,1]
        beta = jax.nn.sigmoid(low("btd,dh->bth", h, lp["wbeta"]))
        gate = 2.0 * jax.nn.sigmoid(mm(h, lp["wz"]).astype(f32))
    else:
        z = low("btr,rf->btf",
                low("btd,dr->btr", h, lp["wf_down"]).astype(h.dtype),
                lp["wf_up"]) + lp["f_bias"].astype(f32)
        log_a = rate * jax.nn.softplus(z.reshape(B, T, H, dk))
        beta = 2.0 * jax.nn.sigmoid(low("btd,dh->bth", h, lp["wbeta"]))
        gate = jax.nn.sigmoid(
            low("btr,rf->btf",
                low("btd,dr->btr", h, lp["wg_down"]).astype(h.dtype),
                lp["wg_up"]))
    if T == 1 and keep is not None:
        o, s = _state_update(s0, at, q[:, 0], k[:, 0], v[:, 0], log_a[:, 0],
                             beta[:, 0], keep)
        o = o[:, None]
        new_tail = jnp.where(keep[:, None, None], x_ext[:, 1:], tail)
    else:
        live = jnp.arange(T)[None, :] < n_valid[:, None]        # [B,T]
        log_a = jnp.where(live[..., None, None], log_a, 0.0)
        beta = jnp.where(live[..., None], beta, 0.0)
        o, s = kda_chunked(q, k, v, log_a, beta, s0)
        new_tail = jax.vmap(lambda row, at: jax.lax.dynamic_slice_in_dim(
            row, at, taps - 1, axis=0))(x_ext, n_valid)
    o = rms_norm(o, lp["out_norm"], c.rms_eps)              # per head, f32
    out = (o.reshape(B, T, H * dk) * gate).astype(h.dtype)
    return mm(out, lp["wo"]), s, new_tail.astype(tail.dtype)


# ---------------------------------------------------------------------------
# The expert layer
# ---------------------------------------------------------------------------

def route(hf: jax.Array, router: jax.Array, c: ModelConfig,
          bias: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    """hf [N,D] float32 (un-quantised) -> the top-k experts of ALL
    ``n_experts``, [N,k] ids and weights that sum to ``routed_scale``
    (float32, full-precision product: a rounded score flips the 8th and
    9th expert). ``moe_router`` "sigmoid": by sigmoid score — plus
    ``bias`` [E] where the router has one, for the SELECTION only —, the
    selected scores normalised; "softmax": by logit, softmax over the
    selected (= softmax over all, renormalised on the selected)."""
    logits = jnp.dot(hf, router.astype(jnp.float32), precision=HIGHEST)
    if c.moe_router == "softmax":
        top, idx = jax.lax.top_k(logits, c.experts_per_token)
        w = jax.nn.softmax(top, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
        if bias is None:
            top, idx = jax.lax.top_k(scores, c.experts_per_token)
        else:
            _, idx = jax.lax.top_k(scores + bias, c.experts_per_token)
            top = jnp.take_along_axis(scores, idx, axis=-1)
        w = top / jnp.sum(top, axis=-1, keepdims=True)
    return idx, w if c.routed_scale == 1.0 else w * c.routed_scale


def held_weights(idx: jax.Array, w: jax.Array, c: ModelConfig) -> jax.Array:
    """[N,k] routing -> [N,held]: each token's weight on each expert held
    here, 0 where the expert is not among its top-k."""
    local = idx - c.first_expert_held
    hit = local[:, :, None] == jnp.arange(c.experts_held)[None, None, :]
    return jnp.sum(jnp.where(hit, w[:, :, None], 0.0), axis=1)


def _at(tree: Any, i: jax.Array | None) -> Any:
    """``tree``'s leaves at index ``i`` of their leading axis (None: as
    they are)."""
    if i is None:
        return tree
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), tree)


def experts_dense(x: jax.Array, probs: jax.Array, lp: Params,
                  period: jax.Array | None = None, act: str = "silu",
                  limit: float = 0.0) -> jax.Array:
    """Every held expert on every token. x [N,D], probs [N,held] -> [N,D]
    float32. With ``period`` the experts' matrices are stacked over
    periods and read at that index. ``act``: the gate's activation;
    ``limit``: its clamp (``llama.gated_hidden``)."""
    lp = _at({k: lp[k] for k in EXPERT_KEYS}, period)
    # The expert axis is a BATCH axis of all three products, so the weights
    # are read where they lie ([held, D, F]: a product that contracts D
    # with the experts as a free axis has them re-laid out first, a copy of
    # every expert's weights a step).
    xe = jnp.broadcast_to(x, (probs.shape[1], *x.shape))
    hid = gated_hidden(act, moe_mm_batched(xe, lp["wg"]),
                       moe_mm_batched(xe, lp["wu"]), limit)
    y = moe_mm_batched(hid, lp["wd"])                       # [held,N,D]
    return jnp.einsum("end,ne->nd", y.astype(jnp.float32), probs)


class GroupedLayout(NamedTuple):
    """Where the routed rows lie when they are sorted by expert in tiles:
    ``row_token`` [rows] the token a row holds (``N``: a padding row),
    ``dest`` [N,k] the row each of a token's assignments landed in
    (``rows``: it landed on no held expert), ``tile_expert`` [n_tiles]
    the expert a tile belongs to, ``counted`` int32 [2] the tiles that
    hold rows (they come first) and the rows they hold."""
    row_token: jax.Array
    dest: jax.Array
    tile_expert: jax.Array
    counted: jax.Array


def grouped_layout(idx: jax.Array, held: int, tile: int) -> GroupedLayout:
    """idx [N,k]: each token's experts, numbered from the first one held
    (anything outside ``[0, held)`` is not held here). Each expert's
    group is padded to whole tiles; the layout has room for the worst
    case — every token on ``min(k, held)`` held experts, a partial tile an
    expert — so nothing is ever dropped."""
    N, k = idx.shape
    landed = (idx >= 0) & (idx < held)                      # [N,k]
    routed = jnp.any(idx[:, :, None] == jnp.arange(held), axis=1)
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)       # [held]
    tiles = (counts + tile - 1) // tile
    last_tile = jnp.cumsum(tiles)                           # inclusive
    n_tiles = -(-N * min(k, held) // tile) + held           # static bound
    rows = n_tiles * tile
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1  # within expert
    row = ((last_tile - tiles) * tile)[None, :] + rank      # [N,held]
    dest = jnp.where(landed, jnp.take_along_axis(
        row, jnp.clip(idx, 0, held - 1), axis=1), rows)
    token = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, k))
    row_token = jnp.full((rows,), N, jnp.int32).at[dest.reshape(-1)].set(
        token.reshape(-1), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(last_tile, jnp.arange(n_tiles), side="right"),
        held - 1).astype(jnp.int32)
    return GroupedLayout(row_token, dest, tile_expert,
                         jnp.stack([last_tile[-1], jnp.sum(counts)]))


def experts_grouped(x: jax.Array, idx: jax.Array, w: jax.Array, lp: Params,
                    held: int, tile: int = GROUP_TILE,
                    period: jax.Array | None = None, act: str = "silu",
                    limit: float = 0.0) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of the result with work that follows the
    assignments. x [N,D]; idx, w [N,k]: each token's experts, numbered
    from the first one held, and their weights -> ([N,D] float32, int32
    [2]: the tiles run and the rows they held).

    Rows are laid out expert by expert in tiles of ``tile``
    (``grouped_layout``) and ONE Pallas kernel
    (``ops/grouped_experts.py``) runs the LIVE tiles only. ``x`` is
    quantised ONCE, outside the kernel (per-row quantisation commutes with
    a gather of rows, so every product sees the numbers ``mm`` would give
    it; the hidden activation is quantised in its tile, where alone it
    exists). In the kernel a tile's int8 rows and their scales arrive by
    index, its three products and the gate stay in fast memory, and its
    result — rounded to the rows' dtype, as ``mm`` returns it — is added,
    weighted, in float32 onto its tokens' rows of the result, which lives
    in fast memory for the whole call: nothing scatters, nothing is
    gathered back, and an assignment that landed on no held expert costs
    nothing (there is ONE way back to the tokens; ``combine_form`` chose
    between two until PR 43).

    With ``period`` the experts' matrices are the whole stack over periods
    and the kernel reads ``[period, expert]`` of it in place: a sliced
    period is a COPY of it (0.69 ms a matrix a layer on a v5e).

    The call's rows and its result are resident in the kernel, so a call
    of more rows than fit beside the streamed matrices (``rows_that_fit``:
    3 392 rows of 4 096 at a width of 2 048, int8) runs in slices of that
    many, each with a layout of its own.

    A slice is one jitted call (``_grouped``): the expert layers a program
    unrolls share one traced body and one lowered function, and programs
    of the same row count share the trace — what a Pallas kernel costs in
    set-up is paid once a program, not once a layer (PERF.md section 6,
    PR 43)."""
    stack = {key: lp[key] for key in EXPERT_KEYS}
    if period is None:
        stack = jax.tree.map(lambda a: a[None], stack)
        period = jnp.zeros((), jnp.int32)
    quantized = is_quantized(stack["wg"])
    wg = stack["wg"]["q"] if quantized else stack["wg"]
    cap = rows_that_fit(*wg.shape[-2:], wg.dtype.itemsize,
                        1 if quantized else x.dtype.itemsize)
    parts = [_grouped(x[lo:lo + cap], idx[lo:lo + cap], w[lo:lo + cap],
                      stack, period, held=held, tile=tile, act=act,
                      limit=limit)
             for lo in range(0, x.shape[0], cap)]
    if len(parts) == 1:
        return parts[0]
    return (jnp.concatenate([out for out, _ in parts]),
            sum(counted for _, counted in parts))


def grouped_inputs(x, idx, w, stack, period, held: int, tile: int) -> tuple:
    """What the kernel is handed for a call, beside the layout's counts:
    (live tiles and period, each tile's expert, each row's token, each
    row's weight, the rows with their zero row — quantised ONCE for int8
    matrices —, the matrices flattened)."""
    lay = grouped_layout(idx, held, tile)
    # Padding rows read token N: a zero row, whose result is zero.
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    if is_quantized(stack["wg"]):
        src = _dynamic_int8(x_pad)
        mats = tuple(stack[key][part] for key in EXPERT_KEYS
                     for part in ("q", "s"))
    else:
        src = (x_pad,)
        mats = tuple(stack[key] for key in EXPERT_KEYS)
    meta = jnp.stack([lay.counted[0], period.astype(jnp.int32)])
    row_weight = jnp.zeros(lay.row_token.shape, jnp.float32).at[
        lay.dest.reshape(-1)].set(w.reshape(-1), mode="drop")
    return (meta, lay.tile_expert, lay.row_token, row_weight, src,
            mats), lay.counted


@partial(jax.jit, static_argnames=("held", "tile", "act", "limit"))
def _grouped(x, idx, w, stack, period, *, held, tile, act, limit=0.0):
    """``experts_grouped`` on a period-stacked tree: the layout, the
    rows' one rounding, each row's weight, the kernel."""
    given, counted = grouped_inputs(x, idx, w, stack, period, held, tile)
    return grouped_experts(*given, tile=tile, act=act, dtype=x.dtype,
                           limit=limit), counted


def moe_block(x: jax.Array, lp: Params, c: ModelConfig,
              count: jax.Array | None = None,
              period: jax.Array | None = None,
              route_on: jax.Array | None = None,
              normed: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array]:
    """x [B,T,D] (the residual stream) -> (the BRANCH ``R + S`` of
    ``norm(x)`` — the routed experts held here and the shared ones, several
    of those as the MEAN of their outputs; the caller adds it to the
    stream, through the post norm where the family has one —, int32
    [``N_COUNTERS``]: of rows where ``count`` [B] is True
    the routed assignments, all and those landing on a held expert, the held
    experts with at least one of them (zeros without ``count``); then the
    tiles the grouped product ran and the rows they held (zeros from the
    dense form)). ``period``: the routed experts' matrices
    (``EXPERT_KEYS``) are stacked over periods and this is the index to
    read. ``route_on`` [B,T,D]: what the router reads instead of the
    MLP's normalised input (the block's input, before attention).
    ``normed`` [B,T,D] float32: a PARALLEL block's one normed input, given
    — the layer norms nothing of its own (``lp`` has no ``norm``)."""
    B, T, D = x.shape
    hf = (block_norm(x.astype(jnp.float32), lp["norm"], c)
          if normed is None else normed)
    h = hf.astype(x.dtype)
    with jax.named_scope("moe.experts"):
        seen = hf if route_on is None else route_on.astype(jnp.float32)
        idx, w = route(seen.reshape(B * T, D), lp["router"], c,
                       lp.get("router_bias"))
        probs = held_weights(idx, w, c)
        xf = h.reshape(B * T, D)
        if B * T <= DENSE_MAX_TOKENS:
            y = experts_dense(xf, probs, lp, period, c.moe_act,
                              c.swiglu_limit)
            tiled = jnp.zeros((2,), jnp.int32)
        else:
            y, tiled = experts_grouped(
                xf, idx - c.first_expert_held, w, lp, c.experts_held,
                period=period, act=c.moe_act, limit=c.swiglu_limit)
        y = y.reshape(B, T, D).astype(x.dtype)
    with jax.named_scope("moe.shared"):
        if c.n_shared_experts:
            shared = swiglu_mlp(h, lp["sg"], lp["su"], lp["sd"],
                                limit=c.swiglu_limit)
            if c.n_shared_experts > 1:
                # ``sd`` contracts the shared experts side by side: their
                # SUM. A power of two when they are four: exact.
                shared = shared * (1.0 / c.n_shared_experts)
            y = y + shared
    routed = jnp.zeros((3,), jnp.int32)
    if count is not None:
        on = jnp.repeat(count, T)
        landed = (probs > 0.0) & on[:, None]
        routed = jnp.stack([
            jnp.sum(on, dtype=jnp.int32) * c.experts_per_token,
            jnp.sum(landed, dtype=jnp.int32),
            jnp.sum(jnp.any(landed, axis=0), dtype=jnp.int32)])
    return y, jnp.concatenate([routed, tiled])


# ---------------------------------------------------------------------------
# The softmax layer with an indexer
# ---------------------------------------------------------------------------

def indexer(h: jax.Array, lp: Params, c: ModelConfig, positions: jax.Array
            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """h [B, T, D] (the NORMED block input) at ``positions`` [B, T] ->
    (index queries [B, T, J, W], the token's index key [B, T, W], both
    rotated over their whole width and in h's dtype; the heads' weights
    [B, T, J] float32 with both scale factors multiplied in). The key goes
    through a LayerNorm with weight and bias before its rotary."""
    B, T, _ = h.shape
    J, W = c.idx_heads, c.idx_head_dim
    cos, sin = rope_tables(positions, W, c.rope_theta, c.rope_scaling)
    qi = apply_rope(mm(h, lp["wqi"]).reshape(B, T, J, W), cos, sin)
    ki = layer_norm(mm(h, lp["wki"]), lp["ki_norm"], c.rms_eps) \
        + lp["ki_bias"].astype(h.dtype)
    ki = apply_rope(ki[:, :, None, :], cos, sin)[:, :, 0]
    w = jnp.einsum("btd,dj->btj", h, lp["wwi"],
                   preferred_element_type=jnp.float32) * (J * W) ** -0.5
    return qi, ki, w


def sparse_block(x: jax.Array, lp: Params, c: ModelConfig, pool: tuple,
                 layer: jax.Array, fn: Any, lengths: jax.Array,
                 active: jax.Array | None) -> tuple[jax.Array, tuple]:
    """x [B, T, D] -> (the attention branch of ``norm(x)``, which the
    caller adds to the stream, the group's pool ``(K, V, index keys)`` with
    the call's rows written into layer ``layer``). ``fn``: the group's
    ``ops.sparse_attention.SparseAttention``. Insert, then select, then
    attend the selected keys only: the same three steps in both step
    programs (a decode step and a chunk both walk the live pages under the
    selection's mask, each in its own kernel; the "pallas" provider's
    decode step also SELECTS by a kernel that hands the read its mask
    words, ``select_words``, where every other call takes ``select``'s plain
    form). A row that is not ``active`` writes to the trash page and attends
    from position 0; what it returns is not looked at."""
    B, T, _ = x.shape
    dh = c.head_dim
    start = lengths if active is None else jnp.where(active, lengths, 0)
    positions = start[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    h = block_norm(x, lp["norm"], c)
    with jax.named_scope("attn.index"):
        qi, ki, w = indexer(h, lp, c, positions)
    with jax.named_scope("attn.sparse"):
        cos, sin = rope_tables(positions, dh, c.rope_theta, c.rope_scaling)
        q = mm(h, lp["wq"]).reshape(B, T, c.n_heads, dh)
        k = mm(h, lp["wk"]).reshape(B, T, c.n_kv_heads, dh)
        v = mm(h, lp["wv"]).reshape(B, T, c.n_kv_heads, dh)
        if c.qk_norm:
            q = rms_norm(q, lp["q_norm"], c.rms_eps)
            k = rms_norm(k, lp["k_norm"], c.rms_eps)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        pool = fn.write(pool, k, v, ki, layer, lengths, active)
    with jax.named_scope("attn.index"):
        select = (fn.select_words if T == 1 and fn.impl == "pallas"
                  else fn.select)
        selected = select(qi, w, pool[2], layer, start)
    with jax.named_scope("attn.sparse"):
        attn = fn.attend(q, pool, layer, start, selected)
        return mm(attn, lp["wo"]), pool


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params: Params, config: ModelConfig, tokens: jax.Array,
            lengths: jax.Array, cache: HybridCache,
            active: jax.Array | None = None,
            attention_fn: Callable | tuple[Callable, ...] | None = None, *,
            slots: jax.Array | None = None,
            n_valid: jax.Array | None = None
            ) -> tuple[jax.Array, HybridCache]:
    """One forward over new tokens, in the family signature of
    ``llama.forward`` (tokens [B,T], lengths [B], the cache, ``active``,
    an ``attention_fn`` over the page pool — or one a cache group, in the
    order of ``ModelConfig.cache_groups``: each closes over its group's
    page table and window).

    Decode (T == 1, an ``attention_fn`` with ``.decode``): row b IS slot
    b; the state of a row with ``active`` False leaves bit-identical.
    Prefill: row i is slot ``slots[i]``; a row whose ``lengths`` is 0
    starts from ZERO state whatever the slot's block holds, any other row
    continues from the block; ``n_valid`` [B] (default T) is each row's
    true token count, and the padding after it changes nothing.
    Returns (logits [B,T,V] float32, the cache). A prefill call that
    states ``n_valid`` wants its rows' LAST real position only (the first
    sampled token): the head runs on that position alone and every
    position of the row carries its logits (a broadcast, never
    materialised; at 8 rows of 512 tokens the full logits are 1.2 GB of
    temporaries the chip has no room for beside this family's weights).
    """
    c = config
    B, T = tokens.shape
    dh = c.head_dim
    if attention_fn is None:
        raise ValueError("the hybrid family serves from the paged cache: "
                         "it needs a paged attention_fn")
    fns = attention_fn if isinstance(attention_fn, tuple) else (attention_fn,)
    groups = c.cache_groups
    if len(fns) != len(groups):
        raise ValueError(f"{len(groups)} cache groups need a provider each, "
                         f"got {len(fns)}")
    # Where a softmax position's KV lies: (its group, its place among the
    # group's layers of a period, how many of those there are).
    place = {p: (g, j, len(ps)) for g, (_, ps) in enumerate(groups)
             for j, p in enumerate(ps)}
    n_soft = len(c.softmax_positions)
    # A latent pool and a pool with an index side ride BOTH programs'
    # scans and are written in place, layer by layer.
    rides = c.is_mla or c.is_sparse
    decoding = T == 1 and (rides or getattr(fns[0], "decode", None)
                           is not None)
    # ``.decode_at`` / ``.prefill_at``: the provider reads the stacked pool
    # at a layer's index, and the pool stays out of the scanned inputs —
    # in prefill it is the scan's carry, written in place (llama.forward).
    # A latent layer's pool is the carry of BOTH programs' scans.
    by_decode_at = decoding and hasattr(fns[0], "decode_at")
    by_prefill_at = rides or (not decoding and T > 1
                              and hasattr(fns[0], "prefill_at"))
    by_index = by_decode_at or by_prefill_at
    scope = "decode" if decoding else "prefill"

    def post(y, lp):
        """A sub-block's branch as it joins the stream."""
        return block_norm(y, lp["post_norm"], c) if c.post_norm else y

    last_only = not decoding and n_valid is not None
    if decoding:
        s_in, tail_in = cache.state, cache.conv
        keep = active if active is not None else jnp.ones((B,), bool)
        count = keep
        n_valid = jnp.ones((B,), jnp.int32)
    else:
        if slots is None:
            slots = jnp.arange(B, dtype=jnp.int32)
        n_valid = (jnp.full((B,), T, jnp.int32) if n_valid is None
                   else n_valid.astype(jnp.int32))
        fresh = (lengths == 0)
        s_in = tuple(jnp.where(fresh[:, None, None, None], 0.0, s[:, slots])
                     for s in cache.state)
        tail_in = tuple(jnp.where(fresh[:, None, None], 0, t[:, slots])
                        for t in cache.conv)
        keep = count = None

    x = jnp.take(params["embed"], tokens, axis=0)               # [B,T,D]

    def linear_layer(x, lp, s, tail, at=None):
        with jax.named_scope(f"{scope}.kda"):
            out, s, tail = linear_block(block_norm(x, lp["norm"], c), lp, c,
                                        s, tail, n_valid, keep, at)
            return x + post(out, lp), s, tail

    def ride(s):
        """A scan's state as (what rides its CARRY, what it scans in or
        out). DECODE: the stacked blocks ride, and a layer updates its own
        in place (``_state_update``), as the pools do under
        ``by_prefill_at`` — scanned out, a step would leave a fresh stack
        for the burst's scan to copy into its carry. PREFILL scans the
        gathered rows in and out."""
        return (s, None) if decoding else (None, s)

    if c.leading_dense:
        # In FRONT of the periods: a linear mixer and a dense gated MLP a
        # layer, one scanned body over their own stacked tree; their state
        # is the LAST entry of the cache's tuples.
        def lead_step(carry, scanned):
            x, stack = carry
            lp, s, tail, at = scanned
            x, s, tail = linear_layer(x, lp, stack if decoding else s, tail,
                                      at)
            stack, s = ride(s)
            with jax.named_scope(f"{scope}.mlp"), \
                    jax.named_scope("mlp.dense"):
                m = lp["mlp"]
                y = swiglu_mlp(block_norm(x, m["norm"], c), m["wg"], m["wu"],
                               m["wd"], limit=c.swiglu_limit)
                return (x + post(y, m), stack), (s, tail)
        stack, rows = ride(s_in[-1])
        (x, stack), (rows, lead_tail) = jax.lax.scan(
            lead_step, (x, stack),
            (params["lead"], rows, tail_in[-1],
             jnp.arange(c.leading_dense) if decoding else None))
        lead_out = (stack if decoding else rows, lead_tail)
        s_in, tail_in = s_in[:-1], tail_in[:-1]
    if any(c.rope_at(p) for p in c.softmax_positions):
        cos, sin = rope_tables(lengths[:, None] + jnp.arange(T)[None, :],
                               dh, c.rope_theta, c.rope_scaling)

    def rotary(a):
        if c.rope_interleave:   # pairs (2i, 2i+1); q and k leave alike
            return mla.rotate(a.astype(jnp.float32), cos, sin,
                              True).astype(a.dtype)
        return apply_rope(a, cos, sin)

    def softmax_layer(x, pool, lp, at, position, normed=None):
        """-> (the attention BRANCH, which the caller adds to the stream,
        the pool, the layer's new K/V). ``at``: the layer's index in its
        group's pool, or its (K, V) slice of that pool; ``pool``: the
        group's stacked pool (under ``prefill_at`` the carried one).
        ``normed``: a parallel block's normed input, in place of the
        sub-block's own norm."""
        fn = fns[place[position][0]]
        if c.is_mla:
            with jax.named_scope(f"{scope}.attention"), \
                    jax.named_scope("attn.mla"):
                return (*mla.mla_block(x, lp, c, pool, at, fn, lengths,
                                       active), None)
        if c.is_sparse:
            with jax.named_scope(f"{scope}.attention"):
                return (*sparse_block(x, lp, c, pool, at, fn, lengths,
                                      active), None)
        kind = "attn.window" if c.window_at(position) else "attn.global"
        with jax.named_scope(f"{scope}.attention"), jax.named_scope(kind):
            h = block_norm(x, lp["norm"], c) if normed is None else normed
            q = mm(h, lp["wq"]).reshape(B, T, c.n_heads, dh)
            k = mm(h, lp["wk"]).reshape(B, T, c.n_kv_heads, dh)
            v = mm(h, lp["wv"]).reshape(B, T, c.n_kv_heads, dh)
            if c.rope_at(position):
                q, k = rotary(q), rotary(k)
            ys = (k, v)
            if by_prefill_at:
                attn, pool_k, pool_v = fn.prefill_at(q, k, v, *pool, at,
                                                     lengths, active)
                pool, ys = (pool_k, pool_v), None
            elif by_decode_at:
                attn = fn.decode_at(q, k, v, *pool, at, lengths, active)
            elif decoding:
                attn = fn.decode(q, k, v, *at, lengths, active)
            else:
                attn, layer_k, layer_v = fn(q, k, v, *at, lengths, active)
                ys = (layer_k, layer_v)
            if c.attn_gate:
                gate = jax.nn.sigmoid(mm(h, lp["wgate"]).astype(jnp.float32))
                attn = (attn.astype(jnp.float32) * gate).astype(x.dtype)
            return mm(attn, lp["wo"]), pool, ys

    # The routed experts' matrices stay OUT of the scanned slices: the
    # expert layer reads them from the whole stack at the period's index
    # (``experts_grouped`` says why).
    layers = params["layers"]
    soft = layers["attn"] if n_soft > 1 else (layers["attn"],)
    every = (*soft, *layers["lin"])     # in the order of a period
    held = [{k: lp["mlp"][k] for k in EXPERT_KEYS} for lp in every]
    rest = [{**lp, "mlp": {k: v for k, v in lp["mlp"].items()
                           if k not in EXPERT_KEYS}} for lp in every]

    def mlp(x, lp, i, period, x_in, normed=None):
        with jax.named_scope(f"{scope}.mlp"):
            return moe_block(x, {**lp, **held[i]}, c, count, period,
                             x_in if c.router_reads_block_input else None,
                             normed)

    # A (K, V) pair a group — with an indexer (K, V, index keys); a
    # latent group's ONE pool.
    pools = tuple(cache.k) if c.is_mla else tuple(
        zip(cache.k, cache.v, *([cache.index] if c.is_sparse else [])))

    def by_period(pool, n):
        """A group's pool [P*n, ...] as the scan slices it: [P, n, ...]."""
        return pool if n == 1 else jax.tree.map(
            lambda a: a.reshape(a.shape[0] // n, n, *a.shape[1:]), pool)

    def period_step(carry, scanned):
        x, carried, stacks = carry
        period, lps, sides, s0, tail0 = scanned
        new = [[None] * len(ps) for _, ps in groups]
        counted = 0
        for i in range(n_soft):
            g, j, n = place[i]
            if by_index:
                at = period if n == 1 else period * n + j
                pool = carried[g] if by_prefill_at else pools[g]
            else:
                pool = None
                at = sides[g] if n == 1 else jax.tree.map(
                    lambda a: a[j], sides[g])
            x_in = x
            if c.parallel_block:
                # ONE norm feeds both branches, ONE add joins them.
                with jax.named_scope("block.norm"):
                    hf = block_norm(x.astype(jnp.float32), lps[i]["norm"], c)
                attn, pool, new[g][j] = softmax_layer(
                    x, pool, lps[i], at, i, hf.astype(x.dtype))
                branch, more = mlp(x, lps[i]["mlp"], i, period, x_in, hf)
                x = x + attn + branch
            else:
                attn, pool, new[g][j] = softmax_layer(x, pool, lps[i], at, i)
                x = x + post(attn, lps[i])
                branch, more = mlp(x, lps[i]["mlp"], i, period, x_in)
                x = x + post(branch, lps[i]["mlp"])
            if by_prefill_at:
                carried = (*carried[:g], pool, *carried[g + 1:])
            counted = counted + more
        states, tails = [], []
        for i, (lp, s, tail) in enumerate(
                zip(lps[n_soft:], stacks if decoding else s0, tail0), n_soft):
            x_in = x
            x, s, tail = linear_layer(x, lp, s, tail,
                                      period if decoding else None)
            branch, more = mlp(x, lp["mlp"], i, period, x_in)
            x = x + post(branch, lp["mlp"])
            counted = counted + more
            states.append(s)
            tails.append(tail)
        stacks, states = ride(tuple(states))
        return (x, carried, stacks), (new, states, tuple(tails), counted)

    stacks, rows = ride(s_in)
    (x, carried, stacks), (new, rows, tail_out, counts) = jax.lax.scan(
        period_step, (x, pools if by_prefill_at else None, stacks),
        (jnp.arange(c.n_periods), rest,
         None if by_index else tuple(
             by_period(pool, len(ps)) for pool, (_, ps) in zip(pools, groups)),
         rows, tail_in))
    s_out = stacks if decoding else rows

    def of_group(parts):
        """A group's per-position results [P, ...] in the pool's layer
        order [P*n, ...]."""
        if len(parts) == 1:
            return parts[0]
        return jax.tree.map(
            lambda *a: jnp.stack(a, 1).reshape(-1, *a[0].shape[1:]), *parts)

    if by_prefill_at:
        new_pools = carried
    elif decoding:
        new_pools = tuple(
            fn.insert_all(*pool, *of_group(parts), lengths, active)
            for fn, pool, parts in zip(fns, pools, new))
    else:
        new_pools = tuple(of_group(parts) for parts in new)
    if c.leading_dense:
        s_out, tail_out = (*s_out, lead_out[0]), (*tail_out, lead_out[1])
    if decoding:
        state, conv = s_out, tail_out
    else:
        state = tuple(s.at[:, slots].set(new)
                      for s, new in zip(cache.state, s_out))
        conv = tuple(t.at[:, slots].set(new)
                     for t, new in zip(cache.conv, tail_out))
    counters = cache.counters + jnp.sum(counts, axis=0)

    if last_only:
        x = jnp.take_along_axis(x, (n_valid - 1)[:, None, None], axis=1)
    x = block_norm(x, params["final_norm"], c)
    logits = head_matmul(x, _select_head(params, c))
    if last_only:
        logits = jnp.broadcast_to(logits, (B, T, logits.shape[-1]))
    k, v = (tuple(new_pools), ()) if c.is_mla else (
        tuple(p[0] for p in new_pools), tuple(p[1] for p in new_pools))
    return logits, HybridCache(k=k, v=v, state=state, conv=conv,
                               counters=counters,
                               index=tuple(p[2] for p in new_pools)
                               if c.is_sparse else ())
