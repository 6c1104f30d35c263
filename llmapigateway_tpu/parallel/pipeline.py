"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pipe``
mesh axis, expressed with ``shard_map`` + ``jax.lax.ppermute``.

SURVEY.md §2b "Pipeline Parallelism (PP)" row: layer-blocked params +
collective-permute microbatching. The reference has no counterpart (it has
no parallelism of any kind — SURVEY.md §2b); this is the TPU-native
equivalent of the stage-to-stage p2p a GPU framework would run over
NCCL send/recv.

Design:

* Params stay in the stacked-layer layout ``[L, ...]`` (models/llama.py)
  and shard the layer dim over ``pipe`` (parallel/sharding.py) — stage ``p``
  holds the contiguous block of layers ``[p·L/P, (p+1)·L/P)``. The KV cache
  shards the same way, so a stage only ever touches its own layers' cache.
* The batch is split into ``M`` microbatches. One forward = ``M + P - 1``
  ticks; at tick ``t`` stage ``p`` runs microbatch ``m = t - p`` through its
  layer block, then hands the activation to stage ``p+1`` via ``ppermute``
  (one hop per tick — rides whatever link the ``pipe`` axis is laid on,
  ideally DCN across hosts).
* Bubble ticks (``t - p`` outside ``[0, M)``) compute on a zero activation
  with ``active=False``, so their cache writes are routed to the
  never-visible row tail (models/llama.py ``insert_kv`` invariant) — no
  masking pass over the cache is ever needed.
* Embedding and the LM head are replicated on every stage: each stage
  embeds its own microbatch input (stage 0's is the only real one) and the
  last stage's logits are broadcast to all stages with a masked ``psum``,
  so the caller sees a fully-replicated ``[B, T, V]`` — the same contract
  as the non-pipelined forward.

Tested against the sequential forward on a virtual CPU mesh
(tests/test_pipeline.py) — same logits, same cache, bubbles and all.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..models import llama
from ..models.config import ModelConfig


def stage_size(n_layers: int, n_stages: int) -> int:
    if n_layers % n_stages != 0:
        raise ValueError(
            f"n_layers={n_layers} not divisible by pipe={n_stages} stages")
    return n_layers // n_stages


def _block_forward(lp_block: dict, c: ModelConfig, x: jax.Array,
                   lengths: jax.Array, k_block: jax.Array,
                   v_block: jax.Array, active: jax.Array,
                   cos: jax.Array, sin: jax.Array, mlp_fn=None,
                   attention_fn=None
                   ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Run one stage's layer block: scan over the local layers.
    x [Bm, T, D]; k/v_block [Lp, Bm, KV, S, Dh] — or the int8-quantized
    ``{"q", "s"}`` dict (the scan unstacks dim 0 of every leaf; the
    attention handles plain-or-quantized via llama._kv_dequant_views) —
    or, with ``attention_fn`` set, the stage's slice of a paged pool
    ([Lp, NP, KV, page, Dh]) routed by the table the attention closes
    over. ``mlp_fn(h, lp)`` replaces the SwiGLU MLP (the MoE hook — same
    contract as llama.forward's).

    Decode ticks (T == 1) run the DEFERRED-insert protocol exactly when
    the attention provider carries it — the SAME dispatch as llama.forward,
    including the dense default and the windowed (Mistral) default, both of
    which carry ``.decode``/``.insert_all`` (models/llama.py:493-494,:506).
    Per-layer functional cache updates inside the scan would serialize into
    2·L scatters per step; the deferred form attends the stale cache plus
    the self-column and lands ONE stacked insert after the scan, keeping
    the full cache out of the scan's ys. Because the SAME decode kernel
    runs pipelined and non-pipelined, greedy outputs bit-match the
    non-pipelined engine even on float rounding ties. Chunks (T > 1) stay
    insert-then-attend, as in llama.forward for providers without
    ``.verify``."""
    B, T, _ = x.shape
    if attention_fn is None and c.sliding_window:
        # Mistral-family: the default dense path carries the window.
        attend = llama.windowed_dense_attention(c.sliding_window)
    else:
        attend = attention_fn or llama.dense_cache_attention
    decode_attend = insert_all = None
    if T == 1:
        decode_attend = getattr(attend, "decode", None)
        insert_all = getattr(attend, "insert_all", None)
    deferred = decode_attend is not None and insert_all is not None

    def layer_step(x, scanned):
        lp, layer_k, layer_v = scanned
        h = llama.rms_norm(x, lp["attn_norm"], c.rms_eps, c.rms_offset)
        q, k, v = llama.qkv_proj(h, lp, c)
        q = llama.apply_rope(q, cos, sin)
        k = llama.apply_rope(k, cos, sin)
        if deferred:
            attn = decode_attend(q, k, v, layer_k, layer_v, lengths, active)
            ys = (k, v)                      # stacked for insert_all below
        else:
            attn, layer_k, layer_v = attend(
                q, k, v, layer_k, layer_v, lengths, active)
            ys = (layer_k, layer_v)
        x = x + llama.mm(attn, lp["wo"])
        h = llama.rms_norm(x, lp["mlp_norm"], c.rms_eps, c.rms_offset)
        if mlp_fn is not None:
            x = x + mlp_fn(h, lp)
        else:
            x = x + llama.swiglu_mlp(h, lp["wg"], lp["wu"], lp["wd"], c.act)
        return x, ys

    x, (ys_k, ys_v) = jax.lax.scan(layer_step, x, (lp_block, k_block, v_block))
    if deferred:
        new_k, new_v = insert_all(k_block, v_block, ys_k, ys_v, lengths,
                                  active)
    else:
        new_k, new_v = ys_k, ys_v
    return x, new_k, new_v


@functools.lru_cache(maxsize=32)
def _build_run(c: ModelConfig, mesh: Mesh, n_stages: int, M: int, Bm: int,
               T: int, has_lm_head: bool, has_head_q8: bool = False,
               make_attention=None):
    """Build (once per signature) the jitted shard_map pipeline program.
    jax.jit caches by function identity, so the closure must be memoized —
    a fresh closure per call would retrace/recompile every invocation.

    ``make_attention(table_rows) -> attention_fn`` switches the cache to
    PAGED mode: the run gains a trailing ``table [B, slots]`` argument,
    stages hold their slice of the page POOL (no batch dim — the
    microbatch tick slices TABLE rows instead of cache rows, and each
    microbatch's writes land in its own pages), and bubble-tick writes
    ride the pool's trash-page-0 redirect (active=False). The callable
    must be identity-stable (the engine builds one partial per engine)
    or this memo would retrace per call."""
    B = M * Bm
    # MoE (mixtral): the staged block runs the family MLP hook per layer
    # — the scanned lp slice carries router [D,E] + expert stacks, which
    # is exactly what moe_mlp_* consume. NB the dense/dispatch shape
    # switch sees the MICROBATCH's N = Bm·T, so a pipelined long prefill
    # may pick capacity dispatch at a different N than the sequential
    # forward would — capacity is an approximation knob either way;
    # decode (T=1) and small chunks always run the exact dense form.
    if c.is_moe:
        from ..models import mixtral
        mlp_fn = mixtral.make_mlp_fn(c)
    else:
        mlp_fn = None
    # Spec prefix-trees: P("pipe") applies to every leaf under "layers".
    param_spec = {"embed": P(), "final_norm": P(), "layers": P("pipe")}
    if has_lm_head:
        param_spec["lm_head"] = P()
    if has_head_q8:
        param_spec["lm_head_q8"] = P()     # prefix spec covers {q, s}
    paged = make_attention is not None
    in_specs = (
        P("pipe"),               # stage index [n_stages] -> local [1]
        param_spec,
        P(),                     # tokens (replicated; every stage embeds)
        P(),                     # lengths
        P("pipe"), P("pipe"),    # cache k, v (layer dim)
        P(),                     # active
    ) + ((P(),) if paged else ())   # page table (replicated)
    out_specs = (P(), P("pipe"), P("pipe"))

    @functools.partial(
        shard_map, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names={"pipe"}, check_vma=False)
    def run(stage, params, tokens, lengths, cache_k, cache_v, active,
            *table):
        # The stage id arrives as this stage's shard of an iota input —
        # NOT jax.lax.axis_index: under a partially-manual shard_map
        # (auto `model` axis) axis_index lowers to a PartitionId
        # instruction SPMD partitioning rejects on older jax.
        p = stage[0]
        lp = params["layers"]                  # [Lp, ...] local block

        # Every stage embeds every microbatch (replicated compute, tiny):
        # [M, Bm, T, D].
        x_all = jnp.take(params["embed"], tokens, axis=0).reshape(M, Bm, T, -1)
        if c.scale_embed:
            x_all = x_all * jnp.asarray(c.d_model ** 0.5, x_all.dtype)
        positions = (lengths[:, None] + jnp.arange(T)[None, :])     # [B, T]
        cos_all, sin_all = llama.rope_tables(positions, c.head_dim,
                                             c.rope_theta, c.rope_scaling)
        cos_all = cos_all.reshape(M, Bm, T, -1)
        sin_all = sin_all.reshape(M, Bm, T, -1)
        len_all = lengths.reshape(M, Bm)
        act_all = active.reshape(M, Bm)

        n_ticks = M + n_stages - 1

        def tick(t, carry):
            inbuf, cache_k, cache_v, outs = carry
            m = t - p                               # this stage's microbatch
            valid = (m >= 0) & (m < M)
            mc = jnp.clip(m, 0, M - 1)
            # Stage 0 reads its own embedding; later stages read the
            # ppermuted activation from the previous stage.
            x_in = jnp.where(p == 0, x_all[mc], inbuf)
            mb_len = len_all[mc]
            mb_act = act_all[mc] & valid            # bubbles → tail writes
            if paged:
                # The pool has no batch dim: slice TABLE rows for this
                # microbatch instead of cache rows; writes land in the
                # microbatch's own pages (bubbles → trash page 0 via
                # active=False), so the updated stage pool carries whole.
                mb_table = jax.lax.dynamic_slice_in_dim(
                    table[0], mc * Bm, Bm, 0)
                y, cache_k, cache_v = _block_forward(
                    lp, c, x_in, mb_len, cache_k, cache_v, mb_act,
                    cos_all[mc], sin_all[mc], mlp_fn=mlp_fn,
                    attention_fn=make_attention(mb_table))
            else:
                # Tree-mapped batch slicing: an int8-quantized cache is a
                # {"q": [L,B,KV,S,Dh], "s": [L,B,KV,1,S]} dict — the
                # batch dim is axis 1 of EVERY leaf, so one per-leaf
                # slice covers both layouts (VERDICT r3 item 7:
                # kv_quant × PP).
                def rows(cache):
                    return jax.tree.map(
                        lambda a: jax.lax.dynamic_slice_in_dim(
                            a, mc * Bm, Bm, 1), cache)
                y, k_rows, v_rows = _block_forward(
                    lp, c, x_in, mb_len, rows(cache_k), rows(cache_v),
                    mb_act, cos_all[mc], sin_all[mc], mlp_fn=mlp_fn)
                cache_k = jax.tree.map(
                    lambda full, r: jax.lax.dynamic_update_slice_in_dim(
                        full, r, mc * Bm, 1), cache_k, k_rows)
                cache_v = jax.tree.map(
                    lambda full, r: jax.lax.dynamic_update_slice_in_dim(
                        full, r, mc * Bm, 1), cache_v, v_rows)
            # Last stage collects its finished microbatch.
            take = valid & (p == n_stages - 1)
            outs = jax.lax.cond(
                take,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, y[None], mc, 0),
                lambda o: o, outs)
            # Hand the activation to the next stage (ring permute; the
            # wrap-around hop P-1 → 0 carries a bubble, never real data).
            inbuf = jax.lax.ppermute(
                y, "pipe",
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return inbuf, cache_k, cache_v, outs

        inbuf = jnp.zeros_like(x_all[0])
        outs = jnp.zeros_like(x_all)
        inbuf, cache_k, cache_v, outs = jax.lax.fori_loop(
            0, n_ticks, tick, (inbuf, cache_k, cache_v, outs))

        # Final norm + head on the last stage's collected activations;
        # masked psum broadcasts the logits to every stage.
        x = outs.reshape(B, T, -1)
        x = llama.rms_norm(x, params["final_norm"], c.rms_eps, c.rms_offset)
        head = llama._select_head(params, c)
        logits = llama.head_matmul(x, head)   # plain bf16 or int8 {q,s} head
        logits = jnp.where(p == n_stages - 1, logits, 0.0)
        logits = jax.lax.psum(logits, "pipe")
        return logits, cache_k, cache_v

    # Partially-manual shard_map (axis_names ⊂ mesh axes, so GSPMD keeps
    # managing e.g. the `model` axis inside each stage) only traces under
    # jit in current JAX.
    return jax.jit(run)


def pipelined_forward(params: dict, config: ModelConfig, tokens: jax.Array,
                      lengths: jax.Array, cache, mesh: Mesh,
                      n_microbatches: int,
                      active: jax.Array | None = None,
                      make_attention=None, table: jax.Array | None = None):
    """Pipelined equivalent of ``llama.forward`` over the mesh's ``pipe``
    axis. Same signature contract: tokens [B, T] → (logits [B, T, V] fp32
    replicated, updated cache). B must divide into ``n_microbatches``.

    PAGED mode: pass ``make_attention(table_rows) -> attention_fn`` (an
    identity-stable builder — one partial per engine) plus the page
    ``table [B, slots]``; ``cache`` is then the PagedKVCache pool with
    its layer dim staged over ``pipe``. The cache pytree type is
    preserved in the return.
    """
    B, T = tokens.shape
    n_stages = mesh.shape.get("pipe", 1)
    stage_size(config.n_layers, n_stages)     # validate divisibility
    M = n_microbatches
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    if active is None:
        active = jnp.ones((B,), bool)
    run = _build_run(config, mesh, n_stages, M, B // M, T,
                     "lm_head" in params, "lm_head_q8" in params,
                     make_attention)
    extra = () if make_attention is None else (table,)
    stage = jnp.arange(n_stages, dtype=jnp.int32)
    logits, new_k, new_v = run(stage, params, tokens, lengths, cache.k,
                               cache.v, active, *extra)
    return logits, type(cache)(k=new_k, v=new_v)
