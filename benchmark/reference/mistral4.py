"""Plain float32 forward of the Mistral-Small-4 decoder: every layer a
latent attention (MLA) followed by a sparse expert MLP with one shared
expert, of which this chip holds a share.

Written from the published config's keys and the family's convention
(source in ``configs/mistral-small4-119b-ep4.json``). One layer, residual
stream x [T, D], as computed below — the EXPANDED form only, no cache::

    x'  = rmsnorm(x, w_1)
    c_q = rmsnorm(x' W_qa);  q_h = c_q W_qb = [q_nope_h | q_rope_h]
    [c_kv | k_r] = x' W_kva;  c = rmsnorm(c_kv)
    [k_nope_h | v_h] = c W_kvb            (k_r: ONE rotary key, all heads)
    rotary on q_rope_h and k_r: pairs (2i, 2i+1) turned by pos * f_i, the
        32 frequencies f_i YaRN-blended (factor, beta_fast, beta_slow over
        original_max_position_embeddings); cos/sin factor mscale /
        mscale_all_dim
    score_h(t, s <= t) = a_t sigma (q_nope_h(t) . k_nope_h(s)
                                    + rope(q_rope_h)(t) . rope(k_r)(s))
        sigma = (d_nope + d_rope)^-1/2 (0.1 mscale_all_dim ln(factor) + 1)^2
        a_t   = 1 + llama_4_scaling_beta ln(1 + floor(t / original))
    h   = x + concat_h(softmax_s(score_h) v_h) W_o
    m   = rmsnorm(h, w_2);  g = m W_r  over ALL experts, float32
    S   = top-k of g;  w_e = exp(g_e) / sum_{e' in S} exp(g_e')
    y   = h + sum_{e in S, held here} w_e E_e(m) + E_shared(m)

with ``E(m) = (silu(m W_g) * (m W_u)) W_d``; then a final rmsnorm and the
untied head over the vocabulary rows held here. Experts ``[first, first +
held)`` live on this chip; what the absent ones would add is left out, as
in the program.

It shares no code with ``llmapigateway_tpu/models``: only the LAYOUT of the
weight tree is the program's (``layers/attn`` stacked over layers, with its
``mlp`` sub-tree; ``wkvb`` [r, H, d_nope + d_v]), dequantised a layer and an
expert at a time so that it fits beside the engine; attention runs a block
of queries at a time for the same reason. Everything under
``jax.default_matmul_precision("highest")``.

``kernel_checks`` adds what the harness's own sample cannot reach: the
latent pool's write and attention kernels at the cell's widths, as a decode
step and as a prefill chunk, against plain ``jax.numpy`` on the same inputs;
and one prompt past ``original_max_position_embeddings`` taken through the
engine's own prefill chunks and decode steps and held to ``logits``
(``served_past_8192``: there ``a_t`` is no longer 1 and YaRN's blend
differs from plain rotary).

``mla_decode_cost`` and ``mla_prefill_cost`` give the operations and bytes
of one call of the latent attention kernel from shapes, in
``roofline.paged_decode_cost``'s form: each cached latent byte read once.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256           # queries attended at a time ([heads, 256, T])


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    theta: float
    eps: float
    factor: float           # YaRN
    original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    interleave: bool
    scale_beta: float       # llama_4_scaling_beta
    top: int
    first: int              # experts [first, first + held) live here
    held: int


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE — the published widths, the
    experts held here (``n_routed_experts``) and the first of them
    (``first_expert_held``, absent: 0) — but the depth, which the harness
    cut in the program's config from the same file."""
    rp = config["rope_parameters"]
    if rp["rope_type"] != "yarn" or config.get("n_group", 1) != 1:
        raise ValueError("the reference computes YaRN rotary and one "
                         "expert group alone")
    return Sizes(
        layers=model_cfg.n_layers, heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], theta=float(rp["rope_theta"]),
        eps=float(config["rms_norm_eps"]), factor=float(rp["factor"]),
        original=int(rp["original_max_position_embeddings"]),
        beta_fast=float(rp["beta_fast"]), beta_slow=float(rp["beta_slow"]),
        mscale=float(rp["mscale"]),
        mscale_all_dim=float(rp["mscale_all_dim"]),
        interleave=bool(config["rope_interleave"]),
        scale_beta=float(rp.get("llama_4_scaling_beta", 0.0)),
        top=config["num_experts_per_tok"],
        first=int(config.get("first_expert_held", 0)),
        held=int(config["n_routed_experts"]))


def f32(w: Any) -> jax.Array:
    """A leaf of the engine's tree as float32 (int8 ``{"q", "s"}``: one
    scale per output channel, the contraction axis second to last; the
    head ``[V, D]`` one scale per row)."""
    if not isinstance(w, dict):
        return jnp.asarray(w, jnp.float32)
    q, s = w["q"].astype(jnp.float32), w["s"].astype(jnp.float32)
    if q.ndim >= 2 and s.shape == q.shape[:-2] + q.shape[-1:]:
        return q * s[..., None, :]
    return q * s[..., None]


def _is_q(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _magnitude(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0


def yarn_frequencies(c: Sizes) -> np.ndarray:
    """The ``rope / 2`` pair frequencies: a pair that turns more than
    ``beta_fast`` times inside the original context keeps ``theta^(-2i/d)``,
    one that turns less than ``beta_slow`` times is divided by ``factor``,
    a linear ramp over the pair index between (the range floored and
    ceiled, as the family's code does)."""
    half = c.rope // 2
    plain = c.theta ** (-np.arange(half, dtype=np.float64) / half)

    def pair_turning(turns: float) -> float:
        return (half * math.log(c.original / (turns * 2 * math.pi))
                / math.log(c.theta))
    low = max(math.floor(pair_turning(c.beta_fast)), 0)
    high = min(math.ceil(pair_turning(c.beta_slow)), half - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0, 1)
    return (plain / c.factor * ramp + plain * (1 - ramp)).astype(np.float32)


def _rotate(x, c: Sizes):
    """x [T, heads, rope] at positions 0..T-1, pairs (2i, 2i+1) (or (i, i +
    rope/2) without ``interleave``); the pair's two numbers stay where they
    were."""
    t = x.shape[0]
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(yarn_frequencies(c))[None, :])
    mag = _magnitude(c.factor, c.mscale) / _magnitude(c.factor,
                                                      c.mscale_all_dim)
    cos, sin = (mag * jnp.cos(ang))[:, None, :], (mag * jnp.sin(ang))[:, None, :]
    if c.interleave:
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         -1).reshape(x.shape)
    half = c.rope // 2
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(h, lp, c: Sizes):
    """h [T, D] (normalised) -> [T, D], expanded: every token's K and V
    rebuilt from its latent, ``QUERY_BLOCK`` queries at a time over all T
    keys."""
    t = h.shape[0]
    q = (_rms(h @ lp["wqa"], lp["q_norm"], c.eps) @ lp["wqb"]).reshape(
        t, c.heads, c.nope + c.rope)
    kva = h @ lp["wkva"]
    latent = _rms(kva[:, :c.kv_rank], lp["kv_norm"], c.eps)
    kv = jnp.einsum("tc,chx->thx", latent, lp["wkvb"])
    k_nope, v = kv[..., :c.nope], kv[..., c.nope:]
    k_rope = _rotate(kva[:, None, c.kv_rank:], c)[:, 0]     # one, all heads
    q = jnp.concatenate([q[..., :c.nope], _rotate(q[..., c.nope:], c)], -1)
    sigma = (c.nope + c.rope) ** -0.5 * _magnitude(c.factor,
                                                   c.mscale_all_dim) ** 2
    pos = jnp.arange(t)
    a_t = 1.0 + c.scale_beta * jnp.log1p(
        (pos // c.original).astype(jnp.float32))
    q = q * (a_t * sigma)[:, None, None]
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, c.heads, c.nope + c.rope)

    def block(args):
        qi, i0 = args
        seen = pos[None, :] <= i0 + jnp.arange(QUERY_BLOCK)[:, None]
        scores = (jnp.einsum("qhd,khd->hqk", qi[..., :c.nope], k_nope)
                  + jnp.einsum("qhd,kd->hqk", qi[..., c.nope:], k_rope))
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)
    out = jax.lax.map(block, (qb, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, -1)[:t] @ lp["wo"]


@functools.partial(jax.jit, static_argnums=(2,))
def _attend(x, lp, c: Sizes):
    lp = jax.tree.map(f32, lp, is_leaf=_is_q)
    return x + attention(_rms(x, lp["norm"], c.eps), lp, c)


@functools.partial(jax.jit, static_argnums=(2,))
def _experts(x, mp, c: Sizes, stacks, layer):
    """x [T, D] (after attention) -> x + the held experts' part + the
    shared expert. ``stacks``: the routed experts' three matrices as the
    engine holds them, [layers, held, ...], read one expert of layer
    ``layer`` at a time inside the scan (a layer's slice of them is 0.8 GB
    beside the engine at the cell's widths): every held expert on every
    token, weighted by the token's routing weight for it — 0 where it is
    not among the token's top-k of ALL experts."""
    m = _rms(x, f32(mp["norm"]), c.eps)
    g = m @ f32(mp["router"])
    ids = jnp.argsort(-g, axis=-1)[:, :c.top]
    w = jax.nn.softmax(jnp.take_along_axis(g, ids, -1), -1)

    def one(out, e):
        eg, eu, ed = (f32(jax.tree.map(lambda a: a[layer, e], stack))
                      for stack in stacks)
        gate = jnp.sum(jnp.where(ids == c.first + e, w, 0.0), -1)
        y = (jax.nn.silu(m @ eg) * (m @ eu)) @ ed
        return out + gate[:, None] * y, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(c.held))
    shared = (jax.nn.silu(m @ f32(mp["sg"])) * (m @ f32(mp["su"]))
              ) @ f32(mp["sd"])
    return x + out + shared


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, w, last: int, eps: float):
    return _rms(x[-last:], f32(norm), eps) @ f32(w).T


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree: ``layers/attn`` stacked over
    layers, with its ``mlp`` sub-tree."""
    attn = dict(params["layers"]["attn"])
    mlp = dict(attn.pop("mlp"))
    stacks = tuple(mlp.pop(k) for k in ("wg", "wu", "wd"))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq, jnp.int32))
        for layer in range(c.layers):       # one layer's weights at a time
            x = _attend(x, jax.tree.map(lambda a: a[layer], attn), c)
            x = _experts(x, jax.tree.map(lambda a: a[layer], mlp), c, stacks,
                         jnp.int32(layer))
        out = _head(x, params["final_norm"], params["lm_head"], last, c.eps)
        return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# What the latent attention kernel has to do, from shapes
# ---------------------------------------------------------------------------

def mla_decode_cost(ctx_lens: list[int], heads: int, width: int,
                    value_width: int, itemsize: int = 2
                    ) -> tuple[float, float]:
    """(operations, bytes) of ONE call of the latent attention kernel as a
    decode step — one layer — over slots that hold ``ctx_lens`` tokens
    before the new one. A key is ``width`` numbers of which the first
    ``value_width`` are the value: 2 multiply-adds per key, head and number
    of either product; every visible latent byte is read ONCE (it is key
    and value both); absorbed q in and the latent-wide out, bfloat16."""
    keys = sum(n + 1 for n in ctx_lens)
    flops = 2.0 * heads * (width + value_width) * keys
    io = len(ctx_lens) * heads * (width + value_width) * 2
    return flops, float(keys * width * itemsize + io)


def mla_prefill_cost(pos: int, t: int, heads: int, width: int,
                     value_width: int, itemsize: int = 2
                     ) -> tuple[float, float]:
    """(operations, bytes) of ONE call of the latent attention kernel for
    one row — one layer, a chunk of ``t`` tokens that starts at ``pos``.
    Each query sees the keys up to its own; the keys any query of the
    chunk can see are read once."""
    keys = t * pos + t * (t + 1) // 2
    flops = 2.0 * heads * (width + value_width) * keys
    io = t * heads * (width + value_width) * 2
    return flops, float((pos + t) * width * itemsize + io)


# ---------------------------------------------------------------------------
# kernel_checks: what the harness's own sample cannot reach
# ---------------------------------------------------------------------------

DECODE_STEPS = 8            # after the prefill's first token


def latent_kernel_parity(*, heads: int, width: int, value_width: int,
                         page: int, interpret: bool,
                         pages_per_slot: int = 32, t: int = 256
                         ) -> list[dict[str, Any]]:
    """The latent pool's in-place write, then its attention kernel, as a
    decode step (one token a slot) and as a prefill chunk (``t`` tokens),
    against plain ``jax.numpy`` on the same inputs: a scatter of the new
    rows into a gathered dense view, one softmax over each query's visible
    keys in float32. Slots start at the head of a page run, mid-context
    and a page short of the table's end; unit-normal latents, queries of
    unit-variance scores (as the served scores are, up to ``sigma``)."""
    from benchmark.correctness import KERNEL_TOL
    from llmapigateway_tpu.ops.latent_attention import (
        latent_insert_in_place, latent_paged_attention)
    b, s = 3, page * pages_per_slot
    n_pages = b * pages_per_slot + 1
    rng = np.random.default_rng(0)
    table = rng.permutation(np.arange(1, n_pages)).reshape(
        b, pages_per_slot).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(keys[0], (2, n_pages, width, page), jnp.bfloat16)
    out = []
    for kind, tt, at in (("decode", 1, [page // 2, s // 2 + 3, s - page - 1]),
                         ("prefill", t, [0, s // 2, s - page - t])):
        q = (jax.random.normal(keys[1], (b, tt, heads, width), jnp.float32)
             * width ** -0.5).astype(jnp.bfloat16)
        new = jax.random.normal(keys[2], (b, tt, width), jnp.bfloat16)
        start = jnp.asarray(at, jnp.int32)

        @jax.jit
        def served(pool, q, new, start):
            written = latent_insert_in_place(
                pool, new, jnp.asarray(table), start, None, layer=1,
                interpret=interpret)
            return latent_paged_attention(
                q, written, jnp.asarray(table), start,
                value_width=value_width, layer=1, interpret=interpret)
        got = np.asarray(served(pool, q, new, start), np.float32)
        # Plain: the slot's pages in logical order, the new rows over them.
        dense = np.asarray(pool[1], np.float32)[table]      # [b, n, W, page]
        dense = dense.transpose(0, 1, 3, 2).reshape(b, s, width)
        want = np.zeros_like(got)
        for i in range(b):
            dense[i, at[i]:at[i] + tt] = np.asarray(new[i], np.float32)
            scores = np.einsum("thw,sw->hts", np.asarray(q[i], np.float32),
                               dense[i])
            seen = np.arange(s)[None, :] <= at[i] + np.arange(tt)[:, None]
            scores = np.where(seen[None], scores, -np.inf)
            p = np.exp(scores - scores.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            want[i] = np.einsum("hts,sv->thv", p, dense[i, :, :value_width])
        err = float(np.max(np.abs(got - want)))
        out.append({"kernel": f"latent_{kind}", "kv": "bf16",
                    "max_abs_err": err,
                    "ok": bool(np.isfinite(got).all() and err <= KERNEL_TOL)})
    return out


def past_original_tokens(engine: Any, config: dict[str, Any]) -> int:
    """A prompt a whole prefill chunk past the rotary's original context,
    in whole chunks: 8,704 at the cell's geometry (17 chunks of 512)."""
    original = config["rope_parameters"]["original_max_position_embeddings"]
    chunk = engine.prefill_chunk
    return -(-(original + chunk) // chunk) * chunk


def served_past_8192(engine: Any, config: dict[str, Any]) -> dict[str, Any]:
    """One seeded prompt of ``past_original_tokens`` on slot 0 of an IDLE
    engine, through the calls its scheduler makes and in its order: the
    slot's pages, then a chunk at a time the compiled prefill
    (``_exec_prefill``), then ``DECODE_STEPS`` greedy decode steps
    (``_decode_burst``). Every generated position is held to ``logits`` as
    ``correctness.served_against_reference`` holds the harness's sample
    (the reasons stand with those limits). Past the original context the
    queries are scaled by ``a_t`` > 1 and the low rotary frequencies are
    YaRN's: a program that left either out, or lost a latent page, puts a
    token ~4 below the maximum. It runs in set-up, after
    ``run.warm_programs``: the slot leaves its group and the host state is
    as the warm-up left it. ``peak_gb``: the device's peak memory at entry
    (engine, warm-up and the kernel cases), after serving, and after the
    reference — whose float32 blocks beside the engine are set-up's, not a
    deployment's."""
    from benchmark.correctness import LOGIT_GAP_P50_TOL, LOGIT_GAP_TOL

    def peak_gb() -> float | None:
        """The device's peak so far (None where the backend keeps none):
        what the program held by now, apart from what the reference adds."""
        peak = (engine.mesh.devices.flat[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        return None if peak is None else round(peak / 1e9, 3)
    t0 = time.monotonic()
    peak_before = peak_gb()
    slot, chunk = 0, engine.prefill_chunk
    n = past_original_tokens(engine, config)
    vocab = engine.model_cfg.vocab_size
    prompt = np.random.default_rng(38).integers(3, vocab, n).astype(np.int32)
    before = engine.stats()
    if engine.active.any() or not engine.kv_groups.allocate(
            slot, n + 1 + DECODE_STEPS):
        raise RuntimeError("served_past_8192 needs an idle engine")
    for pos in range(0, n, chunk):
        first, engine.cache = engine._exec_prefill(
            slot, pos, prompt[pos:pos + chunk])
    served = [int(np.asarray(first)[0])]
    engine.lengths[slot], engine.active[slot] = n, True
    engine.last_token[slot] = served[0]
    engine._d_dirty = True
    for _ in range(DECODE_STEPS):
        served.append(int(engine._decode_burst(1)[-1][slot]))
    engine.active[slot], engine.lengths[slot] = False, 0
    engine.last_token[slot] = 0
    engine.kv_groups.release(slot)
    engine._d_dirty = True
    after = engine.stats()
    t1 = time.monotonic()
    peak_served = peak_gb()
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    ref = logits(engine.params, sizes(engine.model_cfg, config), seq,
                 last=len(served))
    gaps = [float(r.max() - r[tok]) for r, tok in zip(ref, served)]
    keys = (after["mla_prefill_keys_total"] - before["mla_prefill_keys_total"]
            + after["mla_decode_keys_total"] - before["mla_decode_keys_total"])
    released = [g["pages_free"] == g["pages"] for g in after["kv_groups"]]
    total = n + DECODE_STEPS
    return {"kernel": "served_past_8192", "tokens": n,
            "positions": len(served), "keys_attended": keys,
            "max_abs_err": max(gaps), "gap_p50": float(np.median(gaps)),
            "serve_s": round(t1 - t0, 2),
            "reference_s": round(time.monotonic() - t1, 2),
            "peak_gb": [peak_before, peak_served, peak_gb()],
            "ok": bool(max(gaps) <= LOGIT_GAP_TOL
                       and np.median(gaps) <= LOGIT_GAP_P50_TOL
                       and keys == total * (total + 1) // 2
                       and all(released))}


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """(a) The latent write and attention kernels at the file's widths, as
    a decode step and as a prefill chunk. (b) ``served_past_8192``."""
    out = latent_kernel_parity(
        heads=config["num_attention_heads"],
        width=config["kv_lora_rank"] + config["qk_rope_head_dim"],
        value_width=config["kv_lora_rank"], page=engine.kv_page,
        interpret=interpret,
        **({"pages_per_slot": 8, "t": 16} if interpret else {}))
    return out + [served_past_8192(engine, config)]
