"""Plain float32 forward of the SmallThinker decoder: every layer a
grouped-query softmax attention followed by a sparse expert MLP, the layers
in periods of four — a GLOBAL layer without rotary embedding, then three
layers that attend inside a sliding WINDOW and rotate q and k — and an
expert layer whose softmax router reads the block's input.

Written from the published config and the family's description (source in
``configs/smallthinker-21b-pp3.json``). One layer, residual stream x
[T, D], as computed below:

    g   = x W_r                         float32, the block's input itself
    S   = top-k of g;  w_e = exp(g_e) / sum_{e' in S} exp(g_e')
    h   = rmsnorm(x, w_1);  q, k, v = h W_q, h W_k, h W_v    (no bias)
          rope_layout[l] = 1: rotate q, k (whole head, half-split pairs)
          sliding_window_layout[l] = 1: key j visible to query i iff
          0 <= i - j < window;  0: iff j <= i
    x'  = x + concat_heads(softmax(q k^T / sqrt(head)) v) W_o
    m   = rmsnorm(x', w_2)
    x'' = x' + sum_{e in S} w_e (relu(m Wg_e) * (m Wu_e)) Wd_e

then a final rmsnorm and the untied head. Which layer is windowed and which
is rotary is read from the FILE's ``sliding_window_layout`` and
``rope_layout``, entry by entry; nothing of a period is assumed here.

Departures from the publication, each also under ``assumed`` in the file:
the router reads the un-normalised block input (the description says only
"before attention"); the description's
"secondary experts" are off (no key of the config sizes them); weights are
the engine's int8 weights times their scales, so the reference computes in
float32 what the engine computes in W8A8.

It shares no code with ``llmapigateway_tpu/models``: only the LAYOUT of the
weight tree is the program's (``layers/attn``: a tuple over a period's
positions of trees stacked over periods), dequantised a layer and an
expert at a time so that it fits beside the engine (a scan over the
experts inside one compiled call a layer: a call an expert read 45 s a
sequence of 1,088 tokens on the chip, the scan 0.9 s); attention runs a
block of queries at a time for the same reason. Everything under
``jax.default_matmul_precision("highest")``.

``kernel_checks`` adds what the harness's own sample cannot reach: the
paged kernels WITHOUT a window at the cell's heads, and one sequence long
enough to turn the windowed group's page ring, taken through the engine's
own prefill chunks and decode steps and held to ``logits``
(``served_past_window``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 512           # queries attended at a time ([heads, 512, T])


@dataclasses.dataclass(frozen=True)
class Sizes:
    windowed: tuple[bool, ...]      # per layer, as deep as the engine is
    rotary: tuple[bool, ...]
    period: int                     # of the engine's weight tree
    window: int
    heads: int
    kv_heads: int
    head: int
    theta: float
    eps: float
    experts: int
    top: int


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE but the depth, which the
    harness cut in the program's config from the same file: the first
    ``n_layers`` entries of the published layouts are the layers held."""
    n = model_cfg.n_layers
    if not config["moe_primary_router_apply_softmax"]:
        raise ValueError("the reference scores by softmax alone")
    return Sizes(
        windowed=tuple(bool(v) for v in config["sliding_window_layout"][:n]),
        rotary=tuple(bool(v) for v in config["rope_layout"][:n]),
        period=config["layer_kinds"]["period"],
        window=int(config["sliding_window_size"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head=config["head_dim"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        experts=config["moe_num_primary_experts"],
        top=config["moe_num_active_primary_experts"])


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


def _rotate(x, theta: float):
    """x [T, heads, head] at positions 0..T-1: pairs (x[i], x[i + head/2])
    turned by pos * theta^(-2i/head)."""
    t, _, d = x.shape
    half = d // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(h, lp, c: Sizes, windowed: bool, rotary: bool):
    """h [T, D] (normalised) -> [T, D], ``QUERY_BLOCK`` queries at a time
    over all T keys."""
    t = h.shape[0]
    q = (h @ lp["wq"]).reshape(t, c.heads, c.head)
    k = (h @ lp["wk"]).reshape(t, c.kv_heads, c.head)
    v = (h @ lp["wv"]).reshape(t, c.kv_heads, c.head)
    if rotary:
        q, k = _rotate(q, c.theta), _rotate(k, c.theta)
    rep = c.heads // c.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        blocks, QUERY_BLOCK, c.heads, c.head)
    j = jnp.arange(t)[None, :]

    def block(args):
        qi, i0 = args
        i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
        seen = j <= i
        if windowed:
            seen &= i - j < c.window
        scores = jnp.einsum("qhd,khd->hqk", qi, k) / np.sqrt(c.head)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)
    out = jax.lax.map(block, (qb, jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, -1)[:t] @ lp["wo"]


def routing(seen, router, c: Sizes):
    """seen [T, D] -> (ids [T, top], weights [T, top]): g = seen W_r over
    ALL experts, the top-k by g, softmax over the k (= softmax over all,
    renormalised on the k; ``norm_topk_prob``)."""
    g = seen @ router
    order = jnp.argsort(-g, axis=-1)[:, :c.top]
    return order, jax.nn.softmax(jnp.take_along_axis(g, order, -1), -1)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _attend(x, lp, router, c: Sizes, windowed: bool, rotary: bool):
    """(x + attention(rmsnorm(x)), the routing of the block's INPUT x)."""
    lp = jax.tree.map(f32, lp, is_leaf=_is_q)
    x_out = x + attention(_rms(x, lp["norm"], c.eps), lp, c, windowed, rotary)
    return x_out, routing(x, f32(router), c)


@functools.partial(jax.jit, static_argnums=(2,))
def _experts(x, norm, c: Sizes, ids, w, wg, wu, wd):
    """With m = rmsnorm(x): sum_e gate_e * E_e(m) over ALL experts, one dequantised at a time
    (a scan over the expert axis of the three stacks): every expert on
    every token, weighted by ``gate_e`` [T] — the token's routing weight
    for ``e``, 0 where ``e`` is not among its top-k."""
    m = _rms(x, f32(norm), c.eps)

    def one(out, ew):
        e, g, u, d = ew
        gate = jnp.sum(jnp.where(ids == e, w, 0.0), -1)
        y = (jax.nn.relu(m @ f32(g)) * (m @ f32(u))) @ f32(d)
        return out + gate[:, None] * y, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(m),
                          (jnp.arange(c.experts), wg, wu, wd))
    return out


def expert_mlp(x, mp, c: Sizes, routed):
    """x [T, D] (after attention) -> sum_e w_e E_e(rmsnorm(x)).
    ``routed``: (ids, weights) from the block's INPUT (``routing``)."""
    return _experts(x, mp["norm"], c, *routed, mp["wg"], mp["wu"], mp["wd"])


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, w, last: int, eps: float):
    return _rms(x[-last:], f32(norm), eps) @ f32(w).T


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree: ``layers/attn`` a tuple over a
    period's positions of trees stacked over periods, each with its
    ``mlp`` sub-tree."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq, jnp.int32))
        for layer, (windowed, rotary) in enumerate(zip(c.windowed, c.rotary)):
            p, i = divmod(layer, c.period)      # one layer's weights at a time
            lp = jax.tree.map(lambda a: a[p], params["layers"]["attn"][i])
            mp = lp.pop("mlp")
            x, routed = _attend(x, lp, mp["router"], c, windowed, rotary)
            x = x + expert_mlp(x, mp, c, routed)
        out = _head(x, params["final_norm"], params["lm_head"], last, c.eps)
        return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# kernel_checks: what the harness's own sample cannot reach
# ---------------------------------------------------------------------------

DECODE_STEPS = 8            # after the prefill's first token
TURNS = 3                   # pages the ring re-targets, at the least


def past_window_tokens(engine: Any) -> int:
    """A prompt long enough that the windowed group's ring of pages
    re-targets ``TURNS`` of them, in whole prefill chunks: 6144 at the
    cell's geometry (ring 21, page 256, chunk 512)."""
    ring = max(g["pages_per_slot"] for g in engine.stats()["kv_groups"]
               if g["window"])
    chunk = engine.prefill_chunk
    return -(-(ring + TURNS) * engine.kv_page // chunk) * chunk


def served_past_window(engine: Any, config: dict[str, Any]
                       ) -> dict[str, Any]:
    """One seeded prompt of ``past_window_tokens`` on slot 0 of an IDLE
    engine, through the calls its scheduler makes and in its order: the
    slot's pages in every cache group, then a chunk at a time the ring's
    rotation and the compiled prefill (``_exec_prefill``), then
    ``DECODE_STEPS`` greedy decode steps, each after the rotation a burst
    gets (``_swa_rotate``, ``_decode_burst``). Every generated position is
    held to ``logits`` as ``correctness.served_against_reference`` holds
    the harness's sample: the reference's logit of the token SERVED within
    ``LOGIT_GAP_TOL`` of the reference's own maximum, the median gap within
    ``LOGIT_GAP_P50_TOL`` (the reasons stand with those limits). A global
    layer that lost a page, a windowed layer that sees past its window or
    a rotated NoPE layer puts a token ~4 below the maximum. It runs in
    set-up, after ``run.warm_programs``: the slot leaves every group and
    the host state is as the warm-up left it (no slot active, lengths 0,
    ``_d_dirty`` set)."""
    import types

    from benchmark.correctness import LOGIT_GAP_P50_TOL, LOGIT_GAP_TOL
    t0 = time.monotonic()
    slot, n, chunk = 0, past_window_tokens(engine), engine.prefill_chunk
    vocab = engine.model_cfg.vocab_size
    prompt = np.random.default_rng(35).integers(3, vocab, n).astype(np.int32)
    before = engine.stats()
    if engine.active.any() or not engine.kv_groups.allocate(
            slot, n + 1 + DECODE_STEPS):
        raise RuntimeError("served_past_window needs an idle engine")
    for pos in range(0, n, chunk):
        engine.kv_groups.rotate(slot, pos + chunk - 1, pos)
        first, engine.cache = engine._exec_prefill(
            slot, pos, prompt[pos:pos + chunk])
    served = [int(np.asarray(first)[0])]
    engine.lengths[slot], engine.active[slot] = n, True
    engine.last_token[slot] = served[0]
    engine._d_dirty = True
    row = types.SimpleNamespace(slot=slot)
    for _ in range(DECODE_STEPS):
        engine._swa_rotate([row], 0, 1)
        served.append(int(engine._decode_burst(1)[-1][slot]))
    engine.active[slot], engine.lengths[slot] = False, 0
    engine.last_token[slot] = 0
    engine.kv_groups.release(slot)
    engine._d_dirty = True
    after = engine.stats()
    t1 = time.monotonic()
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    ref = logits(engine.params, sizes(engine.model_cfg, config), seq,
                 last=len(served))
    gaps = [float(r.max() - r[tok]) for r, tok in zip(ref, served)]
    recycled = (after["kv_ring_recycled_total"]
                - before["kv_ring_recycled_total"])
    released = [g["pages_free"] == g["pages"] for g in after["kv_groups"]]
    return {"kernel": "served_past_window", "tokens": n,
            "positions": len(served), "ring_pages_recycled": recycled,
            "max_abs_err": max(gaps), "gap_p50": float(np.median(gaps)),
            "serve_s": round(t1 - t0, 2),
            "reference_s": round(time.monotonic() - t1, 2),
            "ok": bool(max(gaps) <= LOGIT_GAP_TOL
                       and np.median(gaps) <= LOGIT_GAP_P50_TOL
                       and recycled >= TURNS and all(released))}


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """(a) Both paged kernels at the file's heads with NO window — the
    global layers' form; the harness's own ``kernel_parity`` runs them at
    the preset's one window. (b) ``served_past_window``."""
    from benchmark.correctness import kernel_parity
    out = kernel_parity(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], page=engine.kv_page, window=0,
        kv_quant=engine.kv_quant, interpret=interpret,
        **({"pages_per_slot": 8, "t": 16} if interpret else {}))
    for case in out:
        case["kernel"] += "_no_window"
    return out + [served_past_window(engine, config)]
