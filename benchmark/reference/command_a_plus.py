"""Plain float32 forward of the Command A+ decoder (``cohere2_moe``): every
layer a PARALLEL block — one LayerNorm whose result feeds grouped-query
attention, a sparse expert layer and four shared experts side by side, and
one residual add — the layers in periods of four: three that rotate q and k
and attend inside a sliding WINDOW, then a GLOBAL layer without rotary
embedding; a head tied to the embedding.

Written from the published config (source in
``configs/command-a-plus-218b-ep8.json``). One layer, residual stream x
[T, D], as computed below:

    h  = g * (x - mean(x)) / sqrt(var(x) + eps)     LayerNorm, no bias
    q, k, v = h W_q, h W_k, h W_v                   no bias, no q/k norm
          layer_types[l] = sliding_attention: rotate q, k — pairs
          (2i, 2i+1) of the whole head turned by pos * theta^(-2i/head);
          key j visible to query i iff 0 <= i - j < window
          full_attention: no rotary; key j visible iff j <= i
    A  = concat_heads(softmax(q k^T / sqrt(head)) v) W_o
    s  = sigmoid(h W_r) over ALL experts; the top-k by s; w_e = s_e / sum
    R  = sum over the k of  w_e * (silu(h Wg_e) * (h Wu_e)) Wd_e
    S  = 1/n_shared * sum over the shared experts of (silu(h Wg_s) * (h Wu_s)) Wd_s
    x' = x + A + R + S

then ``LayerNorm(x_L) E^T`` with E the embedding (the file's ``logit_scale``
is 1, and ``sizes`` holds it to that). Which layer is windowed is read from
the FILE's ``layer_types``, entry by entry; nothing of a period is assumed
here.

Departures from the publication, each also under ``assumed`` in the file:
the width of a routed and of a shared expert is ``intermediate_size`` (the
config has no key of its own for it); "average" is the mean of the shared
experts' outputs, added to the routed sum; the router reads ``h``; no
scaling factor on the routed sum; only the experts ``[first_expert_held,
first_expert_held + num_experts)`` of the published count live here, the
router scores all of them and normalises over the k it selected wherever
they live, and what the absent experts would add is left out; the vocabulary
is the file's slice; weights are the engine's int8 weights times their
scales (the head: the int8 copy of the embedding's own rows), so the
reference computes in float32 what the engine computes in W8A8.

It shares no code with ``llmapigateway_tpu/models``: only the LAYOUT of the
weight tree is the program's (``layers/attn``: a tuple over a period's
positions of trees stacked over periods; the shared experts side by side
along the hidden axis of ``sg`` / ``su`` / ``sd``), dequantised a layer, an
expert — routed or shared — a KV head's projections and ``HEAD_ROWS`` rows
of the head at a time, each read where it lies in the engine's stacks, so
that the process's peak memory is the ENGINE's (its pools and weights) and
not this file's; attention runs a KV head's query heads and of those a
block of queries at a time (no key is repeated), for the same reason.
Everything under ``jax.default_matmul_precision("highest")``.

``kernel_checks`` adds what the harness's own sample cannot reach: the
paged kernels WITHOUT a window at the cell's heads (the harness runs them
at the window), and one sequence long enough to turn the windowed group's
page ring, taken through the engine's own prefill chunks and decode steps
and held to ``logits`` (``served_past_window``).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 64            # queries attended at a time ([heads, 64, T])
HEAD_ROWS = 8192            # of the head dequantised at a time


@dataclasses.dataclass(frozen=True)
class Sizes:
    windowed: tuple[bool, ...]      # per layer, as deep as the engine is
    period: int                     # of the engine's weight tree
    window: int
    heads: int
    kv_heads: int
    head: int
    theta: float
    eps: float
    top: int
    first: int                      # experts [first, first + held) live here
    held: int
    shared: int
    width: int                      # of one expert, routed or shared
    # What the arithmetic is: "float32" — the reference. ``CONTROLS`` put a
    # coarser one in its place: "bfloat16" (weights, activations and
    # products rounded to it) or "int4" (float32 on weights whose int8
    # values lost their four low bits).
    precision: str = "float32"


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE — the published widths, the
    experts held here (``num_experts``) and the first of them
    (``first_expert_held``, absent: 0) — but the depth, which the harness
    cut in the program's config from the same file: the first ``n_layers``
    entries of the published ``layer_types`` are the layers held."""
    kinds = config["layer_types"][:model_cfg.n_layers]
    if set(kinds) - {"sliding_attention", "full_attention"}:
        raise ValueError(f"layer_types {sorted(set(kinds))}")
    if not (config["use_parallel_block"] and config["norm_topk_prob"]
            and config["expert_selection_fn"] == "sigmoid"
            and config["shared_expert_combination_strategy"] == "average"
            and config["position_embedding_type"] == "rope_gptj"
            and config["rotary_pct"] == 1 and config["tie_word_embeddings"]
            and config["logit_scale"] == 1
            and not config["first_k_dense_replace"]
            and not config["use_qk_norm"]):
        raise ValueError("the reference computes the parallel block with "
                         "sigmoid selection, averaged shared experts, whole-"
                         "head interleaved rotary and a tied, unscaled head "
                         "alone")
    return Sizes(
        windowed=tuple(k == "sliding_attention" for k in kinds),
        period=config["layer_kinds"]["period"],
        window=int(config["sliding_window"]),
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head=config["head_dim"],
        theta=float(config["rope_theta"]),
        eps=float(config["layer_norm_eps"]),
        top=config["num_experts_per_tok"],
        first=int(config.get("first_expert_held", 0)),
        held=int(config["num_experts"]),
        shared=int(config["num_shared_experts"]),
        width=int(config["intermediate_size"]))


def _dtype(c: Sizes):
    return jnp.bfloat16 if c.precision == "bfloat16" else jnp.float32


def weight(w: Any, c: Sizes, rows: bool = False) -> jax.Array:
    """A leaf of the engine's tree as float32 (int8 ``{"q", "s"}``: one
    scale per output channel, the contraction axis second to last; the
    head ``[V, D]``, ``rows``: one scale per row) — or as
    ``Sizes.precision`` has it."""
    if not isinstance(w, dict):
        return jnp.asarray(w, _dtype(c))
    q = w["q"].astype(jnp.float32)
    if c.precision == "int4":
        q = jnp.round(q / 16.0) * 16.0
    s = w["s"].astype(jnp.float32)
    return (q * (s[..., None] if rows else s[..., None, :])).astype(_dtype(c))


def _at(w: Any, period: Any) -> Any:
    """A leaf as it is, or — ``period`` given — its slice of a stack over
    periods: read where it lies, so that no layer is copied whole."""
    return w if period is None else jax.tree.map(lambda a: a[period], w)


def _columns(w: Any, start: Any, size: int) -> Any:
    """``size`` output channels of a leaf from ``start``, scales and all."""
    return jax.tree.map(lambda a: jax.lax.dynamic_slice_in_dim(
        a, start, size, a.ndim - 1), w)


def _rows(w: Any, start: Any, size: int) -> Any:
    """``size`` rows of the contraction axis; a scale is a column's."""
    if not _is_q(w):
        return jax.lax.dynamic_slice_in_dim(w, start, size, w.ndim - 2)
    return {"q": jax.lax.dynamic_slice_in_dim(
        w["q"], start, size, w["q"].ndim - 2), "s": w["s"]}


def _is_q(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w


def layer_norm(x, w, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return w * centred / jnp.sqrt(
        jnp.mean(jnp.square(centred), -1, keepdims=True) + eps)


def rotate(x, theta: float):
    """x [T, heads, head] at positions 0..T-1: pairs (x[2i], x[2i+1]) turned
    by pos * theta^(-2i/head), each left where it lay (``rope_gptj``)."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(
        x.shape).astype(x.dtype)


def attention(h, lp, c: Sizes, windowed: bool, period=None):
    """h [T, D] (normalised) -> A [T, D] under the engine's leaves ``lp``
    (``period`` given: their stacks over periods, at that one). ONE KV head
    and the query heads that read it at a time — its columns of W_q, W_k
    and W_v and its rows of W_o dequantised then — and of those
    ``QUERY_BLOCK`` queries at a time over all T keys: no key is repeated
    and nothing as wide as all the heads is ever held."""
    t = h.shape[0]
    group = c.heads // c.kv_heads
    wide = group * c.head
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t
    j = jnp.arange(t)[None, :]
    wq, wk, wv, wo = (_at(lp[k], period) for k in ("wq", "wk", "wv", "wo"))

    def of_kv_head(out, n):
        q = (h @ weight(_columns(wq, n * wide, wide), c)).reshape(
            t, group, c.head)
        k = (h @ weight(_columns(wk, n * c.head, c.head), c))[:, None]
        v = h @ weight(_columns(wv, n * c.head, c.head), c)
        if windowed:
            q, k = rotate(q, c.theta), rotate(k, c.theta)
        k = k[:, 0]
        qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
            blocks, QUERY_BLOCK, group, c.head)

        def block(args):
            qi, i0 = args
            i = i0 + jnp.arange(QUERY_BLOCK)[:, None]
            seen = j <= i
            if windowed:
                seen &= i - j < c.window
            scores = jnp.einsum("qgd,kd->gqk", qi, k) / float(np.sqrt(c.head))
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
            return jnp.einsum("gqk,kd->qgd", probs, v)
        a = jax.lax.map(block, (qb, jnp.arange(blocks) * QUERY_BLOCK))
        a = a.reshape(blocks * QUERY_BLOCK, wide)[:t]
        return out + a @ weight(_rows(wo, n * wide, wide), c), None
    out, _ = jax.lax.scan(of_kv_head, jnp.zeros_like(h),
                          jnp.arange(c.kv_heads))
    return out


def routing(h, router, c: Sizes):
    """h [T, D] -> (ids [T, top], weights [T, top]): s = sigmoid(h W_r)
    over ALL experts, the top-k by s, each over the sum of the k
    (``norm_topk_prob``)."""
    s = jax.nn.sigmoid(h @ router)
    ids = jnp.argsort(-s, axis=-1)[:, :c.top]
    picked = jnp.take_along_axis(s, ids, -1)
    return ids, picked / jnp.sum(picked, -1, keepdims=True)


def routed_experts(h, c: Sizes, ids, w, wg, wu, wd, period=None):
    """R [T, D]: every expert of the stacks ``wg, wu [held, D, F]``, ``wd
    [held, F, D]`` (``period`` given: of their stacks over periods, at that
    one) on every token, one read and dequantised at a time, weighted by
    the token's routing weight for it — 0 where it is not among the token's
    top-k of ALL experts. Expert ``e`` of the stacks is expert ``c.first +
    e`` of the model."""
    def one(out, e):
        g, u, d = (weight(_at(_at(m, period), e), c) for m in (wg, wu, wd))
        gate = jnp.sum(jnp.where(ids == c.first + e, w, 0.0), -1)
        y = (jax.nn.silu(h @ g) * (h @ u)) @ d
        return out + (gate[:, None] * y).astype(out.dtype), None
    held = jax.tree.leaves(wg)[0].shape[0 if period is None else 1]
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(held))
    return out


def shared_experts(h, c: Sizes, sg, su, sd, period=None):
    """S [T, D]: the MEAN of the shared experts' outputs, one dequantised
    at a time. The engine's tree holds them side by side: expert ``s`` is
    columns ``[s F, (s+1) F)`` of ``sg`` and ``su`` and the same rows of
    ``sd`` (``period`` given: of their stacks over periods, at that one)."""
    sg, su, sd = _at(sg, period), _at(su, period), _at(sd, period)

    def one(out, s):
        own = s * c.width, c.width
        y = (jax.nn.silu(h @ weight(_columns(sg, *own), c))
             * (h @ weight(_columns(su, *own), c))
             ) @ weight(_rows(sd, *own), c)
        return out + y, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(c.shared))
    return out / c.shared


@functools.partial(jax.jit, static_argnums=(3, 4))
def _normed_and_attention(x, norm, lp, c: Sizes, windowed: bool,
                          period=None):
    """(h, x + A(h))."""
    h = layer_norm(x, weight(_at(norm, period), c), c.eps)
    return h, x + attention(h, lp, c, windowed, period)


@functools.partial(jax.jit, static_argnums=(1,))
def _experts(h, c: Sizes, router, wg, wu, wd, sg, su, sd, period=None):
    """R(h) + S(h)."""
    ids, w = routing(h, weight(_at(router, period), c), c)
    return (routed_experts(h, c, ids, w, wg, wu, wd, period)
            + shared_experts(h, c, sg, su, sd, period))


def layer(x, lp, c: Sizes, windowed: bool, period=None):
    """x [T, D] -> x + A + R + S under one layer's tree ``lp`` — or,
    ``period`` given, under that period of the tree ``lp`` stacked over
    periods, as the engine holds it."""
    mp = lp["mlp"]
    h, x_a = _normed_and_attention(
        x, lp["norm"], {k: lp[k] for k in ("wq", "wk", "wv", "wo")}, c,
        windowed, period)
    return x_a + _experts(h, c, mp["router"], mp["wg"], mp["wu"], mp["wd"],
                          mp["sg"], mp["su"], mp["sd"], period)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(table, tok, c: Sizes):
    return jnp.take(table, tok, axis=0).astype(_dtype(c))


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, w, last: int, c: Sizes):
    """[last, V]: ``HEAD_ROWS`` rows of the head ``w`` [V, D] at a time (a
    vocabulary that is no multiple of it: all at once)."""
    h = layer_norm(x[-last:], weight(norm, c), c.eps)
    v = jax.tree.leaves(w)[0].shape[0]
    if v % HEAD_ROWS:
        return h @ weight(w, c, rows=True).T
    blocks = jax.tree.map(
        lambda a: a.reshape(v // HEAD_ROWS, HEAD_ROWS, *a.shape[1:]), w)
    out = jax.lax.map(lambda block: h @ weight(block, c, rows=True).T, blocks)
    return jnp.moveaxis(out, 0, 1).reshape(last, v)


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree: ``layers/attn`` a tuple over a
    period's positions of trees stacked over periods, each with its ``mlp``
    sub-tree; the head is the embedding (under quant the int8 copy of its
    own rows that the engine's head product reads)."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq, jnp.int32), c)
        for n, windowed in enumerate(c.windowed):
            p, i = divmod(n, c.period)
            # ONE layer in flight: a program's result and scratch are
            # allocated when it is enqueued, and eight layers enqueued at
            # once held 2.7 GB beside the engine (15.2 GB of 15.75).
            x = jax.block_until_ready(layer(
                x, params["layers"]["attn"][i], c, windowed, jnp.int32(p)))
        head = params.get("lm_head_q8", params["embed"])
        out = _head(x, params["final_norm"], head, last, c)
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
    ``LOGIT_GAP_P50_TOL`` (the reasons stand with those limits). At the tiny
    preset, where a window of 16 bites, a global layer put on the ring
    fails it (``tests/test_engine_cache_groups.py``); at the cell's sizes on
    random weights the gap sees what breaks the stream (an expert layer, a
    norm, the head, a coarser arithmetic: ``CONTROLS``) and NOT which keys
    an attention layer saw (``READINGS`` says why), so what holds the ring
    here is what is counted: the pages re-targeted, the slot's release from
    every group, and the keys the decode steps attended in each kind of
    group. It runs in set-up, after ``run.warm_programs``: the
    slot leaves every group and the host state is as the warm-up left it
    (no slot active, lengths 0, ``_d_dirty`` set). The decode steps' keys
    are read back from the engine's two counters: a windowed layer's steps
    attend the window, a global layer's the whole context. ``peak_gb``:
    the device's peak memory once the tokens are served and after the
    reference beside the engine (None where the backend keeps none)."""
    import types

    from benchmark.correctness import LOGIT_GAP_P50_TOL, LOGIT_GAP_TOL
    t0 = time.monotonic()
    slot, n, chunk = 0, past_window_tokens(engine), engine.prefill_chunk
    vocab = engine.model_cfg.vocab_size
    prompt = np.random.default_rng(44).integers(3, vocab, n).astype(np.int32)
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

    def peak_gb() -> float | None:
        peak = (engine.mesh.devices.flat[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        return None if peak is None else round(peak / 1e9, 3)
    peak_served = peak_gb()
    seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
    c = sizes(engine.model_cfg, config)
    ref = logits(engine.params, c, seq, last=len(served))
    gaps = [float(r.max() - r[tok]) for r, tok in zip(ref, served)]
    recycled = (after["kv_ring_recycled_total"]
                - before["kv_ring_recycled_total"])
    released = [g["pages_free"] == g["pages"] for g in after["kv_groups"]]
    keys = {kind: after[f"attn_decode_keys_{kind}_total"]
            - before[f"attn_decode_keys_{kind}_total"]
            for kind in ("global", "window")}
    # Step i of DECODE_STEPS sees the n prompt tokens, the i served before
    # it and itself: all of them in a global layer, the window's in a
    # windowed one.
    seen = [n + i + 1 for i in range(DECODE_STEPS)]
    counted = keys == {"global": sum(seen),
                       "window": sum(min(s, c.window) for s in seen)}
    return {"kernel": "served_past_window", "tokens": n,
            "positions": len(served), "ring_pages_recycled": recycled,
            "decode_keys": keys, "peak_gb": [peak_served, peak_gb()],
            "max_abs_err": max(gaps), "gap_p50": float(np.median(gaps)),
            "serve_s": round(t1 - t0, 2),
            "reference_s": round(time.monotonic() - t1, 2),
            "ok": bool(max(gaps) <= LOGIT_GAP_TOL
                       and np.median(gaps) <= LOGIT_GAP_P50_TOL
                       and recycled >= TURNS and all(released) and counted)}


# What ``correct`` has to refuse, each a change of the reference's ``Sizes``
# alone: put in the reference's place (``tools/correct_controls.py``), the
# program's own tokens are judged against a coarser arithmetic or a wrong
# layer exactly as the harness judges them against this file. The engine
# computes in W8A8 with int8 KV, BELOW bfloat16: the nearest precision under
# what the configuration states is four-bit weights.
CONTROLS = {
    "int4_weights": lambda c: dataclasses.replace(c, precision="int4"),
    # The first shared expert alone, where the mean of all is due.
    "one_shared_expert": lambda c: dataclasses.replace(c, shared=1),
}
# What it CANNOT refuse on random weights, read the same way so that the
# record says how far each stands from the limits (PERF.md section 6, PR
# 44): bfloat16 is finer than the engine's own arithmetic; and scores of
# unit variance attend almost evenly, so an attention branch is a mean over
# hundreds of keys, a few hundredths of the stream, whichever keys it saw —
# every layer windowed and rotated reads like the sound reference.
READINGS = {
    "bfloat16": lambda c: dataclasses.replace(c, precision="bfloat16"),
    "global_layers_windowed": lambda c: dataclasses.replace(
        c, windowed=(True,) * len(c.windowed)),
}


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
