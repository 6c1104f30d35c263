"""Plain float32 forward of the Keye-VL-2.0 language model's decoder: every
layer grouped-query attention over the keys a learned indexer selected,
followed by a sparse expert MLP of which this chip holds a share.

Written from the published config's keys and the family's convention
(source in ``configs/keye-vl2-30b-ep4.json``). One layer, residual stream x
[T, D], as computed below — no cache, no pages::

    x'  = rmsnorm(x, w_1)
    q_t = rot(rms_h(x'_t W_q))   32 heads of 128;  rms_h: per head, one weight
    k_s = rot(rms_h(x'_s W_k)),  v_s = x'_s W_v    4 heads of 128
    qI_{t,j} = rot(x'_t W_qI)_j                    16 index heads of 64
    kI_s = rot(LN(x'_s W_kI))                      ONE index key of 64
    w_{t,j} = (x'_t W_w)_j 16^-1/2 64^-1/2
    I(t, s) = sum_j w_{t,j} relu(qI_{t,j} . kI_s)
    S_t = the 2,048 positions s <= t of largest I(t, s), float32 compare,
          ties to the lower s; every s <= t while t < 2,048
    h   = x + concat_h(softmax_{s in S_t}(q_t . k_s / sqrt(128)) v_s) W_o
    m   = rmsnorm(h, w_2);  g = m W_r  over ALL experts, float32
    E   = top-k of g;  w_e = exp(g_e) / sum_{e' in E} exp(g_e')
    y   = h + sum_{e in E, held here} w_e (silu(m W_g,e) * (m W_u,e)) W_d,e

``rot`` is the multimodal rotary: three position streams (time, height,
width) each turn their own section of the pair frequencies
(``mrope_section`` [16, 24, 24] of a head's 64 pairs; the index heads' 32
pairs in the same proportion, [8, 12, 12]), pairs ``(i, i + d/2)``, theta
1e7. On text the three streams are equal and it is the plain half-split
rotary; ``logits`` takes text. Then a final rmsnorm and the untied head
over the vocabulary rows held here. Experts ``[first, first + held)`` live
on this chip; what the absent ones would add is left out, as in the
program.

It shares no code with ``llmapigateway_tpu/models``: only the LAYOUT of the
weight tree is the program's (``layers/attn`` stacked over layers, with its
``mlp`` sub-tree), dequantised a layer and an expert at a time so that it
fits beside the engine; attention runs a block of queries at a time for the
same reason. Everything under ``jax.default_matmul_precision("highest")``.

``kernel_checks`` adds what the harness's own sample cannot reach (its
prompts are two chunks: every key is selected): what the provider selects
at the cell's widths below and past ``topk`` — a decode step's list and the
chunk kernel's mask — against this module's own selection of plain float32
scores; the gathered decode and the masked page walk against plain
``jax.numpy`` given the same selection; and two prompts of four times
``topk`` and more served through the scheduler, two rows a prefill dispatch
beside live decoding slots, held to ``logits`` (``served_past_topk``).
Apart from the provider under test (``SparseAttention``) and a request's
type, nothing here is the program's: pages are laid and read on the host.

``dsa_decode_cost`` gives the bytes a decode step's attention has to read
of one layer, from the contexts alone.
"""
from __future__ import annotations

import dataclasses
import functools
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
    kv_heads: int
    head: int
    theta: float
    eps: float
    sections: tuple[int, ...]   # mrope_section, of a head's pairs
    idx_heads: int
    idx_head: int
    topk: int
    top: int
    first: int              # experts [first, first + held) live here
    held: int
    # What ``CONTROLS`` change: which seen keys a query past ``topk``
    # attends — "top" (the model's), "all" (the selection switched off) or
    # "lowest" (the ``topk`` of LEAST index score: a wrong set of the right
    # size) — and float32 on weights whose int8 values lost their four low
    # bits.
    select: str = "top"
    precision: str = "float32"


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE — the published widths, the
    experts held here (``num_experts``) and the first of them
    (``first_expert_held``, absent: 0) — but the depth, which the harness
    cut in the program's config from the same file."""
    sa, rope = config["sa_config"], config["rope_scaling"]
    if (config["mlp_only_layers"] or config["decoder_sparse_step"] != 1
            or not config["norm_topk_prob"] or config["attention_bias"]
            or config["tie_word_embeddings"]
            or sa["indexer_num_kv_heads"] != 1
            or rope["rope_type"] != "default"):
        raise ValueError("the reference computes an expert layer at every "
                         "depth, a renormalised top-k, projections without "
                         "bias, an untied head, one index key head and "
                         "unscaled rotary alone")
    return Sizes(
        layers=model_cfg.n_layers, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head=config["head_dim"],
        theta=float(config["rope_theta"]), eps=float(config["rms_norm_eps"]),
        sections=tuple(rope["mrope_section"]),
        idx_heads=sa["indexer_num_heads"], idx_head=sa["indexer_head_dim"],
        topk=sa["topk"], top=config["num_experts_per_tok"],
        first=int(config.get("first_expert_held", 0)),
        held=int(config["num_experts"]))


def weight(w: Any, c: Sizes) -> jax.Array:
    """A leaf of the engine's tree as float32 (int8 ``{"q", "s"}``: one
    scale per output channel, the contraction axis second to last; the
    head ``[V, D]`` one scale per row) — under ``precision`` "int4" with
    the int8 values' four low bits gone."""
    if not isinstance(w, dict):
        return jnp.asarray(w, jnp.float32)
    q, s = w["q"].astype(jnp.float32), w["s"].astype(jnp.float32)
    if c.precision == "int4":
        q = jnp.round(q / 16.0) * 16.0
    if q.ndim >= 2 and s.shape == q.shape[:-2] + q.shape[-1:]:
        return q * s[..., None, :]
    return q * s[..., None]


def _is_q(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _layer_norm(x, w, b, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return w * centred / jnp.sqrt(
        jnp.mean(jnp.square(centred), -1, keepdims=True) + eps) + b


def mrope(x, positions, sections: tuple[int, ...], theta: float):
    """x [T, heads, d] turned by the THREE position streams ``positions``
    [3, T] (time, height, width): pair ``i`` of the ``d / 2`` pairs ``(i, i
    + d/2)`` turns by ``positions[stream(i)] theta^(-2i/d)``, the streams
    taking ``sections`` of the pairs in turn (scaled to ``d / 2`` pairs
    where the head is narrower than the sections were written for)."""
    half = x.shape[-1] // 2
    ends = np.cumsum(sections) * half // sum(sections)
    stream = np.searchsorted(ends, np.arange(half), side="right")
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[stream, :].T * freqs    # [T, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def text_positions(t: int):
    """Text: the three streams are the token's index."""
    return jnp.broadcast_to(jnp.arange(t)[None, :], (3, t))


def indexer(h, lp, c: Sizes, positions):
    """h [T, D] (normalised) -> (index queries [T, J, W], index keys
    [T, W], head weights [T, J] with both scale factors in)."""
    t = h.shape[0]
    qi = mrope((h @ lp["wqi"]).reshape(t, c.idx_heads, c.idx_head),
               positions, c.sections, c.theta)
    ki = mrope(_layer_norm(h @ lp["wki"], lp["ki_norm"], lp["ki_bias"],
                           c.eps)[:, None, :],
               positions, c.sections, c.theta)[:, 0]
    w = (h @ lp["wwi"]) * (c.idx_heads ** -0.5 * c.idx_head ** -0.5)
    return qi, ki, w


def index_scores(qi, ki, w):
    """qi [Q, J, W], ki [S, W], w [Q, J] -> ``I`` [Q, S] float32 (every
    pair; the caller masks what a query cannot see)."""
    return jnp.einsum("qj,qjs->qs", w, jax.nn.relu(
        jnp.einsum("qjd,sd->qjs", qi, ki)))


def top_positions(scores, seen, k: int):
    """scores [Q, S] float32, seen bool [Q, S] -> bool [Q, S]: each row's
    ``k`` seen positions of largest score, ties to the lower position
    (``lax.top_k`` keeps the lower index of equals); all of them where a
    row sees no more than ``k``."""
    s = scores.shape[-1]
    if s <= k:
        return seen
    scores = jnp.where(scores == 0.0, 0.0, scores)      # -0.0 IS 0.0
    _, idx = jax.lax.top_k(jnp.where(seen, scores, -jnp.inf), k)
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & seen


def selected(scores, seen, c: Sizes):
    """What each query attends of the keys it sees, by its index scores."""
    if c.select == "all":
        return seen
    if c.select == "lowest":
        return top_positions(-scores, seen, c.topk)
    return top_positions(scores, seen, c.topk)


def attention(h, lp, c: Sizes, positions=None):
    """h [T, D] (normalised) -> [T, D]: ``QUERY_BLOCK`` queries at a time
    over all T keys (their index scores too: [256, 16, T] at a time), each
    query over the keys its index scores selected."""
    t = h.shape[0]
    positions = text_positions(t) if positions is None else positions
    q = _rms((h @ lp["wq"]).reshape(t, c.heads, c.head), lp["q_norm"], c.eps)
    k = _rms((h @ lp["wk"]).reshape(t, c.kv_heads, c.head), lp["k_norm"],
             c.eps)
    v = (h @ lp["wv"]).reshape(t, c.kv_heads, c.head)
    q = mrope(q, positions, c.sections, c.theta)
    k = mrope(k, positions, c.sections, c.theta)
    group = c.heads // c.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    qi, ki, w = indexer(h, lp, c, positions)
    blocks = -(-t // QUERY_BLOCK)
    pad = blocks * QUERY_BLOCK - t

    def blocked(x):
        return jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
            blocks, QUERY_BLOCK, *x.shape[1:])
    pos = jnp.arange(t)

    def block(args):
        q_b, qi_b, w_b, i0 = args
        seen = pos[None, :] <= i0 + jnp.arange(QUERY_BLOCK)[:, None]
        keep = selected(index_scores(qi_b, ki, w_b), seen, c)
        scores = jnp.einsum("qhd,khd->hqk", q_b, k) / np.sqrt(c.head)
        probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, v)
    out = jax.lax.map(block, (blocked(q), blocked(qi), blocked(w),
                              jnp.arange(blocks) * QUERY_BLOCK))
    return out.reshape(blocks * QUERY_BLOCK, -1)[:t] @ lp["wo"]


@functools.partial(jax.jit, static_argnums=(2,))
def _attend(x, lp, c: Sizes):
    lp = jax.tree.map(lambda w: weight(w, c), lp, is_leaf=_is_q)
    return x + attention(_rms(x, lp["norm"], c.eps), lp, c)


@functools.partial(jax.jit, static_argnums=(2,))
def _experts(x, mp, c: Sizes, stacks, layer):
    """x [T, D] (after attention) -> x + the held experts' part.
    ``stacks``: the routed experts' three matrices as the engine holds
    them, [layers, held, ...], read one expert of layer ``layer`` at a time
    inside the scan: every held expert on every token, weighted by the
    token's routing weight for it — 0 where it is not among the token's
    top-k of ALL experts."""
    m = _rms(x, weight(mp["norm"], c), c.eps)
    g = m @ weight(mp["router"], c)
    ids = jnp.argsort(-g, axis=-1)[:, :c.top]
    w = jax.nn.softmax(jnp.take_along_axis(g, ids, -1), -1)

    def one(out, e):
        eg, eu, ed = (weight(jax.tree.map(lambda a: a[layer, e], stack), c)
                      for stack in stacks)
        gate = jnp.sum(jnp.where(ids == c.first + e, w, 0.0), -1)
        y = (jax.nn.silu(m @ eg) * (m @ eu)) @ ed
        return out + gate[:, None] * y, None
    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(c.held))
    return x + out


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3,))
def _head(x, norm, w, c: Sizes):
    return _rms(x, weight(norm, c), c.eps) @ weight(w, c).T


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree: ``layers/attn`` stacked over
    layers, with its ``mlp`` sub-tree. ``seq`` is padded to whole
    ``QUERY_BLOCK``s (what follows a position changes nothing before it),
    so sequences of like length share one compiled forward."""
    attn = dict(params["layers"]["attn"])
    mlp = dict(attn.pop("mlp"))
    stacks = tuple(mlp.pop(k) for k in ("wg", "wu", "wd"))
    t = len(seq)
    seq = np.pad(np.asarray(seq, np.int32), (0, -t % QUERY_BLOCK))
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq))
        for layer in range(c.layers):       # one layer's weights at a time
            x = _attend(x, jax.tree.map(lambda a: a[layer], attn), c)
            # The experts a block of tokens at a time: ONE compiled program
            # whatever the sequence's length (19 s a shape on the chip).
            mp = jax.tree.map(lambda a: a[layer], mlp)
            x = jnp.concatenate([
                _experts(x[i:i + QUERY_BLOCK], mp, c, stacks,
                         jnp.int32(layer))
                for i in range(0, len(seq), QUERY_BLOCK)])
        out = _head(x[t - last:t], params["final_norm"], params["lm_head"],
                    c)
        return np.asarray(out, np.float32)


# ---------------------------------------------------------------------------
# What a decode step's attention has to read, from the contexts
# ---------------------------------------------------------------------------

def dsa_decode_cost(contexts: list[int], topk: int, index_width: int,
                    kv_heads: int, head_dim: int, itemsize: int = 2
                    ) -> tuple[int, int, float]:
    """(keys scored, keys selected, bytes) of ONE layer's attention in a
    decode step over slots whose queries see ``contexts`` keys (the new
    token's own among them): every seen token's index key is read once,
    ``index_width`` numbers; of K and V only the selected tokens' rows,
    ``min(context, topk)`` a slot — the same bytes whatever reads them."""
    scored = sum(contexts)
    selected = sum(min(n, topk) for n in contexts)
    return scored, selected, float(
        itemsize * (scored * index_width
                    + selected * 2 * kv_heads * head_dim))


# ---------------------------------------------------------------------------
# kernel_checks: what the harness's own sample cannot reach
# ---------------------------------------------------------------------------

# The program's index scores against float32 jax.numpy on the SAME rounded
# inputs (bfloat16 queries and keys, float32 weights): both sum 64 exact
# bfloat16 products a head in float32 and 16 weighted heads, in different
# orders — a few float32 roundings of sums of magnitude ~1, 1e-6 each.
# A product rounded to bfloat16 on the way would read 4e-3. The same limit
# says where two sound selections may differ: at a key whose score lies
# this close to the query's k-th largest, and nowhere else.
INDEX_SCORE_TOL = 1e-4


@jax.jit
def _plain_scores(qi, w, keys):
    return index_scores(qi.astype(jnp.float32), keys.astype(jnp.float32), w)


def _paged(rows: np.ndarray, table: np.ndarray, page: int) -> np.ndarray:
    """rows [S, ...] (position-major) -> pages [1 + pages, page, ...] with
    position ``s`` at page ``table[s // page]``, offset ``s % page`` (page 0
    is nobody's): the host-side inverse of a read through the table."""
    pages = np.zeros((table.max() + 1, page) + rows.shape[1:], rows.dtype)
    pages[table] = rows.reshape(len(table), page, *rows.shape[1:])
    return pages


def selection_parity(*, idx_heads: int, idx_head: int, topk: int, page: int,
                     contexts: tuple[int, ...], interpret: bool
                     ) -> list[dict[str, Any]]:
    """At each of ``contexts`` (a slot's tokens before the call), what the
    PROVIDER selects — ``SparseAttention.select`` as a step program calls
    it: one decode query's list, and a chunk's mask from the one Pallas
    kernel that scores and selects (``index_select``) — over seeded index
    keys in shuffled pages, against this module's ``top_positions`` of
    plain float32 scores on the same rounded inputs. Held to: exactly
    min(seen, topk) keys a query, none of them unseen (the pages past the
    context hold keys too, as a stale page would); a key on one side alone
    only where its score lies within ``INDEX_SCORE_TOL`` of the query's
    k-th largest; and — every fifth key IS its left neighbour's, so equal
    scores are there to break — never the higher position of an equal pair
    without the lower. The decode form's scores (``.scores``, what its list
    is taken from) are held to the plain ones by the same limit. One table
    size for every context, so each form compiles once; pools and tables
    are ARGUMENTS of what is jitted, never constants of it."""
    from llmapigateway_tpu.ops import sparse_attention as sa
    t = 16 if interpret else 64
    n_pages = -(-(max(contexts) + t) // page)
    s = n_pages * page
    scale = (idx_heads * idx_head) ** -0.5

    @jax.jit
    def served(qi, w, pool, table, start):
        fn = sa.SparseAttention(table, s, topk, "pallas", interpret=interpret)
        picked = fn.select(qi, w, pool, 0, start)
        return picked, (fn.scores(qi, w, pool, 0) if qi.shape[1] == 1
                        else None)

    out = []
    for n in contexts:
        rng = np.random.default_rng(n)
        table = rng.permutation(np.arange(1, n_pages + 1)).astype(np.int32)
        keys = jax.random.split(jax.random.PRNGKey(n), 3)
        dense = np.array(jax.random.normal(keys[0], (s, idx_head),
                                           jnp.bfloat16))
        dense[5::5] = dense[4:-1:5]
        pool = jnp.asarray(_paged(dense, table, page).transpose(0, 2, 1))[None]
        for kind, tt in (("decode", 1), ("prefill", t)):
            qi = jax.random.normal(keys[1], (1, tt, idx_heads, idx_head),
                                   jnp.bfloat16)
            w = jax.random.normal(keys[2], (1, tt, idx_heads),
                                  jnp.float32) * scale
            picked, scores = served(qi, w, pool, jnp.asarray(table)[None],
                                    jnp.asarray([n], jnp.int32))
            with jax.default_matmul_precision("highest"):
                plain = np.asarray(_plain_scores(qi[0], w[0],
                                                 jnp.asarray(dense)))
            seen = np.arange(s)[None, :] <= (n + np.arange(tt))[:, None]
            want = np.asarray(top_positions(jnp.asarray(plain),
                                            jnp.asarray(seen), topk))
            if kind == "decode":
                listed, total = (np.asarray(x)[0] for x in picked)
                got = np.zeros((1, s), bool)
                got[0, listed[:total]] = True
                err = float(np.abs(np.asarray(scores)[0] - plain).max())
            else:
                got, err = np.asarray(picked)[0], 0.0
            kth = np.where(want, plain, np.inf).min(-1, keepdims=True)
            apart = (got != want) & (np.abs(plain - kth) > INDEX_SCORE_TOL)
            counted = (got.sum(-1) == np.minimum(seen.sum(-1), topk)).all()
            pair = np.arange(5, s, 5)       # pair[i] and pair[i] - 1 tie
            upper_alone = got[:, pair] & ~got[:, pair - 1] & seen[:, pair]
            out.append({"kernel": f"dsa_select_{kind}", "context": n,
                        "selected": int(got.sum(-1).max()),
                        "max_abs_err": err,
                        "differ": int((got != want).sum()),
                        "apart": int(apart.sum()),
                        "ties_broken_upward": int(upper_alone.sum()),
                        "ok": bool(err <= INDEX_SCORE_TOL and counted
                                   and not (got & ~seen).any()
                                   and not apart.any()
                                   and not upper_alone.any())})
    return out


@jax.jit
def _plain_attention(q, k, v, keep):
    """q [T, H, Dh], k and v [S, KV, Dh], keep bool [T, S] -> [T, H * Dh]:
    softmax over the kept keys, float32."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(keep[None], scores, -jnp.inf), -1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(q.shape[0], -1)


def attention_parity(*, n_heads: int, n_kv_heads: int, head_dim: int,
                     idx_head: int, topk: int, page: int, context: int,
                     interpret: bool) -> list[dict[str, Any]]:
    """The provider's two forms of the attention GIVEN a selection — one
    decode query's gathered rows, a chunk's masked page walk — over seeded
    K and V laid into shuffled pages on the host, against
    ``_plain_attention`` over the same rows in position order under the
    same selection (this module's ``top_positions`` of seeded scores: a
    mask for the chunk, its positions as a list for the decode query).
    Unit-normal inputs, bfloat16 storage, float32 accumulation: held to
    ``KERNEL_TOL`` as the paged kernels are (``correctness.kernel_parity``)."""
    from benchmark.correctness import KERNEL_TOL
    from llmapigateway_tpu.ops import sparse_attention as sa
    t = 16 if interpret else 512
    n_pages = -(-(context + t) // page)
    s = n_pages * page
    table = np.random.default_rng(51).permutation(
        np.arange(1, n_pages + 1)).astype(np.int32)
    keys = jax.random.split(jax.random.PRNGKey(51), 4)
    sides = [np.asarray(jax.random.normal(k, (s, n_kv_heads, head_dim),
                                          jnp.bfloat16)) for k in keys[:2]]
    # Layer 1 of two: [L, P, KV, page, Dh], the other layer zeros.
    pool = tuple(jnp.asarray(np.stack([np.zeros_like(p), p]))
                 for p in (_paged(x, table, page).transpose(0, 2, 1, 3)
                           for x in sides)) + (
        jnp.zeros((2, n_pages + 1, idx_head, page), jnp.bfloat16),)
    start = jnp.asarray([context], jnp.int32)

    @jax.jit
    def served(q, given, pool, table):
        fn = sa.SparseAttention(table, s, topk, "pallas", interpret=interpret)
        return fn.attend(q, pool, 1, start, given).astype(jnp.float32)

    out = []
    for kind, tt in (("decode", 1), ("prefill", t)):
        q = jax.random.normal(keys[2], (1, tt, n_heads, head_dim),
                              jnp.bfloat16)
        seen = np.arange(s)[None, :] <= (context + np.arange(tt))[:, None]
        keep = top_positions(jax.random.normal(keys[3], (tt, s)),
                             jnp.asarray(seen), topk)
        given = keep[None]
        if kind == "decode":
            listed = np.flatnonzero(np.asarray(keep[0]))
            given = (jnp.asarray(np.pad(listed, (0, topk - len(listed)))[None]
                                 .astype(np.int32)),
                     jnp.asarray([len(listed)], jnp.int32))
        got = np.asarray(served(q, given, pool, jnp.asarray(table)[None]))[0]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(_plain_attention(
                q[0].astype(jnp.float32),
                *(jnp.asarray(x, jnp.float32) for x in sides), keep))
        err = float(np.max(np.abs(got - want)))
        out.append({"kernel": f"dsa_attend_{kind}", "context": context,
                    "max_abs_err": err,
                    "ok": bool(np.isfinite(got).all() and err <= KERNEL_TOL)})
    return out


# ``served_past_topk``: what is served, through the scheduler. Two LONG
# prompts inside the cell's range (4.5 times ``topk`` each: 9,216 tokens at
# the cell's geometry) are submitted together once two SHORT requests are
# decoding, so their chunks go two rows a dispatch with other slots live,
# as in the cell's window; each then decodes ``LONG_ANSWER`` tokens beside
# them. The two of a kind are as long as each other, and the short ones
# with their answers fall in the harness's sample's block of lengths: the
# reference compiles one forward a block of 256.
LONG_ANSWER = 16
PROMPT_SEED = 51


def past_topk_prompts(engine: Any, config: dict[str, Any]
                      ) -> tuple[list[int], list[int]]:
    """(the two long prompts' lengths, the two short ones'), in whole
    prefill chunks."""
    chunk, topk = engine.prefill_chunk, config["sa_config"]["topk"]
    long_n = -(-(4 * topk + topk // 2) // chunk) * chunk
    return [long_n, long_n], [2 * chunk, 2 * chunk]


def serve_past_topk(engine: Any, config: dict[str, Any]) -> dict[str, Any]:
    """Serve them through ``submit`` / ``stream`` (called from a worker
    thread: on the loop the engine serves on, or on one of this thread's
    where it is not serving yet) -> the requests, the counters' growth, how many two-row prefill dispatches ran and whether every short
    request was still decoding when the long ones' first tokens came."""
    import asyncio
    from llmapigateway_tpu.engine.engine import GenRequest
    long_n, short_n = past_topk_prompts(engine, config)
    rng = np.random.default_rng(PROMPT_SEED)
    vocab, chunk = engine.model_cfg.vocab_size, engine.prefill_chunk
    # Long enough to outlast the long prompts' prefill twice over: the
    # scheduler runs a busy decode burst between two chunk dispatches (160
    # tokens in the cell; every decode step costs what eight slots cost).
    short_answer = engine.decode_burst_busy * (2 * max(long_n) // chunk + 4)

    def request(n, answer):
        return GenRequest(prompt_ids=rng.integers(3, vocab, n).tolist(),
                          max_tokens=answer, temperature=0.0)
    short = [request(n, short_answer) for n in short_n]
    long_ = [request(n, LONG_ANSWER) for n in long_n]

    async def one(req):
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass

    async def all_of_them():
        tasks = [asyncio.ensure_future(one(r)) for r in short]
        while not all(r.t_first_token for r in short):      # both decoding
            await asyncio.sleep(0.005)
        await asyncio.gather(*map(one, long_), *tasks)

    def two_row_calls():
        return sum(row["calls"] for row in engine.kernel_table()
                   if row["kernel"] == f"prefill.b{chunk}.k2")

    async def then_stop():
        try:
            await all_of_them()
        finally:
            await engine.stop()
    before, calls = engine.stats(), two_row_calls()
    t0 = time.monotonic()
    if engine._loop_task is not None:       # serving: on the loop it is on
        asyncio.run_coroutine_threadsafe(all_of_them(),
                                         engine._loop).result(600)
    else:       # not yet: on a loop of this thread's, and stopped again —
        asyncio.run(then_stop())        # its next submit starts it anew
    after = engine.stats()
    return {"long": long_, "short": short,
            "serve_s": round(time.monotonic() - t0, 2),
            "two_row_dispatches": two_row_calls() - calls,
            "others_live": all(r.t_done > max(x.t_first_token for x in long_)
                               for r in short),
            "released": all(g["pages_free"] == g["pages"]
                            for g in after["kv_groups"]),
            **{f"keys_{k}": after[f"dsa_decode_keys_{k}_total"]
               - before[f"dsa_decode_keys_{k}_total"]
               for k in ("scored", "selected")}}


def served_past_topk(engine: Any, config: dict[str, Any],
                     change=None) -> dict[str, Any]:
    """What ``serve_past_topk`` serves, every generated position of the
    four requests held to ``logits`` as
    ``correctness.served_against_reference`` holds the harness's sample
    (the reasons stand with those limits) — under ``change`` of the
    reference's sizes, a control's. Besides: the long prompts' chunks went
    two rows a dispatch while the short requests decoded; the two decode
    counters grew by the steps' contexts (a burst may run up to its depth
    past a request's last token); every page came back. It runs in set-up,
    after ``run.warm_programs``."""
    from benchmark.correctness import LOGIT_GAP_P50_TOL, LOGIT_GAP_TOL
    got = serve_past_topk(engine, config)
    topk, chunk = config["sa_config"]["topk"], engine.prefill_chunk
    c = sizes(engine.model_cfg, config)
    c = c if change is None else change(c)
    t0 = time.monotonic()
    gaps, agree, contexts = {"long": [], "short": []}, 0, []
    for kind in gaps:
        for req in got[kind]:
            served = list(req.generated)
            seq = np.asarray(list(req.prompt_ids) + served[:-1], np.int32)
            ref = logits(engine.params, c, seq, last=len(served))
            gaps[kind] += [float(r.max() - r[tok])
                           for r, tok in zip(ref, served)]
            agree += sum(int(np.argmax(r) == tok)
                         for r, tok in zip(ref, served))
            contexts += [len(req.prompt_ids) + i
                         for i in range(1, len(served))]
    every = gaps["long"] + gaps["short"]
    # What the counters must at least hold, and the most a burst's overrun
    # adds: its depth in steps a request, each at no more than the longest
    # context.
    least = (sum(contexts), sum(min(x, topk) for x in contexts))
    over = 4 * engine.decode_burst
    long_n, _ = past_topk_prompts(engine, config)
    return {"kernel": "served_past_topk",
            "tokens": [len(r.prompt_ids) for r in got["long"] + got["short"]],
            "positions": len(every), "argmax_agree": agree,
            "two_row_dispatches": got["two_row_dispatches"],
            "others_live": got["others_live"],
            "keys_scored": got["keys_scored"],
            "keys_selected": got["keys_selected"],
            "max_abs_err": max(every), "gap_p50": float(np.median(every)),
            "gap_max_long": max(gaps["long"]),
            "gap_p50_long": float(np.median(gaps["long"])),
            "serve_s": got["serve_s"],
            "reference_s": round(time.monotonic() - t0, 2),
            "ok": bool(max(every) <= LOGIT_GAP_TOL
                       and np.median(every) <= LOGIT_GAP_P50_TOL
                       and got["two_row_dispatches"] >= min(long_n) // chunk
                       and got["others_live"] and got["released"]
                       and least[0] <= got["keys_scored"]
                       <= least[0] + over * (max(contexts) + over)
                       and least[1] <= got["keys_selected"]
                       <= least[1] + over * topk)}


# What `correct` has to refuse (tools/correct_controls.py), each read
# through the harness's sample AND through ``served_past_topk``
# (``controlled_checks``), of which `correct` is the conjunction. The engine
# computes in W8A8, BELOW bfloat16, so the nearest precision under what the
# configuration states is four-bit weights (PR 44's control). The other two
# are the selection's: every seen key attended, and a wrong set of the
# right size. The harness's sample cannot see them (its prompts are below
# ``topk``: every key is selected whatever the rule); ``served_past_topk``
# does, because the q/k head norms are drawn so that attention is PEAKED
# (models/hybrid.py ``init_params``; the file's ``assumed``): with
# unit-variance scores an attention branch is a mean over thousands of keys
# whichever of them it saw, and no comparison of logits could tell.
CONTROLS = {
    "int4_weights": lambda c: dataclasses.replace(c, precision="int4"),
    "dense_attention": lambda c: dataclasses.replace(c, select="all"),
    "lowest_scores": lambda c: dataclasses.replace(c, select="lowest"),
}


def controlled_checks(engine: Any, config: dict[str, Any], change
                      ) -> list[dict[str, Any]]:
    """The checks of ``kernel_checks`` that compare served tokens with
    ``logits``, under a control's ``change`` of the reference's sizes."""
    return [served_past_topk(engine, config, change)]


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """(a) The selection below, past and far past ``topk``. (b) The two
    forms of the attention given a selection. (c) ``served_past_topk``."""
    sa = config["sa_config"]
    page, topk = engine.kv_page, engine.model_cfg.idx_topk
    small = interpret
    out = selection_parity(
        idx_heads=sa["indexer_num_heads"], idx_head=sa["indexer_head_dim"],
        topk=topk, page=page, interpret=interpret,
        contexts=((topk // 2, 2 * topk, 5 * topk) if small
                  else (1024, 4096, 28672)))
    out += attention_parity(
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], idx_head=sa["indexer_head_dim"],
        topk=topk, page=page, context=3 * topk if small else 12288,
        interpret=interpret)
    return out + [served_past_topk(engine, config)]
