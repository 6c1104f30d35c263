"""Plain float32 forward of Phi-4-mini-flash-reasoning (``phi4flash``; the
SambaY architecture of arXiv:2507.06607): a decoder of Mamba-1 selective
scans, differential attention inside a window, ONE full-context attention
layer, and an upper half of gated memory units and cross layers that read
that one layer's K and V.

Written from the published config's keys and the architecture's equations as
recalled (source and every assumption in ``configs/phi4-mini-flash-3.8b.json``).
The residual stream x [T, D], ALL 32 layers over ALL T positions — no cache,
no pages, no state carried, nothing stopped half-way up::

    x <- x + mixer_l(LN(x));  x <- x + MLP_l(LN(x));   LN with weight and bias
    MLP(u) = (v * SiLU(g)) W_2,   [g | v] = u W_1

    l in {0, 2, .., 16}   [x | z] = u W_in;  c_t = SiLU(sum_j w_j x_{t-3+j} + b_c)
                          [d_t | B_t | C_t] = c_t W_x;  D_t = softplus(d_t W_D + b_D)
                          h_t = exp(D_t A) h_{t-1} + (D_t c_t) (x) B_t,  A = -exp(A_log)
                          m_t = (h_t C_t + D_skip c_t) SiLU(z_t);   out m_t W_out
                          (l = 16: M = m, the memory)
    l in {1, 3, .., 15}   differential attention, keys t - s < 512;  l = 17: all keys
        [q | k | v] = u W_qkv + b;  query heads (2i, 2i+1) read K/V heads (2j, 2j+1),
        j = i // 2;  a1 = softmax(q_2i K_2j^T / 8) [V_2j | V_2j+1],
        a2 = softmax(q_2i+1 K_2j+1^T / 8) [V_2j | V_2j+1];
        lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init(l),  lam_init = 0.8 - 0.6 e^(-0.3 l)
        o_i = RMSNorm_128(a1 - lam a2) (1 - lam_init);   out concat(o_i) W_o + b_o
    l in {18, 20, .., 30} (SiLU(u G_1) * M_t) G_2
    l in {19, 21, .., 31} q = u W_q + b_q;  K, V are layer 17's;  as above, all keys

then a final LayerNorm and the head ``x E^T`` on the tied embedding (under
quant the int8 copy of its rows, which is what the program's head reads).

It shares no code with ``llmapigateway_tpu/models``: only the LAYOUT of the
weight tree is the program's (``self``, ``mid``, ``cross``; the published
head order — the program's fold of K/V pairs into heads of 128 is a reshape
of the same columns), dequantised a layer at a time; the MLP, the head and
the attention run in blocks so that the whole fits beside the engine.
Everything under ``jax.default_matmul_precision("highest")``.

``kernel_checks`` adds what the harness's own sample (two chunks) cannot
reach: the paged kernels at the served fold over the WHOLE context (the
harness's own parity runs the windowed fold); the program's chunk form of
the selective scan, chunk after chunk with the state carried, against this
module's token-by-token recurrence on the same inputs; and one request of
more than 8,192 tokens served on an idle engine and held to ``logits``
(``served_past_window``), so that the ring past its recycling, the global
group, a long scan and the one-row upper half are all in `correct`.

``cross_decode_cost`` and ``ssm_scan_cost`` give what a decode step's cross
reads and a chunk's scan have to move, from shapes alone.
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

BLOCK = 256                 # MLP rows a block; a sequence's pad unit
QUERY_BLOCK = 64            # queries attended at a time ([heads, 64, T])
HEAD_ROWS = 16672           # vocabulary rows a block of the head (12 blocks)


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    heads: int              # published query heads (40)
    kv_heads: int           # published K/V heads (20)
    head: int               # their width (64)
    window: int
    eps: float
    state: int              # N
    dt_rank: int            # R
    chunk: int              # the engine's prefill chunk (a control reads it)
    # What ``CONTROLS`` change.
    windowed: bool = True           # False: the lower layers see every key
    cross_lambda: bool = True       # False: lam = 0 in the cross layers
    memory: str = "gated"           # "ungated": m taken BEFORE the z gate
    state_dtype: str = "float32"    # "bfloat16": h rounded every token
    cross_sees: str = "all"         # "chunk": keys of the query's chunk only
    precision: str = "float32"      # "int4": int8 weights' low bits gone


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE (the published widths and
    the sizes it states under ``mamba_*``) but the depth, which is the
    program's config's, from the same file."""
    if (not config["tie_word_embeddings"] or config["mlp_bias"]
            or config["lm_head_bias"] or config["mb_per_layer"] != 2
            or config["hidden_act"] != "silu"
            or config["mamba_expand"] != 2 or config["mamba_d_conv"] != 4):
        raise ValueError("the reference computes a tied head without bias, "
                         "SiLU MLPs without bias, one state layer in two, "
                         "expansion 2 and four conv taps alone")
    return Sizes(
        layers=model_cfg.n_layers, heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        head=config["hidden_size"] // config["num_attention_heads"],
        window=config["sliding_window"], eps=float(config["layer_norm_eps"]),
        state=config["mamba_d_state"], dt_rank=config["mamba_dt_rank"],
        chunk=int(config["engine"]["prefill_chunk"]))


def weight(w: Any, c: Sizes, rows: bool = False) -> jax.Array:
    """A leaf of the engine's tree as float32 (int8 ``{"q", "s"}``: one
    scale per output channel, the contraction axis second to last; the
    head ``[V, D]``, ``rows``: one scale per row) — under ``precision``
    "int4" with the int8 values' four low bits gone."""
    if not isinstance(w, dict):
        return jnp.asarray(w, jnp.float32)
    q, s = w["q"].astype(jnp.float32), w["s"].astype(jnp.float32)
    if c.precision == "int4":
        q = jnp.round(q / 16.0) * 16.0
    return q * (s[..., None] if rows else s[..., None, :])


def _is_q(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w


def _f32(lp: Any, c: Sizes) -> Any:
    return jax.tree.map(lambda w: weight(w, c), lp, is_leaf=_is_q)


def _ln(x, w, b, eps):
    centred = x - jnp.mean(x, -1, keepdims=True)
    return w * centred / jnp.sqrt(
        jnp.mean(jnp.square(centred), -1, keepdims=True) + eps) + b


def lambda_init(layer):
    """``layer``: the layer's index, a number or a traced scalar (one
    compiled layer serves every depth)."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))


def _blocks(fn, x):
    """``fn`` on ``BLOCK`` rows of x [T, ...] at a time (T a multiple)."""
    out = jax.lax.map(fn, x.reshape(-1, BLOCK, *x.shape[1:]))
    return out.reshape(-1, *out.shape[2:])


def mlp(x, mp, c: Sizes):
    """x + MLP(LN(x)); ``w1`` is [D, 2F], the gate's columns first."""
    def rows(u):
        g, v = jnp.split(_ln(u, mp["norm_w"], mp["norm_b"], c.eps)
                         @ mp["w1"], 2, axis=-1)
        return (v * jax.nn.silu(g)) @ mp["w2"]
    return x + _blocks(rows, x)


def recurrence(xc, delta, bm, cm, a, c: Sizes, h0=None):
    """The selective scan, token by token. xc, delta [T, E], bm, cm [T, N],
    a [N, E] (the tree's layout of ``A_log``: state number major) -> (the
    state's part of y [T, E], h_T [N, E]). ``state_dtype`` "bfloat16": h is
    rounded after every token, as a bfloat16 state block would hold it."""
    def step(h, t):
        x_t, d_t, b_t, c_t = t
        h = jnp.exp(d_t[None, :] * a) * h + (d_t * x_t)[None, :] * b_t[:, None]
        if c.state_dtype == "bfloat16":
            # (Not a cast and back: the chip's compiler may keep the excess
            # precision of such a pair; this it may not.)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        return h, jnp.sum(h * c_t[:, None], axis=0)
    h0 = jnp.zeros(a.shape, jnp.float32) if h0 is None else h0
    h, y = jax.lax.scan(step, h0, (xc, delta, bm, cm))
    return y, h


def mamba(u, lp, c: Sizes):
    """u [T, D] (normalised) -> (the mixer's output [T, D], the memory m
    [T, E]: gated — or, the control, before the z gate)."""
    n, r = c.state, c.dt_rank
    x, z = jnp.split(u @ lp["w_in"], 2, axis=-1)
    taps = lp["conv_w"].shape[0]
    ext = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    xc = jax.nn.silu(sum(lp["conv_w"][j] * ext[j:j + x.shape[0]]
                         for j in range(taps)) + lp["conv_b"])
    dbc = xc @ lp["w_x"]
    delta = jax.nn.softplus(dbc[:, :r] @ lp["w_dt"] + lp["dt_bias"])
    y, _ = recurrence(xc, delta, dbc[:, r:r + n], dbc[:, r + n:],
                      -jnp.exp(lp["a_log"]), c)
    y = y + lp["d_skip"] * xc
    m = y * jax.nn.silu(z)
    return m @ lp["w_out"], (y if c.memory == "ungated" else m)


def diff_attention(q, k, v, lp, c: Sizes, layer, window, cross: bool):
    """q [T, H, d], k and v [T, KV, d] (published heads) -> [T, H/2 * 2d]
    before ``W_o``: ``QUERY_BLOCK`` queries at a time over all T keys.
    ``layer`` and ``window`` (0: every key) may be traced scalars."""
    t = q.shape[0]
    pair_v = v.reshape(t, c.kv_heads // 2, 2 * c.head)      # [V_2j | V_2j+1]
    # Query head h reads K head 2 (h // 4) + h % 2 and V pair h // 4.
    k_of = jnp.asarray([2 * (h // 4) + h % 2 for h in range(c.heads)])
    keys, vals = k[:, k_of], pair_v[:, jnp.arange(c.heads) // 4]
    pos = jnp.arange(t)

    def block(args):
        q_b, i0 = args
        at = i0 + jnp.arange(QUERY_BLOCK)
        seen = pos[None, :] <= at[:, None]
        seen &= (window == 0) | (at[:, None] - pos[None, :] < window)
        if cross and c.cross_sees == "chunk":
            seen &= pos[None, :] >= (at[:, None] // c.chunk) * c.chunk
        scores = jnp.einsum("qhd,khd->hqk", q_b, keys) / np.sqrt(c.head)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", probs, vals)
    a = jax.lax.map(block, (q.reshape(-1, QUERY_BLOCK, c.heads, c.head),
                            jnp.arange(t // QUERY_BLOCK) * QUERY_BLOCK))
    a = a.reshape(t, c.heads // 2, 2, 2 * c.head)
    lam_init = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"]))
           - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + lam_init)
    if cross and not c.cross_lambda:
        lam = jnp.float32(0.0)
    o = a[:, :, 0] - lam * a[:, :, 1]
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + c.eps) \
        * lp["sub_norm"] * (1.0 - lam_init)
    return o.reshape(t, -1)


@functools.partial(jax.jit, static_argnums=(2,))
def _scan_layer(x, lp, c: Sizes):
    lp = _f32(lp, c)
    out, m = mamba(_ln(x, lp["norm_w"], lp["norm_b"], c.eps), lp, c)
    return mlp(x + out, lp["mlp"], c), m


@functools.partial(jax.jit, static_argnums=(2,))
def _attn_layer(x, lp, c: Sizes, layer, window):
    """A layer with K/V of its own -> (x, its K, its V). ``layer`` and
    ``window`` are traced: ONE compiled program serves the eight windowed
    layers and the full one."""
    lp = _f32(lp, c)
    t = x.shape[0]
    u = _ln(x, lp["norm_w"], lp["norm_b"], c.eps) @ lp["wqkv"] + lp["bqkv"]
    nq, nk = c.heads * c.head, c.kv_heads * c.head
    q = u[:, :nq].reshape(t, c.heads, c.head)
    k = u[:, nq:nq + nk].reshape(t, c.kv_heads, c.head)
    v = u[:, nq + nk:].reshape(t, c.kv_heads, c.head)
    o = diff_attention(q, k, v, lp, c, layer, window, False)
    return mlp(x + o @ lp["wo"] + lp["bo"], lp["mlp"], c), k, v


@functools.partial(jax.jit, static_argnums=(3,))
def _gmu_layer(x, lp, memory, c: Sizes):
    lp = _f32(lp, c)
    u = _ln(x, lp["norm_w"], lp["norm_b"], c.eps)
    return mlp(x + (jax.nn.silu(u @ lp["g1"]) * memory) @ lp["g2"],
               lp["mlp"], c)


@functools.partial(jax.jit, static_argnums=(4,))
def _cross_layer(x, lp, k, v, c: Sizes, layer):
    lp = _f32(lp, c)
    t = x.shape[0]
    q = (_ln(x, lp["norm_w"], lp["norm_b"], c.eps) @ lp["wq"]
         + lp["bq"]).reshape(t, c.heads, c.head)
    o = diff_attention(q, k, v, lp, c, layer, 0, True)
    return mlp(x + o @ lp["wo"] + lp["bo"], lp["mlp"], c)


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(4,))
def _head_rows(x, w, b, rows, c: Sizes):
    """Normed x [last, D] against a block of the head's rows."""
    return _ln(x, weight(w, c), weight(b, c), c.eps) @ weight(
        rows, c, rows=True).T


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree. ``seq`` is padded to whole
    ``BLOCK``s (what follows a position changes nothing before it), so
    sequences of like length share the compiled layers."""
    t = len(seq)
    seq = np.pad(np.asarray(seq, np.int32), (0, -t % BLOCK))
    pairs = c.layers // 4
    at = lambda tree, i: jax.tree.map(lambda a: a[i], tree)
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq))
        for i in range(pairs):
            x, _ = _scan_layer(x, at(params["self"]["ssm"], i), c)
            x, _, _ = _attn_layer(x, at(params["self"]["attn"], i), c,
                                  jnp.int32(2 * i + 1), jnp.int32(
                                      c.window if c.windowed else 0))
        x, memory = _scan_layer(x, params["mid"]["ssm"], c)
        x, k, v = _attn_layer(x, params["mid"]["attn"], c,
                              jnp.int32(2 * pairs + 1), jnp.int32(0))
        for i in range(pairs - 1):
            x = _gmu_layer(x, at(params["cross"]["gmu"], i), memory, c)
            x = _cross_layer(x, at(params["cross"]["attn"], i), k, v, c,
                             jnp.int32(2 * pairs + 3 + 2 * i))
        head = params.get("lm_head_q8", params["embed"])
        n = (head["q"] if _is_q(head) else head).shape[0]
        out = [np.asarray(_head_rows(
            x[t - last:t], params["final_norm_w"], params["final_norm_b"],
            jax.tree.map(lambda a: a[lo:lo + HEAD_ROWS], head), c))
            for lo in range(0, n, HEAD_ROWS)]
        return np.concatenate(out, axis=-1).astype(np.float32)


# ---------------------------------------------------------------------------
# What the new mechanisms have to move, from shapes
# ---------------------------------------------------------------------------

def cross_decode_cost(contexts: list[int], readers: int, kv_heads: int,
                      head_dim: int, itemsize: int = 2) -> tuple[int, float]:
    """(keys read, bytes) of a decode step's reads of the ONE full-context
    K/V: every slot's ``context`` keys (the new token's own among them),
    once a READING layer — the full layer and the cross layers, ``readers``
    in all — at ``2 * kv_heads * head_dim`` numbers a key. The same bytes
    whatever implements the read (the served kernels read them once a
    layer; no layer can share another's read, for each reads with queries
    that depend on the layer below)."""
    keys = sum(contexts) * readers
    return keys, float(keys * 2 * kv_heads * head_dim * itemsize)


def ssm_scan_cost(tokens: int, layers: int, channels: int, state: int
                  ) -> tuple[float, float]:
    """(operations, bytes) of the recurrence alone over ``tokens`` tokens of
    ``layers`` scan layers: a token and channel-state pair costs the decay's
    product and exponential, the input's two products, the update's multiply
    and add and the read-out's multiply and add (8 operations); what MUST
    move is each token's inputs and output (x, D, y: 3 float32 a channel; B,
    C: 2 a state number) — the state itself can stay in fast memory."""
    ops = 8.0 * tokens * layers * channels * state
    return ops, 4.0 * tokens * layers * (3 * channels + 2 * state)


# ---------------------------------------------------------------------------
# kernel_checks: what the harness's own sample cannot reach
# ---------------------------------------------------------------------------

# The program's chunk form against this module's recurrence on the SAME
# float32 inputs: both multiply and add the same float32 numbers, a token at
# a time, and differ by how the compiler fuses a step (an fma here and
# there): 1e-6 of the state's magnitude a token, which a decaying state
# forgets. Measured 2e-6 of the largest output (CPU, 1,536 tokens) and on
# the chip as PERF.md section 6 says; a state block held in bfloat16 reads
# 2e-3 and more (the ``bf16_state`` control). Relative to the largest
# |y| of the case.
SCAN_TOL = 1e-4
LONG_ANSWER = 64
# ``served_past_window``'s limit of its own, beside the harness's two: the
# 90th percentile of its 64 positions' gaps. A sound run's served token IS
# the reference's maximum at 60 of 64 positions and one gap passes 0.05
# (p90 0.0; a bfloat16 state: 0.0004); under each planted fault of the
# architecture 19-34 positions pass it and p90 reads 0.19 (``lam`` = 0 in
# the cross layers) to 0.64 (no window) — my chip runs, PR 54. The maximum
# alone stands 0.34-1.9 against 0.25 there: a maximum of 64 draws moves with
# the seed, a percentile hardly.
LONG_GAP_P90_TOL = 0.05
PROMPT_SEED = 54


def scan_parity(*, channels: int, state: int, chunk: int, chunks: int,
                change=None) -> dict[str, Any]:
    """``sambay.selective_scan`` — the chunk form every prefill program
    runs — over ``chunks`` chunks of ``chunk`` tokens with the state
    carried from call to call, against ``recurrence`` over the whole
    length: Mamba-convention sizes (``A = -(1..N)``, steps log-uniform
    in 1e-3..0.1), unit-normal inputs. Under ``change`` (a control) the
    recurrence is the control's."""
    from llmapigateway_tpu.models import sambay
    c = Sizes(layers=0, heads=0, kv_heads=0, head=0, window=0, eps=0.0,
              state=state, dt_rank=0, chunk=chunk)
    c = c if change is None else change(c)
    t = chunk * chunks
    ks = jax.random.split(jax.random.PRNGKey(PROMPT_SEED), 4)
    x = jax.random.normal(ks[0], (1, t, channels), jnp.float32)
    delta = jnp.exp(jax.random.uniform(ks[1], (1, t, channels), jnp.float32,
                                       math.log(1e-3), math.log(0.1)))
    bm, cm = (jax.random.normal(k, (1, t, state), jnp.float32)
              for k in ks[2:])
    a = -jnp.broadcast_to(jnp.arange(1, state + 1, dtype=jnp.float32)[:, None],
                          (state, channels))
    served = jax.jit(sambay.selective_scan)
    h = jnp.zeros((1, state, channels), jnp.float32)
    got = []
    for i in range(chunks):
        cut = slice(i * chunk, (i + 1) * chunk)
        y, h = served(x[:, cut], delta[:, cut], bm[:, cut], cm[:, cut], a, h)
        got.append(np.asarray(y[0]))
    got = np.concatenate(got)
    with jax.default_matmul_precision("highest"):
        want, h_want = jax.jit(functools.partial(recurrence, c=c))(
            x[0], delta[0], bm[0], cm[0], a)
    want = np.asarray(want)
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / scale
    err_h = float(np.abs(np.asarray(h[0]) - np.asarray(h_want)).max()) / scale
    return {"kernel": "ssm_scan_chunked", "tokens": t, "channels": channels,
            "max_abs_err": max(err, err_h), "state_err": err_h,
            "tolerance": SCAN_TOL,
            "ok": bool(np.isfinite(got).all() and max(err, err_h) <= SCAN_TOL)}


def long_prompt_tokens(engine: Any) -> int:
    """More than 8,192 tokens (and, at a small geometry, what fits), in
    whole chunks: past the ring's recycling whatever the window."""
    chunk = engine.prefill_chunk
    most = (engine.S - LONG_ANSWER - 1) // chunk * chunk
    return min(8192 + chunk, most)


def serve_long(engine: Any) -> dict[str, Any]:
    """One request of ``long_prompt_tokens`` served through ``submit`` /
    ``stream`` on an idle engine (from a worker thread: on the loop the
    engine serves on, or on one of this thread's where it is not serving
    yet) -> the request and the counters' growth."""
    import asyncio
    from llmapigateway_tpu.engine.engine import GenRequest
    rng = np.random.default_rng(PROMPT_SEED)
    n = long_prompt_tokens(engine)
    req = GenRequest(
        prompt_ids=rng.integers(3, engine.model_cfg.vocab_size, n).tolist(),
        max_tokens=LONG_ANSWER, temperature=0.0)

    async def one():
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass

    async def then_stop():
        try:
            await one()
        finally:
            await engine.stop()
    before = engine.stats()
    t0 = time.monotonic()
    if engine._loop_task is not None:       # serving: on the loop it is on
        asyncio.run_coroutine_threadsafe(one(), engine._loop).result(600)
    else:       # not yet: on a loop of this thread's, and stopped again
        asyncio.run(then_stop())
    after = engine.stats()
    grew = {k: after[k] - before[k] for k in (
        "prefill_rows_stopped_total", "cross_decode_keys_read_total",
        "lin_decode_state_updates_total", "kv_ring_recycled_total")}
    return {"request": req, "serve_s": round(time.monotonic() - t0, 2),
            "released": all(g["pages_free"] == g["pages"]
                            for g in after["kv_groups"]), **grew}


def served_past_window(engine: Any, config: dict[str, Any],
                       change=None) -> dict[str, Any]:
    """What ``serve_long`` serves, every generated position held to
    ``logits`` as ``correctness.served_against_reference`` holds the
    harness's sample (the reasons stand with those limits), and their 90th
    percentile to ``LONG_GAP_P90_TOL`` — under
    ``change`` of the reference's sizes, a control's. Besides: every prompt
    row but the last stopped at the full layer's K/V; the ring recycled
    (the prompt is past it); the cross reads grew by the steps' contexts
    times the reading layers, the state updates by steps times scan layers
    (a burst may run up to its depth past the last token); every page came
    back; and the engine's state block is held in the precision the
    reference's ``state_dtype`` states (float32: a bfloat16 block moves a
    served logit by 0.004 — CPU, a quarter of the widths — where W8A8 moves
    it by 0.05, so no comparison of tokens can see one; ``scan_parity`` holds
    the arithmetic, this the block). It runs in set-up, after
    ``run.warm_programs``."""
    from benchmark.correctness import LOGIT_GAP_P50_TOL, LOGIT_GAP_TOL
    got = serve_long(engine)
    req = got.pop("request")
    c = sizes(engine.model_cfg, config)
    c = c if change is None else change(c)
    t0 = time.monotonic()
    served = list(req.generated)
    n = len(req.prompt_ids)
    seq = np.asarray(list(req.prompt_ids) + served[:-1], np.int32)
    ref = logits(engine.params, c, seq, last=len(served))
    gaps = np.asarray([float(r.max() - r[tok])
                       for r, tok in zip(ref, served)])
    agree = sum(int(np.argmax(r) == tok) for r, tok in zip(ref, served))
    readers = c.layers // 4                 # the full layer + the cross ones
    scans = c.layers // 4 + 1
    steps = len(served) - 1
    keys = sum(n + i for i in range(1, steps + 1)) * readers
    over = 2 * engine.decode_burst
    ring = min(g["pages_per_slot"] for g in engine.stats()["kv_groups"]
               if g["window"])
    block = str(engine.cache.state[0].dtype)
    return {"kernel": "served_past_window", "tokens": n,
            "positions": len(served), "argmax_agree": agree,
            "state_dtype": block,
            "max_abs_err": float(gaps.max()),
            "gap_p50": float(np.median(gaps)),
            "gap_p90": float(np.quantile(gaps, 0.9)),
            "gap_over": int((gaps > LOGIT_GAP_P50_TOL).sum()),
            "reference_s": round(time.monotonic() - t0, 2), **got,
            "tolerance_p90": LONG_GAP_P90_TOL,
            "ok": bool(gaps.max() <= LOGIT_GAP_TOL
                       and np.median(gaps) <= LOGIT_GAP_P50_TOL
                       and np.quantile(gaps, 0.9) <= LONG_GAP_P90_TOL
                       and got["released"] and block == c.state_dtype
                       and got["prefill_rows_stopped_total"] == n - 1
                       and (got["kv_ring_recycled_total"] > 0
                            or n <= ring * engine.kv_page)
                       and keys <= got["cross_decode_keys_read_total"]
                       <= keys + over * readers * (n + steps + over)
                       and steps * scans
                       <= got["lin_decode_state_updates_total"]
                       <= (steps + over) * scans)}


def cross_read_parity(engine: Any, interpret: bool) -> dict[str, Any]:
    """ONE cross layer's attention as a decode step computes it — the
    program's ``fold_queries``, the provider's decode form over K and V laid
    into shuffled pages on the host (three and a half chunks before the
    query), the program's ``diff_combine`` — against this module's
    ``diff_attention`` on the same rounded inputs in the PUBLISHED form
    (paired heads of half the width, every key seen). Peaked scores (the
    query drawn at 3), so that WHICH keys are read shows; bfloat16 storage,
    float32 accumulation: held to ``KERNEL_TOL``. A per-kernel extra, as
    ``fold_parity``: it says WHERE a fault lies; what refuses a cross layer
    that reads the wrong keys or under the wrong ``lam`` is the served
    tokens (``CONTROLS``)."""
    from benchmark.correctness import KERNEL_TOL
    from llmapigateway_tpu.models import sambay
    from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn
    m, page, chunk = engine.model_cfg, engine.kv_page, engine.prefill_chunk
    heads, kv, d = m.n_heads, 2 * m.n_kv_heads, m.head_dim // 2
    layer = m.n_layers // 2 + 3                     # the first cross layer
    c = Sizes(layers=m.n_layers, heads=heads, kv_heads=kv, head=d, window=0,
              eps=m.layer_norm_eps, state=0, dt_rank=0, chunk=chunk)
    n = 3 * chunk + chunk // 2                      # keys before the query
    unit = max(page, QUERY_BLOCK)
    t = -(-(n + 1) // unit) * unit
    ks = jax.random.split(jax.random.PRNGKey(PROMPT_SEED + 1), 5)
    q = (3.0 * jax.random.normal(ks[0], (heads, d))).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (t, kv, d), jnp.bfloat16)
            for key in ks[1:3])
    lp = {name: 0.2 * jax.random.normal(key, (d,), jnp.float32)
          for name, key in zip(("lq1", "lk1", "lq2", "lk2"),
                               jax.random.split(ks[3], 4))}
    lp["sub_norm"] = 1.0 + 0.1 * jax.random.normal(ks[4], (2 * d,))
    n_pages = t // page
    table = np.random.default_rng(PROMPT_SEED).permutation(
        np.arange(1, n_pages + 1)).astype(np.int32)

    def paged(rows):        # [t, kv, d] -> [1 + pages, kv / 2, page, 2 d]
        rows = np.asarray(rows).reshape(n_pages, page, kv // 2, 2 * d)
        pages = np.zeros((n_pages + 1, *rows.shape[1:]), rows.dtype)
        pages[table] = rows
        return jnp.asarray(pages.transpose(0, 2, 1, 3))

    @jax.jit
    def served(q, k_own, v_own, pool_k, pool_v, table, lp):
        fn = make_paged_attention_fn(table, t, impl="pallas", window=0,
                                     interpret=interpret)
        attn = fn.decode(sambay.fold_queries(q[None, None], m),
                         k_own.reshape(1, 1, kv // 2, 2 * d),
                         v_own.reshape(1, 1, kv // 2, 2 * d), pool_k, pool_v,
                         jnp.asarray([n], jnp.int32), None)
        return sambay.diff_combine(attn, lp, m, lambda_init(layer))[0, 0]
    got = np.asarray(served(q, k[n], v[n], paged(k), paged(v),
                            jnp.asarray(table)[None], lp), np.float32)
    with jax.default_matmul_precision("highest"):
        qs = jnp.zeros((t, heads, d), jnp.float32).at[n].set(
            q.astype(jnp.float32))
        want = np.asarray(jax.jit(
            lambda qs, k, v, lp: diff_attention(qs, k, v, lp, c, layer, 0,
                                                True))(
            qs, k.astype(jnp.float32), v.astype(jnp.float32), lp)[n])
    err = float(np.abs(got - want).max())
    return {"kernel": "cross_read_decode", "context": n, "max_abs_err": err,
            "ok": bool(np.isfinite(got).all() and err <= KERNEL_TOL)}


def fold_parity(engine: Any, interpret: bool) -> list[dict[str, Any]]:
    """The paged kernels at the served fold over the WHOLE context (the
    full layer and its readers): the harness's own ``kernel_parity`` is
    handed the engine's one window and runs the ring's fold."""
    from benchmark.correctness import kernel_parity
    m = engine.model_cfg
    cases = kernel_parity(
        n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, head_dim=m.head_dim,
        page=engine.kv_page, window=0, kv_quant=engine.kv_quant,
        interpret=interpret,
        **({"pages_per_slot": 8, "t": 16} if interpret else {}))
    return [{**case, "kernel": case["kernel"] + "_full"} for case in cases]


# What `correct` has to refuse (tools/correct_controls.py), each read
# through the harness's sample AND through ``controlled_checks``, of which
# `correct` is the conjunction. The engine computes in W8A8, BELOW bfloat16,
# so the nearest precision under what the configuration states is four-bit
# weights (PR 44's control). The others are this architecture's: the window
# left off the eight ring layers; the memory taken before the z gate;
# ``lam`` = 0 in the cross layers; a cross layer that attends the keys of
# its query's own chunk only — all of which the SERVED TOKENS refuse, on the
# harness's sample (two chunks: 1,024 tokens, past the window and past a
# chunk) and on ``served_past_window`` (sixteen chunks past both), against
# the 0.25 limit of both (my chip runs, PR 54, three sample seeds | the long
# request's maximum, its 90th percentile against 0.05): the window left off
# 0.58-0.82 | 0.85, 0.64; ``lam`` = 0 0.34-0.50 | 0.51, 0.19; the memory
# ungated 1.54-1.88 | 1.05, 0.40; the own chunk 0.64-0.86 | 0.78, 0.36;
# four-bit weights 0.78-0.99 | 1.01, 0.45; the SOUND reference 0.04-0.08 |
# 0.09, 0.0 (the draw that makes them show: ``sambay.init_params``) — and a
# state block held in
# bfloat16, which no served token can show (``served_past_window`` says
# why): the engine's block is held to the reference's ``state_dtype`` there
# and the chunk form's arithmetic to the rounded recurrence in
# ``scan_parity``.
CONTROLS = {
    "int4_weights": lambda c: dataclasses.replace(c, precision="int4"),
    "no_window": lambda c: dataclasses.replace(c, windowed=False),
    "cross_lambda_0": lambda c: dataclasses.replace(c, cross_lambda=False),
    "memory_before_gate": lambda c: dataclasses.replace(c, memory="ungated"),
    "bf16_state": lambda c: dataclasses.replace(c, state_dtype="bfloat16"),
    "cross_own_chunk": lambda c: dataclasses.replace(c, cross_sees="chunk"),
}


def _scan_case(engine: Any, interpret: bool, change=None) -> dict[str, Any]:
    m = engine.model_cfg
    return scan_parity(channels=m.ssm_inner, state=m.ssm_state,
                       chunk=engine.prefill_chunk,
                       chunks=2 if interpret else 17, change=change)


def controlled_checks(engine: Any, config: dict[str, Any], change
                      ) -> list[dict[str, Any]]:
    """The checks of ``kernel_checks`` that hold the program to this
    module's mathematics, under a control's ``change`` of its sizes."""
    interpret = jax.default_backend() != "tpu"
    return [_scan_case(engine, interpret, change),
            served_past_window(engine, config, change)]


def _peak() -> int | None:
    """The device's peak bytes so far (None where the backend keeps none):
    beside each case, so that a line says which step raised it."""
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """(a) The paged kernels at the served fold, whole context, and one
    cross layer's read through fold, kernel and combine. (b) The chunk form
    of the scan, state carried over 17 chunks. (c) ``served_past_window``.
    Each case with the device's peak after it."""
    before = _peak()
    out = [{**case, "peak_bytes": _peak()}
           for case in fold_parity(engine, interpret)]
    out.append({**cross_read_parity(engine, interpret),
                "peak_bytes": _peak()})
    out.append({**_scan_case(engine, interpret), "peak_bytes": _peak()})
    out.append({**served_past_window(engine, config), "peak_bytes": _peak()})
    out[0]["peak_bytes_before"] = before
    return out
