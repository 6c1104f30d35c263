"""Plain float32 forward of the GigaChat 3.5 decoder (``gigachat3_5``):
``first_k_dense_replace`` leading layers of a gated-delta-net mixer and a
dense SwiGLU MLP, then periods of one LATENT attention layer and three
gated-delta-net layers, each followed by a sparse expert MLP of which this
chip holds a share; every sub-block normed before AND after.

Written from the published config's keys (source in
``configs/gigachat35-432b-ep8.json``; what the keys leave open is listed
under ``assumed`` there, each with the reading not taken). As computed
below, residual stream x [T, D]::

    N(x; w)  = x / sqrt(mean(x^2) + eps) * (g sigmoid(w)), g =
               layernorm_gating_weight = 2: gain 1 at w = 0
    block    : x <- x + N(Mixer(N(x)));  x <- x + N(MLP(N(x)))

    linear mixer on h = N(x):
      [q | k | v] = SiLU(conv([h W_q | h W_k | h W_v])), a causal depthwise
          convolution of ``linear_conv_kernel_dim`` taps, no bias
      q, k L2-normalised per head, q x dk^-1/2; key head j serves value
          heads j r .. (j + 1) r - 1, r = value heads / key heads
      b_t = sigmoid(h W_b); a_t = exp(-exp(A) softplus(h W_a + dt)), ONE
          number a value head
      S_t = (I - b_t k_t k_t^T) a_t S_{t-1} + b_t k_t v_t^T; o_t = S_t^T q_t
      out = (rmsnorm_head(o; w_o) * s sigmoid(h W_z)) W_o, s =
          linear_sigmoid_gate_scale = 2
    latent mixer on h: mistral4.py's equations at this file's sizes (a
      normed query bottleneck, a normed key/value latent, one rotary key
      for all heads, YaRN by parts, pairs (2i, 2i+1)), both bottleneck
      norms N; sigma = (d_nope + d_rope)^-1/2 (0.1 ln(factor) + 1)^2;
      out = (attn * sigmoid(h W_g)) W_o
    experts on m = N(x): s = sigmoid(m W_r) over ALL experts; S = top-k of
      s + e; w_j = s_j / sum_{S} s * routed_scaling_factor; the held
      experts of S and one shared expert, each
      E(m) = (SiLU(min(m W_g, L)) * clip(m W_u, -L, L)) W_d, L = swiglu_limit

then a final N and the untied head over the vocabulary rows held here.
Experts ``[first, first + held)`` live on this chip; what the absent ones
would add is left out BEFORE the post norm, as in the program.

It shares no code with ``llmapigateway_tpu/models``: only the LAYOUT of the
weight tree is the program's (``lead`` stacked over the leading layers,
``layers/attn`` and the tuple ``layers/lin`` stacked over periods, each with
its ``mlp`` sub-tree), dequantised a matrix block and an expert at a time
so that it fits beside the engine. No cache, no chunks: the linear layers
run their recurrence token by token from a zero state. Everything under
``jax.default_matmul_precision("highest")``.

``CONTROLS``: changes of the sizes that ``correct`` has to refuse
(``tools/correct_controls.py``); ``READINGS``: what it cannot, and why.
``kernel_checks``: the latent pool's write
and attention kernels at this file's widths (576 x 64 heads), and the
program's chunked and one-token forms of the delta rule at ONE decay a head
and two value heads a key head against ``delta_rule`` here.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256           # queries attended at a time ([heads, 256, T])
COLUMN_BLOCK = 4096         # columns of a matrix dequantised at a time
HEAD_BLOCK = 16             # heads of the latent kernels' plain side at a time


@dataclasses.dataclass(frozen=True)
class Sizes:
    lead: int               # leading layers: linear mixer + dense MLP
    periods: int            # of one latent and ``period - 1`` linear layers
    period: int
    heads: int              # latent attention
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
    lin_kheads: int         # linear layers: key heads, value heads, size
    lin_vheads: int
    lin_head: int
    taps: int
    experts: int            # the router's width (published)
    top: int
    first: int              # experts [first, first + held) live here
    held: int
    routed_scale: float
    limit: float            # swiglu_limit
    norm_gate: float        # layernorm_gating_weight
    out_gate: float         # linear_sigmoid_gate_scale
    # What CONTROLS change; as the file states them:
    state_dtype: str = "float32"
    post_norm: bool = True
    beta_scale: float = 1.0
    clamp: bool = True


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE — the published widths, the
    experts held here (``n_routed_experts``, the published count under
    ``reduced``) and the first of them (``first_expert_held``, absent: 0) —
    but the depth, which the harness cut in the program's config."""
    rp = config["rope_scaling"]
    if (rp["type"] != "yarn" or config.get("n_group", 1) != 1
            or config["layernorm_type"] != "pre_post"
            or config["norm_type"] != "ZeroCenteredGatedNorm"
            or config["n_shared_experts"] != 1):
        raise ValueError("the reference computes YaRN rotary, one expert "
                         "group, one shared expert and pre_post gated "
                         "norms alone")
    period = config["layer_kinds"]["period"]
    lead = config["first_k_dense_replace"]
    published = config.get("reduced", {}).get("n_routed_experts", {}).get(
        "published", config["n_routed_experts"])
    return Sizes(
        lead=lead, periods=(model_cfg.n_layers - lead) // period,
        period=period, heads=config["num_attention_heads"],
        kv_rank=config["kv_lora_rank"], nope=config["qk_nope_head_dim"],
        rope=config["qk_rope_head_dim"], theta=float(config["rope_theta"]),
        eps=float(config["rms_norm_eps"]), factor=float(rp["factor"]),
        original=int(rp["original_max_position_embeddings"]),
        beta_fast=float(rp["beta_fast"]), beta_slow=float(rp["beta_slow"]),
        mscale=float(rp["mscale"]),
        mscale_all_dim=float(rp["mscale_all_dim"]),
        interleave=bool(config["rope_interleave"]),
        lin_kheads=config["linear_num_key_heads"],
        lin_vheads=config["linear_num_value_heads"],
        lin_head=config["linear_key_head_dim"],
        taps=config["linear_conv_kernel_dim"], experts=int(published),
        top=config["num_experts_per_tok"],
        first=int(config.get("first_expert_held", 0)),
        held=int(config["n_routed_experts"]),
        routed_scale=float(config["routed_scaling_factor"]),
        limit=float(config["swiglu_limit"]),
        norm_gate=float(config["layernorm_gating_weight"]),
        out_gate=float(config["linear_sigmoid_gate_scale"]))


# What ``correct`` has to refuse: each a change of the sizes that stands in
# the reference's place (tools/correct_controls.py). A dropped layer part (no
# post norm), the sibling family's b in (0, 2), the MLPs un-clamped.
CONTROLS = {
    "no_post_norm": lambda c: dataclasses.replace(c, post_norm=False),
    "beta_0_2": lambda c: dataclasses.replace(c, beta_scale=2.0),
    "unclamped_mlp": lambda c: dataclasses.replace(c, clamp=False),
}
# What the comparison of LOGITS cannot refuse, taken the same way and
# refusing nothing: the nearest precision below the file's, the state kept
# in bfloat16 between tokens. With decays of 0.9-0.999 a token the rounding
# of a step is forgotten in tens to hundreds of steps, and what is left of
# it is smaller than what W8A8 adds (as the sibling family's reference found
# on the chip, PR 29). What refuses a bfloat16 state BLOCK is
# ``kernel_checks`` below: both forms of the rule through the engine's own
# block dtype, held to ``LINEAR_FORM_TOL``.
READINGS = {
    "bf16_state": lambda c: dataclasses.replace(c, state_dtype="bfloat16"),
}


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


@jax.jit
def _dot_block(x, w):
    return x @ f32(w)


def dot(x, w):
    """``x @ w`` for a matrix leaf [din, dout] of the tree, dequantised
    ``COLUMN_BLOCK`` columns at a time (the dense MLP's matrices are 0.5 GB
    each in float32)."""
    n = (w["q"] if _is_q(w) else w).shape[-1]
    if n <= COLUMN_BLOCK:
        return _dot_block(x, w)
    parts = [_dot_block(x, jax.tree.map(lambda a: a[..., lo:lo + COLUMN_BLOCK],
                                        w))
             for lo in range(0, n, COLUMN_BLOCK)]
    return jnp.concatenate(parts, axis=-1)


def norm(x, w, c: Sizes):
    """``ZeroCenteredGatedNorm``: RMS-normalise, gain ``g sigmoid(w)``."""
    w = jnp.asarray(w, jnp.float32)
    return (x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + c.eps)
            * (c.norm_gate * jax.nn.sigmoid(w)))


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def gated(g, u, c: Sizes):
    """The hidden rows of every gated MLP: SiLU(min(g, L)) * clip(u, -L, L)."""
    if c.clamp and c.limit:
        g, u = jnp.minimum(g, c.limit), jnp.clip(u, -c.limit, c.limit)
    return jax.nn.silu(g) * u


# ---------------------------------------------------------------------------
# The linear mixer
# ---------------------------------------------------------------------------

def delta_rule(q, k, v, alpha, beta, s0=None, state_dtype=jnp.float32):
    """The recurrence itself, token by token, from a zero state (or from
    ``s0`` [H, dk, dv], the state then returned with the outputs). q, k
    [T, H, dk]; v [T, H, dv]; alpha, beta [T, H] — ONE decay a head. Per
    head S_t = (I - b_t k_t k_t^T) a_t S_{t-1} + b_t k_t v_t^T, o_t =
    S_t^T q_t. ``state_dtype``: what the state is kept in between tokens."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, x):
        qt, kt, vt, at, bt = x
        s = at[:, None, None] * s.astype(jnp.float32)
        s = s - bt[:, None, None] * kt[:, :, None] * jnp.einsum(
            "hk,hkv->hv", kt, s)[:, None, :]
        s = s + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        s = s.astype(state_dtype)
        return s, jnp.einsum("hkv,hk->hv", s.astype(jnp.float32), qt)
    start = jnp.zeros((h, dk, dv), state_dtype) if s0 is None else s0
    s, o = jax.lax.scan(step, start, (q, k, v, alpha, beta))
    return o if s0 is None else (o, s)


@functools.partial(jax.jit, static_argnums=(3,))
def _linear_core(pre, h, lp, c: Sizes):
    """``pre`` [T, (2 Hk + Hv) dk]: the three projections side by side;
    ``lp``: the layer's small leaves. -> the gated, normed outputs [T, Hv
    dk] but for the ``2 sigmoid(h W_z)`` gate and ``W_o``."""
    t = pre.shape[0]
    hk, hv, dk = c.lin_kheads, c.lin_vheads, c.lin_head
    padded = jnp.concatenate(
        [jnp.zeros((c.taps - 1, pre.shape[1]), jnp.float32), pre])
    taps = f32(lp["conv"])
    conv = jax.nn.silu(sum(taps[j] * padded[j:j + t] for j in range(c.taps)))
    q = conv[:, :hk * dk].reshape(t, hk, dk)
    k = conv[:, hk * dk:2 * hk * dk].reshape(t, hk, dk)
    v = conv[:, 2 * hk * dk:].reshape(t, hv, dk)
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    q, k = (jnp.repeat(a, hv // hk, axis=1) for a in (q, k))
    beta = c.beta_scale * jax.nn.sigmoid(h @ f32(lp["wbeta"]))
    alpha = jnp.exp(-jnp.exp(f32(lp["a_log"]))[None, :] * jax.nn.softplus(
        h @ f32(lp["wa"]) + f32(lp["dt_bias"])[None, :]))
    o = delta_rule(q, k, v, alpha, beta,
                   state_dtype=jnp.dtype(c.state_dtype))
    return _rms(o, f32(lp["out_norm"]), c.eps).reshape(t, -1)


def linear_mixer(h, lp, c: Sizes):
    """h [T, D] (normed) -> [T, D]."""
    pre = jnp.concatenate([dot(h, lp["wq"]), dot(h, lp["wk"]),
                           dot(h, lp["wv"])], -1)
    small = {k: lp[k] for k in ("conv", "wbeta", "a_log", "wa", "dt_bias",
                                "out_norm")}
    o = _linear_core(pre, h, small, c)
    gate = c.out_gate * jax.nn.sigmoid(dot(h, lp["wz"]))
    return dot(o * gate, lp["wo"])


# ---------------------------------------------------------------------------
# The latent mixer
# ---------------------------------------------------------------------------

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


@functools.partial(jax.jit, static_argnums=(3,))
def _latent_core(q, kva, lp, c: Sizes):
    """q [T, H (dn + dr)] and kva [T, r + dr], projected; ``lp``: kv_norm
    and wkvb. -> the heads' outputs [T, H dv], expanded: every token's K
    and V rebuilt from its latent, ``QUERY_BLOCK`` queries at a time."""
    t = q.shape[0]
    q = q.reshape(t, c.heads, c.nope + c.rope)
    latent = norm(kva[:, :c.kv_rank], lp["kv_norm"], c)
    kv = jnp.einsum("tc,chx->thx", latent, f32(lp["wkvb"]))
    k_nope, v = kv[..., :c.nope], kv[..., c.nope:]
    k_rope = _rotate(kva[:, None, c.kv_rank:], c)[:, 0]     # one, all heads
    q = jnp.concatenate([q[..., :c.nope], _rotate(q[..., c.nope:], c)], -1)
    sigma = (c.nope + c.rope) ** -0.5 * _magnitude(c.factor,
                                                   c.mscale_all_dim) ** 2
    q = q * sigma
    pos = jnp.arange(t)
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
    return out.reshape(blocks * QUERY_BLOCK, -1)[:t]


def latent_mixer(h, lp, c: Sizes):
    """h [T, D] (normed) -> [T, D]."""
    q = dot(norm(dot(h, lp["wqa"]), lp["q_norm"], c), lp["wqb"])
    out = _latent_core(q, dot(h, lp["wkva"]),
                       {k: lp[k] for k in ("kv_norm", "wkvb")}, c)
    return dot(out * jax.nn.sigmoid(dot(h, lp["wgate"])), lp["wo"])


# ---------------------------------------------------------------------------
# The MLPs
# ---------------------------------------------------------------------------

def routing(m, router, bias, c: Sizes):
    """m [T, D] -> (ids [T, top], weights [T, top]): s = sigmoid(m W_r)
    over ALL experts, the top-k by s + e, w = s / sum of the k selected s,
    times ``routed_scaling_factor``. One group."""
    s = jax.nn.sigmoid(m @ router)
    order = jnp.argsort(-(s + bias), axis=-1)[:, :c.top]
    chosen = jnp.take_along_axis(s, order, -1)
    return order, chosen / jnp.sum(chosen, -1, keepdims=True) * c.routed_scale


@functools.partial(jax.jit, static_argnums=(2,))
def _experts(m, mp, c: Sizes, stacks, layer):
    """m [T, D] (normed) -> the held experts' part of the routed sum.
    ``stacks``: the routed experts' three matrices as the engine holds
    them, [periods, held, ...], read one expert of period ``layer`` at a
    time inside the scan: every held expert on every token, weighted by the
    token's routing weight for it — 0 where it is not among the token's
    top-k of ALL experts."""
    ids, w = routing(m, f32(mp["router"]), f32(mp["router_bias"]), c)

    def one(out, e):
        eg, eu, ed = (f32(jax.tree.map(lambda a: a[layer, e], stack))
                      for stack in stacks)
        weight = jnp.sum(jnp.where(ids == c.first + e, w, 0.0), -1)
        return out + weight[:, None] * (gated(m @ eg, m @ eu, c) @ ed), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(m), jnp.arange(c.held))
    return out


def gated_mlp(m, wg, wu, wd, c: Sizes):
    return dot(gated(dot(m, wg), dot(m, wu), c), wd)


def expert_mlp(m, mp, c: Sizes, layer: int):
    """The held experts' part plus the shared expert, un-gated."""
    stacks = tuple(mp[k] for k in ("wg", "wu", "wd"))
    small = {k: mp[k] for k in ("router", "router_bias")}
    return (_experts(m, small, c, stacks, jnp.int32(layer))
            + gated_mlp(m, mp["sg"], mp["su"], mp["sd"], c))


# ---------------------------------------------------------------------------
# The forward
# ---------------------------------------------------------------------------

def block(x, lp, c: Sizes, mixer, mlp):
    """x <- x + N(mixer(N(x))); x <- x + N(mlp(N(x))) on one layer's tree
    ``lp`` (its ``mlp`` sub-tree the MLP's)."""
    def post(y, p):
        return norm(y, p["post_norm"], c) if c.post_norm else y
    x = x + post(mixer(norm(x, lp["norm"], c), lp, c), lp)
    mp = lp["mlp"]
    return x + post(mlp(norm(x, mp["norm"], c), mp), mp)


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


def _at(tree, i, keep=()):
    """``tree``'s leaves at index ``i`` of their leading axis; the leaves
    under the keys ``keep`` stay whole (the expert stacks)."""
    return {k: (v if k in keep else
                _at(v, i, keep) if isinstance(v, dict) and not _is_q(v) else
                jax.tree.map(lambda a: a[i], v))
            for k, v in tree.items()}


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree."""
    held = ("wg", "wu", "wd")
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq, jnp.int32))
        for i in range(c.lead):
            x = block(x, _at(params["lead"], i), c, linear_mixer,
                      lambda m, mp: gated_mlp(m, mp["wg"], mp["wu"],
                                              mp["wd"], c))
        layers = params["layers"]
        for p in range(c.periods):
            sparse = lambda m, mp, p=p: expert_mlp(m, mp, c, p)
            x = block(x, _at(layers["attn"], p, held), c, latent_mixer,
                      sparse)
            for lin in layers["lin"]:
                x = block(x, _at(lin, p, held), c, linear_mixer, sparse)
        x = norm(x[-last:], params["final_norm"], c)
        return np.asarray(dot(x, jax.tree.map(jnp.transpose,
                                              params["lm_head"])),
                          np.float32)


# ---------------------------------------------------------------------------
# kernel_checks: what the harness's own sample cannot reach
# ---------------------------------------------------------------------------

# The program's forms of the delta rule against ``delta_rule``, float32 on
# both sides: the limit and its two readings are solar_open2.py's
# (LINEAR_FORM_TOL there: 17 times the sound reading, a thirteenth of a
# bfloat16 state block's), the forms being the same two functions.
LINEAR_FORM_TOL = 3e-4


def delta_forms_parity(c: Sizes, block_dtype, interpret: bool
                       ) -> list[dict[str, Any]]:
    """At the file's widths — ``lin_vheads`` heads of ``lin_head``, two
    value heads a key head, ONE decay a head: the chunked prefill form over
    2 rows of 256 tokens from a non-zero state, with decays from fast
    (e^-11 a token) to slow (0.9999), and the decode update chained over 8
    tokens, each against ``delta_rule`` continued from the same state.
    Between calls the program keeps the state in the engine's block, so
    every state here goes through that block's dtype (``stored``)."""
    from llmapigateway_tpu.models import hybrid

    def stored(s):
        return s.astype(block_dtype).astype(jnp.float32)
    hk, hv, dk = c.lin_kheads, c.lin_vheads, c.lin_head
    t, rows = (32, 2) if interpret else (256, 2)
    keys = jax.random.split(jax.random.PRNGKey(46), 6)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))
    spread = lambda a: jnp.repeat(a, hv // hk, axis=2)
    q = spread(unit(jax.random.normal(keys[0], (rows, t, hk, dk)))
               ) / np.sqrt(dk)
    k = spread(unit(jax.random.normal(keys[1], (rows, t, hk, dk))))
    v = jax.random.normal(keys[2], (rows, t, hv, dk))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (rows, t, hv)))
    log_a = -jnp.exp(jax.random.uniform(
        keys[4], (rows, t, hv), minval=np.log(1e-4), maxval=np.log(11.0)))
    s0 = jax.random.normal(keys[5], (rows, hv, dk, dk))

    def want(q, k, v, log_a, beta, s0):
        return jax.vmap(delta_rule)(q, k, v, jnp.exp(log_a), beta, s0)

    with jax.default_matmul_precision("highest"):
        o_ref, s_ref = jax.jit(want)(q, k, v, log_a, beta, s0)
    out = []
    o, s = jax.jit(hybrid.kda_chunked)(q, k, v, log_a[..., None], beta,
                                       stored(s0))
    s = stored(s)
    err = float(max(jnp.max(jnp.abs(o - o_ref)), jnp.max(jnp.abs(s - s_ref))))
    out.append({"kernel": "delta_prefill_chunked", "tokens": t, "heads": hv,
                "max_abs_err": err,
                "ok": bool(np.isfinite(err) and err <= LINEAR_FORM_TOL)})
    n = 8
    update = jax.jit(hybrid.kda_decode_update)
    s, outs = stored(s0), []
    for i in range(n):      # a call a token: ``stored`` runs between them
        o, s = update(q[:, i], k[:, i], v[:, i], log_a[:, i, :, None],
                      beta[:, i], s)
        s = stored(s)
        outs.append(o)
    o = jnp.stack(outs, 1)
    with jax.default_matmul_precision("highest"):
        o_ref, s_ref = jax.jit(want)(q[:, :n], k[:, :n], v[:, :n],
                                     log_a[:, :n], beta[:, :n], s0)
    err = float(max(jnp.max(jnp.abs(o - o_ref)), jnp.max(jnp.abs(s - s_ref))))
    out.append({"kernel": "delta_decode_update", "tokens": n, "heads": hv,
                "max_abs_err": err,
                "ok": bool(np.isfinite(err) and err <= LINEAR_FORM_TOL)})
    return out


def latent_parity(*, heads: int, width: int, value_width: int, page: int,
                  interpret: bool, pages_per_slot: int = 32, t: int = 256
                  ) -> list[dict[str, Any]]:
    """The latent pool's in-place write, then its attention kernel, as a
    decode step (one token a slot) and as a prefill chunk (``t`` tokens):
    ``mistral4.latent_kernel_parity``'s cases, inputs and limit, with the
    plain side — the new rows scattered into a gathered dense view, one
    float32 softmax over each query's visible keys — computed on the
    DEVICE, ``HEAD_BLOCK`` heads at a time. At 64 heads of 576 numbers
    over 8,192 keys the sibling's NumPy plain side is 0.44 TFLOP on one
    host core: the cell's ``correctness_s`` read 183 s with it and 13-31 s
    with this (PERF.md, PR 46), the errors 0.00223 and 0.00907 both ways."""
    from benchmark.correctness import KERNEL_TOL
    from llmapigateway_tpu.ops.latent_attention import (
        latent_insert_in_place, latent_paged_attention)
    b, s = 3, page * pages_per_slot
    n_pages = b * pages_per_slot + 1
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        b, pages_per_slot).astype(np.int32))
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = jax.random.normal(keys[0], (2, n_pages, width, page), jnp.bfloat16)
    hb = math.gcd(heads, HEAD_BLOCK)

    @jax.jit
    def served(pool, q, new, start):
        written = latent_insert_in_place(
            pool, new, table, start, None, layer=1, interpret=interpret)
        return latent_paged_attention(
            q, written, table, start, value_width=value_width, layer=1,
            interpret=interpret)

    @jax.jit
    def plain(pool, q, new, start):
        tt = q.shape[1]
        dense = pool[1].astype(jnp.float32)[table]          # [b, n, W, page]
        dense = dense.transpose(0, 1, 3, 2).reshape(b, s, width)

        def slot(args):
            keys_i, q_i, new_i, at = args
            keys_i = jax.lax.dynamic_update_slice(
                keys_i, new_i.astype(jnp.float32), (at, 0))
            seen = jnp.arange(s)[None, :] <= at + jnp.arange(tt)[:, None]

            def some_heads(qh):                             # [tt, hb, W]
                scores = jnp.einsum("thw,sw->hts", qh, keys_i)
                p = jax.nn.softmax(
                    jnp.where(seen[None], scores, -jnp.inf), -1)
                return jnp.einsum("hts,sv->thv", p, keys_i[:, :value_width])
            qb = q_i.astype(jnp.float32).reshape(tt, heads // hb, hb, width)
            out = jax.lax.map(some_heads, jnp.moveaxis(qb, 1, 0))
            return jnp.moveaxis(out, 0, 1).reshape(tt, heads, value_width)
        return jax.lax.map(slot, (dense, q, new, start))

    out = []
    for kind, tt, at in (("decode", 1, [page // 2, s // 2 + 3, s - page - 1]),
                         ("prefill", t, [0, s // 2, s - page - t])):
        q = (jax.random.normal(keys[1], (b, tt, heads, width), jnp.float32)
             * width ** -0.5).astype(jnp.bfloat16)
        new = jax.random.normal(keys[2], (b, tt, width), jnp.bfloat16)
        start = jnp.asarray(at, jnp.int32)
        got = np.asarray(served(pool, q, new, start), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(plain(pool, q, new, start))
        err = float(np.max(np.abs(got - want)))
        out.append({"kernel": f"latent_{kind}", "kv": "bf16",
                    "max_abs_err": err,
                    "ok": bool(np.isfinite(got).all() and err <= KERNEL_TOL)})
    return out


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """(a) The latent write and attention kernels at the file's widths
    (576 x 64 heads), as a decode step and as a prefill chunk. (b) The two
    forms of the delta rule, through the engine's state block's dtype."""
    out = latent_parity(
        heads=config["num_attention_heads"],
        width=config["kv_lora_rank"] + config["qk_rope_head_dim"],
        value_width=config["kv_lora_rank"], page=engine.kv_page,
        interpret=interpret,
        **({"pages_per_slot": 8, "t": 16} if interpret else {}))
    return out + delta_forms_parity(sizes(engine.model_cfg, config),
                                    engine.cache.state[0].dtype, interpret)
