"""Plain float32 forward of the Solar-Open2 decoder: periods of one gated
softmax layer without rotary embedding and three gated delta-rule linear
layers with a per-channel decay, every layer followed by a sparse expert
MLP of which this chip holds a share.

Written from the published config and the family's description (source in
``configs/solar-open2-250b-ep8.json``; the layer equations are repeated
below as they are computed, and what the config does not give is listed
under ``assumed`` in that file). It shares no code with
``llmapigateway_tpu/models``: only the LAYOUT of the weight tree is the
program's, because it runs on the engine's own weights, dequantised one
layer and one expert at a time so that it fits beside the engine.

* no cache, no chunks, no batching: the linear layers run their
  recurrence token by token over the whole sequence from a zero state,
  the softmax layers attend over the whole sequence;
* routing is exact: every token goes to its top-k experts of ALL the
  published experts; of those, the experts this chip holds (``first`` ..
  ``first + held``) contribute, and what the absent ones would add is left
  out, as the configuration's deployment says (model-configs guide,
  section 4). The shared expert is added once;
* everything under ``jax.default_matmul_precision("highest")``.

``kernel_checks`` holds the program's two forms of the linear layer — the
block-parallel prefill form and the one-token decode update — to the
token-by-token recurrence at the cell's widths, in float32.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    periods: int            # of ``period`` layers each
    period: int
    heads: int              # softmax layers: query heads ...
    kv_heads: int
    head: int
    lin_heads: int          # linear layers: heads of ``lin_head`` keys/values
    lin_head: int
    taps: int               # of the causal depthwise convolution
    eps: float
    experts: int            # the router's width (published)
    top: int
    first: int              # experts [first, first + held) live here
    held: int
    shared: int


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    """Everything from the configuration's FILE — the published widths,
    the experts held here (``n_routed_experts``, with the published count
    under ``reduced``) and the first of them (``first_expert_held``,
    absent: 0) — but the depth, which the harness cut in the program's
    config from the same file."""
    lin = config["linear_attn_config"]
    period = config["layer_kinds"]["period"]
    return Sizes(
        periods=model_cfg.n_layers // period, period=period,
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head=config["head_dim"],
        lin_heads=lin["num_heads"], lin_head=lin["head_dim"],
        taps=lin["short_conv_kernel_size"],
        eps=float(config["rms_norm_eps"]),
        experts=int(config["reduced"]["n_routed_experts"]["published"])
        if "n_routed_experts" in config.get("reduced", {})
        else int(config["n_routed_experts"]),
        top=config["num_experts_per_tok"],
        first=int(config.get("first_expert_held", 0)),
        held=int(config["n_routed_experts"]),
        shared=config["n_shared_experts"])


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


def softmax_layer(x, lp, c: Sizes):
    """x [T, D]: q = W_q x (heads x head), k, v = W_k x, W_v x (kv_heads x
    head), NO rotary, causal softmax attention without a window; the
    output W_o(attn * sigmoid(W_gate x)), the gate per element."""
    t = x.shape[0]
    q = (x @ lp["wq"]).reshape(t, c.heads, c.head)
    k = (x @ lp["wk"]).reshape(t, c.kv_heads, c.head)
    v = (x @ lp["wv"]).reshape(t, c.kv_heads, c.head)
    rep = c.heads // c.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(c.head)
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
    attn = jnp.einsum("hqk,khd->qhd", probs, v).reshape(t, -1)
    return (attn * jax.nn.sigmoid(x @ lp["wgate"])) @ lp["wo"]


def delta_rule(q, k, v, alpha, beta, s0=None):
    """The recurrence itself, token by token, from a zero state (or from
    ``s0`` [H, dk, dv], the state then returned with the outputs). q, k,
    alpha [T, H, dk]; v [T, H, dv]; beta [T, H]. Per head
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,
    o_t = S_t^T q_t."""
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(s, x):
        qt, kt, vt, at, bt = x
        s = at[:, :, None] * s                          # Diag(a) S
        s = s - bt[:, None, None] * kt[:, :, None] * jnp.einsum(
            "hk,hkv->hv", kt, s)[:, None, :]            # (I - b k k^T) .
        s = s + bt[:, None, None] * kt[:, :, None] * vt[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", s, qt)
    start = jnp.zeros((h, dk, dv), jnp.float32) if s0 is None else s0
    s, o = jax.lax.scan(step, start, (q, k, v, alpha, beta))
    return o if s0 is None else (o, s)


def linear_layer(x, lp, c: Sizes):
    """x [T, D]: q, k, v = SiLU(conv(W x)) — a causal depthwise
    convolution of ``taps`` taps over time, per channel, no bias; q and k
    L2-normalised per head, q scaled by dk^-1/2; b = 2 sigmoid(W_b x) per
    head (in (0, 2): negative eigenvalues allowed); a = exp(-exp(A_h)
    softplus(W_f_up W_f_down x + bias)) per channel; the output
    W_o(RMSNorm_head(o) * sigmoid(W_g_up W_g_down x))."""
    t = x.shape[0]
    hh, dk = c.lin_heads, c.lin_head
    pre = jnp.concatenate([x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]], -1)
    padded = jnp.concatenate(
        [jnp.zeros((c.taps - 1, pre.shape[1]), jnp.float32), pre])
    conv = sum(lp["conv"][j] * padded[j:j + t] for j in range(c.taps))
    q, k, v = (a.reshape(t, hh, dk)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    beta = 2.0 * jax.nn.sigmoid(x @ lp["wbeta"])
    z = (x @ lp["wf_down"]) @ lp["wf_up"] + lp["f_bias"]
    alpha = jnp.exp(-jnp.exp(lp["a_log"])[None, :, None]
                    * jax.nn.softplus(z).reshape(t, hh, dk))
    o = delta_rule(q, k, v, alpha, beta)                # [T, H, dv]
    o = _rms(o, lp["out_norm"], c.eps).reshape(t, -1)
    gate = jax.nn.sigmoid((x @ lp["wg_down"]) @ lp["wg_up"])
    return (o * gate) @ lp["wo"]


def routing(x, router, c: Sizes):
    """x [T, D] -> (ids [T, top], weights [T, top]): s = sigmoid(x W_r)
    over ALL experts, the top-k by s, w_e = s_e / sum of the k (scaling
    1). No groups, no correction bias."""
    s = jax.nn.sigmoid(x @ router)
    order = jnp.argsort(-s, axis=-1)[:, :c.top]
    chosen = jnp.take_along_axis(s, order, -1)
    return order, chosen / jnp.sum(chosen, -1, keepdims=True)


def _swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


@functools.partial(jax.jit, static_argnums=(3,))
def _norm_and_route(x, norm, router, c: Sizes):
    h = _rms(x, f32(norm), c.eps)
    ids, w = routing(h, f32(router), c)
    return h, ids, w


@jax.jit
def _one_expert(h, gate, wg, wu, wd):
    """One held expert on every token, weighted by ``gate`` [T] (0 where
    the expert is not among the token's top-k)."""
    return gate[:, None] * _swiglu(h, f32(wg), f32(wu), f32(wd))


def expert_mlp(x, mp, c: Sizes):
    """x [T, D] -> the MLP's addition to the residual: the held experts'
    part of sum_e w_e E_e(h) plus the shared expert, h = RMSNorm(x). One
    expert is dequantised at a time."""
    h, ids, w = _norm_and_route(x, mp["norm"], mp["router"], c)
    out = _one_expert(h, jnp.ones((x.shape[0],), jnp.float32),
                      mp["sg"], mp["su"], mp["sd"]) if c.shared else 0.0
    for e in range(c.held):
        gate = jnp.sum(jnp.where(ids == c.first + e, w, 0.0), -1)
        pick = lambda a: jax.tree.map(lambda t: t[e], a)    # leaf or {q, s}
        out = out + _one_expert(h, gate, pick(mp["wg"]), pick(mp["wu"]),
                                pick(mp["wd"]))
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mixer(x, lp, c: Sizes, kind: str):
    """x + mixer(RMSNorm(x)) on one layer's weights, dequantised here."""
    lp = jax.tree.map(f32, lp, is_leaf=_is_q)
    h = _rms(x, lp["norm"], c.eps)
    return x + (softmax_layer if kind == "softmax" else linear_layer)(
        h, lp, c)


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, w, last: int, eps: float):
    return _rms(x[-last:], f32(norm), eps) @ f32(w).T


def _without_mlp(lp):
    return {k: v for k, v in lp.items() if k != "mlp"}


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    """Float32 logits [last, V] of the LAST ``last`` positions of ``seq``
    [T] under the engine's weight tree: layers/attn/* stacked over
    periods, layers/lin a tuple over a period's linear layers of trees
    stacked over periods, each with its ``mlp`` sub-tree."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(seq, jnp.int32))
        layers = params["layers"]
        for p in range(c.periods):              # one layer's weights at a time
            lp = jax.tree.map(lambda a: a[p], layers["attn"])
            x = _mixer(x, _without_mlp(lp), c, "softmax")
            x = x + expert_mlp(x, lp["mlp"], c)
            for i in range(c.period - 1):
                lp = jax.tree.map(lambda a: a[p], layers["lin"][i])
                x = _mixer(x, _without_mlp(lp), c, "linear")
                x = x + expert_mlp(x, lp["mlp"], c)
        out = _head(x, params["final_norm"], params["lm_head"], last, c.eps)
        return np.asarray(out, np.float32)


# The program's forms of the linear layer against ``delta_rule``. Both
# sides are float32 on the same inputs, so what separates them is the
# order of the sums: the block-parallel form adds 64 tokens' contributions
# through a triangular solve and matrix products of 6 bf16 passes on the
# chip, the recurrence adds them one at a time. Outputs are O(1) (unit
# keys, unit-variance values, a decay below 1). The limit lies between two
# readings on a v5e at the cell's widths (PR 29, PERF.md section 6). Sound:
# 1.8e-5 (chunked form), 1.2e-6 (decode update). The nearest precision
# below what the file states: a bfloat16 state block 3.9e-3 / 3.8e-2, the
# chunked form's products in ONE bf16 pass (the default matmul precision)
# 1.1e-2. 3e-4 is 17 times the first and a thirteenth of the least of the
# second, so a reduced precision, a wrong decay or a dropped carry fails
# it by an order of magnitude. (Three bf16 passes, ``Precision.HIGH``,
# read 6.4e-5 and pass: they are float32 products to 2^-16.) The logits
# bound does not see a bfloat16 state (``gap_max`` 0.052-0.064 against
# 0.25, inside the sound runs' own range), so this check is what does.
LINEAR_FORM_TOL = 3e-4


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """At the cell's widths (heads and head size from the file): the
    chunked prefill form over 2 rows of 256 tokens from a non-zero state,
    with decays from fast (e^-11 a token) to slow (0.9999), and the decode
    update chained over 8 tokens, each against ``delta_rule`` continued
    from the same state. Between calls the program keeps the state in the
    engine's block, so every state here goes through that block's dtype
    (``stored``, outside any compiled program so that the compiler cannot
    elide the rounding: nothing for the float32 the file states; a
    narrower block fails both cases)."""
    from llmapigateway_tpu.models import hybrid
    c = sizes(engine.model_cfg, config)
    block = engine.cache.state[0].dtype

    def stored(s):
        return s.astype(block).astype(jnp.float32)
    h, dk = c.lin_heads, c.lin_head
    t, rows = (32, 2) if interpret else (256, 2)
    keys = jax.random.split(jax.random.PRNGKey(29), 6)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))
    q = unit(jax.random.normal(keys[0], (rows, t, h, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(keys[1], (rows, t, h, dk)))
    v = jax.random.normal(keys[2], (rows, t, h, dk))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (rows, t, h)))
    log_a = -jnp.exp(jax.random.uniform(
        keys[4], (rows, t, h, dk), minval=np.log(1e-4), maxval=np.log(11.0)))
    s0 = jax.random.normal(keys[5], (rows, h, dk, dk))

    def want(q, k, v, log_a, beta, s0):
        """``delta_rule`` continued from ``s0``, one row at a time."""
        return jax.vmap(delta_rule)(q, k, v, jnp.exp(log_a), beta, s0)

    with jax.default_matmul_precision("highest"):
        o_ref, s_ref = jax.jit(want)(q, k, v, log_a, beta, s0)
    out = []
    o, s = jax.jit(hybrid.kda_chunked)(q, k, v, log_a, beta, stored(s0))
    s = stored(s)
    err = float(max(jnp.max(jnp.abs(o - o_ref)), jnp.max(jnp.abs(s - s_ref))))
    out.append({"kernel": "kda_prefill_chunked", "tokens": t, "heads": h,
                "max_abs_err": err,
                "ok": bool(np.isfinite(err) and err <= LINEAR_FORM_TOL)})
    n = 8
    update = jax.jit(hybrid.kda_decode_update)
    s, outs = stored(s0), []
    for i in range(n):      # a call a token: ``stored`` runs between them
        o, s = update(q[:, i], k[:, i], v[:, i], log_a[:, i], beta[:, i], s)
        s = stored(s)
        outs.append(o)
    o = jnp.stack(outs, 1)
    with jax.default_matmul_precision("highest"):
        o_ref, s_ref = jax.jit(want)(q[:, :n], k[:, :n], v[:, :n],
                                     log_a[:, :n], beta[:, :n], s0)
    err = float(max(jnp.max(jnp.abs(o - o_ref)), jnp.max(jnp.abs(s - s_ref))))
    out.append({"kernel": "kda_decode_update", "tokens": n, "heads": h,
                "max_abs_err": err,
                "ok": bool(np.isfinite(err) and err <= LINEAR_FORM_TOL)})
    return out
