"""Plain float32 forward of the llama-family decoder block (Mistral:
grouped-query attention, rotary embedding in the rotate-half convention,
optional sliding window, SwiGLU MLP) and of the Mixtral sparse-expert MLP
(top-k router, softmax over the top-k logits, exact routing — no expert
ever drops a token).

Written from the published descriptions (Mistral 7B, arXiv:2310.06825;
Mixtral of Experts, arXiv:2401.04088; the Hugging Face ``modeling_mistral``
/ ``modeling_mixtral`` equations). It shares no code with
``llmapigateway_tpu/models``: only the LAYOUT of the weight tree is the
program's (stacked layers, ``{"q", "s"}`` int8 leaves), because it is run
on the engine's own weights, dequantised one layer at a time so that it
fits beside the engine.

The one departure from the publications: weights are the engine's int8
weights times their scales (dequantised, so the reference computes in
float32 what the engine computes in W8A8). Routing is the publication's:
the program's capacity dispatch (``models/mixtral.py``: a prefill chunk of
more than 64 tokens drops what an expert is sent beyond its capacity) is
NOT modelled here — the reference stays independent of the code under
test, and ``correctness.py`` samples an expert model where the program's
dispatch is exact.

On a TPU a float32 matrix multiplication runs in reduced precision unless
asked otherwise, so everything here runs under
``jax.default_matmul_precision("highest")``.

This is the reference a configuration gets that names none; ``sizes`` and
``logits`` are the names of the contract in ``reference/__init__.py``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class RefConfig:
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    rms_eps: float
    window: int                 # 0 = full causal
    n_experts: int              # 0 = dense MLP
    experts_per_token: int

    @classmethod
    def of(cls, c: Any) -> "RefConfig":
        """From the program's ``ModelConfig`` (sizes only)."""
        return cls(n_layers=c.n_layers, n_heads=c.n_heads,
                   n_kv_heads=c.n_kv_heads, head_dim=c.head_dim,
                   rope_theta=float(c.rope_theta), rms_eps=float(c.rms_eps),
                   window=int(c.sliding_window or 0),
                   n_experts=int(c.n_experts or 0),
                   experts_per_token=int(c.experts_per_token))


def sizes(model_cfg: Any, config: dict[str, Any]) -> RefConfig:
    """The contract's name for ``RefConfig.of``: every size of this family
    is a field of the program's ``ModelConfig``; the file adds none."""
    return RefConfig.of(model_cfg)


def dequant(w: Any) -> jax.Array:
    """A weight leaf as float32: plain, or int8 ``{"q", "s"}`` whose scale
    ``s`` is per output channel (``q`` with the contraction axis second to
    last; the head ``[V, D]`` has one scale per row)."""
    if isinstance(w, dict):
        q, s = w["q"].astype(jnp.float32), w["s"].astype(jnp.float32)
        if q.ndim >= 2 and s.shape == q.shape[:-2] + q.shape[-1:]:
            return q * s[..., None, :]
        return q * s[..., None]            # [V, D] head: scale per row
    return w.astype(jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, N, Dh], positions 0..T-1; pairs are (i, i + Dh/2)."""
    t, _, dh = x.shape
    half = dh // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, lp, c: RefConfig):
    """x [T, D] -> [T, D]: causal (windowed) grouped-query attention."""
    t = x.shape[0]
    q = (x @ lp["wq"]).reshape(t, c.n_heads, c.head_dim)
    k = (x @ lp["wk"]).reshape(t, c.n_kv_heads, c.head_dim)
    v = (x @ lp["wv"]).reshape(t, c.n_kv_heads, c.head_dim)
    q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
    group = c.n_heads // c.n_kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(c.head_dim)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    mask = j <= i
    if c.window:
        mask &= (i - j) < c.window
    scores = jnp.where(mask[None], scores, -jnp.inf)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return out.reshape(t, -1) @ lp["wo"]


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def moe(x, lp, c: RefConfig):
    """x [T, D] -> ([T, D], routed [T, E] bool): every token goes to its
    top-k experts, weighted by the softmax over those k router logits."""
    logits = x @ lp["router"]                             # [T, E]
    top, idx = jax.lax.top_k(logits, c.experts_per_token)
    w = jax.nn.softmax(top, -1)                           # [T, k]
    routed = jnp.stack([jnp.any(idx == e, -1)
                        for e in range(c.n_experts)], -1)
    out = jnp.zeros_like(x)
    for e in range(c.n_experts):
        gate = jnp.sum(jnp.where(idx == e, w, 0.0), -1)
        out += gate[:, None] * swiglu(x, lp["wg"][e], lp["wu"][e],
                                      lp["wd"][e])
    return out, routed


def layer(x, lp, c: RefConfig):
    """One decoder layer on dequantised float32 weights ``lp``."""
    x = x + attention(_rms(x, lp["attn_norm"], c.rms_eps), lp, c)
    h = _rms(x, lp["mlp_norm"], c.rms_eps)
    if c.n_experts:
        y, routed = moe(h, lp, c)
    else:
        y = swiglu(h, lp["wg"], lp["wu"], lp["wd"])
        routed = jnp.zeros((x.shape[0], 0), bool)
    return x + y, routed


def _is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w


@functools.partial(jax.jit, static_argnums=(2,))
def _layer_on_engine_weights(x, lp, c: RefConfig):
    return layer(x, jax.tree.map(dequant, lp, is_leaf=_is_quantized), c)


@jax.jit
def _embed(table, tok):
    return jnp.take(table, tok, axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, norm, w, last: int, eps: float):
    return _rms(x[-last:], norm.astype(jnp.float32), eps) @ dequant(w).T


def logits(params: Any, c: RefConfig, tokens: np.ndarray, last: int
           ) -> np.ndarray:
    """The contract's ``logits``: the rows of ``logits_and_routing``."""
    return logits_and_routing(params, c, tokens, last)[0]


def logits_and_routing(params: Any, c: RefConfig, tokens: np.ndarray,
                       last: int) -> tuple[np.ndarray, np.ndarray]:
    """Float32 logits of the LAST ``last`` positions of ``tokens`` [T]
    under the engine's weight tree ``params`` (stacked layers), and which
    experts each token was routed to, per layer [L, T, E]. Layers are
    dequantised and run one at a time (one compiled function, L calls)."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params["embed"], jnp.asarray(tokens, jnp.int32))
        routed = []
        for i in range(c.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, r = _layer_on_engine_weights(x, lp, c)
            routed.append(np.asarray(r))
        out = _head(x, params["final_norm"], params["lm_head"], last,
                    c.rms_eps)
        return np.asarray(out, np.float32), np.stack(routed)
