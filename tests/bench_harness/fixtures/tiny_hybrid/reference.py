"""A second architecture's plain reference, added by FILES alone (the
rehearsal of ``test_spec_discovery.py`` copies it to
``<root>/benchmark/reference/tiny_hybrid.py``): a sparse-expert decoder
written apart from ``benchmark/reference/forward.py`` — a loop over heads,
routing by sorting — with the contract's three names
(``benchmark/reference/__init__.py``). Its sizes come from BOTH places the
contract allows: the program's ``ModelConfig`` and the configuration's
file (the experts per token, which the file gives as published)."""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    heads: int
    kv_heads: int
    head: int
    theta: float
    eps: float
    experts: int
    top: int


def sizes(model_cfg: Any, config: dict[str, Any]) -> Sizes:
    return Sizes(layers=model_cfg.n_layers, heads=model_cfg.n_heads,
                 kv_heads=model_cfg.n_kv_heads, head=model_cfg.head_dim,
                 theta=float(model_cfg.rope_theta),
                 eps=float(model_cfg.rms_eps), experts=model_cfg.n_experts,
                 top=int(config["num_experts_per_tok"]))


def _f32(w: Any) -> jax.Array:
    """A leaf of the engine's tree as float32 (int8 ``{"q", "s"}``: scale
    per output channel; the head ``[V, D]`` per row)."""
    if not isinstance(w, dict):
        return jnp.asarray(w, jnp.float32)
    q, s = w["q"].astype(jnp.float32), w["s"].astype(jnp.float32)
    if q.ndim >= 2 and s.shape == q.shape[:-2] + q.shape[-1:]:
        return q * s[..., None, :]
    return q * s[..., None]


def _norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [T, Dh] at positions 0..T-1, halves rotated against each other."""
    t, dh = x.shape
    freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.outer(jnp.arange(t, dtype=jnp.float32), freq)
    lo, hi = x[:, :dh // 2], x[:, dh // 2:]
    return jnp.concatenate([lo * jnp.cos(ang) - hi * jnp.sin(ang),
                            hi * jnp.cos(ang) + lo * jnp.sin(ang)], -1)


def _attend(x, lp, c: Sizes):
    t = x.shape[0]
    q, k, v = x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    heads = []
    for h in range(c.heads):
        g = h // (c.heads // c.kv_heads)
        qh = _rotate(q[:, h * c.head:(h + 1) * c.head], c.theta)
        kh = _rotate(k[:, g * c.head:(g + 1) * c.head], c.theta)
        vh = v[:, g * c.head:(g + 1) * c.head]
        s = jnp.where(causal, qh @ kh.T / np.sqrt(c.head), -jnp.inf)
        heads.append(jax.nn.softmax(s, -1) @ vh)
    return jnp.concatenate(heads, -1) @ lp["wo"]


def experts(x, lp, c: Sizes):
    """x [T, D]: each token through its ``top`` best experts, weighted by
    the softmax over those experts' router logits; no token is dropped."""
    scores = x @ lp["router"]
    order = jnp.argsort(-scores, -1)[:, :c.top]
    chosen = jnp.take_along_axis(scores, order, -1)
    weight = jnp.exp(chosen - chosen.max(-1, keepdims=True))
    weight = weight / weight.sum(-1, keepdims=True)
    out = jnp.zeros_like(x)
    for e in range(c.experts):
        y = (jax.nn.silu(x @ lp["wg"][e]) * (x @ lp["wu"][e])) @ lp["wd"][e]
        out += jnp.sum(jnp.where(order == e, weight, 0.0), -1)[:, None] * y
    return out


def logits(params: Any, c: Sizes, seq: np.ndarray, last: int) -> np.ndarray:
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"])[jnp.asarray(seq, jnp.int32)]
        for i in range(c.layers):                      # a layer at a time
            lp = {k: _f32(jax.tree.map(lambda a: a[i], w))
                  for k, w in params["layers"].items()}
            x = x + _attend(_norm(x, lp["attn_norm"], c.eps), lp, c)
            x = x + experts(_norm(x, lp["mlp_norm"], c.eps), lp, c)
        x = _norm(x[-last:], _f32(params["final_norm"]), c.eps)
        return np.asarray(x @ _f32(params["lm_head"]).T, np.float32)


def kernel_checks(engine: Any, config: dict[str, Any], interpret: bool
                  ) -> list[dict[str, Any]]:
    """The program's exact expert layer (``models/mixtral.py``
    ``moe_mlp_dense``) at the engine's widths against ``experts`` above, on
    the first layer's weights as float32 and unit-normal inputs."""
    from llmapigateway_tpu.models.mixtral import moe_mlp_dense
    c = sizes(engine.model_cfg, config)
    lp = {k: _f32(jax.tree.map(lambda a: a[0], w))
          for k, w in engine.params["layers"].items()}
    x = jax.random.normal(jax.random.PRNGKey(0),
                          (16, engine.model_cfg.d_model), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(moe_mlp_dense(x[None], lp, engine.model_cfg)[0])
        want = np.asarray(experts(x, lp, c))
    err = float(np.max(np.abs(got - want)))
    return [{"kernel": "moe_mlp_dense", "experts": c.experts,
             "max_abs_err": err, "ok": bool(err <= 1e-4)}]
