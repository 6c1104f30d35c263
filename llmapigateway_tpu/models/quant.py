"""Int8/int4 weight quantization for the serving engine (W8A8 / W4A8).

No reference counterpart — the reference proxies HTTP and never touches
weights (SURVEY.md §2: no model execution anywhere). This is a TPU-native
performance feature: steady-state decode is HBM-bandwidth-bound (every
weight byte is read once per token), so storing matmul weights as int8
halves the traffic that sets the decode roofline, and the int8×int8
``dot_general`` runs on the MXU's native int8 path (v5e: 394 int8 TOPS vs
197 bf16 TFLOPS).

Scheme (standard dynamic W8A8, no calibration data needed):

* **Weights**: symmetric per-output-channel int8. For a projection
  ``w [D, F]`` (contract over D) the scale is ``s [F] = max|w[:, f]|/127``
  stored fp32; a quantized weight is the sub-dict ``{"q": int8, "s": fp32}``
  in the params tree (a plain pytree — ``lax.scan`` over stacked layers,
  and GSPMD sharding see ordinary leaves).
* **Activations**: symmetric per-row dynamic int8, computed inside the
  compiled step (``max|x|`` over the contraction dim — XLA fuses this with
  the surrounding elementwise work). Row scales commute with the matmul, so
  the result is exact int32 arithmetic rescaled once:
  ``y = (xq @ wq) * xs * s``. Under tensor parallelism the int32 partial
  sums are summed exactly (integer psum) before the fp32 rescale.
* RMSNorm, rotary, embedding gather, KV cache, and logits stay in their
  usual dtypes — only the seven big matmuls per layer (wq/wk/wv/wo and
  wg/wu/wd) and the lm_head are quantized; those carry ~99% of the weight
  bytes of a llama-family model.

``mm``/``head_matmul`` are the single dispatch points: they accept either a
plain array or a quantized dict, so model code (models/llama.py) is layout-
agnostic and a checkpoint loaded with ``quant: "int8"`` streams through the
same forward as a bf16 one.

``quant: "int4"`` (W4A8) stores the layer matmuls as **int4** (levels
±7, same per-channel scheme) while the lm_head stays int8. The dots run
as mixed s8×s4 ``dot_general`` — XLA contracts the int4 operand
directly, and on TPU the packed-int4 HBM layout is what matters: decode
is weight-bandwidth-bound, so int4 MLP/attention weights cut the
per-step stream ~45% past int8 at a quality cost users opt into
per-provider.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

# Layer-stacked weights that quantize (contract dim 1 of [L, D_in, D_out]).
QUANT_LAYER_KEYS = frozenset({"wq", "wk", "wv", "wo", "wg", "wu", "wd"})
# Top-level weights that quantize ([V, D], contract over D → scale per V).
QUANT_TOP_KEYS = frozenset({"lm_head"})

QUANT_MODES = ("", "int8", "int4")


def weight_bits(mode: str, path: str) -> int:
    """Bit width for a quantizable path under a quant mode. ``int4``
    applies to the stacked layer matmuls (wq/wk/wv/wo/wg/wu/wd — they
    carry ~90% of a llama-family model's weight bytes and tolerate 4-bit
    per-channel rounding); the lm_head stays int8 in int4 mode — the
    logits matmul decides every sampled token and is the one projection
    where 4-bit rounding moves argmax measurably, for ~6% of the bytes."""
    if mode == "int4" and path not in QUANT_TOP_KEYS:
        return 4
    return 8


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def _np_quantize(arr: np.ndarray, contract_axis: int,
                 bits: int = 8) -> dict[str, np.ndarray]:
    """Host-side symmetric per-channel quantization (checkpoint load path —
    the int8/int4 copy, not the bf16 original, is what crosses PCIe/DCN)."""
    from ml_dtypes import int4
    levels = (1 << (bits - 1)) - 1          # 127 (int8) / 7 (int4)
    f = np.asarray(arr, np.float32)
    amax = np.max(np.abs(f), axis=contract_axis, keepdims=True)
    scale = np.maximum(amax, 1e-30) / levels
    q = np.clip(np.rint(f / scale), -levels, levels) \
        .astype(np.int8 if bits == 8 else int4)
    return {"q": q, "s": np.squeeze(scale, axis=contract_axis)}


def quantize_array(w: jax.Array, contract_axis: int,
                   bits: int = 8) -> dict[str, jax.Array]:
    """Device-side twin of :func:`_np_quantize` (random-init path)."""
    levels = (1 << (bits - 1)) - 1
    f = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(f), axis=contract_axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / levels
    q = jnp.clip(jnp.round(f / scale), -levels, levels) \
        .astype(jnp.int8 if bits == 8 else jnp.int4)
    return {"q": q, "s": jnp.squeeze(scale, axis=contract_axis)}


def quantizes(path: str) -> bool:
    """Whether a param path participates in int8 quantization: the
    llama-family stacked layer matmuls, MoE expert matmuls, and lm_head
    (norms, biases, router, and the embed table stay full precision)."""
    if path in QUANT_TOP_KEYS:
        return True
    return (path.startswith("layers.")
            and path.split(".", 1)[1] in QUANT_LAYER_KEYS)


def contract_axis_for(path: str, ndim: int) -> int | None:
    """Which axis a quantized *stacked* weight contracts over, or None if
    the param doesn't quantize. Paths follow parallel/sharding.py's dot-key
    scheme."""
    if not quantizes(path):
        return None
    if ndim == 4:   # MoE expert [L, E, D_in, D_out] → per-(e, out) scale
        return 2
    return 1        # lm_head [V, D] → per-V; layers [L, D_in, D_out] → dim 1


def quantize_tree(params: dict, config: ModelConfig,
                  mode: str = "int8") -> dict:
    """Replace every quantizable leaf of a params tree with its
    ``{"q", "s"}`` dict (random-init path; checkpoint load quantizes
    per-parameter on the host instead — engine/checkpoint.py put hook).

    Tied-embedding models (qwen2/gemma families) have no ``lm_head`` leaf;
    the embed table stays full precision (the gather path reads only B
    rows/step), but the HEAD read — the full ``[V, D]`` matrix every step,
    ~25% of gemma-2b's weight bytes — gets its own int8 copy under
    ``lm_head_q8``. +0.5× embed bytes of storage buys a 2× smaller
    per-step head read, which is the bandwidth that matters at decode."""
    out: dict = {}
    for key, val in params.items():
        if key == "layers":
            out[key] = {
                k: (quantize_array(v, contract_axis_for(f"layers.{k}", v.ndim),
                                   bits=weight_bits(mode, f"layers.{k}"))
                    if contract_axis_for(f"layers.{k}", v.ndim) is not None
                    else v)
                for k, v in val.items()
            }
        elif contract_axis_for(key, getattr(val, "ndim", 0)) is not None:
            out[key] = quantize_array(val, contract_axis_for(key, val.ndim),
                                      bits=weight_bits(mode, key))
        else:
            out[key] = val
    if config.tie_embeddings and "lm_head" not in params:
        out["lm_head_q8"] = quantize_array(params["embed"], 1)
    return out


def _dynamic_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8 quantization of activations over the last dim.
    Returns (xq int8, xs fp32 with a keepdims-1 trailing axis)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = jnp.maximum(amax, 1e-30) / 127.0
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    return xq, xs


def mm_q8(xq: jax.Array, xs: jax.Array, w: dict, dtype) -> jax.Array:
    """``mm`` on rows that are int8 already (``_dynamic_int8``'s pair): a
    caller that sends the same rows through several products, or gathers
    rows of a matrix it quantised whole, pays for the rounding once.
    Per-row quantisation commutes with a gather of rows."""
    acc = jax.lax.dot_general(
        xq, w["q"], (((xq.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * xs * w["s"]
    return y.astype(dtype)


def mm(x: jax.Array, w: Any) -> jax.Array:
    """``x [..., D] @ w [D, F]`` where ``w`` is a plain array or a quantized
    ``{"q", "s"}`` dict. Result in ``x.dtype`` either way."""
    if not is_quantized(w):
        return x @ w
    return mm_q8(*_dynamic_int8(x), w, x.dtype)


def moe_mm_dense(x: jax.Array, w: Any) -> jax.Array:
    """All-experts projection: ``x [N, D] × w [E, D, F] → [E, N, F]``
    (mixtral's dense-routing form), plain or int8 ``{"q","s"}`` (scale
    ``s [E, F]``). Activations quantize once per row, shared by all E."""
    if not is_quantized(w):
        return jnp.einsum("nd,edf->enf", x, w)
    xq, xs = _dynamic_int8(x)                       # [N, D], [N, 1]
    acc = jax.lax.dot_general(
        xq, w["q"], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)           # [N, E, F]
    y = acc.astype(jnp.float32) * xs[:, :, None] * w["s"][None]
    return y.transpose(1, 0, 2).astype(x.dtype)


def moe_mm_batched(x: jax.Array, w: Any) -> jax.Array:
    """Expert-batched projection: ``x [E, C, Din] × w [E, Din, Dout] →
    [E, C, Dout]`` (mixtral's capacity-dispatch form and both down
    projections), plain or int8 (scale ``s [E, Dout]``)."""
    if not is_quantized(w):
        return jnp.einsum("ecd,edf->ecf", x, w)
    xq, xs = _dynamic_int8(x)                       # [E, C, Din], [E, C, 1]
    acc = jax.lax.dot_general(
        xq, w["q"], (((2,), (1,)), ((0,), (0,))),
        preferred_element_type=jnp.int32)           # [E, C, Dout]
    y = acc.astype(jnp.float32) * xs * w["s"][:, None, :]
    return y.astype(x.dtype)


def head_matmul(x: jax.Array, head: Any) -> jax.Array:
    """Logits: ``x [B, T, D] · head [V, D] → [B, T, V]`` fp32. Plain head
    keeps the bf16-read / fp32-accumulate einsum; a quantized head contracts
    int8 against dim 1 directly (no transposed copy materializes)."""
    if not is_quantized(head):
        return jnp.einsum("btd,vd->btv", x, head,
                          preferred_element_type=jnp.float32)
    xq, xs = _dynamic_int8(x)
    acc = jax.lax.dot_general(
        xq, head["q"], (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * head["s"]
