"""HF safetensors checkpoints → stacked-layer JAX params, sharded on load.

The reference has no model checkpoints at all (SURVEY.md §5 "Checkpoint /
resume"); this implements the TPU-side story: stream tensors from
safetensors shards and place each directly into its GSPMD sharding layout
(per-device ``jax.device_put``), so a 70B model never materializes unsharded
on one host.

Supports the HF Llama/Mistral naming scheme (TinyLlama, Llama-2/3) and
Mixtral's MoE naming. Torch ``nn.Linear`` stores ``[out, in]``; JAX matmul
layout here is ``[in, out]`` — every projection is transposed on load.
"""
from __future__ import annotations

import json
import logging
import re
from pathlib import Path
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from safetensors import safe_open

from ..models.config import ModelConfig

logger = logging.getLogger(__name__)


def _discover_shards(model_dir: Path) -> list[Path]:
    index = model_dir / "model.safetensors.index.json"
    if index.exists():
        data = json.loads(index.read_text())
        files = sorted(set(data["weight_map"].values()))
        return [model_dir / f for f in files]
    single = model_dir / "model.safetensors"
    if single.exists():
        return [single]
    shards = sorted(model_dir.glob("*.safetensors"))
    if not shards:
        raise FileNotFoundError(f"no safetensors files in {model_dir}")
    return shards


# HF tensor name → (our path, needs_transpose). {i} = layer, {e} = expert.
_LLAMA_MAP: list[tuple[re.Pattern, str, bool]] = [
    (re.compile(r"^model\.embed_tokens\.weight$"), "embed", False),
    (re.compile(r"^model\.norm\.weight$"), "final_norm", False),
    (re.compile(r"^lm_head\.weight$"), "lm_head", False),
    (re.compile(r"^model\.layers\.(\d+)\.input_layernorm\.weight$"),
     "layers.attn_norm.{i}", False),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.q_proj\.weight$"),
     "layers.wq.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.k_proj\.weight$"),
     "layers.wk.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.v_proj\.weight$"),
     "layers.wv.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.o_proj\.weight$"),
     "layers.wo.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.post_attention_layernorm\.weight$"),
     "layers.mlp_norm.{i}", False),
    # Qwen2 QKV bias (1-D: no transpose)
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.q_proj\.bias$"),
     "layers.bq.{i}", False),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.k_proj\.bias$"),
     "layers.bk.{i}", False),
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.v_proj\.bias$"),
     "layers.bv.{i}", False),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.gate_proj\.weight$"),
     "layers.wg.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.up_proj\.weight$"),
     "layers.wu.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.down_proj\.weight$"),
     "layers.wd.{i}", True),
    # Phi-3 family: HF ships the attention and MLP up-projections FUSED
    # (qkv_proj [(H+2KV)*Dh, D], gate_up_proj [2F, D]). Mapped to
    # placeholder keys; load_checkpoint splits them into the stacked
    # wq/wk/wv and wg/wu params (split happens at SOURCE precision and
    # BEFORE the preprocess hook, so int8-at-source quantization scales
    # are per-projection, identical to an unfused checkpoint's).
    (re.compile(r"^model\.layers\.(\d+)\.self_attn\.qkv_proj\.weight$"),
     "layers.__qkv__.{i}", False),
    (re.compile(r"^model\.layers\.(\d+)\.mlp\.gate_up_proj\.weight$"),
     "layers.__gu__.{i}", False),
    # Mixtral MoE
    (re.compile(r"^model\.layers\.(\d+)\.block_sparse_moe\.gate\.weight$"),
     "layers.router.{i}", True),
    (re.compile(r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w1\.weight$"),
     "layers.wg.{i}.{e}", True),
    (re.compile(r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w3\.weight$"),
     "layers.wu.{i}.{e}", True),
    (re.compile(r"^model\.layers\.(\d+)\.block_sparse_moe\.experts\.(\d+)\.w2\.weight$"),
     "layers.wd.{i}.{e}", True),
]


def _map_name(hf_name: str) -> tuple[str, int | None, int | None, bool] | None:
    """→ (bare param key, layer index, expert index, transpose) or None.
    The key is the leaf name inside the params tree ('wq', 'attn_norm', ...
    or 'embed'/'final_norm'/'lm_head' for layerless tensors)."""
    for pattern, target, transpose in _LLAMA_MAP:
        m = pattern.match(hf_name)
        if m:
            groups = m.groups()
            layer = int(groups[0]) if groups else None
            expert = int(groups[1]) if len(groups) > 1 else None
            key = target.split(".{i}")[0]
            if key.startswith("layers."):
                key = key[len("layers."):]
            return key, layer, expert, transpose
    return None


def _fused_bounds(key: str, c: ModelConfig) -> list[tuple[str, int, int]]:
    """Row ranges of each projection inside a Phi-3 fused tensor (HF
    orientation: rows are the output dim)."""
    if key == "__qkv__":
        qw = c.n_heads * c.head_dim
        kvw = c.n_kv_heads * c.head_dim
        return [("wq", 0, qw), ("wk", qw, qw + kvw),
                ("wv", qw + kvw, qw + 2 * kvw)]
    return [("wg", 0, c.d_ff), ("wu", c.d_ff, 2 * c.d_ff)]


def load_checkpoint(model_dir: str | Path, config: ModelConfig,
                    dtype: jnp.dtype = jnp.bfloat16,
                    put: Callable[[str, np.ndarray], jax.Array] | None = None,
                    preprocess: Callable[[str, np.ndarray],
                                         np.ndarray | dict] | None = None
                    ) -> dict[str, Any]:
    """Load an HF checkpoint into the stacked-layer params layout.

    ``put(param_path, np_array) -> jax.Array`` controls placement — the
    engine passes a sharded ``device_put``; default is plain host transfer.
    Stacking happens per-parameter: each layer's tensor is placed as soon as
    all layers for that name are read, bounding host memory.

    ``preprocess(param_path, tensor)`` runs on each tensor at the
    checkpoint's SOURCE precision, before the target-dtype cast and before
    layer stacking — the int8-quantization hook (quant levels computed from
    fp16/fp32 source values, not from a bf16-rounded copy, and the host
    stacks int8 instead of bf16). It may return a ``{"q": ..., "s": ...}``
    dict; each sub-leaf is then stacked and placed under ``path.key``.
    Default: cast to ``dtype``.
    """
    if config.layer_period:
        raise ValueError(
            f"no checkpoint mapping for the {config.family!r} family: the "
            f"published tensor names of its attention and expert layers "
            f"are not known here; it is served on seeded random weights")
    model_dir = Path(model_dir)
    shards = _discover_shards(model_dir)
    put = put or (lambda path, arr: jnp.asarray(arr))
    preprocess = preprocess or (
        lambda path, arr: arr.astype(_np_dtype(dtype)))

    # Pass 1: index — which shard holds each mapped tensor (metadata only).
    index: dict[str, tuple[Path, str, bool, int | None, int | None]] = {}
    grouped: dict[str, list[str]] = {}     # param key -> [hf names]
    for shard in shards:
        with safe_open(str(shard), framework="numpy") as f:
            for name in f.keys():
                mapped = _map_name(name)
                if mapped is None:
                    logger.debug("skipping unmapped tensor %s", name)
                    continue
                key, layer, expert, transpose = mapped
                index[name] = (shard, key, transpose, layer, expert)
                grouped.setdefault(key, []).append(name)

    # Pass 2: one parameter group at a time — read its tensors (layer by
    # layer), stack, place sharded, free. Host memory is bounded by the
    # largest single stacked parameter, not the whole checkpoint.
    open_shards: dict[Path, Any] = {}

    def read_raw(name: str) -> np.ndarray:
        """One tensor at source precision, HF orientation."""
        shard, _, _, _, _ = index[name]
        if shard not in open_shards:
            open_shards[shard] = safe_open(str(shard), framework="numpy")
        return np.asarray(open_shards[shard].get_tensor(name))

    def read(name: str, path: str) -> np.ndarray | dict:
        """One tensor at source precision → preprocessed (cast/quantized)."""
        arr = read_raw(name)
        if index[name][2]:
            arr = arr.T
        return preprocess(path, arr)

    def place(path: str, value: np.ndarray | dict):
        if isinstance(value, dict):
            return {k: put(f"{path}.{k}", v) for k, v in value.items()}
        return put(path, value)

    def stack(values: list) -> np.ndarray | dict:
        if isinstance(values[0], dict):
            return {k: np.stack([v[k] for v in values]) for k in values[0]}
        return np.stack(values)

    params: dict[str, Any] = {"layers": {}}
    try:
        for key, names in grouped.items():
            entries = [(index[n][3], index[n][4], n) for n in names]
            if key in ("__qkv__", "__gu__"):
                # Phi-3 fused tensors: split rows per projection at source
                # precision, then transpose/preprocess/stack each exactly
                # like an unfused checkpoint's tensors. Rows are read via
                # get_slice so each projection's range is read once (no
                # whole-tensor re-read per sub) — and the fused row count
                # is validated against the config-derived bounds: numpy
                # slice-clamping would otherwise turn a geometry mismatch
                # into silently wrong weights with config-derived shapes
                # that pass _validate_shapes.
                by_l = {l: n for l, _, n in entries}
                n_layers = max(by_l) + 1
                subs = _fused_bounds(key, config)
                expect_rows = subs[-1][2]

                def read_rows(name, lo, hi):
                    shard = index[name][0]
                    if shard not in open_shards:
                        open_shards[shard] = safe_open(str(shard),
                                                       framework="numpy")
                    sl = open_shards[shard].get_slice(name)
                    rows = sl.get_shape()[0]
                    if rows != expect_rows:
                        raise ValueError(
                            f"fused tensor {name} has {rows} rows; config "
                            f"implies {expect_rows} "
                            f"({[s[0] for s in subs]})")
                    return np.asarray(sl[lo:hi])

                for sub, lo, hi in subs:
                    path = f"layers.{sub}"
                    stacked = stack([
                        preprocess(path, read_rows(by_l[l], lo, hi).T)
                        for l in range(n_layers)])
                    params["layers"][sub] = place(path, stacked)
                    del stacked
                continue
            if entries[0][0] is None:                       # layerless tensor
                params[key] = place(key, read(names[0], key))
                continue
            path = f"layers.{key}"
            has_experts = any(e is not None for (_, e, _) in entries)
            by_pos = {(l, e): n for l, e, n in entries}
            n_layers = max(l for l, _, _ in entries) + 1
            if has_experts:
                n_experts = max(e for _, e, _ in entries) + 1
                stacked = stack([
                    stack([read(by_pos[(l, e)], path)
                           for e in range(n_experts)])
                    for l in range(n_layers)])
            else:
                stacked = stack([read(by_pos[(l, None)], path)
                                 for l in range(n_layers)])
            params["layers"][key] = place(path, stacked)
            del stacked
    finally:
        open_shards.clear()

    if "lm_head" not in params:
        if not config.tie_embeddings:
            logger.info("no lm_head in checkpoint; using tied embeddings")
        params["lm_head"] = params["embed"]
    _validate_shapes(params, config)
    return params


def _np_dtype(dtype: jnp.dtype):
    # numpy has no bfloat16; use ml_dtypes (bundled with jax).
    if dtype == jnp.bfloat16:
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(dtype)


def _shape(p: Any) -> tuple[int, ...]:
    """Leaf shape; an int8-quantized leaf is a {"q","s"} dict whose logical
    shape is the int8 tensor's (models/quant.py)."""
    return tuple(p["q"].shape) if isinstance(p, dict) else tuple(p.shape)


def _validate_shapes(params: dict[str, Any], config: ModelConfig) -> None:
    c = config
    checks = {
        "embed": (c.vocab_size, c.d_model),
        "final_norm": (c.d_model,),
    }
    for key, want in checks.items():
        got = _shape(params[key])
        if got != want:
            raise ValueError(f"checkpoint/config mismatch: {key} is {got}, "
                             f"config implies {want}")
    lk = params["layers"]
    required = {"attn_norm", "wq", "wk", "wv", "wo", "mlp_norm"}
    required |= {"router"} if c.is_moe else {"wg", "wu", "wd"}
    if c.attn_bias:
        # A qwen2-family checkpoint with missing/unmapped bias tensors must
        # refuse to load, not silently run bias-free.
        required |= {"bq", "bk", "bv"}
    missing = required - set(lk)
    if missing:
        raise ValueError(f"checkpoint is missing layer params {sorted(missing)}; "
                         f"loaded keys: {sorted(lk)}")
    want = (c.n_layers, c.d_model, c.n_heads * c.head_dim)
    if _shape(lk["wq"]) != want:
        raise ValueError(f"checkpoint/config mismatch: layers.wq is "
                         f"{_shape(lk['wq'])}, config implies {want}")
