"""GSPMD sharding rules: param-tree → NamedSharding.

Megatron-style tensor parallelism expressed purely as shardings — XLA
inserts the collectives (all-reduce after row-parallel matmuls rides ICI on
the ``model`` axis):

* attention/MLP input projections (wq/wk/wv/wg/wu): column-parallel —
  output dim sharded on ``model``;
* output projections (wo/wd): row-parallel — input dim sharded on ``model``;
* lm_head: vocab-sharded (logit all-gather at the end);
* norms: replicated; embed: vocab-sharded when divisible;
* MoE expert weights: expert dim on ``expert``, then column/row on ``model``;
* KV cache: batch on ``data``, KV heads on ``model`` when divisible
  (GQA with fewer KV heads than chips → heads replicated, which matches the
  usual TPU serving layout).

Every rule degrades to replication when the dim isn't divisible by the axis
size — correctness never depends on a particular mesh shape.
"""
from __future__ import annotations

import logging
from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


def _axis(mesh: Mesh, name: str, dim_size: int) -> str | None:
    """Use `name` for a dim only if the axis exists and divides the dim."""
    size = mesh.shape.get(name, 1)
    if size > 1 and dim_size % size == 0:
        return name
    return None


# param path (dot key) → function(shape, mesh) -> PartitionSpec
def _spec_for(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    # The tied-embedding int8 head copy (models/quant.py quantize_tree)
    # shards exactly like a real lm_head.
    if path.startswith("lm_head_q8"):
        path = "lm_head" + path[len("lm_head_q8"):]
    # Int8-quantized weights (models/quant.py) add ".q"/".s" sub-leaves:
    # the int8 tensor shards exactly like the bf16 weight it replaces; the
    # per-output-channel scale shards like the weight's output dim (so a
    # column-parallel matmul keeps scale shards co-resident with their
    # channels, and a row-parallel one keeps the scale replicated — the
    # fp32 rescale commutes with the int32 partial-sum all-reduce).
    if path.endswith(".q"):
        return _spec_for(path[:-2], shape, mesh)
    if path.endswith(".s"):
        base = path[:-2]
        if base == "lm_head":                       # [V]
            return P(_axis(mesh, "model", shape[0]))
        key = base.split(".", 1)[1] if base.startswith("layers.") else base
        if key in ("wq", "wk", "wv", "wg", "wu"):   # column-parallel [L, out]
            if len(shape) == 3:                     # MoE expert [L, E, F]
                return P(None, _axis(mesh, "expert", shape[1]),
                         _axis(mesh, "model", shape[2]))
            return P(None, _axis(mesh, "model", shape[1]))
        if key in ("wo", "wd"):                     # row-parallel: out replicated
            if len(shape) == 3:                     # MoE expert [L, E, D]
                return P(None, _axis(mesh, "expert", shape[1]), None)
            return P(None, None)
        return P()
    if path == "embed" or path == "lm_head":
        return P(_axis(mesh, "model", shape[0]), None)
    if path in ("final_norm",):
        return P(None)
    if path.startswith("layers."):
        key = path.split(".", 1)[1]     # dim 0 is the stacked layer dim
        if key in ("attn_norm", "mlp_norm"):
            return P(None, None)
        if key == "router":                       # [L, D, E]
            return P(None, None, None)
        if key in ("bq", "bk", "bv"):             # [L, out] column bias
            return P(None, _axis(mesh, "model", shape[1]))
        n = len(shape)
        if key in ("wq", "wk", "wv", "wg", "wu"):
            if n == 4:                            # MoE expert: [L, E, D, F]
                return P(None, _axis(mesh, "expert", shape[1]), None,
                         _axis(mesh, "model", shape[3]))
            return P(None, None, _axis(mesh, "model", shape[2]))
        if key in ("wo", "wd"):
            if n == 4:                            # [L, E, F, D]
                return P(None, _axis(mesh, "expert", shape[1]),
                         _axis(mesh, "model", shape[2]), None)
            return P(None, _axis(mesh, "model", shape[1]), None)
    logger.debug("no sharding rule for %s %s; replicating", path, shape)
    return P()


def _tree_paths(tree: Any, prefix: str = "") -> dict[str, Any]:
    out = {}
    for key, val in tree.items():
        path = f"{prefix}{key}"
        if isinstance(val, dict):
            out.update(_tree_paths(val, path + "."))
        else:
            out[path] = val
    return out


def param_shardings(params_or_shapes: Any, mesh: Mesh) -> Any:
    """Mirror the params pytree with NamedShardings."""
    def build(tree, prefix=""):
        out = {}
        for key, val in tree.items():
            path = f"{prefix}{key}"
            if isinstance(val, dict):
                out[key] = build(val, path + ".")
            elif isinstance(val, tuple):    # one tree per layer of a period
                out[key] = tuple(build(v, f"{path}.{i}.")
                                 for i, v in enumerate(val))
            else:
                out[key] = NamedSharding(mesh, _spec_for(path, tuple(val.shape), mesh))
        return out
    return build(params_or_shapes)


def spec_for_param(path: str, shape: tuple[int, ...], mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, _spec_for(path, shape, mesh))


def cache_sharding(mesh: Mesh, n_kv_heads: int, batch: int
                   ) -> NamedSharding:
    """KV cache [L, B, KV, S, Dh] (head-major): batch on data, KV heads on
    model."""
    return NamedSharding(mesh, P(
        None, _axis(mesh, "data", batch),
        _axis(mesh, "model", n_kv_heads), None, None))


def paged_cache_sharding(mesh: Mesh, n_kv_heads: int) -> NamedSharding:
    """Paged pool [L, P, KV, page, Dh]: KV heads on model. The page dim is
    a global pool indexed by the (replicated) page table — unsharded."""
    return NamedSharding(mesh, P(
        None, None, _axis(mesh, "model", n_kv_heads), None, None))


def batch_sharding(mesh: Mesh, batch: int) -> NamedSharding:
    """[B, ...] host batch arrays: batch dim on data axis."""
    return NamedSharding(mesh, P(_axis(mesh, "data", batch)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
