from .mesh import build_mesh, MeshSpec
from .sharding import (
    batch_sharding,
    cache_sharding,
    paged_cache_sharding,
    param_shardings,
)

__all__ = ["build_mesh", "MeshSpec", "param_shardings", "cache_sharding",
           "paged_cache_sharding", "batch_sharding"]
