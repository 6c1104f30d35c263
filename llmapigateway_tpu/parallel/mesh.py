"""Device mesh construction.

The communication backend of this framework is XLA collectives over ICI —
the TPU-native equivalent of the NCCL/MPI tier a GPU framework would carry
(SURVEY.md §2b, §5 "Distributed communication backend"). A
:class:`jax.sharding.Mesh` with named axes is the single abstraction
everything shards over:

  axes: ``data`` (DP, batch dim) · ``model`` (TP, weight columns/rows)
        · ``expert`` (EP, MoE experts)

One process drives every device of the mesh (the engine refuses to build
in a process that is one of several).
"""
from __future__ import annotations

import logging
from dataclasses import dataclass, field

import jax
import numpy as np
from jax.sharding import Mesh

from ..config.schemas import MESH_AXES, check_mesh_axes

logger = logging.getLogger(__name__)


@dataclass
class MeshSpec:
    """Named axis sizes; unspecified axes default to 1. ``model`` absorbs
    remaining devices when sizes don't cover the device count and
    ``auto_model`` is set."""
    sizes: dict[str, int] = field(default_factory=dict)
    auto_model: bool = True

    def resolve(self, n_devices: int) -> dict[str, int]:
        check_mesh_axes(self.sizes)
        sizes = {ax: int(self.sizes.get(ax, 1)) for ax in MESH_AXES}
        known = 1
        for ax, s in sizes.items():
            if s <= 0:
                raise ValueError(f"mesh axis {ax} must be positive, got {s}")
            known *= s
        if known == n_devices:
            return sizes
        if self.auto_model and "model" not in self.sizes and \
                n_devices % (known // sizes["model"]) == 0:
            rest = known // sizes["model"]
            if n_devices % rest == 0:
                sizes["model"] = n_devices // rest
                return sizes
        raise ValueError(
            f"mesh sizes {self.sizes} (product {known}) do not match "
            f"{n_devices} devices")


def build_mesh(spec: MeshSpec | dict[str, int] | None = None,
               devices: list | None = None) -> Mesh:
    """Build a mesh over the given (default: all) devices.

    Device order: JAX returns devices in row-major ICI order; reshaping to
    (data, expert, model) keeps the fastest-varying axis (`model` — the
    axis with the most collective traffic) on adjacent ICI neighbors.
    """
    if isinstance(spec, dict):
        spec = MeshSpec(sizes=spec)
    spec = spec or MeshSpec()
    devices = devices if devices is not None else jax.devices()
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[ax] for ax in MESH_AXES)
    arr = np.array(devices).reshape(shape)
    mesh = Mesh(arr, MESH_AXES)
    logger.info("mesh: %s over %d %s devices",
                {ax: s for ax, s in sizes.items() if s > 1} or {"single": 1},
                len(devices), devices[0].platform)
    return mesh

