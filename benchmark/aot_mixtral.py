"""Scratch script, not a test and not part of a run: compile Mixtral-8x7B's
big programs for a DESCRIBED four-chip v5e host (no chip attached) and
print what each needs per chip, before any four-chip minute is spent.

    JAX_PLATFORMS=cpu python -m benchmark.aot_mixtral

Compiled: the engine's own random-init program (``_random_init_program``,
borrowed through a stand-in object that carries the four attributes it
reads), and the model's forward over 8 rows x 512 tokens (a full prefill
group) and 8 rows x 1 token (a decode step) on a contiguous int8 cache of
8 x 4096 — the MoE block, the sharding rules and the all-reduces are the
engine's; the paged kernels and the scheduler's page table are not in
these two (the kernels' own compile is tests/test_aot_tpu_compile.py).
A compile that passes is not a chip run.
"""
from __future__ import annotations

import json
import os
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax                      # noqa: E402
import jax.numpy as jnp         # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402


def main() -> None:
    from jax.experimental import topologies
    from llmapigateway_tpu.engine.engine import InferenceEngine
    from llmapigateway_tpu.models import PRESETS, forward_fn, llama
    from llmapigateway_tpu.parallel.mesh import MeshSpec, build_mesh
    from llmapigateway_tpu.parallel.sharding import (cache_sharding,
                                                     param_shardings)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = build_mesh(MeshSpec(sizes={"model": 4}), list(topo.devices))
    c = PRESETS["mixtral-8x7b"]
    stand_in = types.SimpleNamespace(model_cfg=c, quant="int8",
                                     dtype=jnp.bfloat16, mesh=mesh)
    init, key = InferenceEngine._random_init_program(stand_in)
    out: dict[str, dict] = {}

    def report(name: str, compiled) -> None:
        m = compiled.memory_analysis()
        text = compiled.as_text()
        out[name] = {
            "argument_gb": m.argument_size_in_bytes / 1e9,
            "output_gb": m.output_size_in_bytes / 1e9,
            "temp_gb": m.temp_size_in_bytes / 1e9,
            "peak_gb": (m.argument_size_in_bytes + m.output_size_in_bytes
                        + m.temp_size_in_bytes
                        - m.alias_size_in_bytes) / 1e9,
            "all_reduces": text.count("all-reduce("),
            "all_to_alls": text.count("all-to-all(")}
        print(json.dumps({name: out[name]}), flush=True)

    report("random_init", init.lower(jax.ShapeDtypeStruct(
        key.shape, key.dtype)).compile())

    shapes = jax.eval_shape(init, key)
    shard = param_shardings(shapes, mesh)
    params = jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=sh), shapes, shard)
    rep = NamedSharding(mesh, P())
    B, S = 8, 4096
    kv = cache_sharding(mesh, c.n_kv_heads, B)
    cache_shapes = jax.eval_shape(
        lambda: llama.KVCache.create(c, B, S, jnp.bfloat16, "int8"))
    cache = jax.tree.map(lambda s: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=kv), cache_shapes)
    fwd = forward_fn(c)
    for name, t in (("forward_8x512_contiguous", 512),
                    ("forward_8x1_contiguous", 1)):
        def run(p, tokens, lengths, kvc):
            return fwd(p, c, tokens, lengths, kvc)
        report(name, jax.jit(run, donate_argnums=(3,)).lower(
            params, jax.ShapeDtypeStruct((B, t), jnp.int32, sharding=rep),
            jax.ShapeDtypeStruct((B,), jnp.int32, sharding=rep),
            cache).compile())


if __name__ == "__main__":
    main()
