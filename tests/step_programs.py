"""The ENGINE'S OWN step programs of a period family, lowered from shapes
alone: ``InferenceEngine._compile`` on a stand-in that carries what
it reads, its parameters, cache, penalty counts and page tables as
``ShapeDtypeStruct`` placed on the given device (a CPU, or a chip that is
described and not attached). Nothing runs and nothing is allocated."""
from __future__ import annotations

import types

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def lower_step_program(config, device, program: str, *, quant: str,
                       kv_quant: str, dtype, page: int, slots: int,
                       per_slot: int, depth: int, chunk: int = 512,
                       group_pages: tuple[int, ...] | None = None):
    """``decode``: the greedy ``decode_scan`` of ``depth`` steps;
    ``prefill-<rows>``: ``prefill_step`` on that many rows of ``chunk``
    tokens. ``slots`` slots of ``per_slot`` pages of ``page`` tokens (and
    the trash page; ``group_pages``: the pages of each cache group's pool
    instead, where a ring's pool is smaller than a whole context's).
    Returns (the lowered program, the cache's shapes)."""
    from llmapigateway_tpu.engine.engine import InferenceEngine
    from llmapigateway_tpu.engine.sampling import SamplingParams
    from llmapigateway_tpu.models import hybrid
    from llmapigateway_tpu.parallel.mesh import build_mesh

    pages = slots * per_slot + 1
    config = config.served()
    mesh = build_mesh({}, devices=[device])
    engine = types.SimpleNamespace(
        model_cfg=config, quant=quant, kv_quant=kv_quant, dtype=dtype,
        mesh=mesh, attention_impl="pallas", kv_ppb=1, S=per_slot * page,
        B=slots, spec_k=0, decode_burst=depth, _burst_depths=(depth,),
        allocator=types.SimpleNamespace(num_pages=pages, page_size=page))
    InferenceEngine._compile(engine)
    init, key = InferenceEngine._random_init_program(engine)
    placed = NamedSharding(mesh, P())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=placed)

    def shapes(tree):
        return jax.tree.map(lambda s: sds(s.shape, s.dtype), tree)
    cache = shapes(jax.eval_shape(lambda: hybrid.HybridCache.create(
        config, group_pages or pages, page, slots, dtype, kv_quant)))
    state = (shapes(jax.eval_shape(init, key)), cache,
             sds((slots, config.vocab_size), jnp.int32),
             tuple(sds((slots, per_slot), jnp.int32)
                   for _ in config.cache_groups))
    rng = jax.random.key(0)
    rng = jax.ShapeDtypeStruct(rng.shape, rng.dtype)
    if program == "decode":
        vec = lambda dtype: sds((slots,), dtype)
        sampling = SamplingParams(
            temperature=vec(jnp.float32), top_p=vec(jnp.float32),
            top_k=vec(jnp.int32), presence_penalty=vec(jnp.float32),
            frequency_penalty=vec(jnp.float32))
        return engine._decode_fns[True][1][depth].lower(
            *state, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), sampling,
            rng), cache
    rows = int(program.rpartition("-")[2])
    vec = lambda dtype: sds((rows,), dtype)
    return engine._prefill_fn.lower(
        *state, sds((rows, chunk), jnp.int32), vec(jnp.int32),
        vec(jnp.int32), vec(jnp.int32), vec(jnp.float32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.float32), rng,
        *([vec(jnp.bool_)] if engine._rows_stop else [])), cache
