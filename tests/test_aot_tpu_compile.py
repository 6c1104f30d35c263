"""Compile — not just lower — the serving kernels for a described TPU.

``tests/test_tpu_lowering.py`` stops at Mosaic lowering. The chip's own
compiler is installed here and compiles for a chip that is described and
not attached (``v5e:2x2``), which is where the limits interpret mode never
sees are enforced: tile alignment, fast-memory use, and — found by this
file's sharded cases — a Mosaic kernel refused under a partially-manual
``shard_map``. The kernels are compiled at the widths the gateway's
bring-up model serves (Mistral-7B: 32 heads over 8 KV heads of 128, page
256, 32 pages per slot). Nothing runs: a pass here is not a chip run.
"""
from __future__ import annotations

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax                                          # noqa: E402
import jax.numpy as jnp                             # noqa: E402
import pytest                                       # noqa: E402
from jax.sharding import (NamedSharding,            # noqa: E402
                          PartitionSpec as P, SingleDeviceSharding)

from llmapigateway_tpu.ops import paged_attention as pa        # noqa: E402
from llmapigateway_tpu.parallel.mesh import build_mesh         # noqa: E402

B, H, KV, DH, PAGE, NP, T = 8, 32, 8, 128, 256, 32, 512
POOL = B * NP + 4                 # the ppb=2 / 4 kernels need whole runs
WINDOWS = pytest.mark.parametrize("window", [0, 4096],
                                  ids=["full", "window4096"])
KV_DTYPES = pytest.mark.parametrize("quant", [False, True],
                                    ids=["bf16", "int8kv"])
PPB = pytest.mark.parametrize("ppb", [1, 2], ids=["ppb1", "ppb2"])


@pytest.fixture(scope="module")
def chips():
    """The four described chips of a v5e 2x2 host."""
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r}")
    return list(topo.devices)


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip: keep it off, and silent."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", saved)
    compilation_cache.reset_cache()


def _shapes(quant: bool, place):
    """(pool side, page table) as shapes placed by ``place(spec)``: the
    pool's KV-head dim is the one a mesh shards."""
    def sds(shape, dtype, spec=P()):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place(spec))
    heads = P(None, "model", None, None)
    if quant:
        side = {"q": sds((POOL, KV, PAGE, DH), jnp.int8, heads),
                "s": sds((POOL, KV, 1, PAGE), jnp.float32, heads)}
    else:
        side = sds((POOL, KV, PAGE, DH), jnp.bfloat16, heads)
    return sds, side, sds((B, NP), jnp.int32)


def _compiled_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@KV_DTYPES
@WINDOWS
@PPB
def test_paged_decode_compiles_for_v5e(chips, quant, window, ppb):
    sds, side, table = _shapes(
        quant, lambda spec: SingleDeviceSharding(chips[0]))
    _compiled_kernel(
        lambda *a: pa.paged_decode_attention(
            *a, window=window, pages_per_block=ppb, interpret=False),
        sds((B, H, DH), jnp.bfloat16), sds((B, KV, DH), jnp.bfloat16),
        sds((B, KV, DH), jnp.bfloat16), side, side, table,
        sds((B,), jnp.int32))


@pytest.mark.parametrize("case", [
    # (KV, G, ppb, quant, window) -> heads in a block. The module's B,
    # page 256, Dh 128 and 32 pages a slot are the benchmarked geometry;
    # int8 with window 4096 IS the benchmarked kernel (also above).
    ((8, 4, 1, True, 4096), 8),
    # A bf16 pool in runs of four pages: the VMEM-budget rule folds four
    # of the eight heads (two programs), and the buffers it sized are
    # what the chip's compiler must find room for.
    ((8, 4, 4, False, 0), 4),
    ((8, 4, 4, True, 4096), 4),
    # One query row a head (MQA-style grouping), everything folded.
    ((8, 1, 2, False, 4096), 8),
], ids=["mistral7b-int8", "bf16-ppb4", "int8-ppb4", "g1-ppb2"])
def test_paged_decode_head_fold_compiles_for_v5e(chips, case):
    (kv, g, ppb, quant, window), heads = case
    assert pa._decode_heads_per_block(
        kv, PAGE, DH, 1 if quant else 2, quant, ppb) == heads
    sds, side, table = _shapes(
        quant, lambda spec: SingleDeviceSharding(chips[0]))
    _compiled_kernel(
        lambda *a: pa.paged_decode_attention(
            *a, window=window, pages_per_block=ppb, interpret=False),
        sds((B, kv * g, DH), jnp.bfloat16), sds((B, kv, DH), jnp.bfloat16),
        sds((B, kv, DH), jnp.bfloat16), side, side, table,
        sds((B,), jnp.int32))


@KV_DTYPES
@WINDOWS
@PPB
def test_paged_prefill_compiles_for_v5e(chips, quant, window, ppb):
    sds, side, table = _shapes(
        quant, lambda spec: SingleDeviceSharding(chips[0]))
    _compiled_kernel(
        lambda *a: pa.paged_prefill_attention(
            *a, window=window, pages_per_block=ppb, interpret=False),
        sds((B, T, H, DH), jnp.bfloat16), side, side, table,
        sds((B,), jnp.int32))


@KV_DTYPES
@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_tp4_attention_compiles_on_the_engine_mesh(chips, quant, kind):
    """What a ``"mesh": {"model": 4}`` engine runs: the kernels under
    shard_map on the engine's own FIVE-axis mesh. Four of its axes have
    size 1, and a map manual over ``model`` alone is still refused by the
    chip's compiler ("Mosaic kernels cannot be automatically partitioned")
    — which no CPU run and no one-axis test mesh can show."""
    mesh = build_mesh({"model": 4}, devices=chips)
    sds, side, table = _shapes(quant, lambda spec: NamedSharding(mesh, spec))
    t = 1 if kind == "decode" else T
    heads = P(None, None, "model", None)
    args = (sds((B, t, H, DH), jnp.bfloat16, heads),
            sds((B, t, KV, DH), jnp.bfloat16, heads),
            sds((B, t, KV, DH), jnp.bfloat16, heads),
            side, side, sds((B,), jnp.int32), sds((B,), jnp.bool_))

    def attend(q, kn, vn, pk, pv, lengths, active, tbl):
        fn = pa.make_paged_attention_fn(tbl, max_seq=PAGE * NP,
                                        impl="pallas", interpret=False,
                                        mesh=mesh, window=4096)
        if kind == "decode":
            return fn.decode(q, kn, vn, pk, pv, lengths, active)
        return fn(q, kn, vn, pk, pv, lengths, active)[0]
    _compiled_kernel(attend, *args, table)
