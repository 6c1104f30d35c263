"""Compile — not just lower — the serving kernels for a described TPU.

``tests/test_tpu_lowering.py`` stops at Mosaic lowering. The chip's own
compiler is installed here and compiles for a chip that is described and
not attached (``v5e:2x2``), which is where the limits interpret mode never
sees are enforced: tile alignment, fast-memory use, and — found by this
file's sharded cases — a Mosaic kernel refused under a partially-manual
``shard_map``. The kernels are compiled at the widths the gateway's
bring-up model serves (Mistral-7B: 32 heads over 8 KV heads of 128, page
256, 32 pages per slot). Nothing runs: a pass here is not a chip run.
The engine's own step programs, the latent kernels and the grouped expert
kernel are tests/test_aot_tpu_programs.py (a file of their own, so that
the two run on a worker each).
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
def full_effort_uncached():
    """What a module that asserts on a COMPILED program needs of the
    compiler. Full effort: tier-1 compiles its tiny programs with most
    optimisations off (tests/conftest.py), and VMEM fit, a pool that is not
    copied and a loop's arrays are read off the program a served engine
    would get. No persistent cache: a compile for a described chip can be
    written to it but not read back without the chip, and says so."""
    from jax.experimental.compilation_cache import compilation_cache
    saved = {name: jax.config.values[name] for name in (
        "jax_disable_most_optimizations", "jax_enable_compilation_cache")}
    for name in saved:
        jax.config.update(name, False)
    compilation_cache.reset_cache()
    yield
    for name, value in saved.items():
        jax.config.update(name, value)
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


@pytest.mark.parametrize("case", [
    # (query heads, KV heads, tokens) -> (bt, KV heads a program).
    # SmallThinker: a fold of SEVEN (448 rows a head: whole sublanes, not
    # whole MXU tiles), two heads a program ...
    ((28, 4, T), (64, 2)),
    # ... and the smallest bucket at each served fold.
    ((28, 4, 8), (8, 4)),
    ((32, 8, 8), (8, 8)),
    ((64, 8, 8), (8, 8)),
    # Command A+: a fold of SIXTEEN, two heads a program since a program
    # holds q and out by the row-block (PR 48).
    ((128, 8, T), (32, 2)),
], ids=["28over4-t512", "28over4-t8", "32over8-t8", "64over8-t8",
        "128over8-t512"])
@WINDOWS
def test_paged_prefill_fold_compiles_for_v5e(chips, window, case):
    """PR 37: the block shapes the rule picks at the served folds are what
    the chip's compiler must find room for (int8 pool, as served)."""
    (heads, kv, tokens), shape = case
    assert pa.prefill_block_shape(tokens, heads // kv, kv, PAGE, DH, 2, 1,
                                  True, 1) == shape
    one = SingleDeviceSharding(chips[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    side = {"q": sds((POOL, kv, PAGE, DH), jnp.int8),
            "s": sds((POOL, kv, 1, PAGE), jnp.float32)}
    _compiled_kernel(
        lambda *a: pa.paged_prefill_attention(
            *a, window=window, interpret=False),
        sds((2, tokens, heads, DH), jnp.bfloat16), side, side,
        sds((2, NP), jnp.int32), sds((2,), jnp.int32))


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


# ---------------------------------------------------------------------------
# PR 30: the pool stays where it lies — the stacked read, the aliased write,
# and the decode program that holds no copy of the pool
# ---------------------------------------------------------------------------

# What the benchmark's two configurations serve: (layers that hold a pool,
# pages, KV heads, query heads, slots, table width, window).
SERVED = {
    "mistral-7b": (32, 169, 8, 32, 8, 32, 4096),
    "solar-open2-ep8": (2, 1025, 8, 64, 32, 32, 0),
    # PR 44, command-a-plus-218b-ep8: 128 query heads over 8 KV heads (16 a
    # group, a 16,384-wide query row), 16 slots of 68 pages. Its two cache
    # groups: six windowed layers on rings of 21 pages, two global layers
    # on the whole context.
    "command-a-plus-ring": (6, 16 * 21 + 1, 8, 128, 16, 68, 4096),
    "command-a-plus-global": (2, 16 * 68 + 1, 8, 128, 16, 68, 0),
}


# Rows of a prefill call (``prefill_batch``) at those geometries.
PREFILL_ROWS = {"mistral-7b": 1, "solar-open2-ep8": 4,
                "command-a-plus-ring": 2, "command-a-plus-global": 2}


def test_the_block_shapes_at_sixteen_heads_a_group_and_at_the_older_cells():
    """Pure shape arithmetic (no chip, no compile): at 16 query heads a KV
    head a 512-token chunk runs 32 positions a row-block and TWO KV heads a
    program (1,024 rows) since a program holds q and out by the row-block
    (PR 48; one head until then), where Mistral (4 a group) gets 128
    positions and two heads and SmallThinker (7 a group, 4 KV heads) 64 and
    two — the shapes PR 37 gave them; a decode block holds all 8 KV heads
    of a page at either grouping."""
    def at(G, KV):
        return pa.prefill_block_shape(T, G, KV, PAGE, DH, 2, 1, True, 1)
    assert at(16, 8) == (32, 2)
    assert at(4, 8) == (128, 2)
    assert at(7, 4) == (64, 2)
    assert pa._decode_heads_per_block(8, PAGE, DH, 1, True, 1) == 8
    assert pa._prefill_vmem_bytes(32, 2, 16, PAGE, DH, 2, 1, True, 1) \
        <= pa._PREFILL_VMEM_BYTES
    # The walk that shape costs: four times Mistral's row-blocks a chunk.
    starts = [8192]
    walked = {bt: pa.prefill_pages_walked(starts, T, bt, PAGE, 4096, 68)[0]
              for bt in (32, 128)}
    assert walked[32] == 16 * 17 and walked[128] == 4 * 17


def _stacked(chips, geometry):
    layers, pages, kv, heads, slots, width, window = SERVED[geometry]
    one = SingleDeviceSharding(chips[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    side = {"q": sds((layers, pages, kv, PAGE, DH), jnp.int8),
            "s": sds((layers, pages, kv, 1, PAGE), jnp.float32)}
    return sds, side, sds((slots, width), jnp.int32)


@pytest.mark.parametrize("geometry", list(SERVED))
def test_stacked_decode_compiles_at_the_served_geometry(chips, geometry):
    """The decode kernel on the whole layer-stacked int8 pool, the layer a
    traced scalar: no slice of the pool is among its operands."""
    layers, pages, kv, heads, slots, width, window = SERVED[geometry]
    sds, side, table = _stacked(chips, geometry)
    compiled = jax.jit(
        lambda q, kn, vn, pk, pv, tbl, n, layer: pa.paged_decode_attention(
            q, kn, vn, pk, pv, tbl, n, layer=layer, window=window,
            interpret=False)).lower(
        sds((slots, heads, DH), jnp.bfloat16),
        sds((slots, kv, DH), jnp.bfloat16),
        sds((slots, kv, DH), jnp.bfloat16), side, side, table,
        sds((slots,), jnp.int32), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("tokens", [8, T])
@pytest.mark.parametrize("geometry", list(SERVED))
def test_stacked_prefill_compiles_at_the_served_geometry(chips, geometry,
                                                         tokens):
    """PR 34: the prefill kernel on the whole layer-stacked int8 pool, a
    512-token chunk a row (PR 37: and the smallest bucket), the layer a
    traced scalar: no slice of the pool is among its operands (the
    transposes of q and of the output are the only temporaries)."""
    layers, pages, kv, heads, slots, width, window = SERVED[geometry]
    sds, side, _ = _stacked(chips, geometry)
    rows = PREFILL_ROWS[geometry]
    T = tokens
    compiled = jax.jit(
        lambda q, pk, pv, tbl, start, layer: pa.paged_prefill_attention(
            q, pk, pv, tbl, start, layer=layer, window=window,
            interpret=False)).lower(
        sds((rows, T, heads, DH), jnp.bfloat16), side, side,
        sds((rows, width), jnp.int32), sds((rows,), jnp.int32),
        sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * rows * T * heads * DH * 2


@pytest.mark.parametrize("tokens", [1, 5], ids=["decode", "verify5"])
@pytest.mark.parametrize("geometry", list(SERVED))
def test_in_place_write_compiles_at_the_served_geometry(chips, geometry,
                                                        tokens):
    """The write kernel with the pool donated: its four pool operands are
    its outputs (all of the pool's bytes aliased, no temporary)."""
    layers, pages, kv, heads, slots, width, window = SERVED[geometry]
    sds, side, table = _stacked(chips, geometry)
    new = sds((layers, slots, tokens, kv, DH), jnp.bfloat16)
    compiled = jax.jit(
        lambda *a: pa.paged_insert_in_place(*a, interpret=False),
        donate_argnums=(0, 1)).lower(
        side, side, new, new, table, sds((slots,), jnp.int32),
        sds((slots,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    pool_bytes = 2 * layers * pages * kv * PAGE * (DH + 4)
    assert memory.alias_size_in_bytes == pool_bytes
    assert memory.temp_size_in_bytes < 2 ** 20


@pytest.mark.parametrize("tokens", [8, 16, 96, 512])
@pytest.mark.parametrize("geometry", list(SERVED))
def test_chunk_write_compiles_at_the_served_geometry(chips, geometry, tokens):
    """PR 34: the chunk write with the pool donated, at a traced layer's
    index: its four pool operands are its outputs (all of the pool's bytes
    aliased), and the temporaries are the chunk's own rows cut into tiles
    — nothing of the pool's size."""
    layers, pages, kv, heads, slots, width, window = SERVED[geometry]
    sds, side, _ = _stacked(chips, geometry)
    rows = PREFILL_ROWS[geometry]
    new = sds((rows, tokens, kv, DH), jnp.bfloat16)
    compiled = jax.jit(
        lambda pk, pv, kn, vn, tbl, start, active, layer:
        pa.paged_insert_chunk_in_place(pk, pv, kn, vn, tbl, start, active,
                                       layer=layer, interpret=False),
        donate_argnums=(0, 1)).lower(
        side, side, new, new, sds((rows, width), jnp.int32),
        sds((rows,), jnp.int32), sds((rows,), jnp.bool_),
        sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes \
        == 2 * layers * pages * kv * PAGE * (DH + 4)
    # Against a layer's side of 44 MB (Mistral) or 268 MB (the expert cell).
    assert memory.temp_size_in_bytes < 16 * rows * (tokens + PAGE) * kv * DH
