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
import numpy as np                                  # noqa: E402
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


@pytest.mark.parametrize("case", [
    # (query heads, KV heads, tokens) -> (bt, KV heads a program).
    # SmallThinker: a fold of SEVEN (448 rows a head: whole sublanes, not
    # whole MXU tiles), two heads a program ...
    ((28, 4, T), (64, 2)),
    # ... and the smallest bucket at each served fold.
    ((28, 4, 8), (8, 4)),
    ((32, 8, 8), (8, 8)),
    ((64, 8, 8), (8, 8)),
], ids=["28over4-t512", "28over4-t8", "32over8-t8", "64over8-t8"])
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
    head a 512-token chunk runs 32 positions a row-block and one KV head a
    program (512 rows), where Mistral (4 a group) gets 128 positions and
    two heads and SmallThinker (7 a group, 4 KV heads) 64 and two — the
    shapes PR 37 gave them, which PR 44 left alone; a decode block holds
    all 8 KV heads of a page at either grouping."""
    def at(G, KV):
        return pa.prefill_block_shape(T, G, KV, PAGE, DH, 2, 1, True, 1)
    assert at(16, 8) == (32, 1)
    assert at(4, 8) == (128, 2)
    assert at(7, 4) == (64, 2)
    assert pa._decode_heads_per_block(8, PAGE, DH, 1, True, 1) == 8
    assert pa._prefill_vmem_bytes(32, 1, T, 16, PAGE, DH, 2, 1, True, 1) \
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


def _loop_arrays(text: str, at_least: int) -> list[tuple[str, str, str]]:
    """(instruction, opcode, line) for every instruction inside the
    program's loops — the while bodies and what they call, fused
    computations excluded: their insides are not materialised — whose
    result holds an array of ``at_least`` bytes or more."""
    import re
    width = {"s8": 1, "u8": 1, "pred": 1, "bf16": 2, "f16": 2, "s16": 2,
             "f32": 4, "s32": 4, "u32": 4}
    bodies: dict[str, list[str]] = {}
    name = None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            bodies[name].append(line)
    calls = {n: {c for ln in lines if " fusion(" not in ln
                 for c in re.findall(
                     r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)", ln)}
             for n, lines in bodies.items()}
    todo = [b for lines in bodies.values() for ln in lines
            if " while(" in ln for b in re.findall(r"body=%?([\w.\-]+)", ln)]
    assert todo, "the program has no loop"
    inside: set[str] = set()
    while todo:
        n = todo.pop()
        if n not in inside and n in bodies:
            inside.add(n)
            todo += calls[n]
    found = []
    for n in sorted(inside):
        for ln in bodies[n]:
            m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(?[^=]*?\)?) "
                         r"([\w\-]+)\(", ln)
            if not m or m.group(3) in ("parameter", "get-tuple-element",
                                       "tuple", "while", "bitcast"):
                continue
            sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
                     * width[dt]
                     for dt, dims in re.findall(
                         r"\b([a-z]+\d+|pred)\[([\d,]*)\]", m.group(2))
                     if dt in width]
            if sizes and max(sizes) >= at_least:
                found.append((m.group(1), m.group(3), ln.strip()))
    return found


def _two_layer_engine(chips, monkeypatch, slots: int, pages: int,
                      depth: int):
    """The ENGINE'S OWN step programs (its ``_compile_paged`` on a stand-in
    that carries what it reads) at Mistral-7B's widths, two layers, int8
    weights and pool, with the shapes of what every program takes first
    (params, cache, penalty counts, page table) placed on the described
    chip. The pool is 513 pages — two layers of 169 would fit the chip's
    128 MiB of VMEM, where the compiler then parks the WHOLE pool with a
    copy in and out: an artefact of a two-layer model."""
    import types
    from dataclasses import replace

    from llmapigateway_tpu.engine.engine import InferenceEngine
    from llmapigateway_tpu.models import PRESETS

    # The kernels are chosen for the CPU backend the process runs on; the
    # program is compiled for the chip.
    monkeypatch.setattr(pa, "_interpret_default", lambda: False)
    config = replace(PRESETS["mistral-7b"], n_layers=2)
    mesh = build_mesh({}, devices=chips[:1])
    engine = types.SimpleNamespace(
        model_cfg=config, quant="int8", dtype=jnp.bfloat16, mesh=mesh,
        attention_impl="pallas", kv_ppb=1, S=8192, B=slots, spec_k=0,
        decode_burst=depth, _burst_depths=(depth,),
        allocator=types.SimpleNamespace(num_pages=pages, page_size=PAGE))
    InferenceEngine._compile_paged(engine)
    assert engine.kv_pool_in_place
    init, key = InferenceEngine._random_init_program(engine)
    placed = NamedSharding(mesh, P())

    def shapes(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=placed), tree)
    cache = shapes(jax.eval_shape(lambda: pa.PagedKVCache.create(
        config, pages, PAGE, jnp.bfloat16, "int8")))
    state = (shapes(jax.eval_shape(init, key)), cache,
             jax.ShapeDtypeStruct((slots, config.vocab_size), jnp.int32,
                                  sharding=placed),
             # the page tables: one a cache group, Mistral has one
             (jax.ShapeDtypeStruct((slots, 32), jnp.int32, sharding=placed),))
    return engine, config, state, placed


def _holds_no_copy_of_the_pool(compiled, config, pages: int, write: str,
                               attend: str) -> None:
    """Inside the compiled program's loops nothing but the aliased write
    (a custom call under ``kv.paged_insert`` whose outputs are its pool
    operands, numbered from ``write``) produces an array the size of a
    layer's pool side; the layer scan's body holds one attention kernel
    under the scope ``attend``; the carried pool has the default layout;
    the temporaries are smaller than one layer's K + V."""
    import re
    text = compiled.as_text()
    side = pages * config.n_kv_heads * PAGE * DH          # int8: bytes
    big = _loop_arrays(text, side)
    writes = [ln for _, op, ln in big if op == "custom-call"
              and "kv.paged_insert" in ln
              and "output_to_operand_aliasing=" + write in ln]
    assert len(writes) == 1, big
    assert len(big) == 1, [(n, op) for n, op, _ in big]
    assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"[^\n]*"
                          + re.escape(attend), text)) == 1
    carried = re.findall(r"s8\[2,%d,8,256,128\]\{([\d,]+)" % pages, text)
    assert carried and set(carried) == {"4,3,2,1,0"}, set(carried)
    layer_kv = 2 * pages * config.n_kv_heads * PAGE * (DH + 4)
    assert compiled.memory_analysis().temp_size_in_bytes < layer_kv


def test_decode_scan_leaves_the_pool_where_it_lies(chips, monkeypatch):
    """Tentpole item 4 of PR 30, read from the compiled program: a
    two-layer, two-step ``decode_scan`` of the ENGINE'S OWN step at
    Mistral-7B's widths, int8 weights and pool, compiled for the described
    chip. Inside its loops nothing but the aliased write produces an array
    the size of a layer's pool side; the carried pool has the default
    layout; the temporaries are smaller than one layer's K + V. (At the
    parent of PR 30 this fails three ways: a ``dynamic-slice`` fusion and
    a ``copy_bitcast`` fusion a layer and side, two whole-pool scatter
    fusions a step, the carried layout ``{4,2,3,1,0}``, 1.1 GB of
    temporaries.)"""
    from llmapigateway_tpu.engine.sampling import SamplingParams

    slots, pages, depth = 8, 513, 2
    engine, config, state, placed = _two_layer_engine(
        chips, monkeypatch, slots, pages, depth)

    def vec(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype, sharding=placed)
    sampling = SamplingParams(
        temperature=vec(jnp.float32), top_p=vec(jnp.float32),
        top_k=vec(jnp.int32), presence_penalty=vec(jnp.float32),
        frequency_penalty=vec(jnp.float32))
    rng = jax.random.key(0)
    compiled = engine._decode_fns[True][1][depth].lower(
        *state, vec(jnp.int32), vec(jnp.int32), vec(jnp.bool_), sampling,
        jax.ShapeDtypeStruct(rng.shape, rng.dtype)).compile()
    # One decode kernel a layer a step under its scope: the layer scan's
    # body holds one, and the write is filed elsewhere.
    _holds_no_copy_of_the_pool(
        compiled, config, pages,
        "{{0}: (6, {}), {1}: (7, {}), {2}: (8, {}), {3}: (9, {})}",
        "attention.paged_decode")


def test_prefill_step_leaves_the_pool_where_it_lies(chips, monkeypatch):
    """PR 34, the twin of the test above for the chunk path: the engine's
    own ``prefill_step`` at Mistral-7B's widths, two layers, int8 pool of
    513 pages, one row of bucket 512, compiled for the described chip.
    The stacked pool is the layer scan's CARRY: inside the loop nothing
    but the aliased chunk write produces an array the size of a layer's
    pool side, there is one attention kernel under
    ``attention.paged_prefill``, the carried pool keeps the default
    layout and the temporaries are under one layer's K + V. (At the
    parent of PR 34 the scan is handed the pool's per-layer slices and
    returns them as its ys, and this fails three ways: the loop holds no
    aliased write and TEN arrays of a pool side or more — a layer and
    side a ``constant_dynamic-slice`` fusion, a ``copy_bitcast`` fusion
    into the scatter's layout ``{3,1,2,0}``, the scatter's fusion and a
    ``copy`` back, then two ``copy_dynamic-update-slice`` fusions of the
    whole stacked pool — and 1.69 GB of temporaries against the 0.28 GB
    of a layer's K + V.)"""
    slots, pages, bucket = 8, 513, 512
    engine, config, state, placed = _two_layer_engine(
        chips, monkeypatch, slots, pages, 2)
    rng = jax.random.key(0)

    def row(dtype, *shape):
        return jax.ShapeDtypeStruct((1, *shape), dtype)
    compiled = engine._prefill_fn.lower(
        *state, row(jnp.int32, bucket), row(jnp.int32), row(jnp.int32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype)).compile()
    _holds_no_copy_of_the_pool(
        compiled, config, pages,
        "{{0}: (9, {}), {1}: (10, {}), {2}: (11, {}), {3}: (12, {})}",
        "attention.paged_prefill")


def test_prefill_step_attends_a_layer_in_one_call_with_no_page_axis(
        chips, monkeypatch):
    """PR 37, read from the traced ``prefill_step`` of the same two-layer
    engine: under ``attention.paged_prefill`` the layer scan's body holds
    ONE Pallas call (the benchmark counts chunks by them); every pool
    side reaches it un-sliced — the whole stacked ``[L, P, KV, page, Dh]``
    pool (and scale planes), left in HBM — and its grid is ``(rows,
    KV // heads)``: no axis steps through the table's 32 pages or the 32
    query heads (the parent's grid was ``(1, 32, 4, 32)``)."""
    slots, pages, bucket = 8, 513, 512
    engine, config, state, _ = _two_layer_engine(
        chips, monkeypatch, slots, pages, 2)
    rng = jax.random.key(0)

    def row(dtype, *shape):
        return jax.ShapeDtypeStruct((1, *shape), dtype)
    jaxpr = jax.make_jaxpr(engine._prefill_fn)(
        *state, row(jnp.int32, bucket), row(jnp.int32), row(jnp.int32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32),
        jax.ShapeDtypeStruct(rng.shape, rng.dtype))

    def calls(jp, scans=0):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn, scans
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub, scans + (eqn.primitive.name == "scan"))
    attends = [(eqn, scans) for eqn, scans in calls(jaxpr.jaxpr)
               if "attention.paged_prefill" in str(eqn.source_info.name_stack)]
    assert len(attends) == 1 and attends[0][1] == 1, attends
    eqn = attends[0][0]
    bt, heads = pa.prefill_block_shape(
        bucket, config.n_heads // config.n_kv_heads, config.n_kv_heads,
        PAGE, DH, 2, 1, True, 1)
    assert tuple(eqn.params["grid_mapping"].grid) \
        == (1, config.n_kv_heads // heads)
    pool = {(2, pages, config.n_kv_heads, PAGE, DH): 0,
            (2, pages, config.n_kv_heads, 1, PAGE): 0}
    for var in eqn.invars:
        if var.aval.shape in pool:
            pool[var.aval.shape] += 1
    assert list(pool.values()) == [2, 2], pool       # K, V; their scales
    # In HBM, whole: a BlockSpec cuts q and out alone into blocks.
    spaces = [str(bm.transformed_block_aval.memory_space)
              for bm in eqn.params["grid_mapping"].block_mappings]
    assert spaces == ["None"] + ["any"] * 4 + ["None"], spaces



# PR 38: the latent pool's kernels at the served geometry — 12 layers of
# 8 slots x 128 pages of [320, 256] bfloat16 (2.0 GB), 32 query heads.

@pytest.mark.parametrize("rows, tokens", [(1, 512), (4, 512), (8, 1)],
                         ids=["chunk", "four-chunks", "decode"])
def test_latent_kernels_compile_at_the_served_geometry(chips, rows, tokens):
    """The in-place write with the pool donated (every byte aliased, the
    temporaries the call's own rows cut into tiles) and the absorbed
    attention kernel on the whole stacked pool at a traced layer's index,
    as a prefill chunk (2 048 query rows a program) and as a decode step
    (32): the chip's compiler finds room for both, and no slice of the
    pool is among the operands."""
    from llmapigateway_tpu.ops import latent_attention as la
    layers, pages, width, value, heads, table = 12, 8 * 128 + 1, 320, 256, 32, 128
    place = SingleDeviceSharding(chips[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place)
    pool = sds((layers, pages, width, PAGE), jnp.bfloat16)
    tbl, start = sds((rows, table), jnp.int32), sds((rows,), jnp.int32)
    write = jax.jit(
        lambda pool, new, tbl, start, active, layer:
        la.latent_insert_in_place(pool, new, tbl, start, active,
                                  layer=layer, interpret=False),
        donate_argnums=(0,)).lower(
        pool, sds((rows, tokens, width), jnp.bfloat16), tbl, start,
        sds((rows,), jnp.bool_), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in write.as_text()
    memory = write.memory_analysis()
    assert memory.alias_size_in_bytes == layers * pages * width * PAGE * 2
    assert memory.temp_size_in_bytes < 8 * rows * (tokens + 2 * 128) * width
    attend = jax.jit(
        lambda q, pool, tbl, start, layer: la.latent_paged_attention(
            q, pool, tbl, start, value_width=value, layer=layer,
            interpret=False)).lower(
        sds((rows, tokens, heads, width), jnp.bfloat16), pool, tbl, start,
        sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in attend.as_text()
    assert la.latent_block_t(tokens, heads) == min(tokens, 64)
    # q in, the latent-wide out, and nothing the size of a layer's pool.
    assert attend.memory_analysis().temp_size_in_bytes < pages * width * PAGE


# -- the grouped expert product's kernel (PR 43) -------------------------------

@pytest.mark.parametrize("rows, D, F, held, k, act", [
    (1024, 2560, 768, 64, 6, "relu"),       # smallthinker-21b-pp3
    (2048, 4096, 1280, 40, 8, "silu"),      # solar-open2-250b-ep8
    (128, 4096, 1280, 40, 8, "silu"),       # its smallest bucket
    (2048, 4096, 2048, 32, 4, "silu"),      # mistral-small4-119b-ep4
], ids=["cell5", "solar", "solar-128", "small4"])
def test_the_grouped_expert_kernel_compiles_at_the_cells_widths(
        chips, monkeypatch, rows, D, F, held, k, act):
    """``experts_grouped`` at the three expert cells' published widths,
    int8, the stack of two periods read at an index: the chip's compiler
    takes the kernel — rows gathered as 32-bit words, a resident float32
    result, up to 100 MiB of fast memory (small4's width in two blocks) —
    and the compiled program holds ONE kernel and no copy of a matrix."""
    import functools
    from llmapigateway_tpu.models import hybrid
    from llmapigateway_tpu.ops import grouped_experts as ge
    monkeypatch.setattr(hybrid, "grouped_experts", functools.partial(
        ge.grouped_experts, interpret=False))
    hybrid._grouped.clear_cache()
    one = SingleDeviceSharding(chips[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    def stack(din, dout):
        return {"q": sds((2, held, din, dout), jnp.int8),
                "s": sds((2, held, dout), jnp.float32)}
    lp = {"wg": stack(D, F), "wu": stack(D, F), "wd": stack(F, D)}
    compiled = jax.jit(
        lambda x, idx, w, lp, period: hybrid.experts_grouped(
            x, idx, w, lp, held, period=period, act=act)).lower(
        sds((rows, D), jnp.bfloat16), sds((rows, k), jnp.int32),
        sds((rows, k), jnp.float32), lp, sds((), jnp.int32)).compile()
    hybrid._grouped.clear_cache()
    assert compiled.as_text().count("tpu_custom_call") == 1
    # Temporaries: the packed rows, the result, the layout — never a
    # matrix of the stack (the smallest is 2 x held x D x F bytes).
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * held * D * F
