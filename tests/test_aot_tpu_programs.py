"""Compile for a described v5e what is built of more than one kernel: the
ENGINE'S OWN step programs at Mistral-7B's widths (``decode_scan`` and
``prefill_step`` hold no copy of the page pool), the latent pool's
kernels and the grouped expert product's kernel at their cells' served
geometry. The paged kernels alone, and the fixtures (the described chips,
the compiler in full with its cache off), are tests/test_aot_tpu_compile.py;
the step programs of the families with recurrent state are
tests/test_aot_tpu_state_families.py. Nothing runs: a pass here is not a
chip run."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from llmapigateway_tpu.ops import paged_attention as pa
from llmapigateway_tpu.parallel.mesh import build_mesh
from test_aot_tpu_compile import (DH, PAGE, chips,      # noqa: F401
                                  full_effort_uncached)


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
    """The ENGINE'S OWN step programs (its ``_compile`` on a stand-in
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
    InferenceEngine._compile(engine)
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
    KV // heads, row-blocks)`` (PR 48: the row-block is an axis, so q and
    out travel by it): no axis steps through the table's 32 pages or the
    32 query heads (PR 37's parent's grid was ``(1, 32, 4, 32)``)."""
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
        == (1, config.n_kv_heads // heads, bucket // bt)
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

# PR 46: the same kernels at 1 layer of 32 slots x 80 pages of [576, 256]
# (0.75 GB), 64 query heads: 32 positions a row-block.
LATENT_GEOMETRY = {
    "small4": (12, 8 * 128 + 1, 320, 256, 32, 128),
    "gigachat35": (1, 32 * 80 + 1, 576, 512, 64, 80)}


@pytest.mark.parametrize("rows, tokens, cell", [
    (1, 512, "small4"), (4, 512, "small4"), (8, 1, "small4"),
    (32, 1, "gigachat35")],     # its chunk: the step program below
    ids=["chunk", "four-chunks", "decode", "decode-576x64"])
def test_latent_kernels_compile_at_the_served_geometry(chips, rows, tokens,
                                                       cell):
    """The in-place write with the pool donated (every byte aliased, the
    temporaries the call's own rows cut into tiles) and the absorbed
    attention kernel on the whole stacked pool at a traced layer's index,
    as a prefill chunk (2 048 query rows a program) and as a decode step
    (a slot's heads): the chip's compiler finds room for both, and no
    slice of the pool is among the operands."""
    from llmapigateway_tpu.ops import latent_attention as la
    layers, pages, width, value, heads, table = LATENT_GEOMETRY[cell]
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
    assert la.latent_block_t(tokens, heads) == min(tokens, 2048 // heads)
    # q in, the latent-wide out, and nothing the size of a layer's pool —
    # nor of ONE page's score tile: a step's merged tiles (1,024 keys a
    # row in float32, twice) are the kernel's own, in VMEM.
    temp = attend.memory_analysis().temp_size_in_bytes
    assert temp < pages * width * PAGE
    assert temp < la.latent_block_t(tokens, heads) * heads * PAGE * 4


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
