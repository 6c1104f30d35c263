"""Compile for a described v5e what the indexed family (``keye_vl2``:
softmax layers under a learned indexer, ops/sparse_attention.py) adds at
``keye-vl2-30b-ep4``'s served geometry: the ENGINE'S OWN ``decode_scan``
(the selection's kernel over the live index-key pages and the walk of the
selected keys' pages beside three in-place writes: no sort, no gathered view
of the index side, no copy of a pool side),
and the two kernels of a prefill chunk — the selection and the masked page
walk. A file of its own so that a worker can take it beside
tests/test_aot_tpu_programs.py, whose fixtures and reader it borrows.
Nothing runs: a pass here is not a chip run."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from llmapigateway_tpu.ops import paged_attention as pa
from test_aot_tpu_compile import (DH, PAGE, chips,      # noqa: F401
                                  full_effort_uncached)
from test_aot_tpu_programs import _loop_arrays


def test_the_indexed_familys_decode_scan_fits_the_chip_and_walks_pages(
        chips, monkeypatch):
    """The ENGINE'S OWN ``decode_scan`` at ``keye-vl2-30b-ep4``'s served
    geometry — 12 layers at the published widths, int8, 8 slots of 32,768
    positions, a pool of K, V and index-key sides — compiled for the
    described chip: the compiler takes the two in-place writes, the kernel
    that selects (``decode_select``, a custom call under
    ``attention.index_decode``: Mosaic takes its one-row stores at a slot's
    row and a page's) and the kernel that reads the selected keys
    (``selected_decode_attention``, a custom call under
    ``attention.sparse_decode``), both scopes are in the program, the
    indexer's scope holds NO sort and no gather — no scores over every table
    position, nothing the shape of a slot's index keys over its whole table
    row —, nothing in the loops is the size of a pool side but the aliased
    writes' results, and arguments plus temporaries fit."""
    from llmapigateway_tpu.models import PRESETS, hybrid
    from step_programs import lower_step_program

    monkeypatch.setattr(pa, "_interpret_default", lambda: False)
    hybrid._grouped.clear_cache()
    config = PRESETS["keye-vl2-30b-ep4"]
    lowered, cache = lower_step_program(
        config, chips[0], "decode", quant="int8", kv_quant="",
        dtype=jnp.bfloat16, page=PAGE, slots=8, per_slot=128, depth=8)
    compiled = lowered.compile()
    hybrid._grouped.clear_cache()
    pages = 8 * 128 + 1
    assert [a.shape for a in cache.k] == [(12, pages, 4, PAGE, DH)]
    assert [a.shape for a in cache.index] == [(12, pages, 64, PAGE)]
    text = compiled.as_text()
    assert "attn.index" in text and "attn.sparse" in text
    assert "attention.sparse_decode" in text and "kv.paged_insert" in text
    lines = text.splitlines()
    for kernel in ("attention.index_decode", "attention.sparse_decode"):
        assert any("tpu_custom_call" in ln and kernel in ln for ln in lines)
    indexer = [ln for ln in lines if "attn.index" in ln]
    assert not [ln for ln in indexer if " sort(" in ln or "/top_k" in ln
                or "/gather" in ln]
    assert "[8,128,64,%d]" % PAGE not in text and "[8,32768," not in text
    side = 12 * pages * 64 * PAGE * 2           # the SMALLEST side, bytes
    made = [(n, op) for n, op, ln in _loop_arrays(text, side)
            if not (op == "custom-call" and "kv.paged_insert" in ln)]
    assert not made, made
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print("arguments", memory.argument_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes, "held", held)
    assert 9.0e9 < held < 10.5e9


def test_the_masked_page_walk_compiles_at_the_cells_widths(chips):
    """``paged_prefill_attention`` with a selection (``keep``) at the
    cell's geometry — a 512-token chunk of 32 query over 4 KV heads of 128
    against 128 table pages of 256, the stacked bfloat16 pool — compiles
    for the described chip: the int8 selection block of a row-block
    (64 x 32,768) rides the pipeline beside q and out."""
    place = SingleDeviceSharding(chips[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=place)
    pages, table = 2 * 128 + 1, 128
    pool = sds((12, pages, 4, PAGE, DH), jnp.bfloat16)
    compiled = jax.jit(lambda q, k, v, pt, start, layer, keep:
                       pa.paged_prefill_attention(
                           q, k, v, pt, start, layer=layer, keep=keep,
                           interpret=False)).lower(
        sds((2, 512, 32, DH), jnp.bfloat16), pool, pool,
        sds((2, table), jnp.int32), sds((2,), jnp.int32),
        sds((), jnp.int32), sds((2, 512, table * PAGE), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # ... and the kernel that makes the selection: 32 queries' keys over
    # 32,768 positions (4 MiB) in VMEM beside two buffers of four pages.
    from llmapigateway_tpu.ops import sparse_attention as sa
    select = jax.jit(lambda qi, w, pool, pt, start, layer: sa.index_select(
        qi, w, pool, pt, start, layer=layer, topk=2048,
        interpret=False)).lower(
        sds((2, 512, 16, 64), jnp.bfloat16), sds((2, 512, 16), jnp.float32),
        sds((12, pages, 64, PAGE), jnp.bfloat16), sds((2, table), jnp.int32),
        sds((2,), jnp.int32), sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in select.as_text()
