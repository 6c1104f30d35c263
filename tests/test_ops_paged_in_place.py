"""The page pool stays where it lies (PR 30, PR 34): the kernels read the
layer-STACKED pool at a layer's index, and the new tokens go in through
aliased operands — the decode step's after the layer scan, a prefill
chunk's inside it, where the pool is the scan's carry. Interpret mode,
tiny sizes: (a) the stacked read is the per-layer read bit for bit, decode
and prefill, (b) the write kernels leave the bytes the XLA scatters
leave, (c) engines on the kernels and on the reference path serve the same
greedy tokens. The forwards and engines on the carried against the sliced
pool are tests/test_prefill_pool_carried.py; that the chip's compiler
accepts the kernels, and that the compiled programs hold no copy of the
pool, is tests/test_aot_tpu_compile.py."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.ops import paged_attention as pa

L, P, KV, G, PAGE, DH, B, NP = 3, 12, 2, 2, 16, 32, 3, 4


def _pool(key, quant: bool, page: int = PAGE):
    """One random layer-stacked pool side."""
    shape = (L, P, KV, page, DH)
    if quant:
        kq, ks = jax.random.split(key)
        return {"q": jax.random.randint(kq, shape, -127, 128, jnp.int8),
                "s": jax.random.uniform(ks, (L, P, KV, 1, page),
                                        jnp.float32, 0.01, 0.03)}
    return jax.random.normal(key, shape, jnp.bfloat16)


def _layer(side, i: int):
    return jax.tree.map(lambda x: x[i], side)


# ---------------------------------------------------------------------------
# (a) the stacked read
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("window", [0, 24], ids=["full", "window24"])
@pytest.mark.parametrize("ppb", [1, 2], ids=["ppb1", "ppb2"])
def test_the_stacked_read_is_the_per_layer_read(quant, window, ppb):
    """Every layer of an L = 3 pool, read where it lies through a TRACED
    layer index, gives bit for bit what the kernel gives on that layer's
    rank-4 slice (still accepted: one layer, a free reshape)."""
    keys = jax.random.split(jax.random.PRNGKey(7), 5)
    pk, pv = _pool(keys[0], quant), _pool(keys[1], quant)
    q = jax.random.normal(keys[2], (B, KV * G, DH), jnp.bfloat16)
    kn = jax.random.normal(keys[3], (B, KV, DH), jnp.bfloat16)
    vn = jax.random.normal(keys[4], (B, KV, DH), jnp.bfloat16)
    # Packed for ppb = 2: aligned contiguous runs of two pages.
    table = jnp.array([[2, 3, 4, 5], [6, 7, 0, 0], [8, 9, 10, 11]],
                      jnp.int32)
    n_stale = jnp.array([50, 17, 0], jnp.int32)

    @jax.jit
    def stacked(layer):
        return pa.paged_decode_attention(
            q, kn, vn, pk, pv, table, n_stale, layer=layer, window=window,
            pages_per_block=ppb, interpret=True)

    seen = []
    for i in range(L):
        sliced = pa.paged_decode_attention(
            q, kn, vn, _layer(pk, i), _layer(pv, i), table, n_stale,
            window=window, pages_per_block=ppb, interpret=True)
        got = stacked(jnp.int32(i))
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(sliced, np.float32))
        seen.append(np.asarray(got, np.float32))
    assert not np.array_equal(seen[0], seen[1])     # the index is honoured


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("window", [0, 24], ids=["full", "window24"])
@pytest.mark.parametrize("layer", [0, L - 1], ids=["first", "last"])
def test_the_stacked_prefill_read_is_the_per_layer_read(quant, window,
                                                        layer):
    """The PREFILL kernel on the stacked pool at a traced layer index:
    bit for bit what it gives on that layer's rank-4 slice."""
    keys = jax.random.split(jax.random.PRNGKey(13), 3)
    pk, pv = _pool(keys[0], quant), _pool(keys[1], quant)
    q = jax.random.normal(keys[2], (B, 8, KV * G, DH), jnp.bfloat16)
    table = jnp.array([[2, 3, 4, 5], [6, 7, 0, 0], [8, 9, 10, 11]],
                      jnp.int32)
    start = jnp.array([40, 9, 0], jnp.int32)
    sliced = pa.paged_prefill_attention(
        q, _layer(pk, layer), _layer(pv, layer), table, start,
        window=window, interpret=True)
    stacked = jax.jit(lambda at: pa.paged_prefill_attention(
        q, pk, pv, table, start, layer=at, window=window,
        interpret=True))
    np.testing.assert_array_equal(
        np.asarray(stacked(jnp.int32(layer)), np.float32),
        np.asarray(sliced, np.float32))
    assert not np.array_equal(np.asarray(stacked(jnp.int32(1)), np.float32),
                              np.asarray(sliced, np.float32))


# ---------------------------------------------------------------------------
# (b) the write kernels
# ---------------------------------------------------------------------------

_RING = [[5, 0, 0, 6, 4], [0, 7, 8, 9, 0], [1, 2, 3, 0, 0]]
_PLAIN = [[1, 2, 3, 0, 0], [4, 5, 6, 0, 0], [7, 8, 9, 0, 0]]
# name -> (T, lengths, active, table): where the B x T rows land.
WRITES = {
    "one-token": (1, [5, 20, 33], [True, True, True], _PLAIN),
    "five-tokens-over-a-page-edge": (5, [13, 20, 30], [True, True, True],
                                     _PLAIN),
    "an-inactive-slot": (1, [5, 20, 33], [True, False, True], _PLAIN),
    "a-pages-last-offset": (1, [15, 31, 47], [True, True, True], _PLAIN),
    # Logical pages 0..2 of slot 0 rotated away (0 = unmapped), its live
    # pages 3 and 4 on physical 6 and 4: position 70 is page 4, offset 6.
    "a-ring-rotated-table": (5, [70, 28, 0], [True, True, True], _RING),
    # Slot 0's rows run past the table's five pages: trash, not page 0 of
    # a wrapped index.
    "past-the-tables-reach": (5, [78, 20, 33], [True, True, True], _PLAIN),
}


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("case", list(WRITES))
def test_the_write_kernel_leaves_the_scatters_bytes(quant, case):
    """Off trash page 0 the pool equals ``paged_insert_all``'s bit for bit
    (values AND scales); the operands are donated, as in the engine."""
    T, lengths, active, table = WRITES[case]
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    k_news = jax.random.normal(keys[2], (L, B, T, KV, DH), jnp.bfloat16)
    v_news = jax.random.normal(keys[3], (L, B, T, KV, DH), jnp.bfloat16)
    args = (k_news, v_news, jnp.array(table, jnp.int32),
            jnp.array(lengths, jnp.int32), jnp.array(active))
    want = jax.jit(pa.paged_insert_all)(
        _pool(keys[0], quant), _pool(keys[1], quant), *args)
    before = _pool(keys[0], quant)
    got = jax.jit(
        lambda pk, pv, *a: pa.paged_insert_in_place(pk, pv, *a,
                                                    interpret=True),
        donate_argnums=(0, 1))(_pool(keys[0], quant),
                               _pool(keys[1], quant), *args)
    changed = False
    for w, g, b in zip(jax.tree.leaves(want), jax.tree.leaves(got),
                       jax.tree.leaves((before, _pool(keys[1], quant)))):
        assert w.dtype == g.dtype and w.shape == g.shape
        np.testing.assert_array_equal(np.asarray(g[:, 1:], np.float32),
                                      np.asarray(w[:, 1:], np.float32))
        changed |= not np.array_equal(np.asarray(g[:, 1:], np.float32),
                                      np.asarray(b[:, 1:], np.float32))
    assert changed                                  # something was written


def test_a_page_smaller_than_a_tile_is_one_tile():
    """The read-modify-write tile is 32 rows or the page, whichever is
    smaller (page 8 here): the same bytes as the scatter."""
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    news = [jax.random.normal(k, (L, B, 2, KV, DH), jnp.bfloat16)
            for k in keys[2:]]
    args = (*news, jnp.array(_PLAIN, jnp.int32),
            jnp.array([7, 9, 0], jnp.int32), None)
    want = jax.jit(pa.paged_insert_all)(
        _pool(keys[0], True, 8), _pool(keys[1], True, 8), *args)
    got = jax.jit(lambda *a: pa.paged_insert_in_place(*a, interpret=True))(
        _pool(keys[0], True, 8), _pool(keys[1], True, 8), *args)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(g[:, 1:]),
                                      np.asarray(w[:, 1:]))


# The chunk write: pages of 64 rows (two tiles of 32), a table of 16 pages
# a slot — 1 024 positions, so a 512-row chunk can start at 512.
CHUNK_PAGE, CHUNK_P = 64, 72


def _rows_of(first: int, count: int = 16) -> list[int]:
    return list(range(first, first + count))


_SLOT = [_rows_of(1)]
_FOUR = [_rows_of(1), _rows_of(17), _rows_of(33), _rows_of(49)]
# Slot 0's first five logical pages rotated away (0 = unmapped), the live
# ones out of order, and nothing mapped past logical page 9: the pad tail
# of a bucket that reaches there lands on the trash page.
_ROTATED = [[0, 0, 0, 0, 0, 9, 3, 7, 5, 4] + [0] * 6]
# name -> (T, starts, active, table)
CHUNKS = {
    "t8-from-0": (8, [0], None, _SLOT),
    "t8-inside-a-tile": (8, [100], None, _SLOT),
    "t8-over-a-tile-edge": (8, [60], None, _SLOT),
    "t16-at-a-page": (16, [128], None, _SLOT),
    "t16-ragged": (16, [41], None, _SLOT),
    "t32-one-whole-tile": (32, [96], None, _SLOT),
    "t32-over-a-page-edge": (32, [50], None, _SLOT),
    "t96-at-512": (96, [512], None, _SLOT),
    "t96-at-a-page": (96, [192], None, _SLOT),
    "t96-at-neither": (96, [37], None, _SLOT),
    "t512-at-512": (512, [512], None, _SLOT),
    "t512-at-a-page": (512, [64], None, _SLOT),
    "t512-at-neither": (512, [77], None, _SLOT),
    "t96-ends-at-the-tables-reach": (96, [928], None, _SLOT),
    "t96-runs-past-the-tables-reach": (96, [1000], None, _SLOT),
    "t96-a-ring-rotated-table": (96, [330], None, _ROTATED),
    "t96-an-unmapped-pad-tail": (96, [600], None, _ROTATED),
    "k4-t32-each-its-own-start": (32, [0, 45, 512, 224], None, _FOUR),
    "k4-t96-an-inactive-row": (96, [37, 128, 250, 3],
                               [True, False, True, True], _FOUR),
    "k4-t512-at-512": (512, [512, 0, 512, 256], None, _FOUR),
}


def _chunk_pool(key, quant: bool):
    shape = (L, CHUNK_P, KV, CHUNK_PAGE, DH)
    if quant:
        kq, ks = jax.random.split(key)
        return {"q": jax.random.randint(kq, shape, -127, 128, jnp.int8),
                "s": jax.random.uniform(ks, (L, CHUNK_P, KV, 1, CHUNK_PAGE),
                                        jnp.float32, 0.01, 0.03)}
    return jax.random.normal(key, shape, jnp.bfloat16)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("case", list(CHUNKS))
def test_the_chunk_write_leaves_the_scatters_bytes(quant, case):
    """``paged_insert_chunk_in_place`` at every layer's index in turn on
    one donated pool, against ``paged_insert_kv`` applied layer by layer:
    off trash page 0 the pool's bytes (values AND scales) are equal, and
    each call changed its own layer alone."""
    T, starts, active, table = CHUNKS[case]
    rows = len(starts)
    keys = jax.random.split(jax.random.PRNGKey(T + rows), 4)
    k_news = jax.random.normal(keys[2], (L, rows, T, KV, DH), jnp.bfloat16)
    v_news = jax.random.normal(keys[3], (L, rows, T, KV, DH), jnp.bfloat16)
    where = (jnp.array(table, jnp.int32), jnp.array(starts, jnp.int32),
             None if active is None else jnp.array(active))
    before = (_chunk_pool(keys[0], quant), _chunk_pool(keys[1], quant))
    scatter = jax.jit(pa.paged_insert_kv)
    want = [scatter(_layer(before[0], i), _layer(before[1], i),
                    k_news[i], v_news[i], *where) for i in range(L)]
    write = jax.jit(
        lambda pk, pv, kn, vn, at: pa.paged_insert_chunk_in_place(
            pk, pv, kn, vn, *where, layer=at, interpret=True),
        donate_argnums=(0, 1))
    got = (_chunk_pool(keys[0], quant), _chunk_pool(keys[1], quant))
    for i in (2, 0, 1):                     # any order: a layer is its own
        got = write(*got, k_news[i], v_news[i], jnp.int32(i))
    for i in range(L):
        for w, g, b in zip(jax.tree.leaves(want[i]), jax.tree.leaves(got),
                           jax.tree.leaves(before)):
            assert w.dtype == g.dtype and w.shape == g.shape[1:]
            np.testing.assert_array_equal(np.asarray(g[i, 1:], np.float32),
                                          np.asarray(w[1:], np.float32))
            assert not np.array_equal(np.asarray(g[i, 1:], np.float32),
                                      np.asarray(b[i, 1:], np.float32))


def test_a_chunk_on_pages_smaller_than_a_tile():
    """Pages of 16 rows (the engines' tests): a tile is the page, and a
    ragged chunk over three of them leaves the scatter's bytes."""
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    news = [jax.random.normal(k, (B, 24, KV, DH), jnp.bfloat16)
            for k in keys[2:]]
    where = (jnp.array(_PLAIN, jnp.int32), jnp.array([7, 16, 20], jnp.int32),
             None)
    want = jax.jit(pa.paged_insert_kv)(
        _layer(_pool(keys[0], True), 1), _layer(_pool(keys[1], True), 1),
        *news, *where)
    got = jax.jit(lambda *a: pa.paged_insert_chunk_in_place(
        *a, layer=1, interpret=True))(
        _pool(keys[0], True), _pool(keys[1], True), *news, *where)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(g[1, 1:]),
                                      np.asarray(w[1:]))


# ---------------------------------------------------------------------------
# (c) through the engine
# ---------------------------------------------------------------------------

# preset -> (engine options, tokens answered per request)
ENGINES = {
    # Window 16 on pages of 16, a ring of 5 pages a slot: 90 answered
    # tokens after a 20-token prompt reach logical page 6, so the ring
    # rotates (the test counts the rotations).
    "tiny-mistral-test": (dict(max_batch_size=2, max_seq_len=128,
                               prefill_chunk=16, kv_num_pages=9), 90),
    "tiny-hybrid-test": (dict(max_batch_size=2, max_seq_len=128,
                              prefill_chunk=32, prefill_batch=2,
                              prefix_cache=False), 40),
}


async def _serve(eng, prompts, max_tokens):
    out = []
    for ids in prompts:
        req = GenRequest(prompt_ids=list(ids), max_tokens=max_tokens)
        await eng.submit(req)
        async for _ in eng.stream(req):
            pass
        out.append(list(req.generated))
    return out


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["f32kv", "int8kv"])
@pytest.mark.parametrize("preset", list(ENGINES))
async def test_engines_on_the_kernels_and_on_the_reference_path_agree(
        preset, kv_quant):
    """``attention="pallas"`` (the stacked read and the aliased write,
    interpreted) against ``"reference"`` (per-layer slices, the XLA
    scatter): the same greedy tokens over bursts that cross page edges,
    and ``stats()`` says which path each engine was built on."""
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 500, n)] for n in (20, 9)]
    options, n_tokens = ENGINES[preset]
    served = {}
    for impl in ("pallas", "reference"):
        # Built off the event loop: a build holds it for seconds.
        eng = await asyncio.to_thread(
            InferenceEngine,
            LocalEngineConfig(preset=preset, dtype="float32",
                              kv_layout="paged", kv_page_size=16,
                              decode_burst=4, decode_burst_busy=2,
                              attention=impl, kv_quant=kv_quant,
                              **options),
            devices=[jax.devices("cpu")[0]])
        try:
            assert eng.stats()["kv_pool_in_place"] is (impl == "pallas")
            assert eng.stats()["attention"] == impl
            rotations = []
            mapped = eng.allocator.ensure_mapped
            eng.allocator.ensure_mapped = \
                lambda *a, **kw: rotations.append(mapped(*a, **kw)) \
                or rotations[-1]
            served[impl] = await _serve(eng, prompts, n_tokens)
            eng.allocator.check_invariants()
            assert any(rotations) is (preset == "tiny-mistral-test")
        finally:
            await eng.stop()
    assert served["pallas"] == served["reference"]
    # (a stream may end early on the tokenizer's end-of-sequence id)
    assert min(len(t) for t in served["pallas"]) >= 40
