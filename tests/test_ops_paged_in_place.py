"""The page pool stays where it lies (PR 30, PR 34): the kernels read the
layer-STACKED pool at a layer's index, and the new tokens go in through
aliased operands — the decode step's after the layer scan, a prefill
chunk's inside it, where the pool is the scan's carry. Interpret mode,
tiny sizes: (a) the stacked read is the per-layer read bit for bit, decode
and prefill, (b) the decode step's write kernel leaves the bytes the XLA
scatter leaves. A prefill chunk's write is
tests/test_ops_paged_chunk_write.py; engines on the kernels and on the
reference path serving the same greedy tokens,
tests/test_engine_pool_in_place.py (a file each, so that they run on a
worker each); the forwards and the engines on the carried against the
sliced pool, tests/test_prefill_pool_carried.py and
tests/test_engine_pool_carried.py; that the chip's compiler
accepts the kernels, tests/test_aot_tpu_compile.py, and that the compiled
programs hold no copy of the pool, tests/test_aot_tpu_programs.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

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
# (b) the decode step's write kernel
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
