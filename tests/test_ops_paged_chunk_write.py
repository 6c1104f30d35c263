"""A prefill chunk's rows go into the layer-stacked page pool in place
(``paged_insert_chunk_in_place``, PR 34), inside the layer scan whose carry
the pool is: interpret mode, off trash page 0 the bytes the XLA scatter
leaves. The decode step's write and the stacked reads are
tests/test_ops_paged_in_place.py, whose pools these cases draw."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.ops import paged_attention as pa
from test_ops_paged_in_place import _PLAIN, B, DH, KV, L, _layer, _pool

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
