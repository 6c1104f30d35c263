"""The paged prefill kernel's walk (ISSUE 37): a KV head's query group
folded into one block's rows, only the pages a row-block can see visited,
the next one in flight.

Which pages are visited changed, what one dot carries, and where a mask
is built; what is COMPUTED for a (row, head) did not. So the kernel is
held, in interpret mode, to

* the gather + dense reference (``_paged_reference_core``) on the same
  pool, at the tolerances the other paged suites use;
* bit-for-bit equality of a row's result across block shapes — one KV
  head a program against every head folded, ``bt`` query positions a
  row-block against the whole chunk, a row alone against the row among
  others, ``pages_per_block`` 1 against 2 — since per (row, head) the
  updates are the same, in the same page order, and a page a row sees
  nothing of leaves its state bit for bit as it was. One exception, the
  interpreter's and not the kernel's: across ``bt`` on an int8 pool
  XLA's CPU backend (which interpret mode runs on) compiles the two
  multiplications of the scores — by ``Dh ** -0.5``, then by the K
  scale — to another form for another tile height (with a K scale of
  1.0 the bits agree again), so there the bound is sixteen units of
  fp32's last place, as in the decode suite. On the chip every block
  shape from 32 x 8 to 512 x 1 gave the parent kernel's bits at the
  served geometries (PERF.md, PR 37);
* indifference to what DEAD pages hold: NaN in every page no row-block
  walks changes no bit.

Small pages (8 tokens) keep the file quick: a 16-token chunk spans two
pages as a 512-token chunk spans two of 256, the window of 100 spans 14
as 4096 spans 17. The last row of a batch reads through a RING table:
its logical pages below the window are re-targeted at the physical pages
of later ones, as the engine's window ring recycles them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.ops import paged_attention as pa

FEW_ULP = 16 * 2.0 ** -24            # sixteen units of fp32's last place
PAGE, DH, NP = 8, 16, 32
S = PAGE * NP                        # 256 tokens a slot
WINDOW = 100                         # not a multiple of the page
RING = 16                            # pages the ring row keeps
HEADS = pytest.mark.parametrize(
    "H,KV", [(32, 8), (64, 8), (28, 4), (128, 8)],
    ids=["32over8", "64over8", "28over4", "128over8"])
WINDOWS = pytest.mark.parametrize("window", [0, WINDOW],
                                  ids=["full", "windowed"])
QUANT = pytest.mark.parametrize("quant", [True, False],
                                ids=["int8kv", "bf16pool"])


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def _table(B: int, rng, pack: int = 1) -> np.ndarray:
    """Scrambled physical pages (aligned runs of ``pack``); the LAST row
    is a ring of ``RING`` pages, so logical page j and j + RING share a
    physical page — right for a windowed walk, which never reads both."""
    groups = NP // pack
    runs = rng.permutation(np.arange(1, B * groups + 1))
    table = np.zeros((B, NP), np.int32)
    for b in range(B):
        mine = runs[b * groups:(b + 1) * groups]
        for g in range(groups):
            run = mine[g % (RING // pack)] if b == B - 1 else mine[g]
            table[b, g * pack:(g + 1) * pack] = run * pack + np.arange(pack)
    return table


def _inputs(B, T, H, KV, quant, seed=3, pack=1, layers=0):
    rng = np.random.default_rng(seed)
    table = _table(B, rng, pack)
    P = (B * (NP // pack) + 1) * pack            # + the trash run at 0
    lead = (layers,) if layers else ()
    q = jnp.asarray(rng.normal(size=(B, T, H, DH)), jnp.float32)

    def side():
        if quant:
            return {"q": jnp.asarray(rng.integers(
                        -127, 128, (*lead, P, KV, PAGE, DH)), jnp.int8),
                    "s": jnp.asarray(0.01 + 0.02 * rng.random(
                        (*lead, P, KV, 1, PAGE)), jnp.float32)}
        return jnp.asarray(rng.normal(size=(*lead, P, KV, PAGE, DH)),
                           jnp.bfloat16)
    return q, side(), side(), jnp.asarray(table)


def _kernel(q, pk, pv, table, start, window, **kw):
    return np.asarray(pa.paged_prefill_attention(
        q, pk, pv, table, jnp.asarray(start, jnp.int32), window=window,
        interpret=True, **kw))


def _reference(q, pk, pv, table, start, window):
    dense_k = pa.dequant_gathered(pa.gather_pages(pk, table, S), q.dtype)
    dense_v = pa.dequant_gathered(pa.gather_pages(pv, table, S), q.dtype)
    return np.asarray(pa._paged_reference_core(
        q, dense_k, dense_v, jnp.asarray(start, jnp.int32), None,
        q.shape[1], window=window))


def _starts(T, window, B=4):
    """Row 0 from the start, an aligned start, a ragged one past the
    window with its floor mid-page, and (the ring row) as far as the
    table reaches; under a full window the ring row stays inside its
    ring."""
    last = S - T if window else RING * PAGE - T
    return [0, 8 * PAGE, 157 - T // 2, last][:B]


# ---------------------------------------------------------------------------
# Parity with the reference
# ---------------------------------------------------------------------------

@HEADS
@WINDOWS
@QUANT
def test_folded_prefill_matches_reference(quant, window, H, KV):
    """A 16-token chunk (two pages, as 512 tokens are two of 256) a row,
    four rows at different starts in one call."""
    T = 16
    q, pk, pv, table = _inputs(4, T, H, KV, quant)
    start = _starts(T, window)
    got = _kernel(q, pk, pv, table, start, window)
    ref = _reference(q, pk, pv, table, start, window)
    # The reference rounds its probabilities to the pool's dtype before
    # the PV product (the kernel keeps them fp32): bf16's step for a bf16
    # pool, the other suites' tolerance for int8.
    tol = 2e-5 if quant else 4e-3
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("T,B", [(8, 4), (64, 2), (128, 1)],
                         ids=["t8", "t64", "t128"])
@WINDOWS
def test_every_bucket_matches_reference(window, T, B):
    """The small buckets fold every KV head into one program; a 128-token
    chunk walks sixteen pages of its own."""
    q, pk, pv, table = _inputs(B, T, 28, 4, quant=True, seed=T)
    start = [s for s in _starts(T, window) if s + T <= S][-B:]
    if window == 0:
        table = table.at[-1].set(table[0])       # no ring without a window
        start = [min(s, S - T) for s in start]
    got = _kernel(q, pk, pv, table, start, window)
    ref = _reference(q, pk, pv, table, start, window)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# A row's result does not depend on the block's shape
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("H,KV,shape,alone", [
    # Everything folds: one row-block, every KV head a program.
    (8, 4, (64, 4), ([0], [2], [1, 3])),
    # Command A+'s fold, 16 query heads a KV head (PR 48): the rule's own
    # shape is two row-blocks a row and two heads a program, so the walk
    # crosses a row-block's end with the next one's first page in flight.
    (128, 8, (32, 2), ())], ids=["8over4", "128over8"])
@WINDOWS
@QUANT
def test_a_rows_result_is_bit_equal_across_block_shapes(monkeypatch, quant,
                                                        window, H, KV,
                                                        shape, alone):
    T, G = 64, H // KV
    q, pk, pv, table = _inputs(4, T, H, KV, quant, seed=11)
    start = _starts(T, window)
    itemsize = 1 if quant else 2
    assert pa.prefill_block_shape(T, G, KV, PAGE, DH, 4, itemsize,
                                  quant, 1) == shape        # the rule's fold
    full = _kernel(q, pk, pv, table, start, window)

    # A quarter of the chunk a row-block: rows now walk fewer pages they
    # see nothing of, and take their diagonal in another block.
    quarter = _kernel(q, pk, pv, table, start, window, block_t=16)
    if quant:                            # see the module docstring
        np.testing.assert_allclose(quarter, full, rtol=FEW_ULP, atol=FEW_ULP)
    else:
        assert np.array_equal(quarter, full)
    # A row alone against the row among others; two rows against four.
    for rows in alone:
        one = _kernel(q[np.asarray(rows)], pk, pv, table[np.asarray(rows)],
                      [start[r] for r in rows], window)
        assert np.array_equal(one, full[rows])
    # ONE KV head a program: a block that holds one head and no more.
    monkeypatch.setattr(pa, "_PREFILL_BLOCK_ROWS", G * shape[0])
    assert pa.prefill_block_shape(T, G, KV, PAGE, DH, 4, itemsize,
                                  quant, 1) == (shape[0], 1)
    assert np.array_equal(_kernel(q, pk, pv, table, start, window), full)


@WINDOWS
def test_pages_per_block_two_on_a_packed_table_is_bit_equal(window):
    T, H, KV = 16, 8, 2
    q, pk, pv, table = _inputs(3, T, H, KV, quant=True, seed=5, pack=2)
    start = [3, 77, S - T if window else RING * PAGE - T]
    one = _kernel(q, pk, pv, table, start, window)
    two = _kernel(q, pk, pv, table, start, window, pages_per_block=2)
    assert np.array_equal(one, two)
    np.testing.assert_allclose(
        two, _reference(q, pk, pv, table, start, window),
        rtol=2e-5, atol=2e-5)


def test_stacked_pool_is_read_at_the_layer():
    """The stacked pool at ``layer`` 2 is the rank-4 call on that layer's
    side: nothing of another layer is touched (they hold NaN)."""
    T, H, KV, L = 16, 8, 2, 3
    q, pk, pv, table = _inputs(2, T, H, KV, quant=True, seed=6, layers=L)
    start = [40, 200]
    layer = jax.tree.map(lambda x: x[2], (pk, pv))
    only = jnp.arange(L).reshape(L, 1, 1, 1, 1) == 2
    poisoned = [{"q": side["q"], "s": jnp.where(only, side["s"], jnp.nan)}
                for side in (pk, pv)]
    got = np.asarray(jax.jit(lambda layer_index: pa.paged_prefill_attention(
        q, *poisoned, table, jnp.asarray(start, jnp.int32),
        layer=layer_index, window=WINDOW, interpret=True))(jnp.int32(2)))
    assert np.array_equal(got, _kernel(q, *layer, table, start, WINDOW))


@WINDOWS
@QUANT
def test_dead_pages_may_hold_anything(quant, window):
    """Every page no row-block walks — causally ahead, below the window,
    unmapped, the trash page — holds NaN; no bit of the result moves."""
    T, H, KV, bt = 32, 8, 2, 16
    q, pk, pv, table = _inputs(3, T, H, KV, quant, seed=8)
    start = [5, 130, S - T if window else RING * PAGE - T]
    clean = _kernel(q, pk, pv, table, start, window, block_t=bt)

    tbl = np.asarray(table)
    live = np.zeros(pk["q"].shape[0] if quant else pk.shape[0], bool)
    for b, st in enumerate(start):
        first_q = st + np.arange(T // bt) * bt
        first, last = pa._prefill_live_blocks(first_q, bt, PAGE, window, NP,
                                              xp=np)
        for f, l in zip(first, last):
            live[tbl[b, f:l + 1]] = True
    assert 0 < live.sum() < live.size - 1
    dead = jnp.asarray(~live).reshape(-1, 1, 1, 1)

    def poison(side):
        if quant:
            return {"q": side["q"],
                    "s": jnp.where(dead, jnp.nan, side["s"])}
        return jnp.where(dead, jnp.nan, side.astype(jnp.float32)
                         ).astype(side.dtype)
    got = _kernel(q, poison(pk), poison(pv), table, start, window,
                  block_t=bt)
    assert np.isfinite(got).all()
    assert np.array_equal(got, clean)


# ---------------------------------------------------------------------------
# The two rules: pure shape arithmetic
# ---------------------------------------------------------------------------

MIB = 2 ** 20


@pytest.mark.parametrize("case", [
    # (T, G, KV, page, Dh, q bytes, kv bytes, quant, ppb) -> (bt, heads).
    # Mistral-7B (32/8), int8 pool: two heads of 512 rows from a
    # 128-token row-block up; the smaller buckets fold more heads.
    ((512, 4, 8, 256, 128, 2, 1, True, 1), (128, 2)),
    ((128, 4, 8, 256, 128, 2, 1, True, 1), (128, 2)),
    ((64, 4, 8, 256, 128, 2, 1, True, 1), (64, 4)),
    ((8, 4, 8, 256, 128, 2, 1, True, 1), (8, 8)),
    # ... under TP=4: two local KV heads.
    ((512, 4, 2, 256, 128, 2, 1, True, 1), (128, 2)),
    # Solar-Open2 (64/8) and SmallThinker (28/4): a taller fold, so fewer
    # positions a row-block; 7 x 64 rows a head are whole sublanes, not
    # whole MXU tiles.
    ((512, 8, 8, 256, 128, 2, 1, True, 1), (64, 2)),
    ((512, 7, 4, 256, 128, 2, 1, True, 1), (64, 2)),
    ((16, 7, 4, 256, 128, 2, 1, True, 1), (16, 4)),
    # A ragged bucket takes its largest power-of-two divisor.
    ((96, 4, 8, 256, 128, 2, 1, True, 1), (32, 8)),
    # No fold at all: a head's rows are its positions.
    ((512, 1, 8, 256, 128, 2, 1, True, 1), (512, 2)),
    # A bf16 pool in runs of four pages.
    ((512, 4, 8, 256, 128, 2, 2, False, 4), (128, 2)),
    # Pages too large for the rows the rule wants: VMEM decides (13.5 MiB
    # at 64 x 1; 17.5 at 128 x 1 and 27 at 64 x 2 — the runs of four
    # pages and the score tiles, no longer the row's q and out).
    ((512, 4, 8, 1024, 256, 2, 2, False, 4), (64, 1)),
    # Command A+ (128/8), 16 a group: 32 positions and TWO heads since a
    # program holds q and out by the row-block (PR 48; 32 x 1 until then).
    ((512, 16, 8, 256, 128, 2, 1, True, 1), (32, 2)),
    # The tests' geometry: everything folds.
    ((64, 2, 4, 8, 16, 4, 1, True, 1), (64, 4)),
], ids=lambda c: "-".join(map(str, c[0])))
def test_block_shape_rule(case):
    args, (bt, heads) = case
    assert pa.prefill_block_shape(*args) == (bt, heads)
    T, G, KV = args[:3]
    rule_args, args = args, args[1:2] + args[3:]  # a program's: not T, KV
    assert T % bt == 0 and bt & (bt - 1) == 0 and KV % heads == 0
    assert G * bt <= pa._PREFILL_HEAD_ROWS or bt == 8
    assert heads * G * bt <= pa._PREFILL_BLOCK_ROWS or heads == 1
    held = pa._prefill_vmem_bytes(bt, heads, *args)
    # A v5e kernel is lent 16 MiB; the call asks for twice the budget.
    assert held <= pa._PREFILL_VMEM_BYTES < pa._PREFILL_VMEM_LIMIT_BYTES \
        <= 32 * MIB
    if heads < KV:                       # the next divisor would not fit
        nxt = min(d for d in range(heads + 1, KV + 1) if KV % d == 0)
        assert nxt * G * bt > pa._PREFILL_BLOCK_ROWS \
            or pa._prefill_vmem_bytes(bt, nxt, *args) > pa._PREFILL_VMEM_BYTES
    if bt < T & -T:                      # nor would twice the positions
        assert 2 * G * bt > pa._PREFILL_HEAD_ROWS \
            or pa._prefill_vmem_bytes(2 * bt, 1, *args) \
            > pa._PREFILL_VMEM_BYTES
    # A caller's block_t is taken as given; only the fold is chosen.
    assert pa.prefill_block_shape(*rule_args, block_t=8)[0] == 8


@pytest.mark.parametrize("window,bt,bs,n_table", [
    (4096, 256, 256, 32), (4096, 128, 512, 16), (4096, 8, 256, 32),
    (100, 16, 8, 32), (100, 64, 16, 16), (5, 16, 8, 32), (1, 8, 8, 32),
    (0, 16, 8, 32), (0, 128, 8, 32)])
def test_every_visible_key_lies_in_a_walked_page(window, bt, bs, n_table):
    """For every start the live blocks [first, last] of a row-block hold
    every key ANY of its queries can see and no block wholly out of
    sight of all of them, name table entries, and number no more than a
    window plus the row-block can span; the kernel's arithmetic (jnp) and
    the host's (numpy) agree."""
    reach = n_table * bs
    first_q = np.arange(0, reach - bt + 1)
    first, last = pa._prefill_live_blocks(first_q, bt, bs, window, n_table,
                                          xp=np)
    jf, jl = pa._prefill_live_blocks(jnp.asarray(first_q, jnp.int32), bt,
                                     bs, window, n_table)
    assert np.array_equal(np.broadcast_to(np.asarray(jf), first.shape),
                          first) and np.array_equal(np.asarray(jl), last)
    assert ((0 <= first) & (first <= last) & (last < n_table)).all()
    last_q = first_q + bt - 1
    lo = np.maximum(first_q - (window - 1), 0) if window else 0 * first_q
    assert (first == lo // bs).all()         # the first query's first key
    assert (last == last_q // bs).all()      # the last query's own key
    if window:
        assert (last - first + 1 <= -(-(window + bt - 2) // bs) + 1).all()


def test_pages_walked_counts_the_walk():
    # Mistral's longdoc chunk at 3 584: two row-blocks of 256 see pages
    # 0-14 and 0-15 of a 32-entry table.
    assert pa.prefill_pages_walked([3584], 512, 256, 256, 4096, 32) \
        == (15 + 16, 2 * 32)
    # Past the window the floor moves up with the row-block.
    assert pa.prefill_pages_walked([6656], 512, 256, 256, 4096, 32) \
        == (2 * 17, 2 * 32)
    # Chat: a first chunk, and two rows in one call.
    assert pa.prefill_pages_walked([0], 512, 256, 256, 4096, 32) \
        == (1 + 2, 2 * 32)
    assert pa.prefill_pages_walked([0, 512], 512, 128, 256, 0, 64) \
        == ((1 + 1 + 2 + 2) + (3 + 3 + 4 + 4), 8 * 64)
    # Runs of two pages are walked whole.
    assert pa.prefill_pages_walked([700], 64, 64, 256, 0, 32, ppb=2) \
        == (4, 32)
