"""The paged decode kernel's walk (ISSUE 27): a page's KV heads folded
into one block, and only a slot's LIVE blocks visited.

What one copy CARRIES changed, and which blocks are visited at all; what
is COMPUTED for each head did not. So the kernel is held, in interpret
mode, to

* the gather + dense reference on the same pool (the adapter's
  ``impl="reference"``), within the tolerances the other paged suites use;
* bit-for-bit equality across ``pages_per_block`` values and across head
  folds of more than one head;
* a FROZEN copy of the kernel it replaced — grid ``(B, KV, NP // ppb)``,
  one head's ``(ppb, 1, page, Dh)`` block a step, dead steps clamped —
  which lives on below and nowhere in the package. Bit for bit where a
  block holds one head. Where it holds several, the heads are the batch
  dimension of the same dots and reductions, and XLA's CPU backend (which
  interpret mode runs on) rounds a batched row reduction differently from
  a plain one in the last places of a few results in a hundred: there the
  bound is sixteen units of fp32's last place (1e-6; the reference's is
  2e-5). On the
  chip the two kernels agree bit for bit at the served geometry
  (PERF.md, PR 27).

One batch holds the awkward slots: a fresh one, a context past the window
whose first visible key lies mid-page (the window then spans
``ceil((window - 1) / bs) + 1`` blocks), a context exactly on a page
edge, an inactive slot, one short of the cache's end; the last slot's
table is a rotated ring, so its physical runs are not monotone and
repeat.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llmapigateway_tpu.ops import paged_attention as pa

NEG_INF = -1e30
FEW_ULP = 16 * 2.0 ** -24            # sixteen units of fp32's last place
PAGE, DH, S = 16, 16, 128
NP = S // PAGE                       # 8 logical pages a slot
PACK = 2                             # tables packed for ppb 1 and 2
WINDOW = 4 * PAGE + 1                # spans ceil(64 / 16) + 1 = 5 pages
# fresh | w0 = 36, mid-page 2: pages 2..6 live, five of them | on a page
# edge | inactive (masked to 0 by the adapter) | last column | ring slot
LENGTHS = [0, 100, 64, 77, S - 1, 90]
ACTIVE = [True, True, True, False, True, True]
B = len(LENGTHS)


# ---------------------------------------------------------------------------
# The kernel PR 27 replaced, frozen: one KV head's block a grid step. Its
# block arithmetic comes first, frozen with it.
# ---------------------------------------------------------------------------

def self_column_init(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref) -> None:
    """Initialize a decode kernel's online-softmax state from the SELF
    column (the new token attending itself): m = q·k_new, l = 1,
    acc = v_new. The cache is STALE — the current token's K/V never
    touched HBM; its contribution lives entirely in registers (the
    deferred-insert decode protocol, models/llama.py forward())."""
    q = q_ref[0, 0].astype(jnp.float32)            # [G, Dh]
    kn = kn_ref[0, 0].astype(jnp.float32)          # [1, Dh]
    vn = vn_ref[0, 0].astype(jnp.float32)          # [1, Dh]
    self_s = jax.lax.dot_general(
        q, kn, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # [G, 1]
    self_s *= q.shape[-1] ** -0.5
    m_ref[:] = jnp.broadcast_to(self_s, m_ref.shape)
    l_ref[:] = jnp.ones_like(l_ref)
    acc_ref[:] = jnp.broadcast_to(vn, acc_ref.shape)


def attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask,
                 ks_ref=None, vs_ref=None, sub: int = 0) -> None:
    """One online-softmax block update of the frozen kernel (bf16 and
    int8-KV). ``mask(scores)`` applies the caller's visibility rule;
    ``ks_ref``/``vs_ref`` are the optional int8-KV per-token scale blocks
    ``[1, 1, 1, BS]`` (rank-4: the unit dim before the token axis keeps the
    block's trailing two dims ``(1, BS)`` legal under the TPU (8, 128)
    tiling rule — a ``(1, BS)`` block of a rank-3 ``[B, KV, S]`` array
    would put a block of 1 on the KV dim, which real Mosaic lowering
    rejects; interpret mode never catches this): the scale factors out of
    the Dh contraction, so scores
    multiply by ``ks`` after the QK dot and probs by ``vs`` before the PV
    dot (after ``l`` accumulates — the softmax denominator is unscaled),
    and no dequantized [BS, Dh] block is ever built.

    ``sub`` (static) selects the K/V/scale sub-block along the leading
    block dim: the multi-page paged kernels fetch ``pages_per_block``
    physical pages in ONE ``(ppb, 1, page, Dh)`` block and attend them
    per-page (ops/paged_attention.py), so each call here stays the exact
    per-page update — only the DMA granularity grows."""
    q = q_ref[0, 0]                                # [rows, Dh]
    k = k_ref[sub, 0]                              # [BS, Dh] (bf16 or int8)
    v = v_ref[sub, 0].astype(jnp.float32)
    if k.dtype == jnp.int8:
        # int8-KV QK dot (the worst_kernel() pick on the int8 ladder —
        # decode.d*.greedy sat at ~0.4 of the HBM roof): dequant is fused
        # into the dot as a cast to q's NATIVE dtype. Every int8 value is
        # exact in bf16 (8 mantissa bits ≥ the 7 magnitude bits of ±127),
        # so scores are bit-identical to the old `.astype(float32)` pair —
        # but the MXU now runs one native low-precision pass with fp32
        # accumulation instead of the multi-pass fp32×fp32 matmul the
        # explicit upcast forced.
        k = k.astype(q.dtype)
    else:
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)        # [rows, BS]
    scores *= q.shape[-1] ** -0.5
    if ks_ref is not None:
        scores = scores * ks_ref[sub, 0]
    scores = mask(scores)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    e = jnp.exp(scores - m_new)                    # [rows, BS]
    l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(e, axis=1, keepdims=True)
    p = e if vs_ref is None else e * vs_ref[sub, 0]
    acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)        # [rows, Dh]
    m_ref[:, :1] = m_new


def unpack_kv_refs(refs):
    """(k, ks, v, vs, o, m, l, acc) from a kernel's trailing refs. Without
    int8-KV the scale refs are absent (arity 6) and come back None."""
    if len(refs) == 8:
        return refs
    k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    return k_ref, None, v_ref, None, o_ref, m_ref, l_ref, acc_ref

def _frozen_per_head_kernel(pt_ref, nvalid_ref, q_ref, kn_ref, vn_ref,
                            *refs, page, window=0, pages_per_block=1):
    k_ref, ks_ref, v_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = \
        unpack_kv_refs(refs)
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_pb = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        self_column_init(q_ref, kn_ref, vn_ref, m_ref, l_ref, acc_ref)

    n_valid = nvalid_ref[b]
    w0 = jnp.maximum(n_valid - (window - 1), 0) if window else 0
    for i in range(pages_per_block):
        lp = j * pages_per_block + i
        live = lp * page < n_valid
        if window:
            live = live & ((lp + 1) * page > w0)

        @pl.when(live)
        def _block(i=i, lp=lp):
            def mask(scores):
                pos = lp * page + jax.lax.broadcasted_iota(
                    jnp.int32, scores.shape, 1)
                ok = pos < n_valid
                if window:
                    ok = ok & (pos >= w0)
                return jnp.where(ok, scores, NEG_INF)
            attend_block(q_ref, k_ref, v_ref, m_ref, l_ref, acc_ref, mask,
                         ks_ref, vs_ref, sub=i)

    @pl.when(j == n_pb - 1)
    def _out():
        l = l_ref[:, :1]
        o_ref[0, 0] = (acc_ref[:] / l).astype(o_ref.dtype)


def frozen_per_head_decode(q, k_new, v_new, k_pages, v_pages, page_table,
                           n_stale, *, window=0, pages_per_block=1):
    B, H, Dh = q.shape
    quant = isinstance(k_pages, dict)
    kq = k_pages["q"] if quant else k_pages
    KV, page = kq.shape[1], kq.shape[2]
    NP = page_table.shape[1]
    ppb = pages_per_block
    bs = ppb * page
    G = H // KV

    def _live_range(nv_b):
        last = jnp.maximum((nv_b + bs - 1) // bs - 1, 0)
        if window:
            first = jnp.minimum(
                jnp.maximum(nv_b - (window - 1), 0) // bs, last)
        else:
            first = 0
        return first, last

    def kv_index(b, h, j, pt, nv):
        first, last = _live_range(nv[b])
        p0 = pt[b, jnp.clip(j, first, last) * ppb]
        return (p0 // ppb if ppb > 1 else p0), h, 0, 0

    kv_spec = pl.BlockSpec((ppb, 1, page, Dh), kv_index)
    s_spec = pl.BlockSpec((ppb, 1, 1, page), kv_index)
    if quant:
        kv_operands = (k_pages["q"], k_pages["s"],
                       v_pages["q"], v_pages["s"])
        kv_specs = [kv_spec, s_spec, kv_spec, s_spec]
    else:
        kv_operands = (k_pages, v_pages)
        kv_specs = [kv_spec, kv_spec]
    head = lambda b, h, j, pt, nv: (b, h, 0, 0)     # noqa: E731
    out = pl.pallas_call(
        functools.partial(_frozen_per_head_kernel, page=page, window=window,
                          pages_per_block=ppb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, KV, NP // ppb),
            in_specs=[pl.BlockSpec((1, 1, G, Dh), head),
                      pl.BlockSpec((1, 1, 1, Dh), head),
                      pl.BlockSpec((1, 1, 1, Dh), head),
                      *kv_specs],
            out_specs=pl.BlockSpec((1, 1, G, Dh), head),
            scratch_shapes=[pltpu.VMEM((G, 128), jnp.float32),
                            pltpu.VMEM((G, 128), jnp.float32),
                            pltpu.VMEM((G, Dh), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), q.dtype),
        interpret=True,
    )(page_table.astype(jnp.int32), n_stale.astype(jnp.int32),
      q.reshape(B, KV, G, Dh), k_new[:, :, None, :], v_new[:, :, None, :],
      *kv_operands)
    return out.reshape(B, H * Dh)


# ---------------------------------------------------------------------------
# Inputs
# ---------------------------------------------------------------------------

def _table(rng) -> np.ndarray:
    """Packed for PACK: aligned physical runs, scrambled across the pool;
    the LAST slot walks a ring of three runs from a rotated start, so its
    logical pages 0.. map to runs r1 r2 r0 r1 — not monotone, repeating —
    the way the engine's window ring recycles pages."""
    groups = NP // PACK
    runs = np.arange(1, B * groups + 1)
    rng.shuffle(runs)
    table = np.zeros((B, NP), np.int32)
    for b in range(B):
        mine = runs[b * groups:(b + 1) * groups]
        for g in range(groups):
            run = mine[(g + 1) % 3] if b == B - 1 else mine[g]
            table[b, g * PACK:(g + 1) * PACK] = run * PACK + np.arange(PACK)
    return table


def _inputs(KV: int, G: int, quant: bool, seed: int = 5):
    rng = np.random.default_rng(seed)
    table = _table(rng)
    P = (B * (NP // PACK) + 1) * PACK          # + the trash run at 0
    H = KV * G
    q = jnp.asarray(rng.normal(size=(B, 1, H, DH)), jnp.float32)
    k_new = jnp.asarray(rng.normal(size=(B, 1, KV, DH)), jnp.float32)
    v_new = jnp.asarray(rng.normal(size=(B, 1, KV, DH)), jnp.float32)

    def side():
        if quant:
            # Scales sized like quantize_kv's on unit-normal data.
            return {"q": jnp.asarray(rng.integers(-127, 128,
                                                  (P, KV, PAGE, DH)),
                                     jnp.int8),
                    "s": jnp.asarray(0.01 + 0.02 * rng.random(
                        (P, KV, 1, PAGE)), jnp.float32)}
        return jnp.asarray(rng.normal(size=(P, KV, PAGE, DH)), jnp.bfloat16)
    return q, k_new, v_new, side(), side(), jnp.asarray(table)


def _decode(table, window, ppb, impl="pallas"):
    return pa.make_paged_attention_fn(table, max_seq=S, impl=impl,
                                      interpret=True, window=window,
                                      pages_per_block=ppb).decode


# ---------------------------------------------------------------------------
# Parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G", [1, 4], ids=["g1", "g4"])
@pytest.mark.parametrize("KV", [1, 2, 8], ids=["kv1", "kv2", "kv8"])
@pytest.mark.parametrize("ppb", [1, 2], ids=["ppb1", "ppb2"])
@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "windowed"])
@pytest.mark.parametrize("quant", [True, False], ids=["int8kv", "bf16pool"])
def test_folded_decode_matches_reference_and_the_per_head_kernel(
        quant, window, ppb, KV, G):
    q, k_new, v_new, pk, pv, table = _inputs(KV, G, quant)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    active = jnp.asarray(ACTIVE)
    assert pa._decode_heads_per_block(       # tiny blocks: every head folds
        KV, PAGE, DH, 1 if quant else 2, quant, ppb) == KV

    got = np.asarray(_decode(table, window, ppb)(
        q, k_new, v_new, pk, pv, lengths, active))[:, 0]
    ref = np.asarray(_decode(table, window, 1, impl="reference")(
        q, k_new, v_new, pk, pv, lengths, active))[:, 0]
    # The reference rounds its probabilities to the pool's dtype before
    # the PV product (the kernel keeps them fp32): bf16's step for a bf16
    # pool, the other suites' tolerance for int8.
    tol = 2e-5 if quant else 4e-3
    np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)

    # Across pages_per_block: the same bits.
    if ppb > 1:
        assert np.array_equal(got, np.asarray(_decode(table, window, 1)(
            q, k_new, v_new, pk, pv, lengths, active))[:, 0])
    # Against the per-head kernel (see the module docstring).
    n_stale = jnp.where(active, lengths, 0)
    old = np.asarray(frozen_per_head_decode(
        q[:, 0], k_new[:, 0], v_new[:, 0], pk, pv, table, n_stale,
        window=window))
    if KV == 1:
        assert np.array_equal(got, old)
    else:
        np.testing.assert_allclose(got, old, rtol=FEW_ULP, atol=FEW_ULP)
    # The inactive slot reads nothing stale: its output is its own value.
    v_self = np.repeat(np.asarray(v_new[3, 0]), G, axis=0).reshape(-1)
    np.testing.assert_allclose(got[3], v_self, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("heads", [2, 4], ids=["fold2", "fold4"])
@pytest.mark.parametrize("window", [0, WINDOW], ids=["full", "windowed"])
def test_partial_fold_is_bit_for_bit_with_the_full_fold(
        monkeypatch, window, heads):
    """A budget that holds only some of a page's heads splits them over
    the grid; each head's arithmetic does not notice."""
    KV, G = 8, 2
    q, k_new, v_new, pk, pv, table = _inputs(KV, G, quant=True, seed=9)
    lengths = jnp.asarray(LENGTHS, jnp.int32)
    active = jnp.asarray(ACTIVE)
    full = np.asarray(_decode(table, window, 2)(
        q, k_new, v_new, pk, pv, lengths, active))
    one_head = 2 * 2 * 2 * (PAGE * DH + 8 * PAGE * 4)   # ppb 2, int8
    monkeypatch.setattr(pa, "_DECODE_KV_VMEM_BYTES", heads * one_head)
    assert pa._decode_heads_per_block(KV, PAGE, DH, 1, True, 2) == heads
    part = np.asarray(_decode(table, window, 2)(
        q, k_new, v_new, pk, pv, lengths, active))
    assert np.array_equal(part, full)


# ---------------------------------------------------------------------------
# The two rules: pure shape arithmetic
# ---------------------------------------------------------------------------

MIB = 2 ** 20


@pytest.mark.parametrize("case", [
    # (KV, page, Dh, itemsize, quant, ppb) -> heads in a block
    # The benchmarked configuration: Mistral-7B, int8 pool, one chip ...
    ((8, 256, 128, 1, True, 1), 8),
    # ... and under TP=4 (two local KV heads a chip).
    ((2, 256, 128, 1, True, 1), 2),
    ((8, 256, 128, 1, True, 4), 4),
    # A bf16 pool in runs of four pages: 1 MiB a head, so four fold.
    ((8, 256, 128, 2, False, 4), 4),
    ((8, 256, 128, 2, False, 1), 8),
    # Heads that do not divide evenly fold by a divisor, never a remainder.
    ((6, 256, 128, 2, False, 4), 3),
    # A block too large for the budget still gets one head.
    ((8, 1024, 256, 2, False, 4), 1),
    # The tests' geometry.
    ((2, 16, 16, 1, True, 2), 2),
], ids=lambda c: "-".join(map(str, c[0])))
def test_heads_per_block_rule(case):
    args, heads = case
    assert pa._decode_heads_per_block(*args) == heads
    KV, page, Dh, itemsize, quant, ppb = args
    assert KV % heads == 0
    buffers = 2 * 2 * ppb * heads * (page * Dh * itemsize
                                     + (8 * page * 4 if quant else 0))
    assert heads == 1 or buffers <= pa._DECODE_KV_VMEM_BYTES
    if heads < KV:                       # the next divisor would not fit
        nxt = min(d for d in range(heads + 1, KV + 1) if KV % d == 0)
        assert buffers // heads * nxt > pa._DECODE_KV_VMEM_BYTES
    assert pa._DECODE_KV_VMEM_BYTES <= 4 * MIB     # a v5e kernel has 16


@pytest.mark.parametrize("window,bs,n_table", [
    (4096, 256, 32), (4096, 512, 16), (65, 16, 8), (24, 16, 8), (5, 16, 8),
    (1, 16, 8), (0, 16, 8)])
def test_every_visible_key_lies_in_a_walked_block(window, bs, n_table):
    """For every query position the live blocks [first, last] hold every
    visible stale key and nothing wholly out of sight, name table entries,
    and number no more than a window can span."""
    n = np.arange(0, n_table * bs + 1)
    first, last = (np.broadcast_to(np.asarray(x), n.shape)
                   for x in pa._decode_live_blocks(
                       jnp.asarray(n, jnp.int32), bs, window, n_table))
    assert ((0 <= first) & (first <= last) & (last < n_table)).all()
    lo = np.maximum(n - (window - 1), 0) if window else np.zeros_like(n)
    seen = n > lo                                # some stale key is visible
    assert (first[seen] == lo[seen] // bs).all()
    assert (last[seen] == (n[seen] - 1) // bs).all()
    assert (last[~seen] == first[~seen]).all()   # one block, skipped inside
    if window:
        assert (last - first + 1 <= -(-(window - 1) // bs) + 1).all()
