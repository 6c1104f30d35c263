"""Multi-page paged-kernel parity matrix (ISSUE 2 tentpole).

The multi-page kernels fetch ``pages_per_block`` contiguous logical pages
per grid step (one larger HBM→VMEM DMA, a smaller grid) but attend them
per-page in order — so every ``pages_per_block`` must be BIT-FOR-BIT
identical to the per-page kernel (``pages_per_block=1``, today's code
path), across {bf16, int8-KV} × {full, windowed} × ragged lengths, for
both decode and prefill. Numerics against the dense math are pinned by
the adapter's reference impl (gather + jnp) on the same pool.

Tables here are PACKED the way the engine's superpage allocator packs
them (engine/paged.py ``pages_per_block``): each aligned group of ppb
logical pages maps to an aligned contiguous physical run, with the runs
themselves scrambled across the pool.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.ops.paged_attention import (
    make_paged_attention_fn,
    paged_decode_attention,
    paged_prefill_attention,
)
from tests.mesh_parity import serve

PPB = 4            # pack for the largest variant; 1/2/4 all divide it


def _setup_packed(B, S, T, H, KV, Dh, page, seed=0, quant=False):
    """Random q/k_new/v_new + a PACKED page table (aligned superpage runs
    of PPB pages, runs scrambled) + a pre-filled pool."""
    NP = S // page
    assert NP % PPB == 0
    n_groups = B * (NP // PPB)
    n_sp = n_groups + 2               # + trash group 0 + one spare
    P = n_sp * PPB
    rng = np.random.default_rng(seed)
    sps = np.arange(1, n_groups + 1)
    rng.shuffle(sps)
    table = np.zeros((B, NP), np.int32)
    for b in range(B):
        for g in range(NP // PPB):
            sp = int(sps[b * (NP // PPB) + g])
            for i in range(PPB):
                table[b, g * PPB + i] = sp * PPB + i

    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(keys[0], (B, T, H, Dh), jnp.float32)
    k_new = jax.random.normal(keys[1], (B, T, KV, Dh), jnp.float32)
    v_new = jax.random.normal(keys[2], (B, T, KV, Dh), jnp.float32)

    if quant:
        # Realistic int8-KV magnitudes: scales sized like quantize_kv's
        # (|x|max/127 of unit-normal data ≈ 0.02) so dequantized values
        # are O(1) — giant synthetic scales would amplify benign fp32
        # accumulation-order differences past any sane tolerance.
        def mk():
            r = np.random.default_rng(seed + 7)
            return {
                "q": jnp.asarray(r.integers(-127, 128, (P, KV, page, Dh)),
                                 jnp.int8),
                "s": jnp.asarray(0.01 + 0.02 * r.random((P, KV, 1, page)),
                                 jnp.float32),
            }
        pk, pv = mk(), mk()
    else:
        pkeys = jax.random.split(jax.random.PRNGKey(seed + 7), 2)
        pk = jax.random.normal(pkeys[0], (P, KV, page, Dh), jnp.float32)
        pv = jax.random.normal(pkeys[1], (P, KV, page, Dh), jnp.float32)
    return q, k_new, v_new, pk, pv, jnp.asarray(table)


def _attn(table, S, window, ppb, impl="pallas"):
    return make_paged_attention_fn(table, max_seq=S, impl=impl,
                                   interpret=True, block_t=16,
                                   window=window, pages_per_block=ppb)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16pool", "int8kv"])
@pytest.mark.parametrize("window", [0, 24], ids=["full", "windowed"])
def test_multipage_decode_bitforbit_and_vs_reference(quant, window):
    B, S, H, KV, Dh, page = 4, 128, 4, 2, 16, 16
    q, k_new, v_new, pk, pv, table = _setup_packed(
        B, S, 1, H, KV, Dh, page, seed=2, quant=quant)
    # Ragged: fresh slot, mid-page, page boundary, near cache end.
    lengths = jnp.asarray([0, 23, 64, S - 1], jnp.int32)
    active = jnp.ones((B,), bool)

    outs = {}
    for ppb in (1, 2, 4):
        outs[ppb] = np.asarray(_attn(table, S, window, ppb).decode(
            q, k_new, v_new, pk, pv, lengths, active))
    # pages_per_block=1 IS today's kernel; 2 and 4 must match it
    # bit-for-bit (same per-page attends in the same order).
    assert np.array_equal(outs[1], outs[2])
    assert np.array_equal(outs[1], outs[4])
    # And the family is numerically pinned to the gather+dense reference.
    ref = np.asarray(_attn(table, S, window, 1, impl="reference").decode(
        q, k_new, v_new, pk, pv, lengths, active))
    np.testing.assert_allclose(outs[1], ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16pool", "int8kv"])
@pytest.mark.parametrize("window", [0, 40], ids=["full", "windowed"])
def test_multipage_prefill_bitforbit_and_vs_reference(quant, window):
    B, S, T, H, KV, Dh, page = 2, 128, 16, 4, 2, 16, 16
    q, k_new, v_new, pk, pv, table = _setup_packed(
        B, S, T, H, KV, Dh, page, seed=3, quant=quant)
    # Chunk starts mid-sequence: the window spans chunk + cache and
    # crosses superpage boundaries.
    start = jnp.asarray([70, 3], jnp.int32)

    outs = {}
    for ppb in (1, 2, 4):
        out, _, _ = _attn(table, S, window, ppb)(
            q, k_new, v_new, pk, pv, start)
        outs[ppb] = np.asarray(out)
    assert np.array_equal(outs[1], outs[2])
    assert np.array_equal(outs[1], outs[4])
    ref, _, _ = _attn(table, S, window, 1, impl="reference")(
        q, k_new, v_new, pk, pv, start)
    np.testing.assert_allclose(outs[1], np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_multipage_rejects_undividable_geometry():
    """The functional API refuses geometry the packed contract can't
    cover (the engine falls back to 1 BEFORE reaching here)."""
    B, S, H, KV, Dh, page = 2, 96, 4, 2, 16, 16     # NP=6: % 4 != 0
    q, k_new, v_new, pk, pv, table = _setup_packed(
        B, 64, 1, H, KV, Dh, page, seed=4)
    bad_table = jnp.concatenate([table, table[:, :2]], axis=1)   # NP=6
    with pytest.raises(ValueError, match="pages_per_block"):
        paged_decode_attention(q[:, 0], k_new[:, 0], v_new[:, 0], pk, pv,
                               bad_table, jnp.zeros((B,), jnp.int32),
                               pages_per_block=4, interpret=True)
    with pytest.raises(ValueError, match="pages_per_block"):
        paged_prefill_attention(q, pk, pv, bad_table,
                                jnp.zeros((B,), jnp.int32), block_t=1,
                                pages_per_block=4, interpret=True)


def test_engine_packed_allocator_tables_satisfy_kernel_contract():
    """The allocator's superpage packing produces exactly the aligned
    contiguous runs the kernels' gather-free index maps assume — checked
    over a churny allocate/release workload."""
    from llmapigateway_tpu.engine.paged import PageAllocator
    rng = np.random.default_rng(11)
    ppb = 4
    alloc = PageAllocator(num_pages=64, page_size=16, batch=6, max_seq=128,
                          pages_per_block=ppb)
    held = {}
    for _ in range(300):
        alloc.check_invariants()
        if held and (rng.random() < 0.4 or len(held) == 6):
            slot = int(rng.choice(list(held)))
            alloc.release(slot)
            del held[slot]
        else:
            free = [s for s in range(6) if s not in held]
            slot = int(rng.choice(free))
            if alloc.allocate(slot, int(rng.integers(1, 140))):
                held[slot] = True
        # The kernel contract over every mapped group of every row.
        for row in alloc.table:
            for g in range(len(row) // ppb):
                p0 = int(row[g * ppb])
                if p0 == 0:
                    continue
                assert p0 % ppb == 0, "run not aligned"
                assert list(row[g * ppb:(g + 1) * ppb]) == \
                    list(range(p0, p0 + ppb)), "run not contiguous"


def test_packed_allocator_rounds_reservations_to_runs():
    from llmapigateway_tpu.engine.paged import PageAllocator
    alloc = PageAllocator(num_pages=32, page_size=16, batch=4, max_seq=128,
                          pages_per_block=4)
    assert alloc.pages_needed(1) == 4          # one whole run
    assert alloc.pages_needed(65) == 8         # 5 raw pages → 2 runs
    assert alloc.free_pages == 28              # trash GROUP reserved
    assert alloc.allocate(0, 1)
    assert alloc.table[0, 0] != 0 and alloc.table[0, 0] % 4 == 0
    alloc.check_invariants()
    alloc.release(0)
    assert alloc.free_pages == 28
    # Ring reservations don't compose with packing (engine disables it).
    with pytest.raises(ValueError, match="ring"):
        alloc.allocate(1, 100, ring_pages=2)


async def test_engine_multipage_blocks_on_a_model_mesh_match_one_device():
    """`kv_pages_per_block` 4 under a mesh: the multi-page kernels
    (interpret mode) run under `shard_map` over `model`, on tables the
    packing allocator laid out, and serve the one-device engine's
    tokens."""
    kw = dict(kv_page_size=8, kv_pages_per_block=4, attention="pallas")
    ref, _ = await serve({}, **kw)
    got, eng = await serve({"model": 2}, **kw)
    assert got == ref
    assert eng.kv_ppb == 4 and eng.attention_impl == "pallas"
    assert eng.stats()["pages_per_block"] == 4
    eng._prefix_cache.check_invariants()
