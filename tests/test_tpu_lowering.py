"""AOT TPU-lowering checks for every Pallas kernel variant.

Mosaic enforces TPU layout rules (e.g. a block's trailing two dims must
be (8, 128)-divisible or equal the array dims) at LOWERING time — which
``interpret=True`` CPU tests never reach. The first on-chip bench ladder
(2026-07-31) found exactly such a bug: the int8-KV per-token scale
tensors' ``(1, 1, block)`` BlockSpecs put a size-1 block on the KV dim,
killing the 8B/kv-quant/int4/SWA rungs on hardware while 264 CPU tests
stayed green (fixed by the rank-4 ``[.., KV, 1, page]`` scale layout).
``jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))`` runs that
validation on a CPU-only box, so this module keeps the paged decode/prefill
x bf16/int8-KV x windowed matrix lowerable without ever touching a chip.

These tests do NOT execute anything — success is "Mosaic accepted the
kernel"; numerics are covered by the interpret-mode parity suites
(test_ops_paged* / test_kv_quant).
"""
import jax
import jax.numpy as jnp
import pytest

from llmapigateway_tpu.ops import paged_attention as pa

# The int8 pools the benchmark's two configurations serve (the file that
# COMPILES the same kernels for the described chip states them).
from test_aot_tpu_compile import SERVED as STACKED

B, KV, G, S, Dh, T = 2, 4, 2, 256, 128, 128
H = KV * G
P, PAGE, NP = 16, 128, 2


def _paged_kv(quant):
    key = jax.random.PRNGKey(0)
    if quant:
        mk = lambda: {"q": jax.random.randint(key, (P, KV, PAGE, Dh),
                                              -127, 127, jnp.int8),
                      "s": jnp.ones((P, KV, 1, PAGE), jnp.float32)}
    else:
        mk = lambda: jax.random.normal(key, (P, KV, PAGE, Dh), jnp.bfloat16)
    return mk(), mk()


def _lower(fn, *args):
    jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("window", [0, 96], ids=["full", "windowed"])
@pytest.mark.parametrize("ppb", [1, 2], ids=["ppb1", "ppb2"])
def test_paged_decode_lowers_for_tpu(quant, window, ppb):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (B, H, Dh), jnp.bfloat16)
    kn = jax.random.normal(key, (B, KV, Dh), jnp.bfloat16)
    vn = jax.random.normal(key, (B, KV, Dh), jnp.bfloat16)
    pk, pv = _paged_kv(quant)
    # Packed for ppb=2: each slot's 2-page group is an aligned run.
    ptab = jnp.array([[2, 3], [4, 5]], jnp.int32)
    ns = jnp.array([100, 0], jnp.int32)
    _lower(lambda *a: pa.paged_decode_attention(
        *a, window=window, pages_per_block=ppb, interpret=False),
        q, kn, vn, pk, pv, ptab, ns)


@pytest.mark.parametrize("geometry", [
    # (B, KV, G, page, Dh, NP, ppb, quant, window) -> heads in a block
    # What the benchmark serves: Mistral-7B, int8 pool, all 8 heads fold.
    ((8, 8, 4, 256, 128, 32, 1, True, 4096), 8),
    # ... under TP=4, two local KV heads a chip.
    ((8, 2, 4, 256, 128, 32, 1, True, 4096), 2),
    # A bf16 pool in runs of four pages: the VMEM budget folds four of
    # eight heads, so the grid has two programs.
    ((8, 8, 4, 256, 128, 32, 4, False, 0), 4),
    ((4, 8, 1, 128, 128, 8, 2, False, 1024), 8),
], ids=["mistral7b-int8", "mistral7b-int8-tp4", "bf16-ppb4", "g1-ppb2"])
def test_paged_decode_lowers_at_served_geometry(geometry):
    """The decode kernel's blocks, buffers and copies at the widths that
    are served (shapes only — nothing is allocated): Mosaic's layout
    rules see the folded-head blocks the tiny matrix above cannot have."""
    (b, kv, g, page, dh, n_pages, ppb, quant, window), heads = geometry
    pool = b * n_pages + ppb
    sds = jax.ShapeDtypeStruct
    if quant:
        side = {"q": sds((pool, kv, page, dh), jnp.int8),
                "s": sds((pool, kv, 1, page), jnp.float32)}
    else:
        side = sds((pool, kv, page, dh), jnp.bfloat16)
    assert pa._decode_heads_per_block(
        kv, page, dh, 1 if quant else 2, quant, ppb) == heads
    _lower(lambda *a: pa.paged_decode_attention(
        *a, window=window, pages_per_block=ppb, interpret=False),
        sds((b, kv * g, dh), jnp.bfloat16), sds((b, kv, dh), jnp.bfloat16),
        sds((b, kv, dh), jnp.bfloat16), side, side,
        sds((b, n_pages), jnp.int32), sds((b,), jnp.int32))


@pytest.mark.parametrize("kernel", ["read", "write", "write-verify5"])
@pytest.mark.parametrize("geometry", list(STACKED))
def test_stacked_pool_kernels_lower_at_served_geometry(geometry, kernel):
    """PR 30's two kernels over the layer-STACKED pool (shapes only): the
    decode kernel reading layer ``layer`` of it in place, and the write
    kernel whose pool operands are its outputs."""
    layers, pages, kv, heads, slots, width, window = STACKED[geometry]
    sds = jax.ShapeDtypeStruct
    side = {"q": sds((layers, pages, kv, 256, 128), jnp.int8),
            "s": sds((layers, pages, kv, 1, 256), jnp.float32)}
    table, ints = sds((slots, width), jnp.int32), sds((slots,), jnp.int32)
    if kernel == "read":
        _lower(lambda q, kn, vn, pk, pv, tbl, n, layer:
               pa.paged_decode_attention(q, kn, vn, pk, pv, tbl, n,
                                         layer=layer, window=window,
                                         interpret=False),
               sds((slots, heads, 128), jnp.bfloat16),
               sds((slots, kv, 128), jnp.bfloat16),
               sds((slots, kv, 128), jnp.bfloat16), side, side, table, ints,
               sds((), jnp.int32))
    else:
        new = sds((layers, slots, 1 if kernel == "write" else 5, kv, 128),
                  jnp.bfloat16)
        _lower(lambda *a: pa.paged_insert_in_place(*a, interpret=False),
               side, side, new, new, table, ints, sds((slots,), jnp.bool_))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("window", [0, 96], ids=["full", "windowed"])
@pytest.mark.parametrize("ppb", [1, 2], ids=["ppb1", "ppb2"])
def test_paged_prefill_lowers_for_tpu(quant, window, ppb):
    key = jax.random.PRNGKey(0)
    qp = jax.random.normal(key, (B, T, H, Dh), jnp.bfloat16)
    pk, pv = _paged_kv(quant)
    ptab = jnp.array([[2, 3], [4, 5]], jnp.int32)
    st = jnp.array([0, 64], jnp.int32)
    _lower(lambda *a: pa.paged_prefill_attention(
        *a, window=window, pages_per_block=ppb, interpret=False),
        qp, pk, pv, ptab, st)


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8kv"])
@pytest.mark.parametrize("window", [0, 96], ids=["full", "windowed"])
@pytest.mark.parametrize("t", [8, T], ids=["t8", "t128"])
def test_paged_prefill_fold_of_seven_lowers_for_tpu(quant, window, t):
    """PR 37: SmallThinker's 28 query heads over 4 KV heads — a fold of
    SEVEN, so a block's rows are a multiple of 8 sublanes and not of 128
    — and the smallest prefill bucket, where every KV head folds into
    one program."""
    key = jax.random.PRNGKey(0)
    qp = jax.random.normal(key, (B, t, 7 * KV, Dh), jnp.bfloat16)
    pk, pv = _paged_kv(quant)
    ptab = jnp.array([[2, 3], [4, 5]], jnp.int32)
    st = jnp.array([0, 64], jnp.int32)
    _lower(lambda *a: pa.paged_prefill_attention(
        *a, window=window, interpret=False), qp, pk, pv, ptab, st)

