"""Int8 KV-cache quantization (kv_quant="int8"): per-token-per-head int8
K/V with fp32 scales, dispatched through the same {"q","s"}-dict convention
as weight quant. Covers quantize/roundtrip bounds, jnp forward fidelity,
the Pallas q8 kernels vs the jnp reference, engine E2E (alone and combined
with weight quant), and the config guardrails."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.models import llama
from llmapigateway_tpu.models.config import get_preset
from tests.mesh_parity import serve, split_dims


def test_quantize_kv_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)) * 4.0, jnp.float32)
    q, s = llama.quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 5, 3)
    deq = np.asarray(q, np.float32) * np.asarray(s)[..., None]
    lsb = np.asarray(s)[..., None]
    assert np.all(np.abs(deq - np.asarray(x)) <= 0.5 * lsb + 1e-7)


@pytest.fixture(scope="module")
def setup():
    cfg = get_preset("tiny-test")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    return cfg, params


def _run_forward(cfg, params, cache, tokens, **kw):
    logits, cache = llama.forward(params, cfg, tokens,
                                  jnp.zeros((tokens.shape[0],), jnp.int32),
                                  cache, **kw)
    return logits, cache


def test_forward_fidelity_with_int8_cache(setup):
    """Prefill + decode through the int8 cache must track the fp32 cache
    within quantization noise (~1% relative on logits)."""
    cfg, params = setup
    B, T, S = 2, 8, 32
    rng = np.random.default_rng(1)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    act = jnp.ones((B,), bool)

    ref_cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)
    ref_pre, ref_cache = _run_forward(cfg, params, ref_cache, tokens)
    q_cache = llama.KVCache.create(cfg, B, S, kv_quant="int8")
    q_pre, q_cache = _run_forward(cfg, params, q_cache, tokens)

    step = jnp.full((B,), T, jnp.int32)
    ref_dec, _ = llama.forward(params, cfg, tokens[:, :1], step, ref_cache,
                               active=act)
    q_dec, _ = llama.forward(params, cfg, tokens[:, :1], step, q_cache,
                             active=act)
    for ref, got in ((ref_pre, q_pre), (ref_dec, q_dec)):
        r, g = np.asarray(ref, np.float64), np.asarray(got, np.float64)
        rel = np.linalg.norm(g - r) / np.linalg.norm(r)
        assert rel < 0.05, rel


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-mistral-test"])
@pytest.mark.parametrize("quant", ["", "int8"])
def test_engine_e2e_with_kv_quant(quant, preset):
    """Engine serves greedily from the int8 pool (int8 pages pack 2x the
    tokens) — alone, and combined with int8 weights (the fully-quantized
    configuration) — whole contexts and the window's page ring (what the
    Mistral cells serve from: int8 weights, an int8 ring)."""
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(preset=preset, max_batch_size=2,
                            max_seq_len=128, prefill_chunk=16,
                            decode_burst=4, kv_quant="int8", quant=quant,
                            kv_page_size=8,
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    assert engine.cache.k["q"].dtype == jnp.int8
    assert engine.cache.k["s"].dtype == jnp.float32
    assert engine.stats()["kv_quant"] == "int8"

    async def run():
        await engine.start()
        req = GenRequest(prompt_ids=list(range(1, 9)), max_tokens=10,
                         temperature=0.0)
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()
        return req

    req = asyncio.run(run())
    assert req.finish_reason == "length" and len(req.generated) == 10


def test_paged_q8_kernels_match_reference(setup):
    """Paged decode/prefill kernels over an int8 pool (interpret mode)
    must match the reference gather+dense path on the same state."""
    from llmapigateway_tpu.ops.paged_attention import (
        PagedKVCache, gather_pages, paged_decode_attention, paged_insert_kv,
        paged_prefill_attention)

    cfg, params = setup
    B, S, page = 2, 64, 16
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    NP = S // page
    num_pages = B * NP + 1
    rng = np.random.default_rng(3)
    # Slot b owns pages [1 + b*NP, 1 + (b+1)*NP).
    table = jnp.asarray(
        [[1 + b * NP + j for j in range(NP)] for b in range(B)], jnp.int32)

    pool = PagedKVCache.create(cfg, num_pages, page, kv_quant="int8")
    lk, lv = pool.k, pool.v
    hist_k = jnp.asarray(rng.standard_normal((B, 48, KV, Dh)), jnp.float32)
    hist_v = jnp.asarray(rng.standard_normal((B, 48, KV, Dh)), jnp.float32)
    layer_k = {"q": lk["q"][0], "s": lk["s"][0]}     # layer-0 pool slice
    layer_v = {"q": lv["q"][0], "s": lv["s"][0]}
    layer_k, layer_v = paged_insert_kv(layer_k, layer_v, hist_k, hist_v,
                                       table, jnp.zeros((B,), jnp.int32),
                                       None)

    lengths = jnp.asarray([37, 48], jnp.int32)
    q1 = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, KV, Dh)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, KV, Dh)), jnp.float32)

    got = np.asarray(paged_decode_attention(
        q1, kn, vn, layer_k, layer_v, table, lengths, interpret=True),
        np.float32)
    dk = gather_pages(layer_k, table, S)
    dv = gather_pages(layer_v, table, S)
    want = np.asarray(llama.dense_decode_attention(
        q1[:, None], kn[:, None], vn[:, None], dk, dv, lengths)[:, 0],
        np.float32)
    np.testing.assert_allclose(got.reshape(want.shape), want,
                               rtol=2e-3, atol=2e-3)

    # Prefill chunk over the pool.
    T = 16
    qT = jnp.asarray(rng.standard_normal((B, T, H, Dh)), jnp.float32)
    kT = jnp.asarray(rng.standard_normal((B, T, KV, Dh)), jnp.float32)
    vT = jnp.asarray(rng.standard_normal((B, T, KV, Dh)), jnp.float32)
    start = jnp.asarray([16, 32], jnp.int32)
    lk2, lv2 = paged_insert_kv(layer_k, layer_v, kT, vT, table, start, None)
    got2 = np.asarray(paged_prefill_attention(
        qT, lk2, lv2, table, start, block_t=8, interpret=True), np.float32)
    # Exact reference: dense attention over the SAME quantized state
    # (gather + dequantize the inserted pool — the adapter's reference
    # path), so both sides see identical int8-rounded K/V.
    from llmapigateway_tpu.ops.paged_attention import _paged_reference_core

    def deq(d):
        # Gathered scale is rank-4 [B, KV, 1, S] -> [B, KV, S, 1].
        return d["q"].astype(jnp.float32) * jnp.swapaxes(d["s"], -1, -2)
    want2 = np.asarray(_paged_reference_core(
        qT, deq(gather_pages(lk2, table, S)),
        deq(gather_pages(lv2, table, S)), start, None, T), np.float32)
    np.testing.assert_allclose(got2, want2, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-mistral-test"])
async def test_engine_pallas_with_kv_quant_matches_reference(build_engine,
                                                             preset):
    """attention=pallas + kv_quant (the best single-chip configuration)
    serves through the interpret-mode q8 kernels — and through the
    WINDOWED ones from a ring, the Mistral cells' kernels — and produces
    the greedy tokens of the models' dense forward over the same
    quantized cache."""
    from llmapigateway_tpu.engine.engine import GenRequest
    from tests.conftest import cpu_devices
    from tests.dense_reference import greedy_tokens

    eng = build_engine(
        LocalEngineConfig(preset=preset, max_batch_size=1,
                          max_seq_len=64, prefill_chunk=16, decode_burst=2,
                          kv_quant="int8", kv_page_size=4,
                          attention="pallas", prewarm_sampler_variants=False,
                          compilation_cache_dir="off"),
        devices=[cpu_devices()[0]])
    req = GenRequest(prompt_ids=list(range(2, 20)), max_tokens=6,
                     temperature=0.0)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    assert req.generated == greedy_tokens(eng, req.prompt_ids, 6)
    assert req.finish_reason == "length"


@pytest.mark.parametrize("kv_quant", ["", "int8"])
def test_paged_sharded_adapter_matches_reference(setup, kv_quant):
    """The paged adapter's shard_map branch (model-axis manual kernels)
    must match the gather+dense reference on the same pool — for both the
    plain and the int8 pool (per-leaf {q,s} specs)."""
    from jax.sharding import Mesh
    from llmapigateway_tpu.ops.paged_attention import (
        PagedKVCache, make_paged_attention_fn, paged_insert_kv)
    from tests.conftest import cpu_devices

    cfg, params = setup
    B, S, page = 2, 64, 16
    H, KV, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    NP = S // page
    rng = np.random.default_rng(6)
    table = jnp.asarray(
        [[1 + b * NP + j for j in range(NP)] for b in range(B)], jnp.int32)
    pool = PagedKVCache.create(cfg, B * NP + 1, page, dtype=jnp.float32,
                               kv_quant=kv_quant)
    pick = (lambda side: {"q": side["q"][0], "s": side["s"][0]}) \
        if kv_quant else (lambda side: side[0])
    layer_k, layer_v = pick(pool.k), pick(pool.v)
    hist_k = jnp.asarray(rng.standard_normal((B, 40, KV, Dh)), jnp.float32)
    hist_v = jnp.asarray(rng.standard_normal((B, 40, KV, Dh)), jnp.float32)
    layer_k, layer_v = paged_insert_kv(layer_k, layer_v, hist_k, hist_v,
                                       table, jnp.zeros((B,), jnp.int32),
                                       None)
    lengths = jnp.asarray([25, 40], jnp.int32)
    q1 = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, 1, KV, Dh)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, 1, KV, Dh)), jnp.float32)

    mesh = Mesh(np.array(cpu_devices()[:2]), ("model",))
    shard_attn = make_paged_attention_fn(table, max_seq=S, impl="pallas",
                                         interpret=True, mesh=mesh)
    ref_attn = make_paged_attention_fn(table, max_seq=S, impl="reference")
    got = np.asarray(
        shard_attn.decode(q1, kn, vn, layer_k, layer_v, lengths), np.float32)
    want = np.asarray(
        ref_attn.decode(q1, kn, vn, layer_k, layer_v, lengths), np.float32)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_kv_quant_guardrails():
    from llmapigateway_tpu.engine.engine import InferenceEngine
    from tests.conftest import cpu_devices

    base = dict(preset="tiny-test", max_batch_size=1, max_seq_len=64,
                compilation_cache_dir="off")
    with pytest.raises(ValueError, match="kv_quant"):
        InferenceEngine(LocalEngineConfig(kv_page_size=16,
                                          kv_quant="int4", **base))
    # int8 + speculation now COMPOSES (the verify self-block went
    # mixed-precision — drafted tokens quantize→dequantize exactly like
    # the insert path): it must build. Parity itself is pinned by
    # tests/test_speculative.py's int8 parity tests.
    InferenceEngine(LocalEngineConfig(kv_quant="int8", spec_draft_len=3,
                                      **base))


@pytest.mark.parametrize("chips", [2, 4])
async def test_int8_pool_on_a_model_mesh_matches_one_device(chips):
    """The int8 page pool served tensor-parallel. On two chips the model's
    two KV heads split, one a chip, and the scale planes with them. On
    four they do not divide: the pool is PLACED whole on every chip
    (test_sharding.py), and how a step hands it back is the partitioner's
    choice, so only the tokens are held to."""
    ref, _ = await serve({}, kv_quant="int8", kv_page_size=16)
    got, eng = await serve({"model": chips}, kv_quant="int8",
                           kv_page_size=16)
    assert got == ref
    assert eng.cache.k["q"].dtype == jnp.int8
    if chips == 2:
        assert split_dims(eng.cache.k["q"]) == (2,)
        assert split_dims(eng.cache.k["s"]) == (2,)
    assert not eng.kv_pool_in_place          # a mesh keeps the sliced read
