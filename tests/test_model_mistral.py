"""Mistral family: sliding-window attention (models/config.py
``sliding_window``) through the windowed dense paths, HF logit parity
with a window narrower than the prompt, engine serving (incl. PP and
speculation — the windowed verify), and the v1 exclusion guardrails.
"""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.models import llama
from llmapigateway_tpu.models.config import ModelConfig, get_preset

from tests.conftest import cpu_devices
from tests.dense_reference import greedy_tokens
from tests.mesh_parity import CYCLING, serve


def test_window_mask_ignores_old_keys():
    """A decode step with window=W must give EXACTLY the same output as
    attending only the last W-1 cached keys (+ the self column): out-of-
    window history cannot leak in."""
    B, H, KV, Dh, S, W = 1, 4, 2, 8, 32, 4
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, 1, H, Dh)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, 1, KV, Dh)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, 1, KV, Dh)), jnp.float32)
    layer_k = jnp.asarray(rng.standard_normal((B, KV, S, Dh)), jnp.float32)
    layer_v = jnp.asarray(rng.standard_normal((B, KV, S, Dh)), jnp.float32)
    L = 20
    lengths = jnp.asarray([L], jnp.int32)

    got = np.asarray(llama.dense_decode_attention(
        q, kn, vn, layer_k, layer_v, lengths, window=W))

    # Reference: physically zero out everything outside the window and
    # re-run with a full mask restricted to the surviving positions by
    # shifting them into a fresh cache of exactly W-1 stale keys.
    keep = list(range(L - (W - 1), L))           # last W-1 stale positions
    k_small = jnp.zeros((B, KV, S, Dh), jnp.float32)
    v_small = jnp.zeros((B, KV, S, Dh), jnp.float32)
    k_small = k_small.at[:, :, :len(keep)].set(layer_k[:, :, keep])
    v_small = v_small.at[:, :, :len(keep)].set(layer_v[:, :, keep])
    want = np.asarray(llama.dense_decode_attention(
        q, kn, vn, k_small, v_small, jnp.asarray([len(keep)], jnp.int32)))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_hf_logit_parity_with_sliding_window(tmp_path):
    """Our windowed forward must match HF MistralForCausalLM logits on a
    prompt LONGER than the window (so the window genuinely bites), for
    the prefill chunk AND a subsequent decode step."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from llmapigateway_tpu.engine.checkpoint import load_checkpoint
    from llmapigateway_tpu.engine.engine import _config_from_checkpoint

    W = 8
    hf_cfg = transformers.MistralConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        sliding_window=W, tie_word_embeddings=False,
        attn_implementation="eager")
    torch.manual_seed(3)
    model = transformers.MistralForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg = _config_from_checkpoint(tmp_path)
    assert cfg.sliding_window == W and cfg.family == "llama"
    params = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)

    rng = np.random.default_rng(4)
    ids = rng.integers(0, 128, size=(1, 3 * W)).astype(np.int32)  # 24 > W
    with torch.no_grad():
        hf_logits = model(
            torch.tensor(ids, dtype=torch.long)).logits.numpy()

    cache = llama.KVCache.create(cfg, 1, 64, dtype=jnp.float32)
    logits, cache = llama.forward(params, cfg, jnp.asarray(ids),
                                  jnp.zeros((1,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits), hf_logits,
                               rtol=2e-3, atol=2e-3)

    # One decode step past the prompt: HF sees the full ids+1 sequence.
    nxt = np.asarray([[7]], np.int32)
    with torch.no_grad():
        hf_step = model(torch.tensor(
            np.concatenate([ids, nxt], axis=1),
            dtype=torch.long)).logits.numpy()[:, -1:]
    step, _ = llama.forward(params, cfg, jnp.asarray(nxt),
                            jnp.full((1,), ids.shape[1], jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(step), hf_step,
                               rtol=2e-3, atol=2e-3)


async def _serve(mesh, devs, max_tokens=16, **kw):
    kw.setdefault("attention", "reference")
    # busy depth == idle depth: parity across engines must not depend
    # on the prefill/first-decode-round busy race (different scan
    # depths = different programs = near-tie argmax flips on random
    # weights; see test_speculative._engine).
    kw.setdefault("decode_burst_busy", 4)
    kw.setdefault("kv_page_size", 16)
    cfg = LocalEngineConfig(preset="tiny-mistral-test", max_batch_size=2,
                            max_seq_len=128, prefill_chunk=32,
                            dtype="float32", decode_burst=4, mesh=mesh,
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off", **kw)
    eng = InferenceEngine(cfg, devices=devs)
    rng = np.random.default_rng(6)
    prompt = list(rng.integers(2, 500, 40))      # 40 tokens >> window 16
    req = GenRequest(prompt_ids=prompt, max_tokens=max_tokens,
                     temperature=0.0)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    await eng.stop()
    return req, eng


def _dense(req, eng):
    """What the windowed dense forward generates for ``req`` with the
    engine's weights (tests/dense_reference.py)."""
    return greedy_tokens(eng, req.prompt_ids, req.max_tokens)


async def test_engine_serves_sliding_window_model():
    req, eng = await _serve({}, [cpu_devices()[0]])
    assert req.finish_reason == "length"
    assert len(req.generated) == 16
    assert eng.model_cfg.sliding_window == 16


async def test_engine_swa_composes_with_spec():
    """The window threads through the speculative verify — tokens must
    match the windowed dense forward's."""
    spec, eng = await _serve({}, [cpu_devices()[0]], spec_draft_len=3)
    assert spec.generated == _dense(spec, eng)
    assert eng._spec_steps_done > 0          # speculation really engaged


async def test_engine_swa_paged_pallas_matches_reference():
    """A window with the WINDOWED paged kernels (interpret mode on CPU):
    greedy tokens must match the windowed dense forward's. 16 generated
    tokens from a 40-token prompt walk the window (16) across page
    boundaries (page=16) during decode."""
    pag, eng = await _serve({}, [cpu_devices()[0]], attention="pallas")
    assert pag.generated == _dense(pag, eng)
    assert eng.model_cfg.sliding_window == 16
    assert eng._resolve_attention_impl() == "pallas"


def test_swa_guardrails():
    """A window was once refused on a ``seq`` mesh at engine build; the
    axis is gone, and the configuration itself refuses it by name."""
    with pytest.raises(ValueError, match="unknown mesh axis 'seq'"):
        LocalEngineConfig(
            preset="tiny-mistral-test", max_batch_size=1, max_seq_len=64, mesh={"seq": 4},
            compilation_cache_dir="off")


async def test_engine_swa_paged_spec_ring_matches_reference():
    """Speculation x window x the page RING: the spec verify reads the
    window from the rotating pool and data-dependent advances stay inside
    the ring margin — greedy tokens must match the windowed dense
    forward's exactly (gate disabled so drafting really runs). The
    request's footprint (40 + 80 = 120 tokens) EXCEEDS the ring (6 pages
    × 16 = 96 tokens), so the slot really is ring-mode and ensure_mapped
    rotates pages mid-generation — a short request would be capped
    under the ring and never rotate."""
    sp, eng = await _serve({}, [cpu_devices()[0]], max_tokens=80,
                           spec_draft_len=3, spec_min_tokens_per_step=0.0)
    assert sp.generated == _dense(sp, eng) and len(sp.generated) == 80
    assert eng._swa_ring_pages > 0
    # The footprint genuinely overflowed the ring (rotation occurred).
    assert eng.allocator.pages_needed(120) > eng._swa_ring_pages
    assert eng._spec_steps_done > 0
    eng.allocator.check_invariants()


async def test_engine_swa_paged_sharded_pallas_matches_reference():
    """A window on a MULTI-CHIP mesh with the WINDOWED paged kernels:
    window x page-table indirection x model-axis shard_map — greedy
    tokens must match the windowed dense forward's."""
    tp, eng = await _serve({"model": 2}, cpu_devices()[:2],
                           attention="pallas")
    assert tp.generated == _dense(tp, eng)
    assert eng.model_cfg.sliding_window == 16
    assert eng.mesh.shape.get("model") == 2     # the REQUESTED mesh ran
    assert eng._resolve_attention_impl() == "pallas"


async def test_engine_swa_spec_on_a_model_mesh_matches_one_device():
    """A window with speculation, from the page pool, tensor-parallel.
    One device serves it from the page ring; a mesh switches the ring off
    (every slot reserves its whole context) — pinned here as it is — and
    the tokens are the same either way, with drafts really accepted."""
    kw = dict(preset="tiny-mistral-test", kv_page_size=16, max_tokens=40,
              spec_draft_len=3, spec_min_tokens_per_step=0.0,
              decode_burst=8, prompts=CYCLING)
    ref, one = await serve({}, **kw)
    got, eng = await serve({"model": 2}, **kw)
    assert got == ref
    assert one._swa_ring_pages > 0 and eng._swa_ring_pages == 0
    assert eng._spec_tokens_out > eng._spec_steps_done > 0
    eng.allocator.check_invariants()
