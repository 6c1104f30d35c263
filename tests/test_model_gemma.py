"""Gemma family: llama block with config-driven variations (GeGLU MLP,
(1+w) RMSNorm, sqrt(D)-scaled tied embeddings, explicit head_dim / MQA) —
verified by logit parity against transformers' GemmaForCausalLM and by an
engine E2E run (SURVEY.md §4d numerics-fidelity pattern)."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.checkpoint import load_checkpoint
from llmapigateway_tpu.models import llama
from llmapigateway_tpu.models.config import get_preset


def test_gemma_preset_geometry():
    cfg = get_preset("gemma-7b")
    assert cfg.head_dim == 256                   # explicit: 16*256 != 3072
    assert cfg.act == "gelu_tanh" and cfg.rms_offset == 1.0
    assert cfg.tie_embeddings and cfg.scale_embed
    tiny = get_preset("tiny-gemma-test")
    assert tiny.head_dim == 16 and tiny.n_kv_heads == 1   # MQA


def test_gemma_forward_shapes_and_finite():
    cfg = get_preset("tiny-gemma-test")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    assert "lm_head" not in params               # tied embeddings
    B, T, S = 2, 8, 32
    cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)
    tokens = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) % cfg.vocab_size
    logits, cache2 = llama.forward(params, cfg, tokens,
                                   jnp.zeros((B,), jnp.int32), cache)
    assert logits.shape == (B, T, cfg.vocab_size)
    assert cache2.k.shape == (cfg.n_layers, B, 1, S, cfg.head_dim)
    assert bool(jnp.all(jnp.isfinite(logits)))


def test_gemma_checkpoint_logit_parity(tmp_path):
    """Config derived from config.json (family/act/offset/scaling/head_dim)
    and our forward matches HF torch logits on prefill AND a decode step."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from llmapigateway_tpu.engine.engine import _config_from_checkpoint

    hf_cfg = transformers.GemmaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=1,
        head_dim=16, max_position_embeddings=256, rms_norm_eps=1e-6,
        rope_theta=10000.0, hidden_act="gelu_pytorch_tanh",
        tie_word_embeddings=True)
    torch.manual_seed(3)
    model = transformers.GemmaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg = _config_from_checkpoint(tmp_path)
    assert cfg.family == "gemma" and cfg.tie_embeddings
    assert cfg.act == "gelu_tanh" and cfg.rms_offset == 1.0
    assert cfg.scale_embed and cfg.head_dim == 16

    params = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)
    ids = np.array([[5, 17, 99, 3, 42, 7, 81, 2]], dtype=np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    cache = llama.KVCache.create(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = llama.forward(params, cfg, jnp.asarray(ids),
                                  jnp.zeros((1,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits), hf_logits,
                               rtol=2e-3, atol=2e-3)

    ids2 = np.concatenate([ids, [[9]]], axis=1)
    with torch.no_grad():
        hf2 = model(torch.tensor(ids2, dtype=torch.long)).logits.numpy()
    logits2, _ = llama.forward(
        params, cfg, jnp.asarray([[9]], jnp.int32),
        jnp.full((1,), 8, jnp.int32), cache, active=jnp.ones((1,), bool))
    np.testing.assert_allclose(np.asarray(logits2[:, 0]), hf2[:, -1],
                               rtol=2e-3, atol=2e-3)


def test_gemma_engine_e2e():
    """tiny-gemma-test preset serves greedy through the real engine
    (exercises MQA GQA-grouping G=H, tied quantizable-free head, scaling)."""
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(kv_page_size=16,
                            preset="tiny-gemma-test", max_batch_size=2,
                            max_seq_len=128, prefill_chunk=16,
                            decode_burst=4, prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)

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
