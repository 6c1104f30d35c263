"""Int8 weight quantization (models/quant.py): roundtrip error bounds,
forward-pass fidelity vs the bf16/fp32 path (dense and MoE expert
matmuls), engine E2E with quant="int8", and sharded execution on the
virtual mesh (TP columns/rows and the expert axis).

No reference counterpart (the reference executes no models); test style
follows SURVEY.md §4 (c) mesh-on-CPU and (d) numerics-fidelity patterns.
"""
import asyncio
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.models import llama
from llmapigateway_tpu.models.config import get_preset
from llmapigateway_tpu.models.quant import (
    contract_axis_for, is_quantized, mm, quantize_array, quantize_tree)

from tests.conftest import cpu_devices
from tests.mesh_parity import serve


def test_quantize_roundtrip_error_bound():
    """Dequantized int8 must sit within half an LSB of the original, per
    output channel (symmetric per-channel scheme)."""
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((32, 48)) * 3.0, jnp.float32)
    qd = quantize_array(w, contract_axis=0)
    assert qd["q"].dtype == jnp.int8 and qd["s"].dtype == jnp.float32
    assert qd["q"].shape == w.shape and qd["s"].shape == (48,)
    deq = np.asarray(qd["q"], np.float32) * np.asarray(qd["s"])
    lsb = np.asarray(qd["s"])                      # one step per channel
    assert np.all(np.abs(deq - np.asarray(w)) <= 0.5 * lsb[None, :] + 1e-7)


def test_mm_matches_dense_within_quant_noise():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    y_ref = np.asarray(x @ w)
    y_q = np.asarray(mm(x, quantize_array(w, 0)))
    # W8A8 error ~ 1% relative for gaussian data at these sizes.
    rel = np.linalg.norm(y_q - y_ref) / np.linalg.norm(y_ref)
    assert rel < 0.02, rel


def test_contract_axis_rules():
    assert contract_axis_for("layers.wq", 3) == 1
    assert contract_axis_for("layers.wd", 3) == 1
    assert contract_axis_for("layers.wg", 4) == 2        # MoE [L,E,D,F]
    assert contract_axis_for("lm_head", 2) == 1
    assert contract_axis_for("layers.attn_norm", 2) is None
    assert contract_axis_for("embed", 2) is None
    assert contract_axis_for("layers.bq", 2) is None


@pytest.fixture(scope="module")
def quant_setup():
    cfg = get_preset("tiny-test")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = quantize_tree(params, cfg)
    return cfg, params, qparams


def test_quantize_tree_structure(quant_setup):
    cfg, params, qparams = quant_setup
    for key in ("wq", "wk", "wv", "wo", "wg", "wu", "wd"):
        assert is_quantized(qparams["layers"][key]), key
        assert qparams["layers"][key]["q"].shape == params["layers"][key].shape
    assert is_quantized(qparams["lm_head"])
    # Norms, biases, embed stay untouched.
    assert not is_quantized(qparams["layers"]["attn_norm"])
    assert not is_quantized(qparams["embed"])


def test_forward_fidelity_prefill_and_decode(quant_setup):
    """Quantized forward must track the fp32 forward within W8A8 noise —
    checked as normalized RMSE and cosine similarity on the logits, for a
    prefill chunk and a decode step."""
    cfg, params, qparams = quant_setup
    B, T, S = 2, 8, 32
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)

    def run(p):
        cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)
        logits, cache = llama.forward(p, cfg, tokens, lengths, cache)
        step, _ = llama.forward(p, cfg, tokens[:, :1],
                                jnp.full((B,), T, jnp.int32), cache)
        return np.asarray(logits, np.float64), np.asarray(step, np.float64)

    ref_pre, ref_dec = run(params)
    q_pre, q_dec = run(qparams)
    for ref, got in ((ref_pre, q_pre), (ref_dec, q_dec)):
        rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
        assert rel < 0.05, rel
        cos = (ref * got).sum() / (np.linalg.norm(ref) * np.linalg.norm(got))
        assert cos > 0.995, cos


def test_sharded_quant_forward_matches_single_device(quant_setup):
    """The same quantized forward under a data×model mesh (sharded int8
    weights + scales) must agree with the unsharded run — exercises the
    .q/.s sharding rules in parallel/sharding.py."""
    from llmapigateway_tpu.parallel.sharding import param_shardings

    cfg, _, qparams = quant_setup
    B, T, S = 2, 8, 32
    rng = np.random.default_rng(3)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)

    cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)
    ref, _ = jax.jit(llama.forward, static_argnames=("config",))(
        qparams, cfg, tokens, lengths, cache)

    mesh = Mesh(np.array(cpu_devices()[:8]).reshape(2, 4), ("data", "model"))
    shardings = param_shardings(qparams, mesh)
    sharded = jax.tree.map(jax.device_put, qparams, shardings)
    got, _ = jax.jit(llama.forward, static_argnames=("config",))(
        sharded, cfg, tokens, lengths, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-qwen-test",
                                    "tiny-gemma-test"])
def test_engine_e2e_with_quant(preset):
    """Engine with quant="int8" serves a greedy request end to end, for
    every non-MoE family (qwen2 exercises the bias path, gemma/qwen the
    tied-embedding int8 head copy)."""
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(kv_page_size=16,
                            preset=preset, max_batch_size=2,
                            max_seq_len=128, prefill_chunk=16,
                            decode_burst=4, quant="int8",
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    # Weights really are int8 on device.
    assert engine.params["layers"]["wq"]["q"].dtype == jnp.int8
    assert engine.stats()["quant"] == "int8"
    if engine.model_cfg.tie_embeddings:
        # Tied models get the int8 HEAD copy (the full-[V,D]-read-per-step
        # tensor); the embed table itself stays full precision for gathers.
        assert engine.params["lm_head_q8"]["q"].dtype == jnp.int8
        assert not is_quantized(engine.params["embed"])

    async def run():
        await engine.start()
        req = GenRequest(prompt_ids=list(range(1, 9)), max_tokens=12,
                         temperature=0.0)
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()
        return req

    req = asyncio.run(run())
    assert req.finish_reason == "length"
    assert len(req.generated) == 12


def _write_llama_checkpoint(path, cfg, seed: int, tied: bool = False) -> None:
    """A llama checkpoint of ``cfg``'s shapes as HF lays one out —
    ``model.safetensors`` beside a ``config.json`` — drawn as HF draws a
    fresh model: weights at 0.02, norms at one. No HF forward is compared
    with, so neither torch nor transformers is loaded to write it."""
    import json
    from safetensors.numpy import save_file
    rng = np.random.default_rng(seed)
    D, dh = cfg.d_model, cfg.head_dim

    def drawn(*shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)
    tensors = {"model.embed_tokens.weight": drawn(cfg.vocab_size, D),
               "model.norm.weight": np.ones((D,), np.float32)}
    if not tied:
        tensors["lm_head.weight"] = drawn(cfg.vocab_size, D)
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        for name in ("input_layernorm", "post_attention_layernorm"):
            tensors[f"{p}{name}.weight"] = np.ones((D,), np.float32)
        for name, shape in (
                ("self_attn.q_proj", (cfg.n_heads * dh, D)),
                ("self_attn.k_proj", (cfg.n_kv_heads * dh, D)),
                ("self_attn.v_proj", (cfg.n_kv_heads * dh, D)),
                ("self_attn.o_proj", (D, cfg.n_heads * dh)),
                ("mlp.gate_proj", (cfg.d_ff, D)),
                ("mlp.up_proj", (cfg.d_ff, D)),
                ("mlp.down_proj", (D, cfg.d_ff))):
            tensors[f"{p}{name}.weight"] = drawn(*shape)
    save_file(tensors, str(path / "model.safetensors"))
    (path / "config.json").write_text(json.dumps({
        "model_type": "llama", "vocab_size": cfg.vocab_size,
        "hidden_size": D, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff,
        "max_position_embeddings": cfg.max_seq_len,
        "rms_norm_eps": cfg.rms_eps, "tie_word_embeddings": tied}))


# The two checkpoint cases below assert on a head of 128 x 64.
CKPT_CFG = replace(get_preset("tiny-test"), vocab_size=128)


def test_checkpoint_load_quantizes_on_host(tmp_path):
    """quant="int8" on a checkpoint engine quantizes each parameter on the
    host (the put hook receives bf16, places int8) and still serves."""
    from llmapigateway_tpu.engine.engine import InferenceEngine

    _write_llama_checkpoint(tmp_path, CKPT_CFG, seed=0)

    cfg = LocalEngineConfig(kv_page_size=16,
                            model_path=str(tmp_path), max_batch_size=1,
                            max_seq_len=64, prefill_chunk=16, decode_burst=2,
                            quant="int8", prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    assert engine.params["layers"]["wd"]["q"].dtype == jnp.int8
    assert engine.params["layers"]["wd"]["s"].dtype == jnp.float32
    assert engine.params["lm_head"]["q"].shape == (128, 64)

    first, engine.cache = engine._exec_prefill(
        0, 0, np.arange(1, 9, dtype=np.int32))
    assert 0 <= int(np.asarray(first)[0]) < 128


def test_tied_head_quant_fidelity_and_structure():
    """Tied-embedding quantize_tree adds the ``lm_head_q8`` int8 head copy
    (ADVICE r3: without it, gemma-2b's 256k×2048 tied table — ~25% of its
    weight bytes — stayed bf16 under quant="int8"); the quantized forward
    must track the fp32 one within W8A8 noise."""
    cfg = get_preset("tiny-qwen-test")
    params = llama.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    qparams = quantize_tree(params, cfg)
    assert is_quantized(qparams["lm_head_q8"])
    assert qparams["lm_head_q8"]["q"].shape == params["embed"].shape
    assert qparams["lm_head_q8"]["s"].shape == (cfg.vocab_size,)
    assert not is_quantized(qparams["embed"])

    B, T, S = 2, 8, 32
    rng = np.random.default_rng(7)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)

    def run(p):
        cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)
        logits, _ = llama.forward(p, cfg, tokens, lengths, cache)
        return np.asarray(logits, np.float64)

    ref, got = run(params), run(qparams)
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel


def test_checkpoint_tied_head_quantizes_on_device(tmp_path):
    """A TIED checkpoint (no lm_head tensor) under quant="int8" gets its
    head copy synthesized on device post-load (engine/_init_params)."""
    from llmapigateway_tpu.engine.engine import InferenceEngine

    _write_llama_checkpoint(tmp_path, CKPT_CFG, seed=1, tied=True)

    cfg = LocalEngineConfig(kv_page_size=16,
                            model_path=str(tmp_path), max_batch_size=1,
                            max_seq_len=64, prefill_chunk=16, decode_burst=2,
                            quant="int8", prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    assert engine.params["lm_head_q8"]["q"].dtype == jnp.int8
    assert engine.params["lm_head_q8"]["q"].shape == (128, 64)
    # The q8 copy must BE a quantization of the loaded embed table.
    deq = (np.asarray(engine.params["lm_head_q8"]["q"], np.float32)
           * np.asarray(engine.params["lm_head_q8"]["s"])[:, None])
    emb = np.asarray(engine.params["embed"], np.float32)
    lsb = np.asarray(engine.params["lm_head_q8"]["s"])[:, None]
    assert np.all(np.abs(deq - emb) <= 0.51 * lsb + 1e-7)

    first, engine.cache = engine._exec_prefill(
        0, 0, np.arange(1, 9, dtype=np.int32))
    assert 0 <= int(np.asarray(first)[0]) < 128


def test_moe_expert_quant_fidelity():
    """Mixtral with int8 expert weights: quantize_tree covers the 4-D
    expert matmuls (per-expert-per-channel scales) and the forward tracks
    fp32 within quant noise. Router stays full precision — expert
    selection shifts only on near-ties, which the norm check absorbs."""
    from llmapigateway_tpu.models import mixtral

    cfg = get_preset("tiny-moe-test")
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
    qparams = quantize_tree(params, cfg)
    assert qparams["layers"]["wg"]["q"].shape == params["layers"]["wg"].shape
    assert qparams["layers"]["wg"]["s"].shape == (
        cfg.n_layers, cfg.n_experts, cfg.d_ff)
    assert not is_quantized(qparams["layers"]["router"])

    B, T, S = 2, 8, 32
    rng = np.random.default_rng(4)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)

    def run(p):
        cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)
        logits, _ = mixtral.forward(p, cfg, tokens, lengths, cache)
        return np.asarray(logits, np.float64)

    ref, got = run(params), run(qparams)
    rel = np.linalg.norm(got - ref) / np.linalg.norm(ref)
    assert rel < 0.05, rel


def test_moe_sharded_quant_forward_matches():
    """Expert-parallel mesh + int8 expert weights: the {q,s} leaves shard
    on the expert axis (restored .s rules) and the forward matches the
    unsharded quantized run."""
    from llmapigateway_tpu.models import mixtral
    from llmapigateway_tpu.parallel.sharding import param_shardings

    cfg = get_preset("tiny-moe-test")
    qparams = quantize_tree(
        mixtral.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32),
        cfg)
    B, T, S = 2, 8, 32
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
    lengths = jnp.zeros((B,), jnp.int32)
    cache = llama.KVCache.create(cfg, B, S, dtype=jnp.float32)

    ref, _ = jax.jit(mixtral.forward, static_argnames=("config",))(
        qparams, cfg, tokens, lengths, cache)

    mesh = Mesh(np.array(cpu_devices()[:4]), ("expert",))
    shardings = param_shardings(qparams, mesh)
    assert shardings["layers"]["wg"]["s"].spec[1] == "expert"
    sharded = jax.tree.map(jax.device_put, qparams, shardings)
    got, _ = jax.jit(mixtral.forward, static_argnames=("config",))(
        sharded, cfg, tokens, lengths, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_moe_engine_e2e_with_quant():
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(kv_page_size=16,
                            preset="tiny-moe-test", quant="int8",
                            max_batch_size=2, max_seq_len=128,
                            prefill_chunk=16, decode_burst=4,
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    assert engine.params["layers"]["wg"]["q"].dtype == jnp.int8

    async def run():
        await engine.start()
        req = GenRequest(prompt_ids=list(range(1, 9)), max_tokens=8,
                         temperature=0.0)
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()
        return req

    req = asyncio.run(run())
    assert req.finish_reason == "length" and len(req.generated) == 8


def test_quant_rejects_unknown_mode():
    from llmapigateway_tpu.engine.engine import InferenceEngine

    cfg = LocalEngineConfig(kv_page_size=16,
                            preset="tiny-test", quant="int2",
                            max_batch_size=1, max_seq_len=64,
                            compilation_cache_dir="off")
    with pytest.raises(ValueError, match="quant"):
        InferenceEngine(cfg)


# ---------------------------------------------------------------------------
# int4 (W4A8) mode
# ---------------------------------------------------------------------------

def test_int4_roundtrip_error_bound():
    """Dequantized int4 sits within half an int4 LSB per channel (levels
    ±7 — the LSB is 127/7 ≈ 18x coarser than int8's)."""
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.standard_normal((32, 48)) * 3.0, jnp.float32)
    qd = quantize_array(w, contract_axis=0, bits=4)
    assert qd["q"].dtype == jnp.int4 and qd["s"].dtype == jnp.float32
    deq = np.asarray(qd["q"].astype(jnp.int8), np.float32) * \
        np.asarray(qd["s"])
    lsb = np.asarray(qd["s"])
    assert np.all(np.abs(deq - np.asarray(w)) <= 0.5 * lsb[None, :] + 1e-7)


def test_int4_mm_mixed_dot_matches_dense_within_noise():
    """mm() contracts the int4 operand directly (mixed s8xs4 dot_general);
    result must track the fp32 matmul within W4A8 noise."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    got = mm(x, quantize_array(w, contract_axis=0, bits=4))
    ref = x @ w
    # int4 noise bound: ~|x|_1 * lsb/2 per output; loose relative check.
    err = np.abs(np.asarray(got) - np.asarray(ref))
    assert np.median(err) < 0.12 * np.median(np.abs(np.asarray(ref)) + 1e-6)


def test_int4_tree_keeps_lm_head_int8():
    """quant="int4": layer matmuls go int4, lm_head (and the tied-head
    copy) stay int8 — the logits projection decides every sampled token
    (models/quant.py weight_bits)."""
    from llmapigateway_tpu.models.llama import init_params
    cfg = get_preset("tiny-test")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    q = quantize_tree(params, cfg, mode="int4")
    assert q["layers"]["wq"]["q"].dtype == jnp.int4
    assert q["layers"]["wd"]["q"].dtype == jnp.int4
    assert q["lm_head"]["q"].dtype == jnp.int8
    assert not is_quantized(q["embed"])


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-qwen-test"])
def test_engine_e2e_with_int4(preset):
    """Engine with quant="int4" serves greedily end to end (qwen2 also
    checks the tied-head copy stays int8)."""
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(kv_page_size=16,
                            preset=preset, max_batch_size=2,
                            max_seq_len=128, prefill_chunk=16,
                            decode_burst=4, quant="int4",
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    assert engine.params["layers"]["wq"]["q"].dtype == jnp.int4
    assert engine.stats()["quant"] == "int4"
    if engine.model_cfg.tie_embeddings:
        assert engine.params["lm_head_q8"]["q"].dtype == jnp.int8

    async def run():
        await engine.start()
        req = GenRequest(prompt_ids=list(range(1, 9)), max_tokens=12,
                         temperature=0.0)
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()
        return req

    req = asyncio.run(run())
    assert req.finish_reason == "length"
    assert len(req.generated) == 12


def test_int4_checkpoint_load_quantizes_on_host(tmp_path):
    """quant="int4" on a checkpoint engine: the preprocess hook stores
    int4 at source precision; lm_head arrives int8."""
    from llmapigateway_tpu.engine.engine import InferenceEngine

    _write_llama_checkpoint(tmp_path, get_preset("tiny-test"), seed=7)

    eng = InferenceEngine(LocalEngineConfig(
        model_path=str(tmp_path), max_batch_size=1, max_seq_len=64,
        prefill_chunk=16, kv_page_size=16, quant="int4",
        prewarm_sampler_variants=False, compilation_cache_dir="off"))
    assert eng.params["layers"]["wq"]["q"].dtype == jnp.int4
    assert eng.params["lm_head"]["q"].dtype == jnp.int8


def test_moe_engine_e2e_with_int4():
    """Mixtral engine with quant="int4": expert matmuls ([L,E,D,F]) store
    int4 with per-(expert, out-channel) scales and still serve."""
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(kv_page_size=16,
                            preset="tiny-moe-test", max_batch_size=2,
                            max_seq_len=128, prefill_chunk=16,
                            decode_burst=4, quant="int4",
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)
    assert engine.params["layers"]["wg"]["q"].dtype == jnp.int4
    assert engine.params["layers"]["wg"]["q"].ndim == 4   # [L, E, D, F]

    async def run():
        await engine.start()
        req = GenRequest(prompt_ids=list(range(1, 9)), max_tokens=8,
                         temperature=0.0)
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()
        return req

    req = asyncio.run(run())
    assert len(req.generated) == 8


@pytest.mark.parametrize("quant", ["int8", "int4"])
async def test_quantized_weights_on_a_model_mesh_match_one_device(quant):
    """Quantized weights served tensor-parallel: the {q, s} leaves are
    placed by the ".q" / ".s" rules on `model` = 4 (a row-parallel
    matmul's integer partial sums are reduced before its scale is
    applied) and two requests decode together, token for token as on one
    device."""
    ref, _ = await serve({}, quant=quant, kv_page_size=16)
    got, eng = await serve({"model": 4}, quant=quant, kv_page_size=16)
    assert got == ref
    layers = eng.params["layers"]
    assert layers["wq"]["q"].dtype == (jnp.int8 if quant == "int8"
                                       else jnp.int4)
    assert layers["wq"]["q"].sharding.spec[2] == "model"
    assert layers["wq"]["s"].sharding.spec[1] == "model"
    assert layers["wd"]["q"].sharding.spec[1] == "model"
    assert "model" not in tuple(layers["wd"]["s"].sharding.spec)
    assert eng.params["lm_head"]["q"].sharding.spec[0] == "model"
