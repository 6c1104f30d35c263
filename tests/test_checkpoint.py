"""Checkpoint loading: HF safetensors → stacked params, verified by logit
parity against the torch/transformers reference implementation (SURVEY.md
§4d — numerics tests vs HF reference logits)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.engine.checkpoint import load_checkpoint
from llmapigateway_tpu.models import llama
from llmapigateway_tpu.models.config import ModelConfig


@pytest.fixture(scope="module")
def hf_checkpoint(tmp_path_factory):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    path = tmp_path_factory.mktemp("hf_ckpt")
    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False)
    torch.manual_seed(0)
    model = transformers.LlamaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(path, safe_serialization=True)
    return path, model, hf_cfg


@pytest.fixture(scope="module")
def our_config():
    return ModelConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                       n_kv_heads=2, d_ff=128, rope_theta=10000.0,
                       rms_eps=1e-5, max_seq_len=256)


def test_load_and_logit_parity(hf_checkpoint, our_config):
    """Our JAX forward on the loaded checkpoint must match HF torch logits."""
    torch = pytest.importorskip("torch")
    path, hf_model, _ = hf_checkpoint
    params = load_checkpoint(path, our_config, dtype=jnp.float32)

    ids = np.array([[5, 17, 99, 3, 42, 7, 81, 2]], dtype=np.int32)
    with torch.no_grad():
        hf_logits = hf_model(torch.tensor(ids, dtype=torch.long)).logits.numpy()

    cache = llama.KVCache.create(our_config, 1, 32, dtype=jnp.float32)
    logits, _ = llama.forward(params, our_config, jnp.asarray(ids),
                              jnp.zeros((1,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits), hf_logits,
                               rtol=2e-3, atol=2e-3)


def test_loaded_params_layout(hf_checkpoint, our_config):
    path, _, _ = hf_checkpoint
    params = load_checkpoint(path, our_config, dtype=jnp.float32)
    c = our_config
    assert params["embed"].shape == (c.vocab_size, c.d_model)
    lk = params["layers"]
    # Bare keys (no 'layers.' prefix), stacked leading layer dim.
    assert set(lk) >= {"attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                       "wg", "wu", "wd"}
    assert lk["wq"].shape == (c.n_layers, c.d_model, c.n_heads * c.head_dim)
    assert lk["wd"].shape == (c.n_layers, c.d_ff, c.d_model)


def test_put_receives_shardable_paths(hf_checkpoint, our_config):
    """The `put` callback must see paths that sharding rules recognize."""
    from jax.sharding import PartitionSpec as P
    from llmapigateway_tpu.parallel.mesh import MeshSpec, build_mesh
    from llmapigateway_tpu.parallel.sharding import _spec_for
    path, _, _ = hf_checkpoint
    mesh = build_mesh(MeshSpec(sizes={"model": 4}, auto_model=False),
                      jax.devices("cpu")[:4])
    seen = {}

    def put(p, arr):
        seen[p] = _spec_for(p, tuple(arr.shape), mesh)
        return jnp.asarray(arr)

    load_checkpoint(path, our_config, dtype=jnp.float32, put=put)
    # Column-parallel projections must actually shard on the model axis.
    assert seen["layers.wq"] == P(None, None, "model")
    assert seen["layers.wd"] == P(None, "model", None)
    assert seen["embed"] == P("model", None)


def test_config_mismatch_detected(hf_checkpoint):
    path, _, _ = hf_checkpoint
    bad = ModelConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128)
    with pytest.raises(ValueError, match="mismatch"):
        load_checkpoint(path, bad, dtype=jnp.float32)


def test_rope_scaling_logit_parity(tmp_path):
    """Llama-3.1-style rope_scaling: our forward must match HF torch logits
    when the checkpoint carries a llama3 rope_scaling block (VERDICT r1
    item 8 — previously ignored, silently wrong RoPE)."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from llmapigateway_tpu.engine.engine import _config_from_checkpoint

    hf_cfg = transformers.LlamaConfig(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False,
        rope_scaling={"rope_type": "llama3", "factor": 8.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 64})
    torch.manual_seed(1)
    model = transformers.LlamaForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg = _config_from_checkpoint(tmp_path)
    assert cfg.rope_scaling is not None
    assert cfg.rope_scaling.rope_type == "llama3"
    assert cfg.rope_scaling.original_max_seq == 64

    params = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)
    ids = np.array([[5, 17, 99, 3, 42, 7, 81, 2]], dtype=np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    cache = llama.KVCache.create(cfg, 1, 32, dtype=jnp.float32)
    logits, _ = llama.forward(params, cfg, jnp.asarray(ids),
                              jnp.zeros((1,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits), hf_logits,
                               rtol=2e-3, atol=2e-3)
    # And the scaling must actually matter: the rotated tables diverge from
    # the unscaled ones at long-context positions (low-frequency band).
    pos = jnp.asarray([[200.0]])
    cos_s, _ = llama.rope_tables(pos, cfg.head_dim, cfg.rope_theta,
                                 cfg.rope_scaling)
    cos_u, _ = llama.rope_tables(pos, cfg.head_dim, cfg.rope_theta)
    assert float(np.max(np.abs(np.asarray(cos_s) - np.asarray(cos_u)))) > 0.1


def test_qwen2_checkpoint_logit_parity(tmp_path):
    """Qwen2 family (llama block + QKV bias, tied embeddings): config
    derived from the checkpoint's config.json, bias tensors loaded, and our
    forward matches HF torch logits."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from llmapigateway_tpu.engine.engine import _config_from_checkpoint

    hf_cfg = transformers.Qwen2Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-6, rope_theta=10000.0,
        tie_word_embeddings=True)
    torch.manual_seed(2)
    model = transformers.Qwen2ForCausalLM(hf_cfg)
    model.eval()
    # HF zero-inits biases; randomize them so parity actually exercises
    # the bias path, not just its shape plumbing.
    with torch.no_grad():
        for layer in model.model.layers:
            for proj in (layer.self_attn.q_proj, layer.self_attn.k_proj,
                         layer.self_attn.v_proj):
                proj.bias.uniform_(-0.5, 0.5)
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg = _config_from_checkpoint(tmp_path)
    assert cfg.family == "qwen2" and cfg.attn_bias and cfg.tie_embeddings

    params = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)
    assert params["layers"]["bq"].shape == (2, 64)
    # Bias must be non-trivially loaded (HF random init is nonzero).
    assert float(np.abs(np.asarray(params["layers"]["bq"])).max()) > 0

    ids = np.array([[5, 17, 99, 3, 42, 7, 81, 2]], dtype=np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    cache = llama.KVCache.create(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = llama.forward(params, cfg, jnp.asarray(ids),
                                  jnp.zeros((1,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits), hf_logits,
                               rtol=2e-3, atol=2e-3)
    # Decode step (deferred-insert path) also matches HF's next position.
    ids2 = np.concatenate([ids, [[9]]], axis=1)
    with torch.no_grad():
        hf2 = model(torch.tensor(ids2, dtype=torch.long)).logits.numpy()
    logits2, _ = llama.forward(
        params, cfg, jnp.asarray([[9]], jnp.int32),
        jnp.full((1,), 8, jnp.int32), cache,
        active=jnp.ones((1,), bool))
    np.testing.assert_allclose(np.asarray(logits2[:, 0]), hf2[:, -1],
                               rtol=2e-3, atol=2e-3)


def test_phi3_checkpoint_logit_parity(tmp_path):
    """Phi-3 family: HF ships qkv_proj and gate_up_proj FUSED — the
    loader must split them into the stacked wq/wk/wv and wg/wu params
    (checkpoint.py _fused_bounds) with logits matching HF torch, and
    the config must pick up the family's sliding window."""
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    from llmapigateway_tpu.engine.engine import _config_from_checkpoint

    hf_cfg = transformers.Phi3Config(
        vocab_size=128, hidden_size=64, intermediate_size=128,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=256, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False, sliding_window=4,
        pad_token_id=0)       # Phi3Config default (32000) exceeds tiny vocab
    torch.manual_seed(3)
    model = transformers.Phi3ForCausalLM(hf_cfg)
    model.eval()
    model.save_pretrained(tmp_path, safe_serialization=True)

    cfg = _config_from_checkpoint(tmp_path)
    # Window (4) narrower than the prompt (8): parity below actually
    # engages the sliding-window mask, so a one-off in the window
    # convention vs HF Phi3 cannot pass silently.
    assert cfg.family == "llama" and cfg.sliding_window == 4
    assert cfg.n_kv_heads == 2

    params = load_checkpoint(tmp_path, cfg, dtype=jnp.float32)
    # The fused tensors landed split and stacked: wq [L, D, H*Dh],
    # wk/wv [L, D, KV*Dh], wg/wu [L, D, F].
    assert params["layers"]["wq"].shape == (2, 64, 64)
    assert params["layers"]["wk"].shape == (2, 64, 32)
    assert params["layers"]["wg"].shape == (2, 64, 128)

    ids = np.array([[5, 17, 99, 3, 42, 7, 81, 2]], dtype=np.int32)
    with torch.no_grad():
        hf_logits = model(torch.tensor(ids, dtype=torch.long)).logits.numpy()
    cache = llama.KVCache.create(cfg, 1, 32, dtype=jnp.float32)
    logits, cache = llama.forward(params, cfg, jnp.asarray(ids),
                                  jnp.zeros((1,), jnp.int32), cache)
    np.testing.assert_allclose(np.asarray(logits), hf_logits,
                               rtol=2e-3, atol=2e-3)
    # Decode step (deferred-insert path) matches HF's next position too.
    ids2 = np.concatenate([ids, [[9]]], axis=1)
    with torch.no_grad():
        hf2 = model(torch.tensor(ids2, dtype=torch.long)).logits.numpy()
    logits2, _ = llama.forward(
        params, cfg, jnp.asarray([[9]], jnp.int32),
        jnp.full((1,), 8, jnp.int32), cache,
        active=jnp.ones((1,), bool))
    np.testing.assert_allclose(np.asarray(logits2[:, 0]), hf2[:, -1],
                               rtol=2e-3, atol=2e-3)
    # Geometry mismatch must REFUSE, not slice-clamp into silently wrong
    # weights (the split derives shapes from the config, so
    # _validate_shapes alone could not catch it).
    import dataclasses
    with pytest.raises(ValueError, match="fused tensor"):
        load_checkpoint(tmp_path, dataclasses.replace(cfg, d_ff=96),
                        dtype=jnp.float32)


def test_rope_scaling_unsupported_type_rejected(tmp_path):
    from llmapigateway_tpu.engine.engine import _parse_rope_scaling
    assert _parse_rope_scaling(None) is None
    assert _parse_rope_scaling({"rope_type": "default"}) is None
    assert _parse_rope_scaling({"type": "linear", "factor": 2.0}).factor == 2.0
    yarn = _parse_rope_scaling({"rope_type": "yarn", "factor": 4.0,
                                "beta_fast": 16, "mscale_all_dim": 1})
    assert (yarn.rope_type, yarn.factor, yarn.beta_fast, yarn.beta_slow,
            yarn.mscale_all_dim) == ("yarn", 4.0, 16.0, 1.0, 1.0)
    with pytest.raises(ValueError, match="unsupported rope_scaling type "
                                         "'longrope'; supported: llama3, "
                                         "linear, yarn"):
        _parse_rope_scaling({"rope_type": "longrope", "factor": 4.0})
