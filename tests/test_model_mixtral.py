"""Mixtral MoE model tests: routing semantics, dense/dispatch agreement,
expert-parallel sharding on the virtual CPU mesh, and engine integration
(SURVEY.md §2b "Expert Parallelism", BASELINE config 4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.models import llama, mixtral
from llmapigateway_tpu.models.config import ModelConfig, get_preset
from llmapigateway_tpu.parallel.mesh import MeshSpec, build_mesh
from llmapigateway_tpu.parallel.sharding import param_shardings
from tests.mesh_parity import serve

CFG = ModelConfig(family="mixtral", vocab_size=128, d_model=32, n_layers=2,
                  n_heads=4, n_kv_heads=2, d_ff=64, max_seq_len=64,
                  n_experts=4, experts_per_token=2)


def _layer_params(key, dtype=jnp.float32):
    params = mixtral.init_params(CFG, key, dtype=dtype)
    # Single layer's MoE params (index layer 0 of the stacked layout).
    lp = {k: v[0] for k, v in params["layers"].items()}
    return params, lp


def _naive_moe(x, lp, k):
    """Per-token loop reference: route, run each selected expert, combine."""
    N, D = x.shape
    out = np.zeros((N, D), np.float32)
    router = np.asarray(lp["router"], np.float32)
    for n in range(N):
        logits = np.asarray(x[n], np.float32) @ router
        top = np.argsort(-logits)[:k]
        w = np.exp(logits[top] - logits[top].max())
        w = w / w.sum()
        for wi, e in zip(w, top):
            wg = np.asarray(lp["wg"][e], np.float32)
            wu = np.asarray(lp["wu"][e], np.float32)
            wd = np.asarray(lp["wd"][e], np.float32)
            h = np.asarray(x[n], np.float32)
            gate = h @ wg
            silu = gate / (1.0 + np.exp(-gate))
            y = (silu * (h @ wu)) @ wd
            out[n] += wi * y
    return out


def test_dense_moe_matches_naive_reference():
    key = jax.random.PRNGKey(0)
    _, lp = _layer_params(key)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 5, CFG.d_model),
                          dtype=jnp.float32)
    got = mixtral.moe_mlp_dense(x, lp, CFG)
    want = _naive_moe(np.asarray(x).reshape(15, CFG.d_model), lp,
                      CFG.experts_per_token).reshape(3, 5, CFG.d_model)
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_dispatch_matches_dense_with_ample_capacity():
    key = jax.random.PRNGKey(2)
    _, lp = _layer_params(key)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, CFG.d_model),
                          dtype=jnp.float32)
    dense = mixtral.moe_mlp_dense(x, lp, CFG)
    # capacity_factor high enough that nothing drops → exact agreement.
    disp = mixtral.moe_mlp_dispatch(x, lp, CFG, capacity_factor=float(CFG.n_experts))
    np.testing.assert_allclose(np.asarray(disp), np.asarray(dense),
                               rtol=2e-4, atol=2e-4)


def test_dispatch_drops_overflow_tokens_deterministically():
    """With capacity 1 per expert, later tokens routed to a full expert
    contribute zero from that expert — output still finite and shaped."""
    key = jax.random.PRNGKey(4)
    _, lp = _layer_params(key)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 8, CFG.d_model),
                          dtype=jnp.float32)
    out = mixtral.moe_mlp_dispatch(x, lp, CFG, capacity_factor=0.25)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()


def test_route_probs_topk_and_normalized():
    key = jax.random.PRNGKey(6)
    router = jax.random.normal(key, (CFG.d_model, CFG.n_experts))
    x = jax.random.normal(jax.random.PRNGKey(7), (9, CFG.d_model))
    probs = mixtral.route(x, router, 2)
    p = np.asarray(probs)
    assert ((p > 0).sum(axis=1) == 2).all()
    np.testing.assert_allclose(p.sum(axis=1), 1.0, rtol=1e-5)


def test_forward_runs_and_updates_cache():
    key = jax.random.PRNGKey(8)
    params = mixtral.init_params(CFG, key, dtype=jnp.float32)
    B, T = 2, 6
    cache = llama.KVCache.create(CFG, B, 32, dtype=jnp.float32)
    tokens = jnp.arange(B * T, dtype=jnp.int32).reshape(B, T) % CFG.vocab_size
    lengths = jnp.zeros((B,), jnp.int32)
    logits, cache2 = mixtral.forward(params, CFG, tokens, lengths, cache)
    assert logits.shape == (B, T, CFG.vocab_size)
    assert not np.array_equal(np.asarray(cache2.k), np.asarray(cache.k))


def test_expert_parallel_sharding_matches_single_device():
    """EP×TP mesh (expert=4, model=2) over 8 CPU devices: sharded forward
    output must match the unsharded one — GSPMD inserts the collectives."""
    devices = jax.devices("cpu")[:8]
    mesh = build_mesh(MeshSpec(sizes={"expert": 4, "model": 2}), devices)
    key = jax.random.PRNGKey(9)
    params = mixtral.init_params(CFG, key, dtype=jnp.float32)
    B, T = 2, 4
    cache = llama.KVCache.create(CFG, B, 16, dtype=jnp.float32)
    tokens = (jnp.arange(B * T, dtype=jnp.int32).reshape(B, T)
              % CFG.vocab_size)
    lengths = jnp.zeros((B,), jnp.int32)

    ref_logits, _ = jax.jit(mixtral.forward, static_argnums=(1,))(
        params, CFG, tokens, lengths, cache)

    shardings = param_shardings(params, mesh)
    sharded = jax.tree.map(jax.device_put, params, shardings)
    got_logits, _ = jax.jit(mixtral.forward, static_argnums=(1,))(
        sharded, CFG, tokens, lengths, cache)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=2e-3, atol=2e-3)


async def test_engine_serves_moe_preset():
    """The tiny MoE preset runs end-to-end through the serving engine."""
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    eng = InferenceEngine(LocalEngineConfig(
        preset="tiny-moe-test", dtype="float32", max_batch_size=2,
        max_seq_len=64, prefill_chunk=16, kv_page_size=16))
    try:
        req = GenRequest(prompt_ids=[1, 2, 3, 4], max_tokens=8)
        await eng.submit(req)
        text = ""
        async for delta in eng.stream(req):
            assert delta.error is None, delta.error
            text += delta.text
        assert req.finish_reason in ("stop", "length")
        assert len(req.generated) >= 1
    finally:
        await eng.stop()


async def test_engine_serves_moe_on_an_expert_and_model_mesh():
    """A sparse-expert engine on `expert` = 2 × `model` = 2 through the
    real scheduler: the experts split over one axis and their width over
    the other, and the tokens are the one-device engine's."""
    kw = dict(preset="tiny-moe-test", kv_page_size=16)
    ref, _ = await serve({}, **kw)
    got, eng = await serve({"expert": 2, "model": 2}, **kw)
    assert got == ref
    assert tuple(eng.params["layers"]["wg"].sharding.spec) == (
        None, "expert", None, "model")
    assert tuple(eng.params["layers"]["wd"].sharding.spec) == (
        None, "expert", "model", None)
