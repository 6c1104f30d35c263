"""Presence/frequency penalties, end to end: engine-level repeat avoidance
vs plain greedy, per-slot count reset on slot reuse, and the HTTP payload
fields reaching the sampling arrays through providers/local.py."""
import asyncio
import json

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmapigateway_tpu.config.loader import ConfigLoader
from llmapigateway_tpu.config.schemas import LocalEngineConfig, ProviderDetails
from llmapigateway_tpu.config.settings import Settings
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.providers.local import LocalProvider
from llmapigateway_tpu.server.app import GatewayApp, build_app
from tests.mesh_parity import serve

# Greedy decode on the deterministic tiny-test weights (PRNGKey(0) init)
# collapses into a single-token repetition loop on this prompt — the
# attractor the penalty machinery exists to break.
LOOPING_PROMPT = "aaa bbb aaa bbb"


@pytest.fixture(scope="module")
def shared_engine(stop_engine):
    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=1,
                            max_seq_len=128, prefill_chunk=32,
                            dtype="float32", kv_page_size=16)
    eng = InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])
    yield eng
    stop_engine(eng)


async def _generate(eng, prompt, max_tokens=12, **kw) -> GenRequest:
    req = GenRequest(prompt_ids=eng.tokenizer.encode(prompt),
                     max_tokens=max_tokens, **kw)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


async def test_penalized_avoids_repeats_greedy_falls_into(engine):
    """temperature=0 + a large presence penalty must still be repeat-free
    (penalties shift logits BEFORE the argmax — OpenAI semantics), where
    plain greedy demonstrably loops."""
    greedy = await _generate(engine, LOOPING_PROMPT)
    penalized = await _generate(engine, LOOPING_PROMPT,
                                presence_penalty=100.0)
    assert len(greedy.generated) > len(set(greedy.generated)), \
        "fixture prompt no longer loops under greedy; pick a new attractor"
    # Every seen token (prompt + generated) is argmax-suppressed, so the
    # penalized stream is pairwise distinct and disjoint from the prompt.
    assert len(set(penalized.generated)) == len(penalized.generated)
    assert not (set(penalized.generated) & set(penalized.prompt_ids))
    assert penalized.generated != greedy.generated


async def test_slot_reuse_resets_penalty_counts(engine):
    """B=1 forces every request through the same slot: a penalized request
    rerun after an interleaved different request must reproduce its exact
    token stream — admission resets the slot's [V] count row, so no
    occurrence state bleeds across requests (device-side reset inside the
    prefill program)."""
    first = await _generate(engine, LOOPING_PROMPT, presence_penalty=100.0)
    # Pollute the slot's count row with a different penalized request.
    await _generate(engine, "hello world", presence_penalty=100.0,
                    max_tokens=8)
    again = await _generate(engine, LOOPING_PROMPT, presence_penalty=100.0)
    assert again.generated == first.generated


async def test_frequency_penalty_engine_roundtrip(engine):
    """frequency_penalty rides the same plumbing (GenRequest -> samp
    arrays -> apply_penalties); a large value is as repeat-free as
    presence on the looping prompt."""
    req = await _generate(engine, LOOPING_PROMPT, frequency_penalty=100.0)
    assert len(set(req.generated)) == len(req.generated)
    # The request's params landed in the per-slot device-mirrored arrays.
    assert float(engine.samp_frequency[req.slot]) == 100.0


# -- HTTP level ---------------------------------------------------------------

class PenaltyGateway:
    """Minimal local-engine gateway whose engine stays inspectable."""

    def __init__(self, tmp_path, factory):
        self.tmp_path = tmp_path
        self.factory = factory

    async def __aenter__(self):
        providers = [
            {"tpu": {"type": "local",
                     "engine": {"preset": "tiny-test", "dtype": "float32",
                                "max_batch_size": 2, "max_seq_len": 128,
                                "prefill_chunk": 32,
                                "max_tokens_default": 8}}}]
        rules = [{"gateway_model_name": "gw/local-model",
                  "fallback_models": [{"provider": "tpu",
                                       "model": "tiny-test"}]}]
        (self.tmp_path / "providers.json").write_text(json.dumps(providers))
        (self.tmp_path / "models_fallback_rules.json").write_text(
            json.dumps(rules))
        settings = Settings(fallback_provider="tpu", base_dir=self.tmp_path,
                            config_dir=self.tmp_path,
                            db_dir=self.tmp_path / "db",
                            logs_dir=self.tmp_path / "logs")
        loader = ConfigLoader(self.tmp_path, fallback_provider=None)
        self.gw = GatewayApp(settings, loader, local_factory=self.factory)
        app = build_app(settings, loader, gateway=self.gw)
        self.client = TestClient(TestServer(app))
        await self.client.start_server()
        return self

    async def __aexit__(self, *exc):
        await self.client.close()


@pytest.fixture(scope="module")
def http_factory():
    cache = {}

    def factory(name: str, details: ProviderDetails) -> LocalProvider:
        if "engine" not in cache:
            cache["engine"] = InferenceEngine(
                details.engine, devices=[jax.devices("cpu")[0]])
        return LocalProvider(name, cache["engine"])

    factory.cache = cache
    return factory


async def test_http_penalty_fields_reach_sampling(tmp_path, http_factory):
    """POST payload presence/frequency penalties must reach the engine's
    per-slot sampling arrays (the values persist in samp_* after release,
    so the served request's slot is directly checkable)."""
    async with PenaltyGateway(tmp_path, http_factory) as g:
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/local-model", "max_tokens": 6,
            "temperature": 0,
            "presence_penalty": 1.25, "frequency_penalty": 0.75,
            "messages": [{"role": "user", "content": "hello"}]})
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["finish_reason"] in ("stop", "length")
        eng = http_factory.cache["engine"]
        assert 1.25 in np.asarray(eng.samp_presence)
        assert 0.75 in np.asarray(eng.samp_frequency)


async def test_http_penalties_default_to_zero(tmp_path, http_factory):
    """Omitted (and explicit-null) payload fields build a zero-penalty
    GenRequest — the greedy fast path stays eligible."""
    async with PenaltyGateway(tmp_path, http_factory) as g:
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/local-model", "max_tokens": 4, "temperature": 0,
            "presence_penalty": None,
            "messages": [{"role": "user", "content": "plain greedy"}]})
        assert resp.status == 200
    prov = http_factory("tpu-probe", ProviderDetails.model_validate(
        {"type": "local",
         "engine": {"preset": "tiny-test", "dtype": "float32"}}))
    req = prov._build_genrequest(
        {"messages": [{"role": "user", "content": "x"}],
         "presence_penalty": None})
    assert req.presence_penalty == 0.0 and req.frequency_penalty == 0.0
    req = prov._build_genrequest(
        {"messages": [{"role": "user", "content": "x"}],
         "presence_penalty": 1.5, "frequency_penalty": -0.5})
    assert req.presence_penalty == 1.5 and req.frequency_penalty == -0.5


async def test_penalties_on_a_model_mesh_match_one_device():
    """Penalised greedy requests tensor-parallel: the general sampler runs
    over logits gathered from a vocabulary split four ways, against the
    replicated count rows, and picks what one device picks."""
    kw = dict(kv_page_size=16, request_kw=dict(presence_penalty=0.8,
                                               frequency_penalty=0.4))
    plain, _ = await serve({}, kv_page_size=16)
    ref, _ = await serve({}, **kw)
    got, eng = await serve({"model": 4}, **kw)
    assert got == ref
    assert ref != plain                      # the penalties really bit
    assert eng.params["lm_head"].sharding.spec[0] == "model"
