"""End-to-end gateway tests: real aiohttp app + fake OpenAI-compatible
upstream over HTTP, exercising streaming, fallback-on-error, auth, models,
config editor, and usage stats."""
import asyncio
import json
from pathlib import Path

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmapigateway_tpu.config.loader import ConfigLoader
from llmapigateway_tpu.config.settings import Settings
from llmapigateway_tpu.server.app import GatewayApp, build_app
from tests.fake_upstream import FakeUpstream


class Gateway:
    """Spin up FakeUpstream + the gateway app wired to it."""

    def __init__(self, tmp_path: Path, api_key: str | None = None,
                 n_upstreams: int = 1):
        self.tmp_path = tmp_path
        self.api_key = api_key
        self.n_upstreams = n_upstreams
        self.upstreams: list[FakeUpstream] = []

    async def __aenter__(self):
        self.upstream_servers = []
        urls = []
        for _ in range(self.n_upstreams):
            up = FakeUpstream()
            server = TestServer(up.app)
            await server.start_server()
            self.upstreams.append(up)
            self.upstream_servers.append(server)
            urls.append(f"http://{server.host}:{server.port}/v1")

        providers = [{"fakeup": {"baseUrl": urls[0], "apikey": "TESTKEY"}}]
        if self.n_upstreams > 1:
            providers.append({"backup": {"baseUrl": urls[1], "apikey": "BK"}})
        (self.tmp_path / "providers.json").write_text(json.dumps(providers))
        fallback_models = [{"provider": "fakeup", "model": "real-a",
                            "retry_count": 0}]
        if self.n_upstreams > 1:
            fallback_models.append({"provider": "backup", "model": "real-b"})
        (self.tmp_path / "models_fallback_rules.json").write_text(json.dumps([
            {"gateway_model_name": "gw/chat", "fallback_models": fallback_models}]))

        settings = Settings(
            gateway_api_key=self.api_key, fallback_provider="fakeup",
            base_dir=self.tmp_path, config_dir=self.tmp_path,
            db_dir=self.tmp_path / "db", logs_dir=self.tmp_path / "logs",
            log_chat_messages=True)
        loader = ConfigLoader(self.tmp_path, fallback_provider="fakeup")
        self.gw = GatewayApp(settings, loader)
        app = build_app(settings, loader, gateway=self.gw)
        self.client = TestClient(TestServer(app))
        await self.client.start_server()
        return self

    async def __aexit__(self, *exc):
        await self.client.close()
        for s in self.upstream_servers:
            await s.close()

    @property
    def up(self) -> FakeUpstream:
        return self.upstreams[0]

    def headers(self):
        return {"Authorization": f"Bearer {self.api_key}"} if self.api_key else {}


async def read_sse_frames(resp):
    frames = []
    async for line in resp.content:
        line = line.decode().strip()
        if line.startswith("data: "):
            frames.append(line[len("data: "):])
    return frames


async def test_health(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.get("/health")
        assert resp.status == 200
        assert await resp.json() == {"status": "ok"}


async def test_nonstreaming_chat(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/chat", "messages": [{"role": "user", "content": "hi"}]})
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["message"]["content"] == "Hello world!"
        # Upstream saw the provider-real model name and bearer key.
        assert g.up.requests[0]["model"] == "real-a"
        assert g.up.headers_seen[0]["Authorization"] == "Bearer TESTKEY"


@pytest.mark.parametrize("body, content", [
    # An ``ensure_ascii`` client's escaped surrogate pair is ONE character
    # (json5 alone left two lone surrogates: a tokenizer counted two tokens
    # or could not encode them at all).
    (json.dumps({"model": "gw/chat", "messages": [
        {"role": "user", "content": "hi \U0001F600 \U00029000"}]}),
     "hi \U0001F600 \U00029000"),
    # What is not JSON is still parsed leniently.
    ("{model: 'gw/chat', /* c */ messages: [{role: 'user', "
     "content: 'hi',},],}", "hi")], ids=["surrogate-pair", "json5"])
async def test_chat_body_is_json_first_then_json5(tmp_path, body, content):
    assert "\\ud83d\\ude00" in body or "'" in body
    async with Gateway(tmp_path) as g:
        resp = await g.client.post(
            "/v1/chat/completions", data=body,
            headers={**g.headers(), "Content-Type": "application/json"})
        assert resp.status == 200
        assert g.up.requests[0]["messages"][0]["content"] == content


async def test_streaming_chat(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/chat", "stream": True,
            "messages": [{"role": "user", "content": "hi"}]})
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        frames = await read_sse_frames(resp)
        assert frames[-1] == "[DONE]"
        text = "".join(
            (json.loads(f)["choices"][0]["delta"].get("content") or "")
            for f in frames[:-1] if f != "[DONE]")
        assert text == "Hello world!"


async def test_streaming_inband_error_falls_back(tmp_path):
    """HTTP 200 + SSE error body on primary → gateway falls back to backup
    and the client still gets a clean 200 stream (priming semantics)."""
    async with Gateway(tmp_path, n_upstreams=2) as g:
        g.upstreams[0].plan.inband_error_next = 1
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/chat", "stream": True, "messages": []})
        assert resp.status == 200
        frames = await read_sse_frames(resp)
        assert frames[-1] == "[DONE]"
        # Served by backup upstream.
        assert len(g.upstreams[1].requests) == 1


async def test_http_error_falls_back_nonstreaming(tmp_path):
    async with Gateway(tmp_path, n_upstreams=2) as g:
        g.upstreams[0].plan.fail_next = 1
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/chat", "messages": []})
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["message"]["content"] == "Hello world!"
        assert len(g.upstreams[1].requests) == 1


async def test_all_upstreams_fail_503(tmp_path):
    async with Gateway(tmp_path) as g:
        g.up.plan.fail_next = 10
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/chat", "messages": []})
        assert resp.status == 503
        body = await resp.json()
        assert "All fallback models failed" in body["error"]["message"]


async def test_auth_enforced(tmp_path):
    """The reference *intends* this but its path-typo disables it
    (auth.py:17); here it must actually work."""
    async with Gateway(tmp_path, api_key="sekret") as g:
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/chat", "messages": []})
        assert resp.status == 401
        resp = await g.client.post(
            "/v1/chat/completions", json={"model": "gw/chat", "messages": []},
            headers={"Authorization": "Bearer wrong"})
        assert resp.status == 403
        resp = await g.client.post(
            "/v1/chat/completions", json={"model": "gw/chat", "messages": []},
            headers=g.headers())
        assert resp.status == 200
        # /health stays open.
        resp = await g.client.get("/health")
        assert resp.status == 200


async def test_models_endpoint_merges_gateway_and_upstream(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.get("/v1/models")
        assert resp.status == 200
        data = (await resp.json())["data"]
        ids = [m["id"] for m in data]
        # Gateway models first, then upstream's.
        assert ids[0] == "gw/chat"
        assert data[0]["owned_by"] == "llmgateway"
        assert "fake-model-1" in ids and "fake-model-2" in ids


async def test_models_agent_formats(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.get("/v1/models/AsOpenCodeFormat")
        assert resp.status == 200
        block = await resp.json()
        models = block["llmgateway"]["models"]
        assert "gw/chat" in models
        assert models["fake-model-1"]["limit"]["context"] == 8192
        assert "image" in models["fake-model-1"]["modalities"]["input"]

        resp = await g.client.get("/v1/models/AsGitHubCopilotFormat")
        assert resp.status == 200
        entries = {e["id"]: e for e in await resp.json()}
        assert entries["gw/chat"]["toolCalling"] is True
        assert entries["gw/chat"]["vision"] is True          # local forced
        assert entries["fake-model-1"]["vision"] is True     # image modality
        assert "reasoningEfforts" in entries["fake-model-1"]


async def test_config_editor_roundtrip_and_hot_reload(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.get("/v1/config/models-rules")
        text = await resp.text()
        assert "gw/chat" in text
        new_rules = ('[\n// hot reloaded\n{"gateway_model_name": "gw/renamed", '
                     '"fallback_models": [{"provider": "fakeup", "model": "real-a"}]}]')
        resp = await g.client.post("/v1/config/models-rules", data=new_rules)
        assert resp.status == 200
        # The chat path sees the new rules immediately (no import-time copy bug).
        resp = await g.client.post("/v1/chat/completions", json={
            "model": "gw/renamed", "messages": []})
        assert resp.status == 200
        # Invalid save → 400 structured errors, file unchanged.
        resp = await g.client.post("/v1/config/models-rules",
                                   data='[{"gateway_model_name": "x", '
                                        '"fallback_models": [{"provider": "ghost", "model": "m"}]}]')
        assert resp.status == 400
        body = await resp.json()
        assert body["errors"]
        assert "gw/renamed" in (tmp_path / "models_fallback_rules.json").read_text()


async def test_usage_recorded_and_stats_served(tmp_path):
    async with Gateway(tmp_path) as g:
        for _ in range(2):
            resp = await g.client.post("/v1/chat/completions", json={
                "model": "gw/chat", "stream": True,
                "messages": [{"role": "user", "content": "hi"}]})
            await read_sse_frames(resp)
        # Stream-end usage write is async-offloaded; give it a beat.
        await asyncio.sleep(0.1)
        resp = await g.client.get("/v1/api/usage-records")
        body = await resp.json()
        assert body["total"] == 2
        rec = body["records"][0]
        assert rec["provider"] == "fakeup" and rec["model"] == "real-a"
        assert rec["prompt_tokens"] == 7 and rec["total_tokens"] == 11
        assert rec["ttft_ms"] is not None
        resp = await g.client.get("/v1/api/usage-stats/day")
        rows = (await resp.json())["data"]
        assert rows and rows[0]["requests"] == 2
        # Transcript files written (LOG_CHAT_MESSAGES=true).
        transcripts = list((tmp_path / "logs").glob("*.txt"))
        assert transcripts
        assert "Hello world!" in transcripts[0].read_text()


async def test_request_id_header(tmp_path):
    async with Gateway(tmp_path) as g:
        resp = await g.client.get("/v1/models")
        assert "x-request-id" in resp.headers


async def test_engine_stats_and_trace_capture(tmp_path):
    async with Gateway(tmp_path) as g:
        # Proxy-only deployment: no local engines built, devices listed.
        resp = await g.client.get("/v1/api/engine-stats")
        assert resp.status == 200
        body = await resp.json()
        assert body["engines"] == {}
        assert isinstance(body["devices"], list)
        assert body["device_status"] == "ok"

        resp = await g.client.post("/v1/api/profiler/trace?duration_ms=150")
        assert resp.status == 200
        body = await resp.json()
        trace_dir = Path(body["trace_dir"])
        assert trace_dir.exists()
        # jax.profiler writes a plugins/profile tree under the trace dir.
        assert any(trace_dir.rglob("*")), "trace capture produced no files"

        resp = await g.client.post("/v1/api/profiler/trace?duration_ms=nope")
        assert resp.status == 400


async def test_engine_stats_survives_hung_backend_init(tmp_path,
                                                       monkeypatch):
    """A jax backend whose init does not return must not hang the stats
    endpoint: the probe runs in one daemon thread and the request returns
    within the bounded wait with device_status "initializing"."""
    import time as _time
    from llmapigateway_tpu.server import profiler_api

    monkeypatch.setattr(profiler_api, "DEVICE_PROBE_WAIT_S", 0.3)
    monkeypatch.setattr(profiler_api, "_dev_state",
                        {"status": "unprobed", "devices": []})

    def hang():
        _time.sleep(60)
    monkeypatch.setattr(
        profiler_api, "_start_device_probe",
        lambda: (profiler_api._dev_state.update(status="initializing"),
                 __import__("threading").Thread(
                     target=hang, daemon=True).start()))
    async with Gateway(tmp_path) as g:
        t0 = _time.monotonic()
        resp = await g.client.get("/v1/api/engine-stats")
        assert _time.monotonic() - t0 < 5.0
        assert resp.status == 200
        body = await resp.json()
        assert body["device_status"] == "initializing"
        assert body["devices"] == []


async def test_request_payload_logged_redacted(tmp_path, caplog):
    """Chat POST payloads are logged with messages/tools redacted
    (reference parity: request_logging.py:49-61) — params visible,
    contents never."""
    import logging
    secret = "my-private-prompt-text-42"
    with caplog.at_level(logging.INFO, logger="gateway.request"):
        async with Gateway(tmp_path) as g:
            resp = await g.client.post("/v1/chat/completions", json={
                "model": "gw/chat", "temperature": 0.5,
                "messages": [{"role": "user", "content": secret}],
                "tools": [{"type": "function", "function": {"name": secret}}]})
            assert resp.status == 200
    payloads = [r.payload for r in caplog.records if hasattr(r, "payload")]
    assert payloads, "chat POST produced no payload log"
    p = payloads[0]
    assert p["model"] == "gw/chat" and p["temperature"] == 0.5
    assert p["messages"] == "<redacted: 1 messages>"
    assert p["tools"] == "<redacted: 1 tools>"
    assert secret not in caplog.text


async def test_cors_preflight_and_vary(tmp_path):
    async with Gateway(tmp_path) as g:
        # Genuine preflight short-circuits with 204 even on protected routes.
        resp = await g.client.options("/v1/chat/completions", headers={
            "Origin": "http://a.example",
            "Access-Control-Request-Method": "POST"})
        assert resp.status == 204
        assert resp.headers["Access-Control-Allow-Origin"] == "*"
        # A plain OPTIONS (no preflight headers) routes normally -> 405/404,
        # not a blanket 204.
        resp = await g.client.options("/v1/chat/completions")
        assert resp.status in (404, 405)


async def test_cors_specific_origin_sets_vary():
    from aiohttp import web
    from llmapigateway_tpu.server.middleware import cors_middleware

    app = web.Application(middlewares=[cors_middleware(["http://a.example"])])
    app.router.add_get("/x", lambda r: web.json_response({}))
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        resp = await client.get("/x", headers={"Origin": "http://a.example"})
        assert resp.headers["Access-Control-Allow-Origin"] == "http://a.example"
        assert resp.headers["Vary"] == "Origin"
        resp = await client.get("/x", headers={"Origin": "http://evil.example"})
        assert "Access-Control-Allow-Origin" not in resp.headers
        # Vary must be on EVERY response when origins are restricted, or a
        # shared cache could serve a CORS-headerless copy to allowed origins.
        assert resp.headers["Vary"] == "Origin"
        resp = await client.get("/x")
        assert resp.headers["Vary"] == "Origin"
    finally:
        await client.close()


async def test_roofline_endpoint(tmp_path, monkeypatch):
    """/v1/api/roofline: proxy-only deployments report no engines; with a
    local engine, the endpoint serves exactly the roofline slice of its
    stats (ISSUE 2 — the number the stats UI and bench ladder poll)."""
    async with Gateway(tmp_path) as g:
        resp = await g.client.get("/v1/api/roofline")
        assert resp.status == 200
        assert (await resp.json())["engines"] == {}

        class FakeEngine:
            def stats(self):
                return {
                    # The r5b-measured operating point, as stats() shapes it.
                    "achieved_gbps": 392.1, "roofline_fraction": 0.478,
                    "hbm_bytes_per_step": 9_018_000_000,
                    "decode_ms_per_step": 23.0, "decode_tok_s": 1391.1,
                    "burst_depth_last": 16, "burst_busy_clamps": 3,
                    "queue_wait_ms_ema": 12.5, "queue_wait_ms_max": 80.0,
                    "queue_waits": 7, "running": 2, "queued": 0,
                    # Non-roofline stats fields must be filtered out.
                    "kv_layout": "paged", "free_pages": 10,
                }

        class FakeProv:
            engine = FakeEngine()

        monkeypatch.setattr(g.gw.registry, "instantiated",
                            lambda: [("local_tpu", FakeProv())])
        resp = await g.client.get("/v1/api/roofline")
        assert resp.status == 200
        row = (await resp.json())["engines"]["local_tpu"]
        assert row["achieved_gbps"] == 392.1
        assert row["roofline_fraction"] == 0.478
        assert row["burst_busy_clamps"] == 3
        assert row["queue_wait_ms_max"] == 80.0
        assert "kv_layout" not in row and "free_pages" not in row
