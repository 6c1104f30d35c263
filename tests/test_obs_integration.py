"""ISSUE 4 acceptance: the unified metrics plane and end-to-end request
tracing, over the real aiohttp app with a real (tiny, CPU) local engine
plus fake remote upstreams.

* ``GET /metrics`` serves grammatical Prometheus text covering ≥ 25
  distinct series spanning all four layers (http, router, provider,
  engine), validated by the grammar checker from tests/test_metrics.py.
* ``GET /v1/api/trace/{request_id}`` returns a complete span tree —
  router attempt → provider call → engine phases, including a fallback
  hop — for a streamed local-engine request AND a remote-provider
  request; ``x-gateway-timings`` summarizes non-streamed responses and
  the request id propagates upstream.
* Chaos: a deadline expiring mid-stream leaves no leaked (unclosed)
  spans.
"""
import asyncio
import json

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmapigateway_tpu.config.loader import ConfigLoader
from llmapigateway_tpu.config.schemas import ProviderDetails
from llmapigateway_tpu.config.settings import Settings
from llmapigateway_tpu.providers.local import LocalProvider
from llmapigateway_tpu.server.app import GatewayApp, build_app
from tests.fake_upstream import FakeUpstream
from tests.test_metrics import validate_prometheus_text


@pytest.fixture(scope="module")
def local_factory():
    """Build the tiny CPU engine once per module (compile cache)."""
    cache = {}

    def factory(name: str, details: ProviderDetails) -> LocalProvider:
        if name not in cache:
            from llmapigateway_tpu.engine.engine import InferenceEngine
            cache[name] = InferenceEngine(details.engine,
                                          devices=[jax.devices("cpu")[0]])
        return LocalProvider(name, cache[name])

    factory.engines = cache
    return factory


class ObsGateway:
    """Gateway wired to one flaky remote, one healthy backup remote, and
    the tiny local engine — enough topology for fallback-hop traces."""

    def __init__(self, tmp_path, local_factory):
        self.tmp_path = tmp_path
        self.local_factory = local_factory

    async def __aenter__(self):
        self.flaky = FakeUpstream()
        self.backup = FakeUpstream()
        self.servers = []
        urls = []
        for up in (self.flaky, self.backup):
            server = TestServer(up.app)
            await server.start_server()
            self.servers.append(server)
            urls.append(f"http://{server.host}:{server.port}/v1")
        providers = [
            {"flaky": {"baseUrl": urls[0], "apikey": "FLK"}},
            {"backup": {"baseUrl": urls[1], "apikey": "BK"}},
            {"tpu": {"type": "local",
                     "engine": {"preset": "tiny-test", "dtype": "float32",
                                "max_batch_size": 2, "max_seq_len": 128,
                                "prefill_chunk": 32, "decode_burst": 4,
                                # Paged + radix prefix cache ride the 0.19
                                # DEFAULTS here; the small page makes chat
                                # prompts span shareable blocks.
                                "kv_page_size": 16,
                                # A (toy) HBM peak so the per-kernel
                                # roofline fractions + worst-kernel pick
                                # engage on CPU (ISSUE 8).
                                "hbm_peak_gbps": 1.0,
                                "max_tokens_default": 8}}},
            # A second tiny engine with the two-pool disaggregated
            # scheduler (ISSUE 13): built lazily, so only the pool tests
            # pay for it.
            {"tpud": {"type": "local",
                      "engine": {"preset": "tiny-test", "dtype": "float32",
                                 "max_batch_size": 2, "max_seq_len": 128,
                                 "prefill_chunk": 32, "decode_burst": 4,
                                 "kv_page_size": 16,
                                 "max_tokens_default": 8,
                                 "disaggregation": {"enabled": True,
                                                    "prefill_slots": 1}}}},
        ]
        rules = [
            {"gateway_model_name": "gw/local",
             "fallback_models": [
                 {"provider": "flaky", "model": "real-a", "retry_count": 0},
                 {"provider": "tpu", "model": "tiny-test"}]},
            {"gateway_model_name": "gw/remote",
             "fallback_models": [
                 {"provider": "flaky", "model": "real-a", "retry_count": 0},
                 {"provider": "backup", "model": "real-b"}]},
            {"gateway_model_name": "gw/local-direct",
             "fallback_models": [
                 {"provider": "tpu", "model": "tiny-test"}]},
            {"gateway_model_name": "gw/disagg",
             "fallback_models": [
                 {"provider": "tpud", "model": "tiny-test"}]},
        ]
        (self.tmp_path / "providers.json").write_text(json.dumps(providers))
        (self.tmp_path / "models_fallback_rules.json").write_text(
            json.dumps(rules))
        settings = Settings(fallback_provider="backup",
                            base_dir=self.tmp_path,
                            config_dir=self.tmp_path,
                            db_dir=self.tmp_path / "db",
                            logs_dir=self.tmp_path / "logs")
        loader = ConfigLoader(self.tmp_path, fallback_provider=None)
        self.gw = GatewayApp(settings, loader,
                             local_factory=self.local_factory)
        app = build_app(settings, loader, gateway=self.gw)
        self.client = TestClient(TestServer(app))
        await self.client.start_server()
        return self

    async def __aexit__(self, *exc):
        await self.client.close()
        for s in self.servers:
            await s.close()


async def read_sse_frames(resp):
    frames = []
    async for line in resp.content:
        line = line.decode().strip()
        if line.startswith("data: "):
            frames.append(line[len("data: "):])
    return frames


def walk_spans(span):
    yield span
    for child in span.get("children", ()):
        yield from walk_spans(child)


def assert_all_closed(doc):
    open_spans = [s["name"] for s in walk_spans(doc["spans"])
                  if s["duration_ms"] is None]
    assert not open_spans, f"leaked (unclosed) spans: {open_spans}"


# -- trace trees --------------------------------------------------------------

async def test_streamed_local_trace_with_fallback_hop(tmp_path,
                                                      local_factory):
    async with ObsGateway(tmp_path, local_factory) as g:
        g.flaky.plan.fail_next = 1
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local", "stream": True, "max_tokens": 6,
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"x-request-id": "trace-local-1"})
        assert resp.status == 200
        assert resp.headers["x-request-id"] == "trace-local-1"
        frames = await read_sse_frames(resp)
        assert frames[-1] == "[DONE]"

        resp = await g.client.get("/v1/api/trace/trace-local-1")
        assert resp.status == 200
        doc = await resp.json()
        assert doc["request_id"] == "trace-local-1"
        assert doc["complete"] is True
        assert_all_closed(doc)

        root = doc["spans"]
        assert root["layer"] == "gateway"
        attempts = [s for s in root["children"]
                    if s["name"] == "router.attempt"]
        # The fallback hop: failed flaky attempt, then the local engine.
        assert [a["attrs"]["provider"] for a in attempts] == ["flaky", "tpu"]
        assert "error" in attempts[0]["attrs"]
        (call,) = [s for s in attempts[1]["children"]
                   if s["name"] == "provider.call"]
        assert call["layer"] == "provider"
        engine_phases = {s["name"] for s in call.get("children", ())}
        assert {"engine.queued", "engine.prefill", "engine.first_token",
                "engine.decode"} <= engine_phases
        # The stream drain is traced at the gateway layer.
        assert any(s["name"] == "gateway.stream_drain"
                   for s in root["children"])
        # Engine phases nest in causal order.
        by_name = {s["name"]: s for s in call["children"]}
        assert (by_name["engine.queued"]["start_ms"]
                <= by_name["engine.prefill"]["start_ms"]
                <= by_name["engine.decode"]["start_ms"])


async def test_remote_trace_timings_header_and_id_propagation(tmp_path,
                                                              local_factory):
    async with ObsGateway(tmp_path, local_factory) as g:
        g.flaky.plan.fail_next = 1
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/remote",
                  "messages": [{"role": "user", "content": "hi"}]},
            headers={"x-request-id": "trace-remote-1"})
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["message"]["content"] == "Hello world!"

        # Satellite: the gateway's request id propagated upstream on BOTH
        # attempts of the fallback chain.
        assert g.flaky.headers_seen[0].get("x-request-id") == "trace-remote-1"
        assert g.backup.headers_seen[0].get("x-request-id") == "trace-remote-1"

        # Non-streamed responses summarize per-phase latency.
        timings = resp.headers["x-gateway-timings"]
        assert "total;dur=" in timings
        assert "router_attempt;dur=" in timings
        assert "provider_call;dur=" in timings

        resp = await g.client.get("/v1/api/trace/trace-remote-1")
        doc = await resp.json()
        assert doc["complete"] is True
        assert_all_closed(doc)
        attempts = [s for s in doc["spans"]["children"]
                    if s["name"] == "router.attempt"]
        assert [a["attrs"]["provider"] for a in attempts] == ["flaky",
                                                              "backup"]
        assert all(any(c["name"] == "provider.call"
                       for c in a["children"]) for a in attempts)


async def test_trace_endpoint_404_for_unknown_id(tmp_path, local_factory):
    async with ObsGateway(tmp_path, local_factory) as g:
        resp = await g.client.get("/v1/api/trace/no-such-request")
        assert resp.status == 404
        assert "ring buffer" in (await resp.json())["detail"]


# -- the metrics plane --------------------------------------------------------

async def test_metrics_exposition_grammar_and_layer_coverage(tmp_path,
                                                             local_factory):
    """The acceptance bar: one scrape, valid grammar, ≥ 25 distinct series
    spanning http, router, provider, and engine."""
    async with ObsGateway(tmp_path, local_factory) as g:
        # Traffic across all layers: a local streamed request (engine), a
        # remote fallback (router fallbacks + provider errors), and a 404.
        g.flaky.plan.fail_next = 2
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local", "stream": True, "max_tokens": 4,
                  "messages": [{"role": "user", "content": "hi"}]})
        await read_sse_frames(resp)
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/remote", "messages": []})
        assert resp.status == 200
        await g.client.get("/v1/does-not-exist")

        resp = await g.client.get("/metrics")
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = await resp.text()

    families = validate_prometheus_text(text)

    # Every family obeys the naming convention the lint pins.
    for name in families:
        assert name.endswith(("_seconds", "_bytes", "_total", "_ratio")), name

    series = set()
    for fam in families.values():
        for name, labels, _ in fam["samples"]:
            series.add((name, tuple(sorted(labels.items()))))
    assert len(series) >= 25, f"only {len(series)} series"

    # All four layers report actual samples, not just HELP/TYPE.
    for prefix in ("gateway_http_", "gateway_router_", "gateway_provider_",
                   "gateway_engine_"):
        assert any(n.startswith(prefix) for n, _ in series), prefix

    def sample_value(fam, sample=None, **labels):
        for name, got, value in families[fam]["samples"]:
            if sample is not None and name != sample:
                continue
            if all(got.get(k) == v for k, v in labels.items()):
                return value
        return None

    # Spot checks across the layers.
    assert sample_value("gateway_router_attempts_total",
                        provider="tpu") >= 1
    assert sample_value("gateway_router_attempts_total",
                        provider="flaky") >= 2
    assert sample_value("gateway_provider_errors_total",
                        provider="flaky", kind="http") >= 2
    assert families["gateway_router_fallbacks_total"]["samples"][0][2] >= 2
    assert sample_value("gateway_engine_running_requests_total",
                        engine="tpu") is not None
    assert sample_value("gateway_engine_ttft_seconds",
                        sample="gateway_engine_ttft_seconds_count",
                        engine="tpu") >= 1
    assert sample_value("gateway_provider_breaker_open_ratio",
                        provider="flaky") == 0.0
    # The chat route label is the route template, status-split.
    assert sample_value("gateway_http_requests_total",
                        path="/v1/chat/completions", status="200") >= 2

    # HBM ledger series (ISSUE 8): static accounting and live buffer
    # bytes per engine, through the same grammar validator. On the CPU
    # backend there are no allocator stats, so the device_* families may
    # legitimately carry no samples — the ledger families must.
    for fam in ("gateway_engine_hbm_weights_bytes",
                "gateway_engine_hbm_kv_pool_bytes",
                "gateway_engine_hbm_ledger_bytes",
                "gateway_engine_hbm_tracked_bytes"):
        assert sample_value(fam, engine="tpu") > 0, fam
    ledger = sample_value("gateway_engine_hbm_ledger_bytes", engine="tpu")
    tracked = sample_value("gateway_engine_hbm_tracked_bytes",
                           engine="tpu")
    assert abs(ledger - tracked) <= max(0.10 * tracked, 1 << 20)
    assert sample_value("gateway_engine_watermark_sheds_total",
                        engine="tpu") == 0
    # XLA compile telemetry: the engine build itself compiled, so the
    # startup phase has a count and nonzero wall.
    assert sample_value("gateway_engine_xla_compile_total",
                        phase="startup") >= 1
    assert sample_value("gateway_engine_xla_compile_seconds",
                        phase="startup") > 0


async def test_roofline_per_kernel_table_and_hbm_ledger(tmp_path,
                                                        local_factory):
    """ISSUE 8 acceptance: after serving a local request,
    GET /v1/api/roofline carries a per-kernel table with ≥2 distinct
    kernels whose decode rows' bytes/step reconcile with the aggregate
    ``hbm_bytes_per_step`` within 10%, names the single worst kernel,
    and exposes the HBM ledger alongside."""
    async with ObsGateway(tmp_path, local_factory) as g:
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local-direct", "stream": True,
                  "max_tokens": 4,
                  "messages": [{"role": "user", "content": "roofline"}]})
        await read_sse_frames(resp)

        # Resolve pending cost closures before the read, so that the table
        # rows carry the cost_analysis columns deterministically — off the
        # loop, as the server does: every closure lowers and compiles, and
        # the sanitizer fails a loop that stood still for five seconds.
        await asyncio.to_thread(
            g.local_factory.engines["tpu"].kernels.resolve_costs)
        resp = await g.client.get("/v1/api/roofline")
        assert resp.status == 200
        block = (await resp.json())["engines"]["tpu"]

    # Aggregate keys survive (backward-compatible endpoint shape).
    assert "hbm_bytes_per_step" in block
    rows = block["kernels"]
    assert len({r["kernel"] for r in rows}) >= 2, rows
    kinds = {r["kind"] for r in rows}
    assert "prefill" in kinds and "decode" in kinds
    agg = block["hbm_bytes_per_step"]
    decode_rows = [r for r in rows if r["kind"] == "decode"]
    assert decode_rows
    for r in decode_rows:
        assert abs(r["hbm_bytes_per_step"] - agg) <= 0.10 * agg, (r, agg)
    # Walls measured (flight join or dispatch walls) → fractions → a
    # nameable worst kernel (hbm_peak_gbps is set on this engine).
    assert block["worst_kernel"] in {r["kernel"] for r in rows}
    assert any("xla_flops_per_call" in r for r in rows), rows
    # The ledger block reconciles (static intent vs live buffers).
    hbm = block["hbm"]
    assert abs(hbm["hbm_ledger_bytes"] - hbm["hbm_tracked_bytes"]) \
        <= max(0.10 * hbm["hbm_tracked_bytes"], 1 << 20)


async def test_metrics_endpoint_is_unauthenticated_and_unlogged(
        tmp_path, local_factory, caplog):
    import logging
    async with ObsGateway(tmp_path, local_factory) as g:
        g.gw.settings.gateway_api_key = "sekret"     # not used by build_app
        with caplog.at_level(logging.INFO, logger="gateway.request"):
            resp = await g.client.get("/metrics")
            assert resp.status == 200
    assert not any("GET /metrics" in r.getMessage() for r in caplog.records)
    assert not any(getattr(r, "path", "") == "/metrics"
                   for r in caplog.records)


# -- prefix cache: /metrics series + SSE usage frame + trace span ------------

async def test_prefix_cache_metrics_usage_frame_and_trace(tmp_path,
                                                          local_factory):
    """ISSUE 6 observability: the engine_prefix_* series appear in the
    exposition with the validator's grammar, a warm request's SSE usage
    frame reports OpenAI-compatible ``prompt_tokens_details.cached_tokens``
    (which the usage DB ingests), and its trace tree carries the
    ``engine.prefix_lookup`` span."""
    async with ObsGateway(tmp_path, local_factory) as g:
        body = {"model": "gw/local-direct", "stream": True, "max_tokens": 3,
                "messages": [{"role": "user",
                              "content": "please summarize the quarterly "
                                         "llama serving report briefly"}]}
        resp = await g.client.post("/v1/chat/completions", json=body)
        assert resp.status == 200
        await read_sse_frames(resp)
        resp = await g.client.post("/v1/chat/completions", json=body,
                                   headers={"x-request-id": "warm-hit-1"})
        assert resp.status == 200
        frames = await read_sse_frames(resp)
        usage_frames = [json.loads(f) for f in frames
                        if f != "[DONE]" and "usage" in f]
        usage = usage_frames[-1]["usage"]
        cached = usage.get("prompt_tokens_details", {}).get("cached_tokens")
        assert cached and cached > 0
        assert cached <= usage["prompt_tokens"]

        # The usage ledger ingested the cached-token detail.
        from llmapigateway_tpu.server.usage_capture import \
            extract_usage_fields
        assert extract_usage_fields(usage)["cached_tokens"] == cached

        # Trace: the lookup span sits among the engine phases with the
        # hit span recorded as an attribute.
        resp = await g.client.get("/v1/api/trace/warm-hit-1")
        doc = await resp.json()
        assert doc["complete"] is True
        assert_all_closed(doc)
        lookups = [s for s in walk_spans(doc["spans"])
                   if s["name"] == "engine.prefix_lookup"]
        assert lookups and lookups[0]["attrs"]["cached_tokens"] == cached

        # /metrics: hit/miss totals, cached tokens, residency + pin
        # gauges, all under the exposition grammar.
        resp = await g.client.get("/metrics")
        text = await resp.text()
    families = validate_prometheus_text(text)

    def val(fam, **labels):
        for name, got, value in families[fam]["samples"]:
            if all(got.get(k) == v for k, v in labels.items()):
                return value
        return None

    assert val("gateway_engine_prefix_cache_hit_total", engine="tpu") >= 1
    assert val("gateway_engine_prefix_cache_miss_total",
               engine="tpu") is not None
    assert val("gateway_engine_prefix_cached_tokens_total",
               engine="tpu") >= cached
    assert val("gateway_engine_prefix_resident_pages_total",
               engine="tpu") >= 1
    assert val("gateway_engine_prefix_pinned_refs_total",
               engine="tpu") is not None


# -- chaos: deadline mid-stream ----------------------------------------------

# -- ISSUE 7: flight recorder + SLO attribution + streamed timings -----------

async def test_streamed_timings_header_and_usage_frame_sibling(
        tmp_path, local_factory):
    """Satellite: streamed requests carry the timing summary too — the
    known-at-start phases as a response-start header, and the FULL
    summary (decode included) as the final SSE usage frame's sibling
    field — without breaking the SSE protocol ([DONE] still terminal,
    chunks still OpenAI-parseable)."""
    async with ObsGateway(tmp_path, local_factory) as g:
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local-direct", "stream": True,
                  "max_tokens": 4,
                  "messages": [{"role": "user", "content": "hi"}]})
        assert resp.status == 200
        header = resp.headers.get("x-gateway-timings", "")
        assert "total;dur=" in header
        assert "router_attempt;dur=" in header
        frames = await read_sse_frames(resp)
        assert frames[-1] == "[DONE]"
        bodies = [json.loads(f) for f in frames if f != "[DONE]"]
        assert all("choices" in b for b in bodies)      # protocol intact
        (final,) = [b for b in bodies if "usage" in b]
        timings = final["gateway_timings"]
        assert "total;dur=" in timings
        # Post-commit phases no header could carry.
        assert "engine_decode;dur=" in timings


async def test_flight_endpoint_serves_live_records_and_trace_crosslink(
        tmp_path, local_factory):
    """Acceptance: GET /v1/api/flight returns step + lifecycle records
    from a live streamed request, the lifecycle records carry the
    gateway request id, and the request's trace tree holds the admit
    record's seq number (the flight↔trace cross-link)."""
    async with ObsGateway(tmp_path, local_factory) as g:
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local-direct", "stream": True,
                  "max_tokens": 4,
                  "messages": [{"role": "user", "content": "hello"}]},
            headers={"x-request-id": "flight-req-1"})
        assert resp.status == 200
        await read_sse_frames(resp)

        resp = await g.client.get("/v1/api/flight")
        assert resp.status == 200
        doc = await resp.json()
        eng = doc["engines"]["tpu"]
        assert eng["flight_seq"] > 0
        records = eng["records"]
        kinds = {r["kind"] for r in records}
        assert {"step", "admit", "finish"} <= kinds
        admit = next(r for r in records if r["kind"] == "admit"
                     and r.get("request_id") == "flight-req-1")
        finish = next(r for r in records if r["kind"] == "finish"
                      and r.get("request_id") == "flight-req-1")
        assert finish["seq"] > admit["seq"]
        steps = [r for r in records if r["kind"] == "step"]
        assert any(r["step_kind"] in ("decode", "mixed") for r in steps)

        # ?since= tails the ring.
        resp = await g.client.get(
            f"/v1/api/flight?since={eng['flight_seq'] - 1}")
        doc2 = await resp.json()
        assert doc2["engines"]["tpu"]["records"] == []
        resp = await g.client.get("/v1/api/flight?since=bogus")
        assert resp.status == 400

        # Trace → flight cross-link: engine.queued carries the admit seq.
        resp = await g.client.get("/v1/api/trace/flight-req-1")
        tdoc = await resp.json()
        queued = [s for s in walk_spans(tdoc["spans"])
                  if s["name"] == "engine.queued"]
        assert queued and queued[0]["attrs"]["flight_seq"] == admit["seq"]


async def test_slo_violation_attributed_queued_metrics_db_and_usage(
        tmp_path, local_factory):
    """ISSUE 7 acceptance: a request with a deliberately tight
    x-slo-ttft-ms, submitted while both engine slots are held, shows
    `gateway_slo_violated_total{phase="queued"}` incremented, the
    violation attributed in its usage DB row, and the SLO block in its
    usage payload. A loose-SLO request then lands on the met counter and
    the goodput gauge."""
    from llmapigateway_tpu.engine.engine import FaultPlan
    async with ObsGateway(tmp_path, local_factory) as g:
        # Saturate both slots: generation runs server-side regardless of
        # client reads, so the slots stay held until max_tokens lands —
        # slowed per decode burst via the fault hook so the probe's queue
        # wait deterministically dwarfs its (one-chunk) prefill.
        provider = await g.gw.registry.get("tpu")
        engine = provider.engine
        engine.fault_plan = FaultPlan(slow_decode_s=0.1)
        # Random tiny-test weights can sample EOS on any step, releasing
        # a slot early and deflating the probe's queue wait — suppress
        # EOS for the window so the holds run their full token budget.
        saved_eos = engine.tokenizer.eos_ids
        engine.tokenizer.eos_ids = frozenset()
        try:
            bg = [await g.client.post(
                "/v1/chat/completions",
                json={"model": "gw/local-direct", "stream": True,
                      "max_tokens": 56, "temperature": 0,
                      "messages": [{"role": "user",
                                    "content": f"busy {i} {'x' * i}"}]})
                for i in range(2)]
            # Committed 200s = first token exists = slots held; the slow
            # bursts keep them held for seconds — the probe MUST queue.
            assert all(r.status == 200 for r in bg)
            assert not engine._free_slots

            resp = await g.client.post(
                "/v1/chat/completions",
                json={"model": "gw/local-direct", "max_tokens": 2,
                      "messages": [{"role": "user", "content": "probe"}]},
                headers={"x-slo-ttft-ms": "1",
                         "x-request-id": "slo-probe-1"})
            assert resp.status == 200
            body = await resp.json()
            slo = body["usage"]["slo"]
            assert slo["met"] is False
            assert slo["phase"] == "queued"
            assert slo["ttft_target_ms"] == 1.0
            assert slo["attribution"]["queued_ms"] >= \
                slo["attribution"]["prefill_ms"]
            for r in bg:
                await read_sse_frames(r)
        finally:
            engine.fault_plan = None
            engine.tokenizer.eos_ids = saved_eos

        # A loose-SLO request meets its target → met + goodput.
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local-direct", "max_tokens": 2,
                  "messages": [{"role": "user", "content": "easy"}]},
            headers={"x-slo-ttft-ms": "60000"})
        assert resp.status == 200
        assert (await resp.json())["usage"]["slo"]["met"] is True

        await asyncio.sleep(0.2)          # offloaded usage-DB writes
        resp = await g.client.get("/metrics")
        text = await resp.text()

        resp = await g.client.get("/v1/api/usage-records")
        rows = (await resp.json())["records"]

    # Exposition-grammar validator over the NEW series (satellite).
    families = validate_prometheus_text(text)

    def val(fam, **labels):
        for name, got, value in families[fam]["samples"]:
            if all(got.get(k) == v for k, v in labels.items()):
                return value
        return None

    assert val("gateway_slo_violated_total",
               engine="tpu", phase="queued") >= 1
    assert val("gateway_slo_met_total", engine="tpu") >= 1
    goodput = val("gateway_slo_goodput_ratio", engine="tpu")
    assert goodput is not None and 0.0 < goodput < 1.0
    assert val("gateway_trace_ring_evicted_total") is not None
    assert val("gateway_engine_flight_ring_evicted_total",
               engine="tpu") == 0

    # The violation is attributed in the usage DB row.
    probe_rows = [r for r in rows if r["slo_phase"] == "queued"]
    assert probe_rows and probe_rows[0]["slo_met"] == 0
    assert any(r["slo_met"] == 1 for r in rows)


async def test_disagg_pool_series_and_per_pool_goodput(tmp_path,
                                                       local_factory):
    """ISSUE 13 observability: serving through the two-pool engine puts
    the gateway_engine_pool_* gauges, the handoff counters, and the
    per-pool SLO attribution (slo_pool_* + the per-pool goodput ratio —
    the pooled-vs-unified scoreboard) into /metrics under the exposition
    grammar. The request's usage SLO block names the pool that served
    its decode."""
    async with ObsGateway(tmp_path, local_factory) as g:
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/disagg", "max_tokens": 4,
                  "messages": [{"role": "user", "content": "pools"}]},
            headers={"x-slo-ttft-ms": "60000"})
        assert resp.status == 200
        slo = (await resp.json())["usage"]["slo"]
        # Cold admission lands on the prefill pool and hands off; the
        # decode pool owns the request by stream end.
        assert slo["met"] is True and slo["pool"] == "decode"

        resp = await g.client.get("/metrics")
        text = await resp.text()

    families = validate_prometheus_text(text)

    def val(fam, **labels):
        for name, got, value in families[fam]["samples"]:
            if all(got.get(k) == v for k, v in labels.items()):
                return value
        return None

    # Pool topology gauges: one prefill slot + one decode slot (B=2).
    assert val("gateway_engine_pool_slots_total",
               engine="tpud", pool="prefill") == 1
    assert val("gateway_engine_pool_slots_total",
               engine="tpud", pool="decode") == 1
    assert val("gateway_engine_pool_admits_total",
               engine="tpud", pool="prefill") >= 1
    assert val("gateway_engine_pool_free_slots_total",
               engine="tpud", pool="decode") == 1    # drained by scrape
    assert val("gateway_engine_pool_sheds_total",
               engine="tpud", pool="prefill") == 0
    # The zero-copy handoff counters moved pages without copying them.
    assert val("gateway_engine_disagg_handoffs_total", engine="tpud") >= 1
    assert val("gateway_engine_disagg_handoff_pages_total",
               engine="tpud") >= 1
    # Per-pool SLO attribution → the scoreboard ratio.
    assert val("gateway_slo_pool_met_total",
               engine="tpud", pool="decode") >= 1
    assert val("gateway_slo_pool_goodput_ratio",
               engine="tpud", pool="decode") == 1.0
    # The unified engine never grows pool-topology gauges; its SLO
    # attribution keeps the single "unified" series (the other half of
    # the pooled-vs-unified scoreboard), never a prefill/decode split.
    assert all(got.get("engine") != "tpu"
               for _, got, _ in
               families["gateway_engine_pool_slots_total"]["samples"])
    assert all(got.get("pool") == "unified"
               for _, got, _ in
               families["gateway_slo_pool_met_total"]["samples"]
               if got.get("engine") == "tpu")


async def test_goodput_shed_maps_to_429_with_numeric_retry_after(
        tmp_path, local_factory):
    """ISSUE 13 acceptance: when the decode pool's predicted TPOT misses
    the request's target, admission sheds through the PR 3 overload path
    — HTTP 429 with a numeric Retry-After — and the pool's shed counter
    reaches /metrics."""
    async with ObsGateway(tmp_path, local_factory) as g:
        provider = await g.gw.registry.get("tpud")
        engine = provider.engine
        # Pin the fitted decode step time far above the ask so the
        # predictor's verdict is deterministic (no warm-up dependence).
        saved = engine._ema_step_ms_stats
        engine._ema_step_ms_stats = 500.0
        try:
            resp = await g.client.post(
                "/v1/chat/completions",
                json={"model": "gw/disagg", "max_tokens": 4,
                      "messages": [{"role": "user", "content": "shed"}]},
                headers={"x-slo-tpot-ms": "0.01"})
            assert resp.status == 429
            retry_after = resp.headers.get("Retry-After")
            assert retry_after is not None and float(retry_after) >= 1
            body = await resp.json()
            assert "TPOT target" in json.dumps(body)
        finally:
            engine._ema_step_ms_stats = saved

        resp = await g.client.get("/metrics")
        text = await resp.text()
    families = validate_prometheus_text(text)
    shed_samples = {got["pool"]: value for _, got, value in
                    families["gateway_engine_pool_sheds_total"]["samples"]
                    if got.get("engine") == "tpud"}
    assert shed_samples.get("decode", 0) >= 1


async def test_rule_level_slo_defaults_apply(tmp_path, local_factory):
    """Rule-config SLO (schemas.py slo_ttft_ms) classifies requests that
    send no SLO headers."""
    async with ObsGateway(tmp_path, local_factory) as g:
        # Rewrite the rules with a rule-level SLO and hot-reload.
        rules = json.loads(
            (g.tmp_path / "models_fallback_rules.json").read_text())
        for rule in rules:
            if rule["gateway_model_name"] == "gw/local-direct":
                rule["slo_ttft_ms"] = 60000.0
        (g.tmp_path / "models_fallback_rules.json").write_text(
            json.dumps(rules))
        ok, err = g.gw.loader.reload_rules()
        assert ok, err
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/local-direct", "max_tokens": 2,
                  "messages": [{"role": "user", "content": "hi"}]})
        assert resp.status == 200
        slo = (await resp.json())["usage"]["slo"]
        assert slo["ttft_target_ms"] == 60000.0 and slo["met"] is True


async def test_deadline_mid_stream_closes_all_spans(tmp_path, local_factory):
    """The request's budget expires while a committed upstream stream is
    being relayed (the upstream stalls past the deadline-capped read
    timeout): the client's 200 stream ends with an in-band error frame and
    — the acceptance bar — the trace holds no leaked (unclosed) spans."""
    async with ObsGateway(tmp_path, local_factory) as g:
        # The chain's first target serves healthy priming frames, then
        # stalls far past the 400 ms budget.
        g.flaky.plan.stall_after_frames = 2
        g.flaky.plan.stall_s = 5.0
        resp = await g.client.post(
            "/v1/chat/completions",
            json={"model": "gw/remote", "stream": True,
                  "messages": [{"role": "user", "content": "go"}]},
            headers={"x-request-id": "chaos-deadline-1",
                     "x-request-timeout-ms": "400"})
        assert resp.status == 200              # committed before expiry
        frames = await read_sse_frames(resp)
        last = json.loads(frames[-1])
        assert "error" in last

        resp = await g.client.get("/v1/api/trace/chaos-deadline-1")
        doc = await resp.json()
        assert doc["complete"] is True
        assert_all_closed(doc)
        names = {s["name"] for s in walk_spans(doc["spans"])}
        assert "gateway.stream_drain" in names
        assert "provider.call" in names


async def test_local_deadline_mid_stream_cancels_and_closes_spans():
    """The local engine's streamed path under a mid-stream deadline expiry,
    driven deterministically with a fake clock at the provider layer: the
    stream ends with an in-band 504 error frame, the engine request is
    cancelled (slot frees), and every recorded span is closed."""
    from llmapigateway_tpu.obs import trace as obs_trace
    from llmapigateway_tpu.obs.trace import Tracer
    from llmapigateway_tpu.providers.local import LocalProvider
    from llmapigateway_tpu.reliability.deadline import Deadline
    from llmapigateway_tpu.engine.engine import Delta, GenRequest

    t = [1000.0]
    clock = lambda: t[0]                       # noqa: E731
    deadline = Deadline(0.5, clock=clock)
    provider = LocalProvider.__new__(LocalProvider)   # no engine needed
    provider.name = "tpu"
    from llmapigateway_tpu.obs.metrics import get_metrics
    provider._metrics = get_metrics()

    req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=10)
    req.t_admitted = req.t_submit
    req.t_first_token = req.t_submit

    class _Detok:
        def flush(self):
            return ""
    req.detok = _Detok()

    async def deltas():
        yield Delta(text="world")
        t[0] += 1.0                            # budget gone mid-stream
        yield Delta(text="never sent")
        raise AssertionError("stream must stop at the deadline")

    class _Obs:
        ended = None

        def on_content_delta(self, text):
            pass

        def on_usage(self, usage):
            pass

        def on_stream_end(self, error=None):
            self.ended = error or "clean"

    tracer = Tracer(clock=clock)
    observer = _Obs()
    first = Delta(text="hello")
    stream_iter = deltas()
    with tracer.trace("local-chaos-1"):
        with obs_trace.span("provider.call", layer="provider") as call:
            frames = [f async for f in provider._sse_frames(
                req, stream_iter, first, "tiny-test", observer,
                deadline=deadline, parent=call)]
    await stream_iter.aclose()      # abandoned by the early deadline return
    last = json.loads(frames[-1].decode().split("data: ", 1)[1])
    assert last["error"]["code"] == 504
    assert "deadline" in last["error"]["message"]
    assert req.cancelled is True               # slot will be freed
    assert observer.ended == "deadline expired mid-stream"
    doc = tracer.get("local-chaos-1")
    assert_all_closed(doc)
    decode = [s for s in walk_spans(doc["spans"])
              if s["name"] == "engine.decode"]
    assert decode and decode[0]["attrs"]["error"].startswith("deadline")
