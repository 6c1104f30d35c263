"""Device observability plane (ISSUE 8): HBM ledger units + live-engine
reconciliation on the CPU backend, kernel cost-registry units (incl. the
flight-ring join over fake records and real cost_analysis numbers), the
watermark shed chaos bar (429 + numeric Retry-After, zero leaked
admits), the XLA compile monitor, and the hardened profiler capture
endpoint (single-flight 409, bounded retention, flight stamping)."""
import asyncio
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import (EngineOverloaded, GenRequest,
                                             InferenceEngine)
from llmapigateway_tpu.obs import device as dev


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# -- HbmLedger units ----------------------------------------------------------

def test_ledger_static_components_and_snapshot():
    led = dev.HbmLedger(weights=1000, kv_pool=500, aux=50, spec=25,
                        page_bytes=10, tracked_fn=lambda: 1575,
                        mem_fn=lambda: None)
    assert led.static_total == 1575
    snap = led.snapshot(prefix_resident_pages=3)
    assert snap["hbm_weights_bytes"] == 1000
    assert snap["hbm_kv_pool_bytes"] == 500
    assert snap["hbm_aux_bytes"] == 50
    assert snap["hbm_spec_bytes"] == 25
    assert snap["hbm_ledger_bytes"] == 1575
    assert snap["hbm_tracked_bytes"] == 1575
    assert snap["hbm_prefix_resident_bytes"] == 30
    # No allocator stats (CPU): no device_* keys, headroom unreported.
    assert "hbm_device_in_use_bytes" not in snap
    assert "hbm_headroom_ratio" not in snap
    assert led.headroom_fraction() is None


def test_ledger_device_memory_ttl_cache_and_headroom():
    clock = FakeClock()
    calls = []

    def mem():
        calls.append(1)
        return {"bytes_in_use": 750, "peak_bytes": 900, "bytes_limit": 1000}

    led = dev.HbmLedger(weights=1, kv_pool=1, mem_fn=mem, mem_ttl_s=0.5,
                        clock=clock)
    assert led.headroom_fraction() == pytest.approx(0.25)
    assert led.headroom_fraction() == pytest.approx(0.25)
    assert len(calls) == 1                    # TTL-cached
    clock.advance(1.0)
    led.headroom_fraction()
    assert len(calls) == 2                    # TTL expired -> re-probed
    snap = led.snapshot()
    assert snap["hbm_device_in_use_bytes"] == 750
    assert snap["hbm_device_peak_bytes"] == 900
    assert snap["hbm_device_limit_bytes"] == 1000
    assert snap["hbm_headroom_ratio"] == pytest.approx(0.25)


def test_ledger_mem_fn_failure_never_raises():
    def boom():
        raise RuntimeError("allocator probe died")
    led = dev.HbmLedger(weights=1, kv_pool=1, mem_fn=boom)
    assert led.device_memory() is None
    assert led.headroom_fraction() is None
    assert "hbm_device_in_use_bytes" not in led.snapshot()


def test_device_memory_stats_is_none_on_cpu():
    # The CPU backend exposes no allocator stats — the ledger must say
    # so (None) rather than fabricate zeros the watermark would act on.
    assert dev.device_memory_stats(jax.devices("cpu")) is None


# -- KernelRegistry units -----------------------------------------------------

def _fake_flight(depth=4, walls=(40.0, 44.0)):
    """STEP records as obs/flight.py snapshot() renders them."""
    recs = [{"kind": "step", "step_kind": "decode", "burst_depth": depth,
             "decode_wall_ms": w, "t": 1.0 + i} for i, w in enumerate(walls)]
    recs.append({"kind": "step", "step_kind": "spec", "burst_depth": 2,
                 "decode_wall_ms": 30.0, "t": 9.0})
    recs.append({"kind": "admit", "slot": 0, "t": 0.5})
    return recs


def test_registry_counts_walls_and_flight_join():
    reg = dev.KernelRegistry()
    assert reg.needs("decode.d4.greedy")
    reg.register("decode.d4.greedy", "decode",
                 variant={"depth": 4, "greedy": True})
    assert not reg.needs("decode.d4.greedy")
    reg.register("decode.d4.greedy", "decode")     # idempotent
    reg.register("prefill.b32.k1", "prefill",
                 variant={"bucket": 32, "k": 1})
    reg.record("decode.d4.greedy", steps=4, wall_ms=40.0)
    reg.record("decode.d4.greedy", steps=4)        # transition: no wall
    reg.record("prefill.b32.k1", wall_ms=12.0)
    rows = {r["kernel"]: r for r in reg.table(
        bytes_per_step_fn=lambda kind: 1_000_000 if kind == "decode"
        else None,
        peak_gbps=1.0, flight=_fake_flight())}
    d = rows["decode.d4.greedy"]
    assert d["calls"] == 2 and d["steps"] == 8
    # Flight join wins the step-time estimate: (40+44)/(4+4) = 10.5 ms.
    assert d["flight_steps"] == 8
    assert d["step_ms"] == pytest.approx(10.5)
    assert d["hbm_bytes_per_step"] == 1_000_000
    # 1 MB / 10.5 ms ≈ 0.095 GB/s; peak 1 GB/s.
    assert d["achieved_gbps"] == pytest.approx(0.095, abs=5e-3)
    assert d["roofline_fraction"] == pytest.approx(0.095, abs=5e-3)
    p = rows["prefill.b32.k1"]
    assert p["calls"] == 1 and p["step_ms"] == pytest.approx(12.0)
    # Shares computed over effective walls; ranking worst-first works.
    assert d["pct_of_step_time"] > p["pct_of_step_time"]
    assert dev.worst_kernel(list(rows.values())) == "decode.d4.greedy"


def test_registry_record_on_unknown_kernel_autoregisters():
    reg = dev.KernelRegistry()
    reg.record("mystery", steps=2, wall_ms=1.0)
    (row,) = reg.table()
    assert row["kernel"] == "mystery" and row["kind"] == "unknown"


def test_registry_cost_resolution_real_jit_and_failure():
    reg = dev.KernelRegistry()
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))

    def cost():
        return f.lower(x).compile().cost_analysis()

    reg.register("matmul", "decode", variant={"depth": 1}, cost_fn=cost)

    def bad():
        raise RuntimeError("no cost analysis on this backend")
    reg.register("broken", "decode", cost_fn=bad)
    reg.resolve_costs()                        # synchronous drain
    assert reg.costs_pending() == 0
    rows = {r["kernel"]: r for r in reg.table()}
    assert rows["matmul"]["xla_flops_per_call"] > 0
    assert rows["matmul"]["xla_bytes_per_call"] > 0
    assert "xla_flops_per_call" not in rows["broken"]
    # Without an engine bytes model, the XLA bytes back-fill per-step.
    reg.record("matmul", steps=1, wall_ms=1.0)
    row = next(r for r in reg.table() if r["kernel"] == "matmul")
    assert row["hbm_bytes_per_step"] == int(row["xla_bytes_per_call"])


def test_worst_kernel_prefers_meaningful_share():
    rows = [
        {"kernel": "big", "roofline_fraction": 0.5,
         "pct_of_step_time": 90.0},
        {"kernel": "tiny-awful", "roofline_fraction": 0.01,
         "pct_of_step_time": 1.0},
    ]
    # The 1%-of-step-time kernel is not the next target; the 90% one is.
    assert dev.worst_kernel(rows) == "big"
    # Unless nothing clears the share floor.
    assert dev.worst_kernel(rows, min_share_pct=95.0) == "tiny-awful"
    assert dev.worst_kernel([]) is None


# -- phase tags + compile monitor --------------------------------------------

def test_phase_tag_nesting_and_restore():
    assert dev.current_phase() == ""
    with dev.phase("decode", annotate=False):
        assert dev.current_phase() == "decode"
        with dev.phase("spec.verify", annotate=False):
            assert dev.current_phase() == "spec.verify"
        assert dev.current_phase() == "decode"
    assert dev.current_phase() == ""


def test_compile_monitor_counts_by_phase():
    mon = dev.install_compile_monitor()
    before = mon.stats()["xla_compile_total"]
    # A never-before-seen shape forces a fresh backend compile.
    side = int(time.time() * 1000) % 400 + 13
    with dev.phase("decode", annotate=False):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones((side, 3))).block_until_ready()
    stats = mon.stats()
    assert stats["xla_compile_total"] > before
    assert stats["xla_compile_by_phase"]["decode"]["count"] >= 1
    assert stats["xla_compile_by_phase"]["decode"]["seconds"] > 0
    assert stats["xla_compile_last"]["phase"] in ("decode", "startup")
    # Installing again must not double-count (listener is once-only).
    dev.install_compile_monitor()
    b2 = mon.stats()["xla_compile_total"]
    side2 = side + 1000
    jax.jit(lambda x: x * 3 + 1)(jnp.ones((side2, 3))).block_until_ready()
    a2 = mon.stats()["xla_compile_total"]
    assert a2 - b2 <= 2        # one compile event, not two per listener


# -- live engine: ledger reconciliation + kernel table ------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=2,
                            max_seq_len=128, prefill_chunk=32,
                            dtype="float32", decode_burst=4,
                            kv_page_size=16, hbm_peak_gbps=1.0,
                            prewarm_sampler_variants=False)
    return InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])


async def _run_one(engine, prompt, max_tokens=6, rid=""):
    req = GenRequest(prompt_ids=list(prompt), max_tokens=max_tokens,
                     temperature=0.0, request_id=rid)
    await engine.submit(req)
    async for _ in engine.stream(req):
        pass
    return req


def test_ledger_reconciles_with_live_buffers(engine):
    """Acceptance: the geometry-derived static accounting matches what
    the engine's device buffers actually occupy, tolerance-banded (the
    tiny per-slot mirrors and rng key live inside the band). On this
    backend memory_stats() is None, so `tracked` is the live side; on
    TPU the same snapshot carries the allocator's bytes_in_use too."""
    s = engine.stats()
    static = s["hbm_ledger_bytes"]
    tracked = s["hbm_tracked_bytes"]
    assert static > 0 and tracked > 0
    assert abs(static - tracked) <= max(0.10 * tracked, 1 << 20), s
    # Components present and consistent.
    assert s["hbm_weights_bytes"] + s["hbm_kv_pool_bytes"] <= static
    assert s["hbm_weights_bytes"] == engine._resident_param_bytes()
    # KV-pool geometry: pages × page tokens × 2 sides × heads × head_dim
    # × itemsize (float32 here).
    c = engine.model_cfg
    expect_kv = (2 * c.n_layers * c.n_kv_heads * c.head_dim * 4
                 * engine.allocator.num_pages * engine.allocator.page_size)
    assert s["hbm_kv_pool_bytes"] == expect_kv


async def test_kernel_table_acceptance_two_kernels_reconcile(engine):
    """ISSUE 8 acceptance: after serving one request the per-kernel
    table has ≥2 distinct kernels, the decode rows' bytes/step agree
    with the aggregate hbm_bytes_per_step within 10%, and a worst
    kernel is named (hbm_peak_gbps is set on this engine)."""
    await _run_one(engine, range(2, 40), rid="dev-1")
    await asyncio.to_thread(engine.kernels.resolve_costs)   # it compiles
    rows = engine.kernel_table()
    assert len({r["kernel"] for r in rows}) >= 2, rows
    kinds = {r["kind"] for r in rows}
    assert "prefill" in kinds and "decode" in kinds
    agg = engine.stats()["hbm_bytes_per_step"]
    decode_rows = [r for r in rows if r["kind"] == "decode"]
    assert decode_rows
    for r in decode_rows:
        assert abs(r["hbm_bytes_per_step"] - agg) <= 0.10 * agg, (r, agg)
    # Measured walls joined from the flight ring give fractions, so the
    # worst kernel is nameable.
    from llmapigateway_tpu.obs.device import worst_kernel
    assert worst_kernel(rows) is not None
    # cost_analysis resolved for at least the prefill programs.
    assert any("xla_flops_per_call" in r for r in rows), rows


# -- watermark shed chaos -----------------------------------------------------

async def test_watermark_shed_zero_leaked_admits(engine):
    """Headroom below the watermark → EngineOverloaded at submit (the
    gateway maps it to 429 + numeric Retry-After, asserted at the HTTP
    layer below), the shed lands in the flight ring, and NO admit record
    leaks (admits == finishes before and after)."""
    fl = engine.flight.stats()
    assert fl["flight_admits"] == fl["flight_finishes"]
    sheds0 = fl["flight_sheds"]
    engine.cfg.hbm_headroom_watermark = 0.10
    old_mem, engine.ledger.mem_fn = engine.ledger.mem_fn, (
        lambda: {"bytes_in_use": 95, "peak_bytes": 99, "bytes_limit": 100})
    engine.ledger._mem_stamp = float("-inf")       # drop the TTL cache
    try:
        req = GenRequest(prompt_ids=[2, 3, 4], max_tokens=4,
                         request_id="wm-1")
        with pytest.raises(EngineOverloaded, match="watermark"):
            await engine.submit(req)
        assert engine.retry_after_hint_s() >= 1.0   # numeric hint exists
        s = engine.stats()
        assert s["watermark_sheds"] >= 1
        assert s["shed_total"] >= 1
        fl = engine.flight.stats()
        assert fl["flight_sheds"] == sheds0 + 1
        assert fl["flight_admits"] == fl["flight_finishes"]
        shed = [r for r in engine.flight.snapshot()
                if r["kind"] == "shed" and r.get("request_id") == "wm-1"]
        assert shed, "watermark shed must land in the flight ring"
    finally:
        engine.cfg.hbm_headroom_watermark = 0.0
        engine.ledger.mem_fn = old_mem
        engine.ledger._mem_stamp = float("-inf")
    # Recovered: the same request admits once pressure clears.
    req2 = await _run_one(engine, [2, 3, 4, 5], max_tokens=3, rid="wm-2")
    assert req2.finish_reason in ("stop", "length")


async def test_watermark_shed_maps_to_429_with_numeric_retry_after(
        tmp_path, engine):
    """The HTTP half of the chaos bar: a single-target chain whose local
    engine sheds on the watermark returns 429 with a numeric
    Retry-After, exactly like the queue-full path."""
    from aiohttp.test_utils import TestClient, TestServer
    from llmapigateway_tpu.config.loader import ConfigLoader
    from llmapigateway_tpu.config.settings import Settings
    from llmapigateway_tpu.providers.local import LocalProvider
    from llmapigateway_tpu.server.app import GatewayApp, build_app

    (tmp_path / "providers.json").write_text(json.dumps([
        {"tpu": {"type": "local", "engine": {"preset": "tiny-test"}}}]))
    (tmp_path / "models_fallback_rules.json").write_text(json.dumps([
        {"gateway_model_name": "gw/local", "fallback_models": [
            {"provider": "tpu", "model": "tiny-test"}]}]))
    settings = Settings(fallback_provider="tpu", base_dir=tmp_path,
                        config_dir=tmp_path, db_dir=tmp_path / "db",
                        logs_dir=tmp_path / "logs")
    loader = ConfigLoader(tmp_path, fallback_provider=None)
    gw = GatewayApp(settings, loader,
                    local_factory=lambda name, details:
                    LocalProvider(name, engine))
    app = build_app(settings, loader, gateway=gw)
    client = TestClient(TestServer(app))
    await client.start_server()
    engine.cfg.hbm_headroom_watermark = 0.10
    old_mem, engine.ledger.mem_fn = engine.ledger.mem_fn, (
        lambda: {"bytes_in_use": 95, "peak_bytes": 99, "bytes_limit": 100})
    engine.ledger._mem_stamp = float("-inf")
    try:
        resp = await client.post("/v1/chat/completions", json={
            "model": "gw/local", "messages": []})
        assert resp.status == 429
        assert float(resp.headers["Retry-After"]) >= 1.0
        body = await resp.json()
        assert "overload" in body["error"]["message"].lower()
    finally:
        engine.cfg.hbm_headroom_watermark = 0.0
        engine.ledger.mem_fn = old_mem
        engine.ledger._mem_stamp = float("-inf")
        await client.close()


# -- profiler capture hardening (server/profiler_api.py) ----------------------

class _CaptureApp:
    """Minimal gateway app over the shared module engine for the capture
    endpoint tests."""

    def __init__(self, tmp_path, engine):
        self.tmp_path = tmp_path
        self.engine = engine

    async def __aenter__(self):
        from aiohttp.test_utils import TestClient, TestServer
        from llmapigateway_tpu.config.loader import ConfigLoader
        from llmapigateway_tpu.config.settings import Settings
        from llmapigateway_tpu.providers.local import LocalProvider
        from llmapigateway_tpu.server.app import GatewayApp, build_app

        (self.tmp_path / "providers.json").write_text(json.dumps([
            {"tpu": {"type": "local",
                     "engine": {"preset": "tiny-test"}}}]))
        (self.tmp_path / "models_fallback_rules.json").write_text(
            json.dumps([{"gateway_model_name": "gw/local",
                         "fallback_models": [
                             {"provider": "tpu", "model": "tiny-test"}]}]))
        settings = Settings(fallback_provider="tpu",
                            base_dir=self.tmp_path,
                            config_dir=self.tmp_path,
                            db_dir=self.tmp_path / "db",
                            logs_dir=self.tmp_path / "logs")
        loader = ConfigLoader(self.tmp_path, fallback_provider=None)
        gw = GatewayApp(settings, loader,
                        local_factory=lambda name, details:
                        LocalProvider(name, self.engine))
        app = build_app(settings, loader, gateway=gw)
        self.client = TestClient(TestServer(app))
        await self.client.start_server()
        # Instantiate the provider so _local_engines sees the engine.
        await self.client.post("/v1/chat/completions", json={
            "model": "gw/local", "messages": [],
            "max_tokens": 2})
        return self

    async def __aexit__(self, *exc):
        await self.client.close()


async def test_capture_smoke_and_flight_stamp(tmp_path, engine):
    """CPU-backend capture smoke (satellite acceptance): a short capture
    succeeds, produces a trace dir, and brackets the flight ring with
    profile start/stop records whose seqs the response reports."""
    async with _CaptureApp(tmp_path, engine) as app:
        before = engine.flight.seq
        resp = await app.client.post(
            "/v1/api/profiler/trace?duration_ms=150")
        assert resp.status == 200, await resp.text()
        body = await resp.json()
        assert (tmp_path / "logs" / "profiles").exists()
        assert body["duration_ms"] == 150
        start, stop = body["flight_seqs"]["tpu"]
        assert before <= start < stop
        profs = [r for r in engine.flight.snapshot(since=before - 1)
                 if r["kind"] == "profile"]
        phases = [p["phase"] for p in profs]
        assert phases == ["start", "stop"]
        # The capture's trace-dir name rides as the record's request id.
        assert all(p["request_id"] == Path(body["trace_dir"]).name
                   for p in profs)


async def test_capture_concurrent_second_gets_409(tmp_path, engine):
    async with _CaptureApp(tmp_path, engine) as app:
        async def go():
            r = await app.client.post(
                "/v1/api/profiler/trace?duration_ms=400")
            return r.status
        first = asyncio.ensure_future(go())
        await asyncio.sleep(0.1)              # let the capture start
        second = await app.client.post(
            "/v1/api/profiler/trace?duration_ms=100")
        assert second.status == 409
        assert (await first) == 200


async def test_capture_retention_prunes_old_dirs(tmp_path, engine):
    from llmapigateway_tpu.server import profiler_api
    profiles = tmp_path / "logs" / "profiles"
    profiles.mkdir(parents=True)
    for i in range(profiler_api.MAX_TRACE_DIRS + 3):
        (profiles / f"trace-0000-{i:02d}").mkdir()
    async with _CaptureApp(tmp_path, engine) as app:
        resp = await app.client.post(
            "/v1/api/profiler/trace?duration_ms=120")
        assert resp.status == 200
        body = await resp.json()
        assert len(body["pruned_trace_dirs"]) >= 3
        remaining = [d for d in profiles.iterdir() if d.is_dir()]
        assert len(remaining) <= profiler_api.MAX_TRACE_DIRS
        # The newest capture (this one) survived the prune.
        assert Path(body["trace_dir"]).exists()
