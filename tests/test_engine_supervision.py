"""Engine supervision chaos matrix (ISSUE 14): injected step-loop
crashes (transient / fatal / fake HBM OOM), disagg handoff crashes,
silent stalls caught by the watchdog, graceful drain with deadline
force-cancel — and the end-to-end acceptance: a mid-decode engine crash
turns into a well-formed SSE error frame + partial usage row, traffic
fails over to the remote provider behind an open breaker, and a
half-open probe brings the recovered engine back."""
from __future__ import annotations

import asyncio
import json
import statistics
import time

import jax
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import (
    EngineUnavailable,
    FaultPlan,
    GenRequest,
    InferenceEngine,
)


# Whole contexts and the window's page ring (pages of 8, 7 a slot of 8):
# a restart rebuilds the pool and its tables, a drain and a park give the
# pages back, whichever kind of cache group holds them. Disaggregation
# refuses a ring at build, so the handoff case stays on one kind.
KINDS = ("tiny-test", "tiny-mistral-test")


def _cfg(**kw):
    base = dict(preset="tiny-test", max_batch_size=2, max_seq_len=64,
                prefill_chunk=16, dtype="float32", decode_burst=2,
                kv_page_size=8)
    base.update(kw)
    return LocalEngineConfig(**base)


@pytest.fixture(scope="module", autouse=True)
def _first_builds_off_the_loop():
    """A preset's first build compiles its init program: seconds, more on
    a shared CPU, and inside an async case that is the loop standing
    still, which the sanitizer fails at five. Once each, here."""
    for preset in KINDS:
        InferenceEngine(_cfg(preset=preset), devices=[jax.devices("cpu")[0]])


@pytest.fixture
def mk(build_engine):
    """``mk(**kw) -> InferenceEngine``, stopped when the case ends and then
    held to every page and slot free (``assert_all_free``)."""
    return lambda **kw: build_engine(_cfg(**kw),
                                     devices=[jax.devices("cpu")[0]])


async def _submit(eng, prompt_ids=(1, 2, 3), max_tokens=16) -> GenRequest:
    req = GenRequest(prompt_ids=list(prompt_ids), max_tokens=max_tokens)
    await eng.submit(req)
    return req


async def _drain_stream(eng, req):
    deltas = []
    async for d in eng.stream(req):
        deltas.append(d)
    return deltas


async def _wait_for(predicate, timeout_s=10.0, msg="condition"):
    t0 = time.monotonic()
    while not predicate():
        if time.monotonic() - t0 > timeout_s:
            raise AssertionError(f"timed out waiting for {msg}")
        await asyncio.sleep(0.01)


def _supervisor_flight_states(eng):
    return [(r.get("state"), r.get("reason")) for r in eng.flight.snapshot()
            if r["kind"] == "supervisor"]


# -- crash recovery -----------------------------------------------------------

@pytest.mark.parametrize("preset", KINDS)
async def test_transient_step_fault_restarts_and_serves(mk, preset):
    """A mid-decode transient crash flushes the in-flight stream with an
    in-band error delta, then the supervisor rebuilds state and the
    engine serves again — with the observability plane (HBM ledger,
    flight ring) surviving the restart."""
    eng = mk(preset=preset,
             supervisor={"backoff_ms": 20.0, "max_restarts": 5})
    eng.fault_plan = FaultPlan(fail_step_after=2)
    req = await _submit(eng, max_tokens=32)
    ledger_before = eng.ledger
    deltas = await _drain_stream(eng, req)
    assert deltas[-1].error is not None
    assert "injected step fault" in deltas[-1].error
    eng.fault_plan = None            # let the restarted loop live

    await _wait_for(lambda: eng.supervisor.state == "serving",
                    msg="supervised restart")
    s = eng.stats()
    assert s["supervisor_restarts_total"] >= 1
    assert s["supervisor_last_failure_kind"] == "transient"
    # Restart-recovery gap (ISSUE 14 satellite): the ledger was
    # rebuilt against the new device buffers, not left tracking
    # ghosts of the donated pre-crash cache.
    assert eng.ledger is not ledger_before
    assert eng.ledger.snapshot() is not None
    # The incident is visible on the flight ring: a restarting
    # instant carrying the classified failure as its reason, then
    # the serving edge that closed it.
    states = _supervisor_flight_states(eng)
    assert ("restarting", "transient: RuntimeError: injected step "
            "fault") in states
    assert any(st == "serving" and "restart complete" in r
               for st, r in states)

    req2 = await _submit(eng)
    deltas = await _drain_stream(eng, req2)
    assert req2.finish_reason is not None and deltas[-1].error is None


@pytest.mark.parametrize("preset", KINDS)
async def test_a_crash_leaves_no_admit_without_its_finish(mk, preset):
    """Two requests in flight when the step loop dies: both streams end
    in an error delta, the supervisor ends ``serving``, the engine serves
    again, and over the whole incident the flight ring counts a finish
    for every admit — the crashed requests' included."""
    eng = mk(preset=preset,
             supervisor={"backoff_ms": 20.0, "max_restarts": 5})
    eng.fault_plan = FaultPlan(fail_step_after=2)
    reqs = [await _submit(eng, max_tokens=32) for _ in range(2)]
    ends = [(await _drain_stream(eng, r))[-1] for r in reqs]
    assert all(d.error is not None for d in ends)
    eng.fault_plan = None
    await _wait_for(lambda: eng.supervisor.state == "serving",
                    msg="supervised restart")
    after = await _submit(eng)
    assert (await _drain_stream(eng, after))[-1].error is None
    assert after.finish_reason is not None
    assert eng.supervisor.state == "serving"
    fs = eng.flight.stats()
    assert fs["flight_admits"] == fs["flight_finishes"] == 3


async def test_fake_hbm_oom_is_classified_transient(mk):
    """XLA's RESOURCE_EXHAUSTED (HBM OOM) shape restarts rather than
    parking the engine: fragmentation events are recoverable by a pool
    rebuild."""
    eng = mk(supervisor={"backoff_ms": 10.0})
    eng.fault_plan = FaultPlan(
        fail_step_after=1,
        fail_step_msg="RESOURCE_EXHAUSTED: out of memory while trying "
                      "to allocate 262144 bytes")
    req = await _submit(eng)
    deltas = await _drain_stream(eng, req)
    assert "RESOURCE_EXHAUSTED" in deltas[-1].error
    eng.fault_plan = None
    await _wait_for(lambda: eng.supervisor.state == "serving",
                    msg="restart after fake OOM")
    assert eng.stats()["supervisor_last_failure_kind"] == "transient"


@pytest.mark.parametrize("preset", KINDS)
async def test_fatal_fault_parks_failed_until_admin_stop(mk, preset):
    """A fatal (config/programming) fault must NOT restart-loop: the
    engine parks in `failed`, admissions raise EngineUnavailable (the
    router fails over), and only an explicit administrative stop()
    un-parks it."""
    eng = mk(preset=preset)
    eng.fault_plan = FaultPlan(fail_step_after=0, fail_step_fatal=True,
                               fail_step_msg="bad lowering shape")
    req = await _submit(eng)
    deltas = await _drain_stream(eng, req)
    assert deltas[-1].error is not None
    await _wait_for(lambda: eng.supervisor.state == "failed",
                    msg="fatal park")
    s = eng.stats()
    assert s["supervisor_last_failure_kind"] == "fatal"
    assert s["supervisor_restarts_total"] == 0      # no restart burned
    with pytest.raises(EngineUnavailable):
        await _submit(eng)
    with pytest.raises(EngineUnavailable):
        await eng.start()

    # Recovery is an explicit operator decision, not automatic.
    eng.fault_plan = None
    await eng.stop()
    assert eng.supervisor.state == "stopped"
    req2 = await _submit(eng)
    deltas = await _drain_stream(eng, req2)
    assert req2.finish_reason is not None and deltas[-1].error is None


@pytest.mark.parametrize("preset", KINDS)
async def test_restart_budget_exhaustion_parks_failed(mk, preset):
    """A fault that survives the restart burns the bounded budget and
    then parks — supervised restarts never loop forever."""
    eng = mk(preset=preset,
             supervisor={"max_restarts": 2, "backoff_ms": 1.0})
    eng.fault_plan = FaultPlan(fail_step_after=0)    # every step fails
    req = await _submit(eng)
    deltas = await _drain_stream(eng, req)
    assert deltas[-1].error is not None
    await _wait_for(lambda: eng.supervisor.state == "failed",
                    msg="budget exhaustion")
    s = eng.stats()
    assert s["supervisor_restarts_total"] == 2
    assert "budget exhausted" in [
        r for st, r in _supervisor_flight_states(eng)
        if st == "failed"][-1]
    with pytest.raises(EngineUnavailable):
        await _submit(eng)


async def test_handoff_fault_on_disagg_engine_recovers(mk):
    """Crash DURING the prefill→decode KV handoff on a disaggregated
    engine: the in-flight request errors, the rebuilt pool passes the
    allocator invariants, and the engine serves again."""
    eng = mk(kv_layout="paged", kv_page_size=16, max_batch_size=4,
              max_seq_len=128, prefill_chunk=32,
              disaggregation={"enabled": True, "prefill_slots": 1},
              supervisor={"backoff_ms": 10.0})
    eng.fault_plan = FaultPlan(fail_handoff_after=0)
    req = await _submit(eng, prompt_ids=list(range(1, 20)))
    deltas = await _drain_stream(eng, req)
    assert "injected handoff fault" in deltas[-1].error
    eng.fault_plan = None
    await _wait_for(lambda: eng.supervisor.state == "serving",
                    msg="restart after handoff crash")
    req2 = await _submit(eng, prompt_ids=list(range(1, 20)))
    deltas = await _drain_stream(eng, req2)
    assert req2.finish_reason is not None and deltas[-1].error is None
    eng._prefix_cache.check_invariants()


# -- watchdog -----------------------------------------------------------------

@pytest.mark.parametrize("preset", KINDS)
async def test_watchdog_recovers_silent_stall(mk, preset):
    """A silent loop stall (the loop is alive but stops stepping while
    work is pending) is the failure only the watchdog can see: it kills
    the loop, the queued request survives the supervised restart, and
    the stall is recorded as the failure kind."""
    # Watchdog starts far above the first-request XLA compile time (a
    # cold compile is a legitimately long step, not a stall — production
    # guidance is watchdog_ms >> worst-case step), then tightens once
    # the programs are warm. 2 s (vs the 30 s stall) still leaves
    # headroom over post-restart recompiles: _rebuild_state's fresh
    # buffers can re-trigger ~1 s XLA compiles on the first steps, and a
    # deadline under that reads a legitimately slow step as a stall.
    eng = mk(preset=preset, supervisor={"watchdog_ms": 60000.0, "backoff_ms": 5.0,
                          "max_restarts": 20})
    warm = await _submit(eng, max_tokens=2)
    await _drain_stream(eng, warm)
    eng.supervisor.watchdog_ms = 2000.0
    eng.fault_plan = FaultPlan(stall_step_after=0, stall_s=30.0)
    req = await _submit(eng, max_tokens=4)
    await _wait_for(
        lambda: eng.stats()["supervisor_restarts_total"] >= 1,
        msg="watchdog restart")
    eng.fault_plan = None
    # The queued-but-unstarted request was NOT errored: it stays
    # queued across the transient restart and completes.
    deltas = await _drain_stream(eng, req)
    assert deltas[-1].error is None
    assert req.finish_reason is not None
    s = eng.stats()
    assert s["supervisor_last_failure_kind"] == "stall"
    assert "stalled" in s["supervisor_last_failure"]


async def test_idle_engine_never_trips_watchdog(mk):
    """An engine parked on its work event past the watchdog deadline is
    idle, not stalled."""
    eng = mk(supervisor={"watchdog_ms": 60000.0})
    req = await _submit(eng, max_tokens=2)
    await _drain_stream(eng, req)    # compile warm, queue empty
    eng.supervisor.watchdog_ms = 100.0
    await asyncio.sleep(0.6)         # several deadlines of pure idle
    s = eng.stats()
    assert s["supervisor_state"] == "serving"
    assert s["supervisor_restarts_total"] == 0


# -- graceful drain -----------------------------------------------------------

@pytest.mark.parametrize("preset", KINDS)
async def test_drain_restart_finishes_inflight_then_serves(mk, preset):
    eng = mk(preset=preset)
    req = await _submit(eng, max_tokens=6)
    task = asyncio.get_running_loop().create_task(
        eng.drain(restart=True))
    await asyncio.sleep(0)           # drain enters "draining"
    with pytest.raises(EngineUnavailable, match="draining"):
        await _submit(eng)
    summary = await task
    assert summary["forced_cancel"] == 0 and summary["restarted"]
    # The in-flight request finished normally under the deadline.
    deltas = await _drain_stream(eng, req)
    assert deltas[-1].error is None and req.finish_reason is not None
    assert eng.supervisor.state == "serving"
    req2 = await _submit(eng)
    await _drain_stream(eng, req2)
    assert req2.finish_reason is not None


@pytest.mark.parametrize("preset", KINDS)
async def test_drain_deadline_expiry_force_cancels(mk, preset):
    """Past the drain deadline, stragglers are force-cancelled through
    the normal scheduler path (finish_reason `cancelled`) and the engine
    stops."""
    eng = mk(preset=preset)
    eng.fault_plan = FaultPlan(slow_decode_s=0.05)
    req = await _submit(eng, max_tokens=50)
    await asyncio.sleep(0.1)         # let it get admitted + decoding
    summary = await eng.drain(deadline_s=0.05)
    assert summary["forced_cancel"] >= 1
    assert summary["restarted"] is False
    assert eng.supervisor.state == "stopped"
    deltas = await _drain_stream(eng, req)
    terminal = deltas[-1]
    assert (terminal.finish_reason == "cancelled"
            or terminal.error is not None)


# -- failover: breaker-skip latency ------------------------------------------

async def test_engine_down_breaker_opens_then_fast_skip(tmp_path):
    """Acceptance (failover half): EngineUnavailable maps to a breaker-
    countable 503, the breaker opens, and from then on the dead local
    provider adds < 5 ms p50 while the backup serves."""
    from llmapigateway_tpu.providers.local import LocalProvider
    from tests.test_chaos import (
        FakeClock, ScriptedProvider, StubRegistry, chaos_router,
        observer_factory)

    class _StubTok:
        bos_id = None

        def apply_chat_template(self, messages, add_generation_prompt=True):
            return "x"

        def encode(self, text):
            return [1]

    class DownEngine:
        class cfg:
            max_tokens_default = 8

        tokenizer = _StubTok()

        async def submit(self, req):
            raise EngineUnavailable("engine is restarting",
                                    retry_after_s=0.4)

    clock = FakeClock()
    local = LocalProvider("deadup", DownEngine())
    backup = ScriptedProvider("backup")
    router = chaos_router(tmp_path, {"deadup": local, "backup": backup},
                          clock)
    # min_requests=2 (PROVIDERS_FAST_BREAKER): two engine_down 503s open.
    for _ in range(2):
        out = await router.dispatch({"model": "gw/chain", "messages": []},
                                    "k", observer_factory)
        assert out.provider == "backup"
    timings = []
    for _ in range(11):
        t0 = time.perf_counter()
        out = await router.dispatch({"model": "gw/chain", "messages": []},
                                    "k", observer_factory)
        timings.append(time.perf_counter() - t0)
        assert out.provider == "backup"
    assert statistics.median(timings) < 0.005
    assert "circuit open" in " ".join(out.errors)


# -- end-to-end acceptance ----------------------------------------------------

class SupervisedGateway:
    """Full-server harness: a disaggregated local engine with supervision
    knobs + a remote backup upstream, with the engine instance exposed
    for fault injection."""

    def __init__(self, tmp_path):
        self.tmp_path = tmp_path
        self.engines = {}

    def _factory(self, name, details):
        from llmapigateway_tpu.providers.local import LocalProvider
        if name not in self.engines:
            self.engines[name] = InferenceEngine(
                details.engine, devices=[jax.devices("cpu")[0]])
        return LocalProvider(name, self.engines[name])

    async def __aenter__(self):
        from llmapigateway_tpu.config.loader import ConfigLoader
        from llmapigateway_tpu.config.settings import Settings
        from llmapigateway_tpu.server.app import GatewayApp, build_app
        from tests.fake_upstream import FakeUpstream

        self.upstream = FakeUpstream()
        self.upstream_server = TestServer(self.upstream.app)
        await self.upstream_server.start_server()
        providers = [
            {"tpu": {"type": "local",
                     "breaker": {"min_requests": 1, "window_s": 60,
                                 "failure_threshold": 0.2,
                                 "cooldown_s": 0.3},
                     "engine": {"preset": "tiny-test", "dtype": "float32",
                                "kv_layout": "paged", "kv_page_size": 16,
                                "max_batch_size": 4, "max_seq_len": 128,
                                "prefill_chunk": 32,
                                "max_tokens_default": 8,
                                "disaggregation": {"enabled": True,
                                                   "prefill_slots": 1},
                                "supervisor": {"max_restarts": 2,
                                               "backoff_ms": 5.0}}}},
            {"backup": {"baseUrl": f"http://{self.upstream_server.host}:"
                                   f"{self.upstream_server.port}/v1",
                        "apikey": "BK"}}]
        rules = [{"gateway_model_name": "gw/local-model",
                  "fallback_models": [{"provider": "tpu",
                                       "model": "tiny-test"},
                                      {"provider": "backup",
                                       "model": "real-b"}]}]
        (self.tmp_path / "providers.json").write_text(json.dumps(providers))
        (self.tmp_path / "models_fallback_rules.json").write_text(
            json.dumps(rules))
        settings = Settings(fallback_provider="tpu", base_dir=self.tmp_path,
                            config_dir=self.tmp_path,
                            db_dir=self.tmp_path / "db",
                            logs_dir=self.tmp_path / "logs")
        loader = ConfigLoader(self.tmp_path, fallback_provider=None)
        self.gw = GatewayApp(settings, loader, local_factory=self._factory)
        app = build_app(settings, loader, gateway=self.gw)
        self.client = TestClient(TestServer(app))
        await self.client.start_server()
        return self

    async def __aexit__(self, *exc):
        for eng in self.engines.values():
            eng.fault_plan = None
            await eng.stop()
        await self.client.close()
        await self.upstream_server.close()

    @property
    def engine(self) -> InferenceEngine:
        return self.engines["tpu"]

    async def chat(self, **extra):
        return await self.client.post("/v1/chat/completions", json={
            "model": "gw/local-model", "max_tokens": 4, "temperature": 0,
            "messages": [{"role": "user", "content": "hello"}], **extra})

    async def sse_frames(self, resp):
        async for line in resp.content:
            line = line.decode().strip()
            if line.startswith("data: "):
                yield line[len("data: "):]


class _LetterTokenizer:
    """A letter an id. Random weights emit ids the byte tokenizer drops or
    holds back as unfinished UTF-8, and a stream that has sent no text is
    not committed: it would fail over whole, with no frame to inspect."""

    def __init__(self, inner):
        self._inner = inner

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def decode(self, ids):
        return "".join(chr(97 + i % 26) for i in ids)


async def test_acceptance_crash_failover_and_halfopen_recovery(tmp_path,
                                                               all_free):
    """The ISSUE 14 acceptance chain, end to end on a disaggregated
    engine: step-loop crash mid-decode → in-band SSE error frame +
    partial usage row; engine parks (budget exhausted) → next requests
    served by the remote fallback behind an opening breaker; operator
    recovery + half-open probe → local serving again with clean
    allocator invariants and zero leaked flight admit/finish pairs."""
    async with SupervisedGateway(tmp_path) as g:
        # Phase A: warm-up — the local engine serves.
        resp = await g.chat()
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["message"]["content"] != "Hello world!"
        eng = g.engine

        # Phase B: crash mid-decode while a stream is on the wire — at the
        # scheduler's next step once the first frame has arrived (bursts
        # slowed so the answer is still being decoded then; by a step
        # count alone the crash can land before the gateway has committed
        # the stream, and the request fails over whole). The fault keeps
        # firing through both budgeted restarts, so the engine
        # deterministically parks in `failed`.
        eng.tokenizer = _LetterTokenizer(eng.tokenizer)
        plan = eng.fault_plan = FaultPlan(slow_decode_s=0.05)
        resp = await g.chat(stream=True, max_tokens=64)
        assert resp.status == 200
        frames = []
        async for frame in g.sse_frames(resp):
            if not frames:
                plan.fail_step_after = plan.step_calls
            frames.append(frame)
        assert len(frames) > 1           # committed before the crash
        err = json.loads(frames[-1])     # an error frame ends the stream
        assert "error" in err            # well-formed in-band error frame
        assert err["error"]["provider"] == "tpu"
        assert "engine failure" in err["error"]["message"]

        t0 = time.monotonic()
        while eng.supervisor.state != "failed":
            assert time.monotonic() - t0 < 10, "engine never parked"
            await asyncio.sleep(0.01)

        # Partial usage for the interrupted stream was persisted through
        # the write-behind recorder (flush forces durability NOW).
        await asyncio.to_thread(g.gw.usage_recorder.flush)
        resp = await g.client.get("/v1/api/usage-records")
        records = (await resp.json())["records"]
        tpu_rows = [r for r in records if r["provider"] == "tpu"]
        assert len(tpu_rows) == 2        # warm-up + the partial stream
        partial = max(tpu_rows, key=lambda r: r["id"])
        assert 0 <= partial["completion_tokens"] < 64

        # Phase C: the engine is down — requests fail over to the remote
        # backup with no hang, and the 503s open the local breaker.
        for _ in range(2):
            resp = await g.chat()
            assert resp.status == 200
            body = await resp.json()
            assert body["choices"][0]["message"]["content"] == "Hello world!"
        resp = await g.client.get("/v1/api/health/providers")
        health = (await resp.json())["providers"]
        assert health["tpu"]["state"] == "open"
        assert health["tpu"]["supervisor"]["supervisor_state"] == "failed"
        assert health["tpu"]["supervisor"]["supervisor_last_failure_kind"] \
            in ("transient", "stall")
        backup_calls_before = len(g.upstream.requests)
        resp = await g.chat()            # breaker-skip: straight to backup
        assert (await resp.json())["choices"][0]["message"]["content"] \
            == "Hello world!"
        assert len(g.upstream.requests) == backup_calls_before + 1

        # Phase D: operator recovery (clear the fault, un-park), breaker
        # cooldown elapses, the half-open probe serves locally and
        # closes the breaker.
        eng.fault_plan = None
        await eng.stop()
        assert eng.supervisor.state == "stopped"
        await asyncio.sleep(0.35)        # cooldown_s=0.3 elapses
        resp = await g.chat()
        assert resp.status == 200
        body = await resp.json()
        assert body["choices"][0]["message"]["content"] != "Hello world!"
        assert eng.supervisor.state == "serving"
        resp = await g.client.get("/v1/api/health/providers")
        health = (await resp.json())["providers"]
        assert health["tpu"]["state"] == "closed"
        assert health["tpu"]["supervisor"]["supervisor_state"] == "serving"

        # Invariants: no leaked pages, no leaked flight admit/finish
        # pairs across the whole incident.
        fs = eng.flight.stats()
        assert fs["flight_admits"] == fs["flight_finishes"]
    all_free(eng)
