"""Engine fault injection (SURVEY.md §5): injected prefill/decode failures
must surface as clean error deltas (pre-commit failures → provider error →
fallback; mid-stream failures → error frame), and the engine must recover
to serve subsequent requests — since ISSUE 14 that recovery is a
supervised restart, so the follow-up request waits for the supervisor to
finish it instead of racing the backoff window."""
import asyncio
import time

import jax
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import FaultPlan, GenRequest, InferenceEngine


KINDS = ("tiny-test", "tiny-mistral-test", "tiny-smallthinker-test",
         "tiny-gigachat35-test")


@pytest.fixture(scope="module", params=KINDS)
def shared_engine(request, stop_engine):
    """Whole contexts; the window's page ring (pages of 4: 9 a slot of
    16); a family of TWO cache groups, a ring beside whole contexts; and
    one with a LATENT pool and a block of recurrent state a slot (the
    period families refuse the prefix cache): a rebuild after a fault
    must rebuild every cache group, of every kind."""
    cfg = LocalEngineConfig(preset=request.param, max_batch_size=2,
                            max_seq_len=64, prefill_chunk=8, decode_burst=2,
                            kv_page_size=4,
                            prefix_cache=request.param in KINDS[:2],
                            supervisor={"max_restarts": 10,
                                        "backoff_ms": 10.0})
    eng = InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])
    assert bool(eng._swa_ring_pages) == (request.param in KINDS[1:3])
    assert len(eng.kv_groups) == 1 + (request.param == KINDS[2])
    assert (eng.kv_groups.whole_context.kind == "latent") == (
        request.param == KINDS[3])
    yield eng
    stop_engine(eng)


async def _run(engine, prompt_ids, max_tokens=6):
    req = GenRequest(prompt_ids=prompt_ids, max_tokens=max_tokens)
    await engine.submit(req)
    deltas = []
    async for d in engine.stream(req):
        deltas.append(d)
    return req, deltas


async def _wait_recovered(engine, timeout_s=10.0):
    """Block until the supervised restart finished (submit would raise
    EngineUnavailable while the engine is still restarting)."""
    t0 = time.monotonic()
    while engine.supervisor.state not in ("serving", "stopped"):
        assert time.monotonic() - t0 < timeout_s, engine.supervisor.state
        await asyncio.sleep(0.01)


async def test_prefill_fault_yields_error_before_any_text(engine):
    engine.fault_plan = FaultPlan(fail_prefill_after=0)
    try:
        req, deltas = await _run(engine, [1, 2, 3])
        assert deltas[-1].error is not None
        assert all(not d.text for d in deltas)
    finally:
        engine.fault_plan = None
    # Engine recovered (supervised restart): next request completes.
    await _wait_recovered(engine)
    req, deltas = await _run(engine, [1, 2, 3])
    assert req.finish_reason is not None and deltas[-1].error is None


async def test_decode_fault_midstream_emits_error_and_recovers(engine):
    engine.fault_plan = FaultPlan(fail_decode_after=1)
    try:
        req, deltas = await _run(engine, [4, 5, 6], max_tokens=16)
        assert deltas[-1].error is not None
    finally:
        engine.fault_plan = None
    await _wait_recovered(engine)
    req, deltas = await _run(engine, [4, 5, 6])
    assert req.finish_reason is not None and deltas[-1].error is None


async def test_slow_decode_still_completes(engine):
    engine.fault_plan = FaultPlan(slow_decode_s=0.05)
    try:
        req, _ = await _run(engine, [7, 8], max_tokens=3)
        assert req.finish_reason is not None
    finally:
        engine.fault_plan = None
