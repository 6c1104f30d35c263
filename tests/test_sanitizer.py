"""Runtime asyncio sanitizer (graftlint v2 dynamic half): stall detector
on fake clocks and a real loop, guarded-field tracking (lock + loop-owner
+ rebind + delegate proxies), leak detectors, and the integration check
that the session-wide sanitizer from tests/conftest.py is actually live
while a real engine decodes.

Deliberately-broken fixtures (blocking sleep inside a coroutine;
unguarded mutation of a guarded field from a thread) use PRIVATE detector
instances — the session sanitizer's violation list must stay empty or the
suite gate fails, which is the point of the gate."""
from __future__ import annotations

import asyncio
import threading
import time

import pytest

from llmapigateway_tpu.analysis.sanitizer import (
    AsyncioSanitizer,
    GuardTracker,
    GuardedDict,
    GuardedList,
    StallDetector,
    Violation,
    _CheckedDelegate,
    guard_map_for,
    leaked_spans,
    leaked_tasks,
)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# -- stall detector -----------------------------------------------------------

def test_stall_detector_fake_clock_threshold():
    clock = FakeClock()
    det = StallDetector(threshold_s=0.1, clock=clock, watchdog=False)
    det.timed_call(lambda: clock.advance(0.05), describe="fast step")
    assert det.violations == []
    det.timed_call(lambda: clock.advance(0.25), describe="slow step")
    assert len(det.violations) == 1
    v = det.violations[0]
    assert v.kind == "stall"
    assert "250.0 ms" in v.message and "slow step" in v.message

    with det.pause():
        det.timed_call(lambda: clock.advance(0.5), describe="paused")
    assert len(det.violations) == 1     # paused sections don't count


def test_stall_detector_catches_blocking_sleep_in_coroutine():
    """The deliberately-broken fixture from the acceptance criteria: a
    blocking time.sleep inside a coroutine step on a real loop."""
    det = StallDetector(threshold_s=0.05)
    det.install()
    try:
        async def broken():
            time.sleep(0.12)            # blocks the loop — the bug class
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(broken())
        finally:
            loop.close()
    finally:
        det.uninstall()
    stalls = [v for v in det.violations if v.kind == "stall"]
    assert stalls, "blocking sleep inside a coroutine must be detected"
    assert any("event-loop callback ran" in v.message for v in stalls)


def test_stall_watchdog_samples_the_blocking_stack():
    det = StallDetector(threshold_s=0.05)
    det.install()
    try:
        async def broken():
            time.sleep(0.3)             # long enough for a watchdog poll
        loop = asyncio.new_event_loop()
        try:
            loop.run_until_complete(broken())
        finally:
            loop.close()
    finally:
        det.uninstall()
    assert any("time.sleep" in v.stack for v in det.violations), \
        "mid-stall stack sample should show the blocking site"


# -- guarded-field tracker ----------------------------------------------------

class Svc:
    """Toy service mirroring the engine/db guard shapes."""

    def __init__(self):
        self._lock = threading.Lock()
        self._table = {}
        self._names = []
        self._jobs = []
        self._head = None


SVC_GUARDS = {"_table": "_lock", "_names": "_lock",
              "_jobs": "loop", "_head": "loop"}


def test_lock_guard_mutations_checked_through_proxies():
    tr = GuardTracker()
    svc = tr.track(Svc(), guards=SVC_GUARDS)
    assert isinstance(svc._table, GuardedDict)
    assert isinstance(svc._names, GuardedList)

    with svc._lock:
        svc._table["a"] = 1             # under the lock: clean
        svc._names.append("x")
    assert tr.violations == []

    svc._table["b"] = 2                 # without the lock: violation
    svc._names.append("y")
    kinds = [v.message for v in tr.violations]
    assert len(kinds) == 2
    assert "Svc._table is `guarded-by: _lock`" in kinds[0]
    assert ".append()" in kinds[1]
    # Violations carry the mutating stack for triage.
    assert "test_sanitizer" in tr.violations[0].stack


def test_loop_guard_catches_cross_thread_mutation():
    """Acceptance fixture: unguarded mutation of a guarded field from a
    thread, while the owner loop is bound."""
    tr = GuardTracker()
    svc = tr.track(Svc(), guards=SVC_GUARDS)

    loop = asyncio.new_event_loop()

    async def loop_side():
        svc._jobs.append(1)             # first loop-side touch binds owner
        svc._head = "req"               # rebind on the owner thread: clean

    try:
        loop.run_until_complete(loop_side())
        assert tr.violations == []

        t = threading.Thread(target=lambda: svc._jobs.append(2))
        t.start()
        t.join()
        t2 = threading.Thread(target=lambda: setattr(svc, "_head", None))
        t2.start()
        t2.join()
    finally:
        loop.close()
    msgs = [v.message for v in tr.violations]
    assert len(msgs) == 2
    assert "guarded-by: loop" in msgs[0] and ".append()" in msgs[0]
    assert "rebind" in msgs[1]


def test_sync_pokes_without_a_running_loop_do_not_bind_or_flag():
    tr = GuardTracker()
    svc = tr.track(Svc(), guards=SVC_GUARDS)
    svc._jobs.append(1)                 # sync context: no loop, no owner
    svc._head = "x"
    assert tr.violations == []


def test_rebind_rewraps_the_container():
    tr = GuardTracker()
    svc = tr.track(Svc(), guards=SVC_GUARDS)
    loop = asyncio.new_event_loop()

    async def rebind():
        svc._jobs = [9, 9]              # rebind (owner binds here)

    try:
        loop.run_until_complete(rebind())
    finally:
        loop.close()
    assert isinstance(svc._jobs, GuardedList)
    assert list(svc._jobs) == [9, 9]
    assert tr.violations == []
    tr.untrack_all()


def test_delegate_proxy_checks_queue_and_connection_mutators():
    import sqlite3
    tr = GuardTracker()

    class Db:
        def __init__(self):
            self._lock = threading.Lock()
            self._conn = sqlite3.connect(":memory:")

    db = tr.track(Db(), guards={"_conn": "_lock"})
    assert isinstance(db._conn, _CheckedDelegate)
    with db._lock:
        db._conn.execute("CREATE TABLE t (x)")     # under lock: clean
    assert tr.violations == []
    db._conn.execute("INSERT INTO t VALUES (1)")   # no lock: violation
    assert len(tr.violations) == 1
    assert ".execute()" in tr.violations[0].message
    # Reads and attribute passthrough still work through the proxy.
    with db._lock:
        db._conn.commit()
    assert db._conn.total_changes == 1
    db._conn.row_factory = sqlite3.Row             # attr set passes through
    tr.untrack_all()


def test_guard_maps_parse_from_live_class_annotations():
    from llmapigateway_tpu.config.loader import ConfigLoader
    from llmapigateway_tpu.db.usage import UsageDB
    from llmapigateway_tpu.routing.router import ProviderRegistry
    assert guard_map_for(ConfigLoader) == {
        "_providers": "_lock", "_rules": "_lock", "_version": "_lock"}
    assert guard_map_for(UsageDB) == {"_conn": "_lock"}
    assert guard_map_for(ProviderRegistry) == {
        "_cache": "_lock", "_name_locks": "_lock", "_retiring": "loop"}


# -- leak detectors -----------------------------------------------------------

def test_leaked_task_detected_then_cleanly_cancelled():
    loop = asyncio.new_event_loop()
    try:
        async def spawn():
            return asyncio.get_running_loop().create_task(asyncio.sleep(60))
        task = loop.run_until_complete(spawn())
        leaks = leaked_tasks(loop)
        assert len(leaks) == 1 and leaks[0].kind == "task-leak"
        task.cancel()
        try:
            loop.run_until_complete(task)
        except asyncio.CancelledError:
            pass
        assert leaked_tasks(loop) == []
    finally:
        loop.close()


def test_leaked_span_detected_in_finished_trace():
    from llmapigateway_tpu.obs import trace as obs_trace
    tracer = obs_trace.Tracer()
    with tracer.trace("req-leak"):
        with obs_trace.span("ok", "router"):
            pass
        obs_trace.begin_span("leaky", "provider")   # never closed  # graftlint: disable=metric-discipline — the leak is the subject under test
    leaks = leaked_spans([tracer])
    assert [v.kind for v in leaks] == ["span-leak"]
    assert "leaky" in leaks[0].message
    # An in-flight (unfinished) trace is not a leak.
    tracer2 = obs_trace.Tracer()
    cm = tracer2.trace("req-open")
    cm.__enter__()
    assert leaked_spans([tracer2]) == []
    cm.__exit__(None, None, None)


# -- the session sanitizer, live under a real decode --------------------------

@pytest.fixture(scope="module")
def engine(stop_engine):
    import jax
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import InferenceEngine
    cfg = LocalEngineConfig(kv_page_size=16,
                            preset="tiny-test", max_batch_size=2,
                            max_seq_len=64, prefill_chunk=16,
                            dtype="float32")
    eng = InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])
    yield eng
    stop_engine(eng)


async def test_session_sanitizer_is_live_during_real_engine_decode(
        graft_sanitizer, engine):
    """The tier-1 integration criterion: while a real engine decodes, the
    conftest-installed sanitizer is armed — stall patch in place, the
    engine's annotated scheduler fields wrapped in checking proxies — and
    a clean decode records zero violations."""
    if graft_sanitizer is None:
        pytest.skip("sanitizer disabled via GRAFT_SANITIZER=0")
    assert graft_sanitizer.active, "Handle._run patch must be installed"
    # Instrumented construction wrapped the engine's guarded fields.
    assert isinstance(engine._running, GuardedDict)
    assert isinstance(engine._prefilling, GuardedDict)
    assert isinstance(engine._free_slots, GuardedList)
    assert isinstance(engine._queue, _CheckedDelegate)
    assert engine.__dict__["_graft_guard_info"].guards["_running"] == "loop"

    before = len(graft_sanitizer.violations())
    from llmapigateway_tpu.engine.engine import GenRequest
    req = GenRequest(prompt_ids=engine.tokenizer.encode("sanitize me"),
                     max_tokens=4)
    await engine.submit(req)
    async for _ in engine.stream(req):
        pass
    assert req.finish_reason in ("stop", "length")
    assert len(req.generated) >= 1
    # A clean decode under full instrumentation adds no violations.
    assert len(graft_sanitizer.violations()) == before


def test_violation_render_shape():
    v = Violation(kind="guard", message="m", stack="  a\n  b", thread="T")
    text = v.render()
    assert text.startswith("[guard] m (thread=T)")
    assert "    a" in text
