"""ISSUE 26: the scheduler's time ledger.

* ``obs/phases.py`` alone, on a fake clock: the six loop counters sum to
  the loop's wall, the four worker counters to the two waits, at every
  reading, mid-wait included; nesting, stop/restart, cancellation.
* The tiny CPU engine: both identities within 1%, ``parked`` grows while
  idle and not under load, every counter present and monotone in
  ``stats()`` and on ``/metrics``.
* The ``sched.*`` spans (``TraceAnnotation`` patched to record): entered on
  the right thread, in the right order, none open across an ``await``.
* The compile monitor counts a fresh ``jit`` traced inside
  ``phase("sched.admit")`` under that phase.
* The PREFILL flight record.
"""
from __future__ import annotations

import asyncio
import contextvars
import sys
import threading
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.obs import device as dev
from llmapigateway_tpu.obs import flight as fl
from llmapigateway_tpu.obs.phases import (LOOP_PHASES, REQ_BUCKETS,
                                          WORKER_PHASES, SchedLedger)

KEYS = [f"sched_{k}_ms_total" for k in LOOP_PHASES + WORKER_PHASES]
# ISSUE 41 (tests/test_request_waits.py): a part of ``fetch``, the requests'
# seven totals and the two counts they are read against.
REQ_KEYS = (["sched_fetch_first_ms_total", "req_first_tokens_total",
             "req_decode_tokens_total"]
            + [f"req_{k}_ms_total" for k in REQ_BUCKETS])
# ISSUE 56 (tests/test_sched_parts.py): the parts of ``worker_other`` and
# of ``hop``, and the threads' CPU beside their wall.
PART_KEYS = ([f"sched_worker_{p}_ms_total" for p in dev.WORKER_PARTS]
             + [f"sched_{k}_ms_total" for k in (
                 "hop_out", "hop_back", "loop_cpu", "dispatch_cpu",
                 "worker_other_cpu")])


class FakeClock:
    def __init__(self, t: float = 100.0):
        self.t = t

    def __call__(self) -> float:
        return self.t

    def tick(self, ms: float) -> None:
        self.t += ms / 1e3


def _sums(stats: dict) -> tuple[float, float, float]:
    loop = sum(stats[f"sched_{k}_ms_total"] for k in LOOP_PHASES)
    worker = sum(stats[f"sched_{k}_ms_total"] for k in WORKER_PHASES)
    waits = (stats["sched_prefill_wait_ms_total"]
             + stats["sched_decode_wait_ms_total"])
    return loop, worker, waits


# -- the ledger alone, on a fake clock ----------------------------------------

def test_loop_counters_partition_the_wall_exactly():
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    assert set(led.stats()) == set(KEYS) | set(REQ_KEYS) | set(PART_KEYS)
    assert all(v == 0.0 for v in led.stats().values())
    led.start()
    clk.tick(3)                              # other
    with led.span("admit"):
        clk.tick(5)
    clk.tick(1)                              # other
    with led.span("emit"):
        clk.tick(7)
    led.switch("parked")
    clk.tick(250)
    led.switch("other")
    clk.tick(2)
    s = led.stats()
    assert s["sched_admit_ms_total"] == pytest.approx(5)
    assert s["sched_emit_ms_total"] == pytest.approx(7)
    assert s["sched_parked_ms_total"] == pytest.approx(250)
    assert s["sched_other_ms_total"] == pytest.approx(6)
    assert _sums(s)[0] == pytest.approx(268)


def test_parked_is_counted_while_it_lasts():
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    led.start()
    led.switch("parked")
    clk.tick(40)
    a = led.stats()["sched_parked_ms_total"]
    clk.tick(60)
    b = led.stats()["sched_parked_ms_total"]
    assert (a, b) == (pytest.approx(40), pytest.approx(100))


def test_worker_counters_split_a_wait_and_nest():
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    led.start()
    with led.wait("decode_wait"):
        clk.tick(2)                                  # hop out
        with dev.phase("sched.decode_burst", annotate=False):
            clk.tick(3)                              # rng split, uploads
            with dev.phase("decode", annotate=False):
                clk.tick(4)                          # the jitted call
                with dev.phase("sched.fetch", annotate=False):
                    clk.tick(30)                     # a read inside it
                clk.tick(1)
            with dev.phase("sched.fetch", annotate=False):
                clk.tick(50)
            clk.tick(5)                              # host mirrors
        clk.tick(6)                                  # hop back
    s = led.stats()
    assert s["sched_hop_ms_total"] == pytest.approx(8)
    assert s["sched_dispatch_ms_total"] == pytest.approx(5)
    assert s["sched_fetch_ms_total"] == pytest.approx(80)
    assert s["sched_worker_other_ms_total"] == pytest.approx(8)
    assert s["sched_decode_wait_ms_total"] == pytest.approx(101)
    loop, worker, waits = _sums(s)
    assert worker == pytest.approx(waits) and loop == pytest.approx(101)
    assert dev.worker_call.get() is None     # the wait took its call back


def test_a_reading_mid_wait_holds_both_identities():
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    led.start()
    readings = []
    with led.wait("prefill_wait"):
        clk.tick(1)
        readings.append(led.stats())                 # worker not begun
        with dev.phase("sched.prefill_group", annotate=False):
            with dev.phase("prefill", annotate=False):
                clk.tick(10)
                readings.append(led.stats())         # inside the dispatch
            with dev.phase("sched.fetch", annotate=False):
                clk.tick(20)
                readings.append(led.stats())         # blocked in the fetch
        clk.tick(2)
        readings.append(led.stats())                 # done, loop not back
    readings.append(led.stats())
    for i, s in enumerate(readings):
        loop, worker, waits = _sums(s)
        assert worker == pytest.approx(waits), i
        assert loop == pytest.approx([1, 11, 31, 33, 33][i]), i
    for a, b in zip(readings, readings[1:]):
        assert all(b[k] >= a[k] for k in KEYS)       # monotone throughout
    assert readings[2]["sched_fetch_ms_total"] == pytest.approx(20)
    assert readings[-1]["sched_hop_ms_total"] == pytest.approx(3)


def test_stopped_ledger_counts_nothing_and_resumes():
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    with led.span("admit"):                  # _step driven without a loop
        clk.tick(5)
    with led.wait("decode_wait"):
        assert dev.worker_call.get() is None
        clk.tick(5)
    assert all(v == 0.0 for v in led.stats().values())
    led.start()
    clk.tick(4)
    led.stop()
    clk.tick(1000)                           # between two loops: no wall
    assert _sums(led.stats())[0] == pytest.approx(4)
    led.start()
    clk.tick(6)
    assert _sums(led.stats())[0] == pytest.approx(10)


def test_a_wait_that_raises_is_still_folded():
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    led.start()
    with pytest.raises(RuntimeError):
        with led.wait("decode_wait"):
            with dev.phase("sched.decode_burst", annotate=False):
                clk.tick(9)
                raise RuntimeError("device fault")
    s = led.stats()
    assert s["sched_worker_other_ms_total"] == pytest.approx(9)
    assert _sums(s)[1] == pytest.approx(_sums(s)[2])
    assert dev.worker_call.get() is None


def test_a_reader_racing_the_worker_sees_whole_tuples():
    """The one piece of state two threads share is ``WorkerCall.live``:
    the worker replaces it whole, the loop reads it whole. A reader that
    hammers ``stats()`` while the worker switches spans as fast as it can
    (switch interval shortened so threads interleave mid-call) never sees
    the identity broken or a counter step back."""
    led = SchedLedger()
    led.start()
    stop = threading.Event()

    def work():
        while not stop.is_set():
            with dev.phase("sched.decode_burst", annotate=False):
                with dev.phase("decode", annotate=False):
                    with dev.phase("sched.fetch", annotate=False):
                        pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        with led.wait("decode_wait"):
            t = threading.Thread(
                target=contextvars.copy_context().run, args=(work,),
                daemon=True)
            t.start()
            prev = led.stats()
            deadline = time.monotonic() + 0.5
            n = 0
            while time.monotonic() < deadline:
                s = led.stats()
                loop, worker, waits = _sums(s)
                assert worker == pytest.approx(waits, abs=0.02)
                assert all(s[k] >= prev[k] - 1e-9 for k in KEYS), (prev, s)
                prev = s
                n += 1
            stop.set()
            t.join(timeout=5.0)
            assert not t.is_alive()
    finally:
        stop.set()
        sys.setswitchinterval(old)
    assert n > 100
    s = led.stats()
    assert s["sched_dispatch_ms_total"] > 0 and s["sched_fetch_ms_total"] > 0
    assert _sums(s)[1] == pytest.approx(_sums(s)[2], abs=0.02)


@pytest.mark.parametrize("span,kind", [
    ("sched.fetch", "fetch"), ("sched.fetch.burst", "fetch"),
    ("sched.fetch.spec", "fetch"), ("sched.fetch.sync", "fetch"),
    ("sched.fetch.first", "fetch_first"), ("prefill", "dispatch"),
    ("decode", "dispatch"), ("spec.verify", "dispatch"),
    ("sched.decode_burst", "worker_other"),
    ("sched.prefill_group", "worker_other"),
    ("cost_analysis", None), ("engine.warm", None)])
def test_which_counter_a_worker_span_feeds(span, kind):
    assert dev.worker_kind(span) == kind


# -- the tiny engine ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=2,
                            max_seq_len=128, prefill_chunk=32,
                            dtype="float32", decode_burst=4,
                            kv_page_size=16, flight_ring_size=512,
                            prewarm_sampler_variants=False)
    return InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])


async def _run_one(engine, prompt, max_tokens=6, rid=""):
    req = GenRequest(prompt_ids=list(prompt), max_tokens=max_tokens,
                     temperature=0.0, request_id=rid)
    await engine.submit(req)
    async for _ in engine.stream(req):
        pass
    return req


async def test_engine_identities_parked_and_monotone(engine):
    """Both identities within 1% on the live engine; ``parked`` grows
    while idle and not under load; nothing ever decreases."""
    try:
        await _run_one(engine, range(2, 40))         # compiles; loop starts
        readings = [engine.stats()]
        t0 = time.monotonic()
        await asyncio.gather(_run_one(engine, range(2, 70), 12),
                             _run_one(engine, range(3, 50), 9),
                             _run_one(engine, range(4, 30), 5))
        readings.append(engine.stats())
        t1 = time.monotonic()
        await asyncio.sleep(0.25)                    # idle: the loop parks
        readings.append(engine.stats())
        t2 = time.monotonic()
    finally:
        await engine.stop()
    for s in readings:
        assert set(KEYS) <= set(s)
        loop, worker, waits = _sums(s)
        assert worker == pytest.approx(waits, rel=0.01)
    for a, b in zip(readings, readings[1:]):
        assert all(b[k] >= a[k] for k in KEYS), (a, b)
    d = {k: readings[2][k] - readings[0][k] for k in KEYS}
    loop_wall = sum(d[f"sched_{k}_ms_total"] for k in LOOP_PHASES)
    assert loop_wall == pytest.approx(1e3 * (t2 - t0), rel=0.01, abs=2.0)
    under_load = (readings[1]["sched_parked_ms_total"]
                  - readings[0]["sched_parked_ms_total"])
    idle = (readings[2]["sched_parked_ms_total"]
            - readings[1]["sched_parked_ms_total"])
    assert idle == pytest.approx(1e3 * (t2 - t1), abs=30.0)
    assert under_load < 0.1 * 1e3 * (t1 - t0) + 5.0
    # Work was done in every phase that the traffic reaches.
    for k in ("admit", "prefill_wait", "decode_wait", "emit", "dispatch",
              "fetch", "hop"):
        assert d[f"sched_{k}_ms_total"] > 0, k
    # A stopped loop's wall stands still.
    after = engine.stats()
    time.sleep(0.02)
    assert engine.stats()["sched_parked_ms_total"] \
        == after["sched_parked_ms_total"]


@pytest.mark.parametrize("key", KEYS)
def test_counter_is_in_stats_and_on_metrics(engine, key):
    """Every counter is a flat float in ``stats()`` and a sample of the one
    labelled family on ``/metrics`` (the real collector, a stub gateway)."""
    from llmapigateway_tpu.obs.metrics import GatewayMetrics
    from llmapigateway_tpu.server.obs_api import make_stats_collector
    from tests.test_metrics import validate_prometheus_text
    stats = engine.stats()
    assert isinstance(stats[key], float) and stats[key] >= 0.0
    gw = SimpleNamespace(
        metrics=GatewayMetrics(), breakers=None, usage_recorder=None,
        tracer=SimpleNamespace(evicted_total=0),
        registry=SimpleNamespace(instantiated=lambda: [
            ("tpu", SimpleNamespace(engine=engine))]))
    make_stats_collector(gw)()
    fams = validate_prometheus_text(gw.metrics.render())
    fam = fams["gateway_engine_sched_phase_ms_total"]
    by_phase = {labels["phase"]: (labels["engine"], value)
                for _, labels, value in fam["samples"]}
    assert len(by_phase) == len(KEYS)
    phase = key[len("sched_"):-len("_ms_total")]
    assert by_phase[phase][0] == "tpu"
    assert by_phase[phase][1] == pytest.approx(stats[key], abs=50.0)


# -- spans: thread, order, none across an await -------------------------------

class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: the spans entered
    and left, per thread."""

    def __init__(self):
        self.events: list[tuple[str, str, int]] = []
        self.open: dict[int, list[str]] = {}
        rec = self

        class Annotation:
            def __init__(self, name, **kw):
                self.name = name

            def __enter__(self):
                tid = threading.get_ident()
                rec.events.append(("enter", self.name, tid))
                rec.open.setdefault(tid, []).append(self.name)
                return self

            def __exit__(self, *exc):
                tid = threading.get_ident()
                rec.events.append(("exit", self.name, tid))
                assert rec.open[tid].pop() == self.name
                return False

        self.cls = Annotation


async def test_spans_thread_order_and_none_across_an_await(engine,
                                                            monkeypatch):
    rec = _Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    loop_tid = threading.get_ident()
    held: list[list[str]] = []
    done = False

    async def watcher():
        # Runs whenever the loop thread is between callbacks: any span
        # open on this thread then is held across somebody's await.
        while not done:
            if rec.open.get(loop_tid):
                held.append(list(rec.open[loop_tid]))
            await asyncio.sleep(0)

    task = asyncio.ensure_future(watcher())
    try:
        await asyncio.gather(_run_one(engine, range(2, 70), 10),
                             _run_one(engine, range(3, 40), 6))
    finally:
        done = True
        await task
        await engine.stop()
    assert not held, held
    names = {n for _, n, _ in rec.events}
    assert {"sched.admit", "sched.emit", "sched.prefill_group", "prefill",
            "sched.decode_burst", "decode", "sched.fetch.first"} <= names
    # Every read is named by what it reads (ISSUE 41): no bare fetch.
    fetches = {n for n in names if n.startswith("sched.fetch")}
    assert fetches <= {"sched.fetch.first", "sched.fetch.burst",
                       "sched.fetch.sync"}
    assert "sched.parked" not in names and "sched.hop" not in names
    for kind, name, tid in rec.events:
        if name in ("sched.admit", "sched.emit", "sched.plan"):
            assert tid == loop_tid, name
        else:
            assert tid != loop_tid, name
    # On each worker thread the spans nest: the jitted call and every
    # blocking read lie inside the whole-call span.
    stacks: dict[int, list[str]] = {}
    inside: set[tuple[str, str]] = set()
    for kind, name, tid in rec.events:
        st = stacks.setdefault(tid, [])
        if kind == "enter":
            if st:
                inside.add((st[-1], name))
            else:
                assert name.startswith("sched.") \
                    and not name.startswith("sched.fetch"), name
            st.append(name)
        else:
            assert st.pop() == name
    assert ("sched.prefill_group", "prefill") in inside
    assert ("sched.prefill_group", "sched.fetch.first") in inside
    assert ("sched.decode_burst", "decode") in inside
    assert (("sched.decode_burst", "sched.fetch.burst") in inside
            or ("decode", "sched.fetch.sync") in inside)
    # Admission comes before the first prefill, emission after it.
    order = [n for k, n, _ in rec.events if k == "enter"]
    assert order.index("sched.admit") < order.index("sched.prefill_group") \
        < order.index("sched.emit")


# -- retraces counted where compiles are --------------------------------------

def test_a_fresh_jit_inside_sched_admit_is_counted_under_it():
    mon = dev.install_compile_monitor()
    before = mon.stats()
    side = int(time.time() * 1000) % 400 + 17
    with dev.phase("sched.admit", annotate=False):
        jax.jit(lambda x: x * 5 - 2)(jnp.ones((side, 7))).block_until_ready()
    after = mon.stats()
    b = before["xla_trace_by_phase"].get("sched.admit",
                                         {"count": 0, "ms": 0.0})
    a = after["xla_trace_by_phase"]["sched.admit"]
    assert a["count"] >= b["count"] + 1
    assert a["ms"] > b["ms"]
    assert after["xla_trace_total"] >= before["xla_trace_total"] + 1
    assert after["xla_trace_ms_total"] > before["xla_trace_ms_total"]
    # The compile it led to carries the same tag, not "startup"...
    assert after["xla_compile_by_phase"]["sched.admit"]["count"] >= 1
    assert after["xla_compile_total"] > before["xla_compile_total"]
    # ...and a second call of the same shape costs neither.
    mid = mon.stats()
    with dev.phase("sched.admit", annotate=False):
        f = jax.jit(lambda x: x + 1)
        f(jnp.ones((side, 7))).block_until_ready()
        settled = mon.stats()
        f(jnp.ones((side, 7))).block_until_ready()
    assert settled["xla_trace_total"] > mid["xla_trace_total"]
    assert mon.stats()["xla_compile_total"] == settled["xla_compile_total"]


def test_engine_stats_carry_the_trace_counters(engine):
    s = engine.stats()
    assert s["xla_trace_total"] >= 1 and s["xla_trace_ms_total"] > 0
    assert isinstance(s["xla_trace_by_phase"], dict)
    assert isinstance(s["xla_compile_by_phase"], dict)
    # The engine's own first calls were traced inside its spans.
    assert {"startup"} <= set(s["xla_trace_by_phase"])


def test_the_annotation_option_is_gone():
    with pytest.raises(Exception):
        LocalEngineConfig(preset="tiny-test", profile_annotations=False)
    # cost_analysis keeps the one caller-side switch.
    with dev.phase("cost_analysis", annotate=False):
        assert dev.current_phase() == "cost_analysis"


# -- the PREFILL flight record ------------------------------------------------

def test_prefill_record_snapshot():
    rec = fl.FlightRecorder(capacity=16, clock=FakeClock(50.0))
    seq = rec.record(fl.PREFILL, t=49.5, dur_ms=166.8, depth=2, val=512.0,
                     tokens=900, free_pages=1024, spec_acc=4096, chunks=58,
                     active=32, free_slots=2)
    assert rec.snapshot() == [{
        "seq": seq, "t": 49.5, "kind": "prefill", "dur_ms": 166.8,
        "rows": 2, "bucket": 512, "tokens": 900, "pos_lo": 1024,
        "pos_hi": 4096, "pages_walked": 58, "block": "32x2"}]
    # A dense cache's dispatch runs no paged kernel and names no block.
    rec.record(fl.PREFILL, t=49.6, dur_ms=1.0, depth=1, val=8.0, tokens=8)
    assert "block" not in rec.snapshot()[-1]
    # No lifecycle counter moves, and the STEP record's shape is untouched.
    assert rec.stats()["flight_admits"] == 0
    rec.record(fl.STEP, flag=fl.F_PREFILL, chunks=1, dur_ms=1.0)
    assert "rows" not in rec.snapshot()[-1]


async def test_engine_leaves_one_prefill_record_per_dispatch(engine):
    try:
        before, stats = engine.flight.seq, engine.stats()
        # 70 tokens in chunks of 32, on a prompt no earlier test left in
        # the prefix cache.
        await _run_one(engine, range(90, 20, -1), 4)
        snap = engine.flight.snapshot(since=before - 1)
        after = engine.stats()
    finally:
        await engine.stop()
    pre = [r for r in snap if r["kind"] == "prefill"]
    chunks = sum(r.get("prefill_chunks", 0) for r in snap
                 if r["kind"] == "step")
    assert len(pre) == chunks == 3
    assert [r["pos_lo"] for r in pre] == [0, 32, 64]
    assert [r["tokens"] for r in pre] == [32, 32, 6]
    assert [r["bucket"] for r in pre] == [32, 32, 8]
    assert all(r["rows"] == 1 and r["pos_hi"] == r["pos_lo"] for r in pre)
    assert all(r["dur_ms"] > 0 for r in pre)
    # The paged prefill kernel's walk (ISSUE 37), pages of 16 and a table
    # of 8: each chunk walks up to the page of its last token, where a
    # grid with a page axis stepped through the table.
    assert [r["pages_walked"] for r in pre] == [2, 4, 5]
    # ... at the block the kernel's rule picks a bucket (ISSUE 48): the
    # tiny model's two KV heads both fold, the whole bucket a row-block;
    # the kernel registry's prefill rows say the same.
    assert [r["block"] for r in pre] == ["32x2", "32x2", "8x2"]
    assert {"8": "8x2", "32": "32x2"}.items() \
        <= after["prefill_kernel_blocks"].items()
    variants = {r["kernel"]: r["variant_block"]
                for r in engine.kernels.table() if r["kind"] == "prefill"}
    assert variants["prefill.b32.k1"] == "32x2" \
        and variants["prefill.b8.k1"] == "8x2"
    assert after["prefill_kv_pages_walked_total"] \
        - stats["prefill_kv_pages_walked_total"] == 11
    assert after["prefill_kv_pages_table_total"] \
        - stats["prefill_kv_pages_table_total"] == 3 * 8
    # Each lies on the ring's one timeline, before the step that ran it.
    ts = [r["t"] for r in snap]
    assert ts == sorted(ts)


def test_flight_report_draws_a_prefill_slice():
    from tools.flight_report import TID_PREFILL, engine_events
    recs = [{"seq": 0, "t": 10.0, "kind": "admit", "slot": 0,
             "queue_wait_ms": 1.0, "cached_tokens": 0, "queued": 0},
            {"seq": 1, "t": 10.2, "kind": "prefill", "dur_ms": 150.0,
             "rows": 2, "bucket": 512, "tokens": 900, "pos_lo": 0,
             "pos_hi": 512}]
    evs = engine_events("tpu", recs, pid=1, epoch=10.0)
    sl = next(e for e in evs if e.get("cat") == "prefill")
    assert sl["name"] == "prefill[2x512]@0-512"
    assert (sl["ts"], sl["dur"], sl["tid"]) == (50000, 150000, TID_PREFILL)
    assert any(e["ph"] == "M" and e.get("tid") == TID_PREFILL for e in evs)
