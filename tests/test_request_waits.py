"""ISSUE 41: the scheduler's ledger seen from the request.

* ``obs/phases.py`` alone, on a fake clock, driven the way ``_step`` drives
  it: a request's seven buckets partition ``[t_admitted, t_first_loop]``
  and ``[t_first_loop, t_done]``; a chunk is its rows' own prefill and
  every other request's time behind a prefill, on both sides of the first
  token; a request admitted, finished or cancelled inside a segment gets
  only its part; the ``req_*_ms_total`` counters grow by exactly what the
  requests were credited; the first-token fetch is a part of ``fetch``.
* The tiny CPU engine, long and short prompts mixed: every finished
  request's buckets against its own stamps, the counters against the
  requests, and the request tree's ``engine.prefill`` / ``engine.decode``
  attributes.
"""
from __future__ import annotations

import asyncio

import jax
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.obs import device as dev
from llmapigateway_tpu.obs import trace as obs_trace
from llmapigateway_tpu.obs.phases import (REQ_BUCKETS, WORKER_PHASES,
                                          SchedLedger)

from tests.test_sched_phases import FakeClock, _sums

TTFT, DECODE = REQ_BUCKETS[:4], REQ_BUCKETS[4:]


def buckets(req, names=REQ_BUCKETS) -> dict[str, float]:
    return {k: getattr(req.waits, k) for k in names}


def totals(led: SchedLedger) -> dict[str, float]:
    s = led.stats()
    return {k: s[f"req_{k}_ms_total"] for k in REQ_BUCKETS}


class Loop:
    """The calls ``_step`` / ``_admit`` / ``_finish`` make of the ledger,
    with the clock advanced by hand. Times are the engine's stamps: read
    off the same clock, inside the segment that is open."""

    def __init__(self):
        self.clk = FakeClock()
        self.led = SchedLedger(clock=self.clk)
        self.led.start()

    def new(self) -> GenRequest:
        return GenRequest(prompt_ids=[1], max_tokens=4)

    def admit(self, *reqs, before=2.0, after=1.0) -> None:
        with self.led.span("admit"):
            self.clk.tick(before)
            for req in reqs:
                req.t_admitted = self.clk()
                self.led.admitted(req, req.t_admitted)
            self.clk.tick(after)

    def chunk(self, *own, ms=60.0, done=()) -> None:
        with self.led.wait("prefill_wait", own):
            with dev.phase("sched.prefill_group", annotate=False):
                self.clk.tick(ms - 5)
                if done:
                    with dev.phase("sched.fetch.first", annotate=False):
                        self.clk.tick(3)
                    for req in done:
                        req.t_first_token = self.clk()
                    self.clk.tick(2)
                else:
                    self.clk.tick(5)

    def burst(self, ms=12.0) -> None:
        with self.led.wait("decode_wait"):
            with dev.phase("sched.decode_burst", annotate=False):
                self.clk.tick(2)
                with dev.phase("sched.fetch.burst", annotate=False):
                    self.clk.tick(ms - 2)

    def emit(self, *finishing, before=0.5, after=0.25) -> None:
        with self.led.span("emit"):
            self.clk.tick(before)
            for req in finishing:
                req.t_done = self.clk()
                self.led.left(req, req.t_done)
            self.clk.tick(after)


def assert_partition(req) -> None:
    w = req.waits
    ttft = sum(buckets(req, TTFT).values())
    if w.t_first_loop is None:          # never reached its first token
        assert ttft == pytest.approx(1e3 * (req.t_done - req.t_admitted),
                                     abs=1e-6)
        assert all(v == 0.0 for v in buckets(req, DECODE).values())
        return
    assert w.t_first_loop >= req.t_first_token
    assert ttft == pytest.approx(1e3 * (w.t_first_loop - req.t_admitted),
                                 abs=1e-6)
    assert sum(buckets(req, DECODE).values()) == pytest.approx(
        1e3 * (req.t_done - w.t_first_loop), abs=1e-6)
    assert all(v >= 0.0 for v in buckets(req).values())


@pytest.fixture(scope="module")
def served():
    """Three scheduler steps' worth of mixed traffic: ``long`` (three
    chunks), ``short`` (one chunk, in a call of its own), ``late``
    (admitted while the two decode; finishes last) and ``gone`` (admitted
    with ``late``, cancelled before its first token)."""
    lp = Loop()
    long, short, late, gone = (lp.new() for _ in range(4))
    lp.clk.tick(4)                                   # other
    lp.admit(long, short)
    lp.chunk(long)                                   # 60: long own
    lp.chunk(short, ms=40, done=(short,))            # 40: short own, first
    lp.emit()
    lp.burst()                                       # short decodes
    lp.emit()
    lp.admit(late, gone)
    lp.chunk(long, late, gone, ms=70)                # a call of three rows
    lp.burst()
    lp.emit()
    lp.led.switch("other")
    lp.clk.tick(1)
    lp.chunk(long, ms=50, done=(long,))
    lp.emit(gone)                                    # cancelled mid-emit
    lp.chunk(late, ms=30, done=(late,))
    lp.burst()
    lp.emit(short)
    lp.burst(ms=9)
    lp.emit(long)
    lp.led.switch("parked")                          # nobody to step for...
    lp.clk.tick(100)
    lp.led.switch("other")
    lp.burst()
    lp.emit(late)
    lp.clk.tick(3)
    return lp, {"long": long, "short": short, "late": late, "gone": gone}


@pytest.mark.parametrize("who", ["long", "short", "late", "gone"])
def test_the_buckets_partition_a_requests_two_lives(served, who):
    assert_partition(served[1][who])


def test_a_chunk_is_own_for_its_rows_and_behind_for_the_rest(served):
    _, r = served
    # Before the first token: own calls, and the calls of the others.
    assert r["long"].waits.ttft_own_prefill == pytest.approx(60 + 70 + 50)
    assert r["long"].waits.ttft_behind_prefill == pytest.approx(40)
    assert r["short"].waits.ttft_own_prefill == pytest.approx(40)
    assert r["short"].waits.ttft_behind_prefill == pytest.approx(60)
    assert r["late"].waits.ttft_own_prefill == pytest.approx(70 + 30)
    assert r["late"].waits.ttft_behind_prefill == pytest.approx(50)
    assert r["gone"].waits.ttft_own_prefill == pytest.approx(70)
    assert r["gone"].waits.ttft_behind_prefill == pytest.approx(50)
    # After it: every chunk is somebody else's.
    assert r["short"].waits.decode_behind_prefill == pytest.approx(
        70 + 50 + 30)
    assert r["long"].waits.decode_behind_prefill == pytest.approx(30)
    assert r["late"].waits.decode_behind_prefill == pytest.approx(0)
    # A decode burst is a wait behind decode before the first token and
    # the request's own tokens after it.
    assert r["long"].waits.ttft_behind_decode == pytest.approx(12 + 12)
    assert r["short"].waits.ttft_behind_decode == pytest.approx(0)
    assert r["short"].waits.decode_in_decode == pytest.approx(12 * 3)
    assert r["long"].waits.decode_in_decode == pytest.approx(12 + 9)
    assert r["late"].waits.decode_in_decode == pytest.approx(12 + 9 + 12)


def test_a_request_gets_only_the_part_of_a_segment_it_lived_through(served):
    _, r = served
    # Admitted 2 ms into a 3 ms admit span: 1 ms of it, then the emit
    # spans (0.75 each) up to the first token's wait.
    assert r["short"].waits.ttft_loop == pytest.approx(1.0)
    assert r["long"].waits.ttft_loop == pytest.approx(
        1.0 + 0.75 * 3 + 3.0 + 1.0)
    # Cancelled 0.5 ms into a 0.75 ms emit span: 0.5 of it.
    assert r["gone"].waits.ttft_loop == pytest.approx(1.0 + 0.75 + 1.0 + 0.5)
    assert r["gone"].waits.ttft_behind_decode == pytest.approx(12)
    # Finished mid-emit, the loop parked afterwards: none of the park.
    assert r["long"].waits.decode_loop == pytest.approx(0.75 + 0.75 + 0.5)
    assert r["late"].waits.decode_loop == pytest.approx(
        0.75 * 2 + 100 + 0.5)


def test_the_totals_grew_by_what_the_requests_were_credited(served):
    lp, r = served
    got = totals(lp.led)
    for k in REQ_BUCKETS:
        assert got[k] == pytest.approx(
            sum(getattr(q.waits, k) for q in r.values()), abs=1e-6), k
    s = lp.led.stats()
    assert s["req_first_tokens_total"] == 3          # not the cancelled one
    # Slot-milliseconds never exceed the wall times the requests alive.
    assert sum(got.values()) == pytest.approx(sum(
        1e3 * (q.t_done - q.t_admitted) for q in r.values()), abs=1e-6)


def test_the_totals_grow_as_each_segment_closes_and_never_step_back():
    lp = Loop()
    a, b = lp.new(), lp.new()
    seen = [totals(lp.led)]

    def reading():
        seen.append(totals(lp.led))
        assert all(seen[-1][k] >= seen[-2][k] for k in REQ_BUCKETS)
        return seen[-1]

    lp.admit(a, b)
    assert reading()["ttft_loop"] == pytest.approx(2.0)      # 1 ms, twice
    with lp.led.wait("prefill_wait", (a,)):
        lp.clk.tick(30)
        mid = reading()                  # credited at the close, not before
        assert mid["ttft_own_prefill"] == 0.0
        a.t_first_token = lp.clk()
        lp.clk.tick(1)
    got = reading()
    assert got["ttft_own_prefill"] == pytest.approx(31)
    assert got["ttft_behind_prefill"] == pytest.approx(31)
    assert a.t_first_loop == pytest.approx(lp.clk()) and b.t_first_loop is None
    lp.burst()
    got = reading()
    assert got["decode_in_decode"] == pytest.approx(12)
    assert got["ttft_behind_decode"] == pytest.approx(12)
    lp.emit(a, b)
    # The requests in flight at a reading are in the totals up to the last
    # boundary; once both are out the two agree.
    for k in REQ_BUCKETS:
        assert reading()[k] == pytest.approx(
            getattr(a.waits, k) + getattr(b.waits, k), abs=1e-6)
    assert a.waits.closed and b.waits.closed


def test_the_first_token_fetch_is_a_part_of_fetch(served):
    lp, _ = served
    s = lp.led.stats()
    assert s["sched_fetch_first_ms_total"] == pytest.approx(3 * 3)
    # Five bursts, each blocked in its read but for 2 ms: the rest of fetch.
    assert s["sched_fetch_ms_total"] == pytest.approx(9 + 10 * 4 + 7)
    loop, worker, waits = _sums(s)
    assert worker == pytest.approx(waits)
    assert "sched_fetch_first_ms_total" not in [
        f"sched_{k}_ms_total" for k in WORKER_PHASES]


def test_a_reading_inside_the_first_token_fetch_holds_the_identity():
    lp = Loop()
    a = lp.new()
    lp.admit(a)
    readings = []
    with lp.led.wait("prefill_wait", (a,)):
        with dev.phase("sched.prefill_group", annotate=False):
            lp.clk.tick(20)
            with dev.phase("sched.fetch.first", annotate=False):
                lp.clk.tick(6)
                readings.append(lp.led.stats())
                lp.clk.tick(2)
            a.t_first_token = lp.clk()
            readings.append(lp.led.stats())
    readings.append(lp.led.stats())
    for s, first in zip(readings, (6, 8, 8)):
        loop, worker, waits = _sums(s)
        assert worker == pytest.approx(waits)
        assert s["sched_fetch_first_ms_total"] == pytest.approx(first)
        assert s["sched_fetch_ms_total"] == pytest.approx(first)
    assert lp.led.wait_ms == pytest.approx(28)


def test_a_stopped_ledger_gives_the_wall_and_credits_nothing():
    """``_step`` driven without a loop: the flight record still gets its
    burst wall (two readings of the ledger's clock), no counter moves."""
    clk = FakeClock()
    led = SchedLedger(clock=clk)
    req = GenRequest(prompt_ids=[1], max_tokens=2)
    led.admitted(req, clk())
    with led.wait("prefill_wait", (req,)):
        clk.tick(17)
        req.t_first_token = clk()
    assert led.wait_ms == pytest.approx(17)
    led.left(req, clk())
    assert req.t_first_loop is None and not req.waits.closed
    assert all(v == 0.0 for v in buckets(req).values())
    s = led.stats()
    assert all(v == 0 for v in s.values())


def test_a_request_outliving_the_loop_ends_its_wall_where_the_loop_stopped():
    lp = Loop()
    a, b = lp.new(), lp.new()
    lp.admit(a, b)
    lp.chunk(a, done=(a,))
    lp.clk.tick(5)
    lp.led.stop()
    lp.clk.tick(1000)                        # no loop: nobody's wall
    lp.led.left(a)                           # the engine failed: no t_done
    lp.led.left(b)
    assert sum(buckets(a, TTFT).values()) == pytest.approx(1 + 60)
    assert sum(buckets(a, DECODE).values()) == pytest.approx(5)
    assert sum(buckets(b).values()) == pytest.approx(1 + 60 + 5)
    lp.led.left(a)                           # idempotent
    lp.led.start()
    lp.clk.tick(50)
    lp.led.stop()
    got = totals(lp.led)
    assert sum(got.values()) == pytest.approx(2 * 66)


# -- the tiny engine ----------------------------------------------------------

@pytest.fixture(scope="module")
def engine():
    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=4,
                            max_seq_len=256, prefill_chunk=32,
                            dtype="float32", decode_burst=4,
                            kv_page_size=16, flight_ring_size=512,
                            prefix_cache=False,
                            prewarm_sampler_variants=False)
    return InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])


async def _run_one(engine, prompt, max_tokens):
    req = GenRequest(prompt_ids=list(prompt), max_tokens=max_tokens,
                     temperature=0.0)
    await engine.submit(req)
    async for _ in engine.stream(req):
        pass
    return req


async def test_engine_buckets_against_each_requests_own_stamps(engine):
    try:
        await _run_one(engine, range(2, 40), 4)      # compiles; loop starts
        before = engine.stats()
        reqs = await asyncio.gather(
            _run_one(engine, range(2, 150), 12),     # five chunks
            _run_one(engine, range(3, 20), 9),       # one
            _run_one(engine, range(4, 100), 5),      # four
            _run_one(engine, range(5, 30), 16),
            _run_one(engine, range(6, 200), 3),      # queued behind four slots
            _run_one(engine, range(7, 12), 7))
        after = engine.stats()
        flight = engine.flight.snapshot()
    finally:
        await engine.stop()
    for req in reqs:
        assert req.waits.closed
        assert req.t_first_token <= req.t_first_loop <= req.t_done
        assert_partition(req)
        assert req.waits.ttft_own_prefill > 0
        assert req.waits.decode_in_decode > 0
    # Mixed lengths in four slots: somebody's chunk ran while another
    # request decoded, and somebody's burst while another prefilled.
    assert sum(r.waits.decode_behind_prefill for r in reqs) > 0
    assert sum(r.waits.ttft_behind_prefill for r in reqs) > 0
    # The counters grew by what these six were credited, and count them.
    for k in REQ_BUCKETS:
        grew = after[f"req_{k}_ms_total"] - before[f"req_{k}_ms_total"]
        assert grew == pytest.approx(
            sum(getattr(r.waits, k) for r in reqs), abs=0.05), k
    assert after["req_first_tokens_total"] \
        - before["req_first_tokens_total"] == 6
    assert after["req_decode_tokens_total"] \
        - before["req_decode_tokens_total"] \
        == sum(len(r.generated) - 1 for r in reqs)
    assert 0 < after["sched_fetch_first_ms_total"] \
        <= after["sched_fetch_ms_total"]
    # One reading: a step record's burst wall is the decode wait's, so the
    # records of the run sum to the counter's growth (none was dropped: the
    # ring holds 512).
    walls = sum(r["decode_wall_ms"] for r in flight
                if r["kind"] == "step" and "burst_depth" in r)
    assert walls == pytest.approx(after["sched_decode_wait_ms_total"],
                                  abs=0.05)


async def test_the_request_tree_carries_the_buckets(engine):
    from llmapigateway_tpu.providers.base import (CompletionRequest,
                                                  NullUsageObserver)
    from llmapigateway_tpu.providers.local import LocalProvider
    provider = LocalProvider("tpu", engine)
    tracer = obs_trace.Tracer()
    try:
        with tracer.trace("waits-1"):
            with obs_trace.span("provider.call", layer="provider"):
                other = asyncio.ensure_future(
                    _run_one(engine, range(2, 120), 6))
                result, error = await provider.complete(
                    CompletionRequest(
                        payload={"model": "m", "max_tokens": 6,
                                 "temperature": 0,
                                 "messages": [{"role": "user",
                                               "content": "hello there"}]},
                        stream=False),
                    NullUsageObserver())
                await other
    finally:
        await engine.stop()
    assert error is None and result is not None
    doc = tracer.get("waits-1")
    spans = {}

    def walk(s):
        spans[s["name"]] = s
        for c in s.get("children", ()):
            walk(c)

    walk(doc["spans"])
    pre, dec = spans["engine.prefill"], spans["engine.decode"]
    ttft = [pre["attrs"][k] for k in ("own_ms", "behind_prefill_ms",
                                      "behind_decode_ms", "loop_ms")]
    tpot = [dec["attrs"][k] for k in ("in_decode_ms", "behind_prefill_ms",
                                      "loop_ms")]
    assert pre["attrs"]["own_ms"] > 0 and dec["attrs"]["in_decode_ms"] > 0
    assert all(v >= 0 for v in ttft + tpot)
    # They sum to the spans' walls, to the worker's tail after its
    # first-token stamp: it lengthens prefill's sum and shortens decode's.
    tail = sum(ttft) - pre["duration_ms"]
    assert 0 <= tail < 50
    assert sum(tpot) == pytest.approx(dec["duration_ms"] - tail, abs=0.02)
