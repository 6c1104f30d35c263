"""ISSUE 56: the host's half of a step, by name.

* ``obs/phases.py`` alone, on a fake clock and a fake CPU clock: the five
  parts of ``worker_other`` and the two of ``hop`` fold into their parent
  AND stand under their own names; the four worker counters still sum to
  the two waits at every reading; a part entered outside any wait moves
  nothing; a CPU counter never exceeds its phase's wall and never steps
  back.
* ``obs.device.part`` names a part after the enclosing call, and the
  compile monitor's tag follows.
* The tiny CPU engine: ``prefill_calls_unread_total`` counts a two-chunk
  prompt's first call and not its last; one served request leaves all
  thirteen counters in ``stats()``; the loop's prelude is ``sched.plan``
  on the loop thread and the parts are on the worker's.
"""
from __future__ import annotations

import asyncio
import threading

import jax
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.obs import device as dev
from llmapigateway_tpu.obs.phases import (CPU_PHASES, LOOP_PHASES,
                                          WORKER_PHASES, SchedLedger)
from tests.test_sched_phases import FakeClock, _Recorder, _sums

PARTS = dev.WORKER_PARTS
# What the thirteen per-layer metrics of ISSUE 56 read.
THIRTEEN = (["sched_worker_other_ms_total"]
            + [f"sched_worker_{p}_ms_total" for p in PARTS]
            + ["sched_hop_out_ms_total", "sched_hop_back_ms_total",
               "sched_loop_cpu_ms_total", "sched_dispatch_cpu_ms_total",
               "sched_worker_other_cpu_ms_total",
               "proc_invol_ctx_switches_total",
               "prefill_calls_unread_total"])


def _ledger() -> tuple[SchedLedger, FakeClock, FakeClock]:
    clk, cpu = FakeClock(), FakeClock(7.0)
    return SchedLedger(clock=clk, cpu_clock=cpu), clk, cpu


def _run(clk: FakeClock, cpu: FakeClock, wall_ms: float,
         cpu_ms: float | None = None) -> None:
    """The calling thread holds the wall for ``wall_ms`` and computes for
    ``cpu_ms`` of it (all of it by default)."""
    clk.tick(wall_ms)
    cpu.tick(wall_ms if cpu_ms is None else cpu_ms)


# -- the ledger alone ---------------------------------------------------------

@pytest.mark.parametrize("part", PARTS)
def test_a_part_folds_into_worker_other_and_stands_alone(part):
    led, clk, cpu = _ledger()
    led.start()
    with led.wait("decode_wait"):
        clk.tick(2)                                      # hop out
        with dev.phase("sched.decode_burst", annotate=False):
            _run(clk, cpu, 3)                            # unnamed
            with dev.part(part):
                assert dev.current_phase() == "sched.decode_burst." + part
                _run(clk, cpu, 5)
            assert dev.current_phase() == "sched.decode_burst"
            with dev.phase("decode", annotate=False):
                _run(clk, cpu, 4)
            with dev.phase("sched.fetch.burst", annotate=False):
                clk.tick(50)
            with dev.part(part):
                _run(clk, cpu, 6)
        clk.tick(1)                                      # hop back
    s = led.stats()
    assert s[f"sched_worker_{part}_ms_total"] == pytest.approx(11)
    assert s["sched_worker_other_ms_total"] == pytest.approx(14)
    for other in set(PARTS) - {part}:
        assert s[f"sched_worker_{other}_ms_total"] == 0.0
    assert s["sched_dispatch_ms_total"] == pytest.approx(4)
    assert s["sched_fetch_ms_total"] == pytest.approx(50)
    assert s["sched_hop_ms_total"] == pytest.approx(3)
    loop, worker, waits = _sums(s)
    assert worker == pytest.approx(waits) and waits == pytest.approx(71)


def test_the_hand_off_by_direction():
    led, clk, cpu = _ledger()
    led.start()
    readings = []
    for out_ms, back_ms in ((2, 6), (1, 30)):
        with led.wait("prefill_wait"):
            clk.tick(out_ms)
            readings.append(led.stats())                 # worker not begun
            with dev.phase("sched.prefill_group", annotate=False):
                _run(clk, cpu, 10)
                readings.append(led.stats())
            clk.tick(back_ms)
            readings.append(led.stats())                 # done, loop busy
        readings.append(led.stats())
    s = readings[-1]
    assert s["sched_hop_out_ms_total"] == pytest.approx(3)
    assert s["sched_hop_back_ms_total"] == pytest.approx(36)
    assert s["sched_hop_ms_total"] == pytest.approx(39)
    # A reading before the worker began is all on the way out; one after
    # it finished has the way back open and counted.
    assert readings[0]["sched_hop_out_ms_total"] == pytest.approx(2)
    assert readings[0]["sched_hop_back_ms_total"] == 0.0
    assert readings[2]["sched_hop_back_ms_total"] == pytest.approx(6)
    for i, r in enumerate(readings):
        _, worker, waits = _sums(r)
        assert worker == pytest.approx(waits), i
        assert (r["sched_hop_out_ms_total"] + r["sched_hop_back_ms_total"]
                == pytest.approx(r["sched_hop_ms_total"])), i
    for a, b in zip(readings, readings[1:]):
        assert all(b[k] >= a[k] for k in a)


def test_readings_mid_wait_hold_the_identity_with_parts_open():
    led, clk, cpu = _ledger()
    led.start()
    readings = []
    with led.wait("decode_wait"):
        clk.tick(1)
        with dev.phase("sched.decode_burst", annotate=False):
            for part in PARTS:
                with dev.part(part):
                    _run(clk, cpu, 2)
                    readings.append(led.stats())         # inside the part
                _run(clk, cpu, 1)
                readings.append(led.stats())             # between two
        clk.tick(3)
        readings.append(led.stats())
    readings.append(led.stats())
    for i, s in enumerate(readings):
        loop, worker, waits = _sums(s)
        assert worker == pytest.approx(waits), i
        parts = sum(s[f"sched_worker_{p}_ms_total"] for p in PARTS)
        assert parts <= s["sched_worker_other_ms_total"] + 1e-9, i
    for a, b in zip(readings, readings[1:]):
        assert all(b[k] >= a[k] for k in a)              # monotone, all
    s = readings[-1]
    assert s["sched_worker_other_ms_total"] == pytest.approx(15)
    assert [s[f"sched_worker_{p}_ms_total"] for p in PARTS] \
        == [pytest.approx(2)] * len(PARTS)


@pytest.mark.parametrize("part", PARTS)
def test_a_part_outside_any_wait_moves_no_counter(part):
    led, clk, cpu = _ledger()
    led.start()
    before = led.stats()
    with dev.part(part):                     # a direct call from a test
        assert dev.current_phase() == "sched." + part
        assert dev.worker_call.get() is None
    with dev.phase("sched.decode_burst", annotate=False):
        with dev.part(part):
            assert dev.current_phase() == "sched.decode_burst." + part
    assert dev.current_phase() == ""
    assert led.stats() == before


@pytest.mark.parametrize("phase_", CPU_PHASES)
def test_worker_cpu_stays_inside_its_phases_wall(phase_):
    """The worker computes for half of what it holds the wall for — in
    the phase under test, and in the other one too. The CPU the loop folds
    is the closed segments': never above the wall, never stepping back,
    and none for a blocked fetch or the hand-off."""
    led, clk, cpu = _ledger()
    led.start()
    readings = []
    with led.wait("decode_wait"):
        _run(clk, cpu, 4, 0)                             # hop: no thread's
        with dev.phase("sched.decode_burst", annotate=False):
            _run(clk, cpu, 6, 3)
            readings.append(led.stats())
            with dev.part("state"):
                _run(clk, cpu, 10, 5)
                readings.append(led.stats())
            with dev.phase("decode", annotate=False):
                _run(clk, cpu, 8, 4)
                readings.append(led.stats())
            with dev.phase("sched.fetch.burst", annotate=False):
                _run(clk, cpu, 40, 0.5)                  # blocked: not booked
                readings.append(led.stats())
            _run(clk, cpu, 2, 1)
        _run(clk, cpu, 5, 0)
        readings.append(led.stats())
    readings.append(led.stats())
    key = f"sched_{phase_}_cpu_ms_total"
    for i, s in enumerate(readings):
        assert s[key] <= s[f"sched_{phase_}_ms_total"] + 1e-9, i
    for a, b in zip(readings, readings[1:]):
        assert b[key] >= a[key]
    s = readings[-1]
    assert s["sched_worker_other_cpu_ms_total"] == pytest.approx(9)
    assert s["sched_worker_other_ms_total"] == pytest.approx(18)
    assert s["sched_dispatch_cpu_ms_total"] == pytest.approx(4)
    assert s["sched_dispatch_ms_total"] == pytest.approx(8)
    # A reading inside an open segment has its wall and not yet its CPU:
    # the worker reads its CPU clock where it leaves ``worker_other`` (a
    # part's edges inside it are not such a place).
    assert readings[1]["sched_worker_other_cpu_ms_total"] == 0.0
    assert readings[1]["sched_worker_other_ms_total"] == pytest.approx(16)
    assert readings[2]["sched_worker_other_cpu_ms_total"] \
        == pytest.approx(8)


def test_loop_cpu_runs_while_the_ledger_runs_on_the_loops_thread():
    led, clk, cpu = _ledger()
    _run(clk, cpu, 50)                       # before start: nobody's
    led.start()
    _run(clk, cpu, 4, 3)                     # other
    with led.span("admit"):
        _run(clk, cpu, 6, 6)
    a = led.stats()
    assert a["sched_loop_cpu_ms_total"] == pytest.approx(9)
    with led.wait("decode_wait"):
        _run(clk, cpu, 20, 2)                # SSE frames while the worker runs
        b = led.stats()
    assert b["sched_loop_cpu_ms_total"] == pytest.approx(11)
    # Another thread reads the boundaries' sum and not the loop's clock.
    _run(clk, cpu, 5, 5)
    got = []
    t = threading.Thread(target=lambda: got.append(led.stats()))
    t.start()
    t.join()
    assert got[0]["sched_loop_cpu_ms_total"] == pytest.approx(11)
    assert led.stats()["sched_loop_cpu_ms_total"] == pytest.approx(16)
    led.stop()
    _run(clk, cpu, 1000)                     # between two loops: none
    s = led.stats()
    assert s["sched_loop_cpu_ms_total"] == pytest.approx(16)
    assert s["sched_loop_cpu_ms_total"] <= sum(
        s[f"sched_{k}_ms_total"] for k in LOOP_PHASES)
    led.start()
    _run(clk, cpu, 2, 1)
    assert led.stats()["sched_loop_cpu_ms_total"] == pytest.approx(17)


def test_the_ledgers_keys():
    s = SchedLedger().stats()
    assert {k for k in THIRTEEN if k.startswith("sched_")} <= set(s)
    assert all(f"sched_{k}_ms_total" in s
               for k in LOOP_PHASES + WORKER_PHASES)
    assert WORKER_PHASES == ("hop", "dispatch", "fetch", "worker_other")


@pytest.mark.parametrize("span,kind", [
    ("sched.decode_burst.state", "worker_state"),
    ("sched.spec_burst.tables", "worker_tables"),
    ("sched.decode_burst.rng", "worker_rng"),
    ("sched.prefill_group.args", "worker_args"),
    ("sched.prefill_group.mirrors", "worker_mirrors"),
    ("sched.tables", "worker_tables"),
    ("sched.decode_burst.other", "worker_other"),
    ("sched.plan", "worker_other"), ("state", None)])
def test_which_counter_a_part_feeds(span, kind):
    assert dev.worker_kind(span) == kind


def test_a_compile_inside_a_part_carries_the_parts_name():
    import jax.numpy as jnp
    mon = dev.install_compile_monitor()
    side = 23 + threading.get_ident() % 97
    with dev.phase("sched.decode_burst", annotate=False):
        with dev.part("rng"):
            jax.jit(lambda x: x * 3 - 7)(
                jnp.ones((side, 5))).block_until_ready()
    s = mon.stats()
    assert s["xla_trace_by_phase"]["sched.decode_burst.rng"]["count"] >= 1
    assert s["xla_compile_by_phase"]["sched.decode_burst.rng"]["count"] >= 1


# -- the tiny engine ----------------------------------------------------------

@pytest.fixture(scope="module")
def shared_engine():
    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=2,
                            max_seq_len=128, prefill_chunk=32,
                            dtype="float32", decode_burst=4,
                            kv_page_size=16, flight_ring_size=512,
                            prewarm_sampler_variants=False)
    return InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])


async def _run_one(engine, prompt, max_tokens=6):
    req = GenRequest(prompt_ids=list(prompt), max_tokens=max_tokens,
                     temperature=0.0)
    await engine.submit(req)
    async for _ in engine.stream(req):
        pass
    return req


@pytest.fixture(scope="module")
def served(shared_engine):
    """``stats()`` before and after ONE request of two chunks (40 tokens in
    chunks of 32), and the spans it entered."""
    rec = _Recorder()
    loop_tid: list[int] = []

    async def go():
        loop_tid.append(threading.get_ident())
        before = shared_engine.stats()
        try:
            await _run_one(shared_engine, range(100, 60, -1), 8)
            after = shared_engine.stats()
        finally:
            await shared_engine.stop()
        return before, after

    mp = pytest.MonkeyPatch()
    mp.setattr(jax.profiler, "TraceAnnotation", rec.cls)
    try:
        before, after = asyncio.run(go())
    finally:
        mp.undo()
    return before, after, rec.events, loop_tid[0]


@pytest.mark.parametrize("key", THIRTEEN)
def test_a_served_request_leaves_the_counter_in_stats(served, key):
    before, after, _, _ = served
    assert isinstance(after[key], (int, float)) and after[key] >= 0
    assert after[key] >= before[key]                     # monotone
    if key != "proc_invol_ctx_switches_total":           # the machine's
        assert after[key] > before[key], key


def test_a_two_chunk_prompt_leaves_one_call_unread(served):
    before, after, _, _ = served
    assert after["prefill_calls_unread_total"] \
        - before["prefill_calls_unread_total"] == 1
    assert after["sched_fetch_first_ms_total"] \
        > before["sched_fetch_first_ms_total"]


def test_the_engines_parts_stay_inside_their_parents(served):
    _, s, _, _ = served
    parts = sum(s[f"sched_worker_{p}_ms_total"] for p in PARTS)
    assert 0 < parts <= s["sched_worker_other_ms_total"] + 0.01
    assert (s["sched_hop_out_ms_total"] + s["sched_hop_back_ms_total"]
            == pytest.approx(s["sched_hop_ms_total"], abs=0.01))
    _, worker, waits = _sums(s)
    assert worker == pytest.approx(waits, rel=0.01)
    # Real clocks: a thread's CPU can pass its wall by a clock's grain.
    assert s["sched_dispatch_cpu_ms_total"] \
        <= 1.05 * s["sched_dispatch_ms_total"] + 1.0
    assert s["sched_worker_other_cpu_ms_total"] \
        <= 1.05 * s["sched_worker_other_ms_total"] + 1.0


def test_the_parts_are_spans_of_their_call_on_the_workers_thread(served):
    _, _, events, loop_tid = served
    names = {n for _, n, _ in events}
    assert {"sched.prefill_group.args", "sched.prefill_group.rng",
            "sched.prefill_group.tables", "sched.prefill_group.mirrors",
            "sched.decode_burst.state", "sched.decode_burst.tables",
            "sched.decode_burst.rng", "sched.decode_burst.mirrors",
            "sched.plan"} <= names
    stacks: dict[int, list[str]] = {}
    for kind, name, tid in events:
        st = stacks.setdefault(tid, [])
        if kind == "exit":
            assert st.pop() == name
            continue
        if name == "sched.plan":
            assert tid == loop_tid and not st
        elif name.rpartition(".")[2] in PARTS:
            # Directly inside the call it is named after, never inside the
            # jitted call or a read (whose counters keep their intervals).
            assert tid != loop_tid
            assert st and st[-1] == name.rpartition(".")[0], (st, name)
        st.append(name)
