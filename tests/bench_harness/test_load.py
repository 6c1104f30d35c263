"""Repair A.4: the window opens at the same point of the trace every
time — closed loop: when every client has finished its first request;
open loop: a fixed lead-in into the schedule — and load goes on until
every request of the window has its first token."""
import asyncio
import json
import time

import pytest

from benchmark.load import OPEN_GUARD_S, Player
from benchmark.metrics import RequestLog, end_to_end
from benchmark.tokenizer import CharTokenizer
from benchmark.traffic import Traffic


class FakeGateway:
    """Streams one token every ``gap`` seconds after ``first`` seconds."""

    def __init__(self, first=0.02, gap=0.004):
        self.tokenizer = CharTokenizer(512)
        self.first, self.gap = first, gap
        self.sent: list[tuple[int, int, int]] = []

    async def stream_chat(self, log: RequestLog, content, temperature=0.0):
        log.t_send = time.monotonic()
        self.sent.append((log.index, len(content), log.max_tokens))
        try:
            await asyncio.sleep(self.first)
            for _ in range(log.max_tokens):
                log.frames.append((time.monotonic(), 1))
                await asyncio.sleep(self.gap)
            log.status, log.done, log.finish_reason = 200, True, "length"
            log.usage = {"completion_tokens": log.tokens}
        except asyncio.CancelledError:
            log.cancelled = True
            raise
        finally:
            log.t_end = time.monotonic()
        return log


def traffic(tmp_path, **kw):
    spec = {"trace_seed": 4,
            "prompt_tokens": {"kind": "uniform", "min": 40, "max": 80,
                              "snap": 8},
            "max_tokens": {"kind": "uniform", "min": 4, "max": 12}, **kw}
    path = tmp_path / "t.json"
    path.write_text(json.dumps(spec))
    return Traffic.load(path)


async def test_closed_loop_opens_when_every_client_finished_its_first(tmp_path):
    t = traffic(tmp_path, loop="closed", clients=3, stagger_s=0.03)
    g = FakeGateway(first=0.1)      # no client finishes before the last starts
    opened = []
    played = await Player(g, t, seed=1, seconds=0.4,
                          on_open=lambda: opened.append(time.monotonic())
                          ).play()
    firsts = sorted(played.logs, key=lambda r: r.t_send)[:3]
    assert all(r.t_end <= played.t_open for r in firsts)
    # ... and not before the last of them: client 2 began 0.06 s in.
    assert played.t_open - played.t_start >= 0.06 + g.first
    assert opened and 0 <= opened[0] - played.t_open < 0.1
    # The edge is the last first-finish's own stamp plus the guard, not
    # the moment the harness noticed: a burst that arrives with that
    # finish lies wholly before the window.
    assert played.t_open == pytest.approx(
        max(r.t_end for r in firsts) + OPEN_GUARD_S, abs=1e-6)
    assert played.t_close - played.t_open == pytest.approx(0.4)
    assert played.drained
    # One ordered trace for all clients, every run: entries 0, 1, 2, ...
    assert [i for i, _, _ in g.sent] == list(range(len(g.sent)))
    # Prompts are exactly as long as the trace says (template included).
    again = FakeGateway()
    await Player(again, t, seed=99, seconds=0.1).play()
    n = min(len(g.sent), len(again.sent))
    assert g.sent[:n] == again.sent[:n]
    values, _ = end_to_end(played.logs, played.t_open, played.t_close)
    assert values["out_tok_s"] > 0
    assert any(r.cancelled for r in played.logs)       # hung up at the end
    assert not any(r.failed for r in played.logs)


async def test_open_loop_opens_after_the_lead_in_and_times_from_due(tmp_path):
    t = traffic(tmp_path, loop="open", rate_rps=40.0, lead_in_s=0.25,
                burst={"size": 3, "every_s": 0.2})
    g = FakeGateway()
    played = await Player(g, t, seed=1, seconds=0.5).play()
    assert played.t_open - played.t_start == pytest.approx(0.25, abs=2e-3)
    assert played.lateness_ms and max(played.lateness_ms) < 50
    due = [r.t_due for r in played.logs]
    assert due == sorted(due) and all(r.t_send >= r.t_due for r in played.logs)
    inside = [r for r in played.logs
              if played.t_open <= r.t_due < played.t_close]
    assert 12 <= len(inside) <= 28              # 40/s for half a second
    assert all(r.frames for r in inside) and played.drained


async def test_open_loop_calls_on_open_where_the_window_opens(tmp_path):
    """Not ``lead_in_s`` before it: the counters' open snapshot and the
    profiler's trace belong to the window, not to the lead-in (ledger, PR
    23, cell mistral-7b-chat: 4 s of a 12 s lead-in were traced)."""
    t = traffic(tmp_path, loop="open", rate_rps=40.0, lead_in_s=0.3)
    opened, closed = [], []

    def on_open():
        opened.append(time.monotonic())

    async def on_close():
        closed.append(time.monotonic())
    played = await Player(FakeGateway(), t, seed=1, seconds=0.4,
                          on_open=on_open, on_close=on_close).play()
    assert played.t_open - played.t_start == pytest.approx(0.3, abs=2e-3)
    assert 0 <= opened[0] - played.t_open < 0.05
    assert 0 <= closed[0] - played.t_close < 0.1
    assert closed[0] - opened[0] == pytest.approx(0.4, abs=0.1)


@pytest.mark.parametrize("loop", ["closed", "open"])
async def test_a_slow_on_open_neither_moves_the_edge_nor_is_lost(
        tmp_path, loop):
    """``on_open`` is called at the edge; what it returns (the harness: the
    profiler's task) the player waits for only when the run is over: a
    profiler that takes its time to start, or outlasts a short window,
    shifts nothing, and its result comes back with the run."""
    t = traffic(tmp_path, loop=loop, clients=2, stagger_s=0.02,
                rate_rps=40.0, lead_in_s=0.2)
    g = FakeGateway(first=0.05)
    stamps = {}

    async def slow():
        await asyncio.sleep(0.5)               # longer than the window
        stamps["end"] = time.monotonic()
        return "traced"

    def on_open():
        stamps["begin"] = time.monotonic()
        return asyncio.ensure_future(slow())
    played = await Player(g, t, seed=1, seconds=0.3, on_open=on_open).play()
    if loop == "closed":
        firsts = sorted(played.logs, key=lambda r: r.t_send)[:2]
        edge = max(r.t_end for r in firsts) + OPEN_GUARD_S
    else:
        edge = played.t_start + 0.2
    assert played.t_open == pytest.approx(edge, abs=1e-6)
    assert played.t_close - played.t_open == pytest.approx(0.3)
    assert 0 <= stamps["begin"] - played.t_open < 0.05
    assert stamps["end"] > played.t_close      # awaited, not cancelled
    assert played.opened == "traced"
    # Load went on through the window while on_open slept.
    assert any(played.t_open + 0.1 < r.t_send < played.t_close
               for r in played.logs)


async def test_a_stalled_event_loop_inside_the_window_is_reported(tmp_path):
    """The player's own naps say how long the loop once stood still and
    when: a process that froze is told from a server that was slow."""
    t = traffic(tmp_path, loop="open", rate_rps=20.0, lead_in_s=0.1)

    def on_open():
        async def freeze():
            await asyncio.sleep(0.2)
            time.sleep(0.3)                    # blocks the whole loop
        return asyncio.ensure_future(freeze())
    played = await Player(FakeGateway(), t, seed=1, seconds=0.8,
                          on_open=on_open).play()
    late, at, cpu = played.stall
    assert 0.2 < late < 0.4 and 0.15 < at < 0.3
    assert cpu < 0.2                # asleep, not computing
    calm = await Player(FakeGateway(), t, seed=1, seconds=0.3).play()
    assert calm.stall[0] < 0.05


class BurstGateway(FakeGateway):
    """Every stream gets its tokens in bursts, at multiples of ``period``
    on a clock all streams share, as a decode step serves every slot."""

    def __init__(self, period=0.1, per_burst=4):
        super().__init__()
        self.period, self.per_burst = period, per_burst
        self.t0 = time.monotonic()

    async def stream_chat(self, log: RequestLog, content, temperature=0.0):
        log.t_send = time.monotonic()
        try:
            while log.tokens < log.max_tokens:
                k = int((time.monotonic() - self.t0) / self.period) + 1
                await asyncio.sleep(self.t0 + k * self.period
                                    - time.monotonic())
                for _ in range(min(self.per_burst,
                                   log.max_tokens - log.tokens)):
                    log.frames.append((time.monotonic(), 1))
            log.status, log.done, log.finish_reason = 200, True, "length"
            log.usage = {"completion_tokens": log.tokens}
        except asyncio.CancelledError:
            log.cancelled = True
            raise
        finally:
            log.t_end = time.monotonic()
        return log


@pytest.mark.parametrize("seconds", [0.5, 0.53, 0.56, 0.59])
async def test_a_bursty_stream_reads_one_rate_wherever_the_close_falls(
        tmp_path, seconds):
    """Two clients, 4 tokens each every 0.1 s: 80 tokens/s. The window
    opens ``OPEN_GUARD_S`` behind a burst and closes ``seconds`` later,
    anywhere against the bursts; counting frames it would read 5 or 6
    bursts of 8 over half a second (PERF.md: longdoc's two rates)."""
    t = traffic(tmp_path, loop="closed", clients=2, stagger_s=0.0,
                max_tokens={"kind": "uniform", "min": 8, "max": 8})
    g = BurstGateway(period=0.1, per_burst=4)
    played = await Player(g, t, seed=1, seconds=seconds).play()
    assert played.t_close - played.t_open == pytest.approx(seconds)
    values, counts = end_to_end(played.logs, played.t_open, played.t_close)
    # Load went on until the burst astride the close had landed: it has
    # an interval to be shared out by.
    assert any(r.frames and r.frames[-1][0] >= played.t_close
               for r in played.logs)
    # (A busy machine delays a burst or two: the arithmetic is held to
    # the digit in test_metrics.py, on stamps that are given.)
    assert values["out_tok_s"] == pytest.approx(80.0, rel=0.15)
    assert counts["out_tok_s"] % 8 == 0


async def test_closed_loop_opens_at_the_stamp_though_noticed_late(tmp_path):
    """The loop is blocked when the last first request ends (the server
    parses the next body on it): the window still opens the guard behind
    that finish's stamp, not where the harness got round to it."""
    t = traffic(tmp_path, loop="closed", clients=2, stagger_s=0.02)

    class Blocking(FakeGateway):
        async def stream_chat(self, log, content, temperature=0.0):
            out = await super().stream_chat(log, content, temperature)
            if log.index == 1:
                time.sleep(0.15)               # blocks the whole loop
            return out
    opened = []
    played = await Player(Blocking(first=0.05), t, seed=1, seconds=0.3,
                          on_open=lambda: opened.append(time.monotonic())
                          ).play()
    firsts = sorted(played.logs, key=lambda r: r.t_send)[:2]
    assert played.t_open == pytest.approx(
        max(r.t_end for r in firsts) + OPEN_GUARD_S, abs=1e-6)
    assert opened[0] - played.t_open > 0.05        # acted on late
