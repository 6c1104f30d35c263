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

    async def on_open():
        opened.append(time.monotonic())
    played = await Player(g, t, seed=1, seconds=0.4, on_open=on_open).play()
    firsts = sorted(played.logs, key=lambda r: r.t_send)[:3]
    assert all(r.t_end <= played.t_open for r in firsts)
    # ... and not before the last of them: client 2 began 0.06 s in.
    assert played.t_open - played.t_start >= 0.06 + g.first
    assert opened and abs(opened[0] - played.t_open) < 0.1
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
