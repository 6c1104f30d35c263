"""Playing a trace against the gateway, and deciding where the window is.

The window opens at the same point of the trace every time:

* closed loop — ``OPEN_GUARD_S`` after the last client has finished its
  first request (client k first sends at ``k * stagger_s``, so equal
  lengths cannot march in step). The clients draw from ONE ordered trace:
  whichever client is free takes the next entry, so the engine is offered
  the same requests in the same order in every run. That last finish IS
  the arrival of a decode burst, whose frames for the other slots are
  stamped within a millisecond of it on either side; the guard puts the
  edge well after them and well before the next burst (a burst is at
  least 4 steps of some 30 ms), so a burst's worth of tokens — 1% of a
  long-document window — does not fall in or out of the window by a race.
  The edge is computed from the finish's own stamp, not from the moment
  the harness noticed it, also where the loop noticed it late (it parses
  the next request's body, some 0.2 s for a long document): frames are
  stamped as they arrive and counted afterwards, so the window opens at
  the stamp although the harness acts on it later.
* open loop — exactly ``lead_in_s`` seconds into the fixed schedule.

At that moment, and not before, ``on_open`` is called, a plain function:
the harness takes its counter snapshot in it and, traced, starts the
profiler as a task, which it returns. The player waits for what
``on_open`` returns only when the run is over, so the profiler's start-up
neither moves the edge nor lies before it: in the open loop too, counters
and trace begin where the window does, not where the lead-in does.

It closes ``seconds`` later. The engine streams tokens in bursts (28
every 0.3 s under long documents, 1% of a 40 s window), and no guard can
keep the close clear of them; ``metrics.tokens_in_window`` gives a burst
that straddles an edge to the window by the share of its interval inside.

Load goes on unchanged after the close until every request that was due
or sent inside the window has its first token, so that their time to
first token is measured under the same load, and until a frame has
arrived after the close, so that the burst astride it has an interval to
be shared out by (both bounded by ``DRAIN_LIMIT_S``); then whatever is
still in flight is hung up on.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Awaitable, Callable

from .gateway import Gateway
from .metrics import RequestLog
from .traffic import Entry, Traffic, make_trace, prompt_ids

DRAIN_LIMIT_S = 45.0
OPEN_GUARD_S = 0.05


@dataclasses.dataclass
class Played:
    logs: list[RequestLog]
    t_start: float                 # traffic began
    t_open: float
    t_close: float
    lateness_ms: list[float]       # open loop: send time minus due time
    drained: bool                  # every in-window request got a token
    opened: Any = None             # what ``on_open``'s awaitable gave
    # The longest the event loop overslept one of the player's own naps
    # inside the window: seconds late, seconds from the open, and the CPU
    # seconds the whole process used over that nap (next to none: it was
    # not running at all; the nap's length or more: a thread was computing
    # and kept the others out). Tells a stalled process from a slow server
    # (PERF.md, the stalled runs).
    stall: tuple[float, float, float] = (0.0, 0.0, 0.0)


class Player:
    def __init__(self, gateway: Gateway, traffic: Traffic, seed: int,
                 seconds: float,
                 on_open: Callable[[], Awaitable[None] | None] | None = None,
                 on_close: Callable[[], Awaitable[None]] | None = None):
        self.g = gateway
        self.traffic = traffic
        self.seed = seed
        self.seconds = seconds
        self.on_open = on_open
        self.on_close = on_close
        self.logs: list[RequestLog] = []
        self.lateness_ms: list[float] = []
        self._next = 0
        self._trace: list[Entry] = []
        self._tasks: set[asyncio.Task] = set()
        self._stop = asyncio.Event()
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.stall = (0.0, 0.0, 0.0)

    # -- the trace ----------------------------------------------------------
    def _entry(self) -> Entry:
        if self._next >= len(self._trace):
            # An entry depends on its index alone, so a longer trace
            # starts with the same entries.
            self._trace = make_trace(self.traffic, max(1024, 2 * self._next))
        e = self._trace[self._next]
        self._next += 1
        return e

    def _request(self, e: Entry, t_due: float | None) -> tuple[RequestLog, str]:
        tok = self.g.tokenizer
        n = e.prompt_tokens - tok.template_overhead()
        ids = prompt_ids(e, self.seed, tok.vocab_size, n,
                         self.traffic.sessions)
        log = RequestLog(index=e.index, rid=f"r{e.index}",
                         prompt_tokens=e.prompt_tokens,
                         max_tokens=e.max_tokens, t_due=t_due)
        self.logs.append(log)
        return log, tok.text_of(ids)

    async def _send(self, e: Entry, t_due: float | None) -> RequestLog:
        log, content = self._request(e, t_due)
        return await self.g.stream_chat(log, content,
                                        self.traffic.temperature)

    # -- loops --------------------------------------------------------------
    async def _client(self, k: int, firsts: list[RequestLog | None]) -> None:
        await asyncio.sleep(k * self.traffic.stagger_s)
        while not self._stop.is_set():
            log = await self._send(self._entry(), None)
            firsts[k] = firsts[k] or log

    async def _schedule(self, t_start: float) -> None:
        while not self._stop.is_set():
            e = self._entry()
            due = t_start + e.due_s
            delay = due - time.monotonic()
            if delay > 0:
                try:
                    await asyncio.wait_for(self._stop.wait(), delay)
                    return
                except asyncio.TimeoutError:
                    pass
            self.lateness_ms.append(1000.0 * (time.monotonic() - due))
            task = asyncio.ensure_future(self._send(e, due))
            self._tasks.add(task)
            task.add_done_callback(self._tasks.discard)

    # -- the run ------------------------------------------------------------
    async def play(self) -> Played:
        t = self.traffic
        t_start = time.monotonic()
        if t.loop == "closed":
            firsts: list[RequestLog | None] = [None] * t.clients
            for k in range(t.clients):
                self._tasks.add(asyncio.ensure_future(
                    self._client(k, firsts)))
            while not all(firsts):
                await self._check_tasks()
                await asyncio.sleep(0.005)
            edge = max(r.t_end for r in firsts) + OPEN_GUARD_S
        else:
            self._tasks.add(asyncio.ensure_future(self._schedule(t_start)))
            edge = t_start + t.lead_in_s
        # Closed loop: the stamp decides, however late the loop noticed it.
        self.t_open = edge
        await asyncio.sleep(max(0.0, self.t_open - time.monotonic()))
        opening = self.on_open() if self.on_open else None
        self.t_close = self.t_open + self.seconds
        while time.monotonic() < self.t_close:
            await self._check_tasks()
            nap = min(0.05, max(0.0, self.t_close - time.monotonic()))
            due, cpu = time.monotonic() + nap, time.process_time()
            await asyncio.sleep(nap)
            self.stall = max(self.stall, (
                time.monotonic() - due, due - self.t_open,
                time.process_time() - cpu))
        if self.on_close:
            await self.on_close()
        drained = await self._drain()
        await self._hang_up()
        opened = await opening if opening is not None else None
        return Played(logs=self.logs, t_start=t_start, t_open=self.t_open,
                      t_close=self.t_close, lateness_ms=self.lateness_ms,
                      drained=drained, opened=opened, stall=self.stall)

    def _waiting_for_first(self) -> list[RequestLog]:
        return [r for r in self.logs
                if self.t_open <= r.t_ref < self.t_close
                and r.t_end is None and not r.frames]

    def _burst_astride_close_landed(self) -> bool:
        return any(r.frames and r.frames[-1][0] >= self.t_close
                   for r in self.logs)

    async def _drain(self) -> bool:
        limit = time.monotonic() + DRAIN_LIMIT_S
        while (self._waiting_for_first()
               or not self._burst_astride_close_landed()):
            if time.monotonic() > limit:
                return False
            await self._check_tasks()
            await asyncio.sleep(0.02)
        return True

    async def _hang_up(self) -> None:
        self._stop.set()
        tasks = list(self._tasks)
        for task in tasks:
            task.cancel()
        for task in tasks:
            try:
                await task
            except asyncio.CancelledError:
                pass
        self._tasks.clear()

    async def _check_tasks(self) -> None:
        """A load task that died takes the run with it."""
        for task in list(self._tasks):
            if task.done() and not task.cancelled() and task.exception():
                raise task.exception()
