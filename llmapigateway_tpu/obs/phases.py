"""The scheduler's time ledger: every millisecond of the engine loop under
a named phase (ISSUE 26).

Two accumulators on one timeline (``time.monotonic``, the flight ring's
clock):

* :class:`SchedLedger` partitions the engine loop's wall into six phases
  (``LOOP_PHASES``). The loop advances it with ONE clock read per phase
  boundary, per scheduler step and never per token, so the six counters
  sum to the loop's wall by construction.
* :class:`WorkerCall` partitions one ``asyncio.to_thread`` wait into what
  the worker thread did with it (``WORKER_PHASES``). The worker never
  touches a counter: ``obs.device.phase`` switches the call's state as the
  worker enters and leaves its spans, and the loop folds the call into the
  ledger when the await returns. So every counter has one writer (the
  loop), a span and its counter cannot disagree (the counter IS the span's
  wall), and a ``stats()`` taken mid-wait reads the call in flight
  exactly: the four worker counters sum to the two wait counters at every
  reading.

The call reaches the worker through a context variable
(``asyncio.to_thread`` copies the context), so a direct call of a worker
function — a test, the bench — finds none and pays
one ``ContextVar.get`` per span.

The same readings seen from the REQUEST (ISSUE 41): every closed stretch of
the loop's wall is credited to each request that held a slot during it,
under what the request was waiting behind (``REQ_BUCKETS``). The ledger
keeps no list of requests: three clocks — the wall spent in prefill waits,
in decode waits, in everything else — ARE the ledger's own counters, a
request's buckets are their growth between its own events (admission,
first token, release; ``ReqWaits``), and the ``req_*_ms_total`` counters
grow at each boundary by the stretch's wall times the requests alive in
it. A request costs arithmetic at its three events and at the close of the
prefill calls that held its chunk; a boundary costs a few multiplies
whatever the batch; a token costs nothing.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Iterator, Sequence

from .device import phase, worker_call

LOOP_PHASES = ("parked", "admit", "prefill_wait", "decode_wait", "emit",
               "other")
WORKER_PHASES = ("hop", "dispatch", "fetch", "worker_other")
# What a worker span can be booked under (``obs.device.worker_kind``): the
# four counters, and the part of ``fetch`` that is prefill's first token,
# which the loop folds into ``fetch`` AND keeps as a sub-counter.
_WORKER_KINDS = WORKER_PHASES + ("fetch_first",)
_KINDS = {k: i for i, k in enumerate(_WORKER_KINDS)}
# A request's life in a slot, by what it waited behind. Before its first
# token: its own chunks' calls, other requests' chunks, decode bursts, the
# loop's own work. After: decode bursts (its own tokens), others' chunks,
# the loop.
REQ_BUCKETS = ("ttft_own_prefill", "ttft_behind_prefill",
               "ttft_behind_decode", "ttft_loop", "decode_in_decode",
               "decode_behind_prefill", "decode_loop")
_QUEUED, _PREFILLING, _DECODING, _LEFT = range(4)
# Which of a request's three clocks a loop phase advances; any other: the
# third.
_CLOCK_OF = {"prefill_wait": 0, "decode_wait": 1}


def _credit(acc: tuple, kind: str, seconds: float) -> tuple:
    """``acc`` (seconds per _WORKER_KINDS) with ``seconds`` more on
    ``kind``, as a new tuple."""
    i = _KINDS[kind]
    return acc[:i] + (acc[i] + seconds,) + acc[i + 1:]


class WorkerCall:
    """One worker-thread call's wall, split by kind. ``live`` is
    ``(kind, t_mark, seconds per _WORKER_KINDS)``, replaced whole at each
    switch by the worker and read whole by the loop. The lock makes a
    clock reading and the tuple it belongs to one step: without it a
    reader could book a span's first moments (the worker between its clock
    read and its publish) under the span before, and step back at the
    next reading. It is held for two statements, never across a call."""

    __slots__ = ("_clock", "_lock", "live")

    def __init__(self, clock: Callable[[], float], t0: float):
        self._clock = clock
        self._lock = threading.Lock()
        self.live = ("hop", t0, (0.0,) * len(_KINDS))  # guarded-by: _lock

    def switch(self, kind: str) -> str:
        """Close the current kind's segment and open ``kind``'s; returns
        the kind that was current (to restore on the way out)."""
        with self._lock:
            prev, t_mark, acc = self.live
            now = self._clock()
            self.live = (kind, now, _credit(acc, prev, now - t_mark))
        return prev

    def split(self) -> tuple[float, tuple[float, ...]]:
        """``(now, seconds per _WORKER_KINDS)`` with the open segment
        counted: they sum to ``now`` minus the wait's start."""
        with self._lock:
            kind, t_mark, acc = self.live
            now = self._clock()
        return now, _credit(acc, kind, now - t_mark)


class ReqWaits:
    """What one request's time in a slot went to: ``REQ_BUCKETS`` in
    milliseconds, written by the ledger at the request's own events. The
    first four partition ``[t_admitted, t_first_loop]``, the last three
    ``[t_first_loop, t_done]``; a request that never reached its first
    token has only the first four. ``t_first_loop`` is the ledger's reading
    at the close of the prefill wait whose call finished the prompt: the
    worker stamps ``t_first_token`` inside that wait, the loop learns of it
    here."""

    __slots__ = ("life", "t_first_loop", "_mark") + REQ_BUCKETS

    def __init__(self):
        self.life = _QUEUED
        self.t_first_loop: float | None = None
        self._mark = (0.0, 0.0, 0.0)    # the three clocks at the last event
        for k in REQ_BUCKETS:
            setattr(self, k, 0.0)

    @property
    def closed(self) -> bool:
        """The request has left its slot: every bucket is final."""
        return self.life == _LEFT


class SchedLedger:
    """The loop's wall since ``start()``, partitioned. Every method runs
    on the engine's event-loop thread.

    A request is anything with a ``waits`` (:class:`ReqWaits`) and the
    worker's ``t_first_token`` stamp; the times handed to ``admitted`` and
    ``left`` are readings of this ledger's clock taken inside the open
    segment (the engine's ``t_admitted`` and ``t_done``)."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        # "fetch_first" is a part of "fetch", not a phase beside it.
        self._ms = dict.fromkeys(LOOP_PHASES + _WORKER_KINDS, 0.0)
        self._cur = "other"
        self._t: float | None = None        # None = the loop is not running
        self._call: WorkerCall | None = None
        # The wall of the wait that closed last, for the flight STEP record:
        # the reading the counters and the requests were credited with.
        self.wait_ms = 0.0
        # Requests in a slot before / after their first token, and of the
        # former those whose chunk the open prefill wait's call holds.
        self._n_pre = self._n_dec = self._n_own = 0
        self._t_req = 0.0                   # the req totals are credited to here
        self._req_ms = dict.fromkeys(REQ_BUCKETS, 0.0)
        self.first_tokens = 0               # prompts that finished prefill
        self.decode_tokens = 0              # tokens after a request's first

    def start(self) -> None:
        if self._t is None:
            self._t, self._cur = self._clock(), "other"
            self._t_req = self._t

    def stop(self) -> None:
        if self._t is not None:
            self._advance(self._clock(), "other")
            self._t = None

    @staticmethod
    def _fold(ms: dict[str, float], call: WorkerCall) -> float:
        """Add ``call``'s split, up to now, to the worker counters of
        ``ms``, the first-token fetch under ``fetch`` as well as under its
        own name; returns that now."""
        now, acc = call.split()
        for k, s in zip(_WORKER_KINDS, acc):
            ms[k] += 1e3 * s
        ms["fetch"] += 1e3 * acc[-1]
        return now

    def _advance(self, now: float, phase_: str) -> str:
        self._ms[self._cur] += 1e3 * (now - self._t)
        self._credit_requests(now)
        self._t = now
        prev, self._cur = self._cur, phase_
        return prev

    def switch(self, phase_: str) -> str:
        """Enter ``phase_`` now; returns the phase left. A no-op while the
        loop is not running (``_step`` driven directly by a test)."""
        if self._t is None:
            return self._cur
        return self._advance(self._clock(), phase_)

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[None]:
        """A synchronous segment of the loop thread: the counter
        ``sched_<name>_ms_total`` and the profiler span ``sched.<name>``
        over the same interval. Never held across an ``await``: the HTTP
        server shares the thread."""
        prev = self.switch(name)
        try:
            with phase("sched." + name):
                yield
        finally:
            self.switch(prev)

    @contextlib.contextmanager
    def wait(self, name: str, own: Sequence = ()) -> Iterator[None]:
        """Around ``await asyncio.to_thread(...)``: the loop is in the wait
        phase ``name`` and the worker has a call to account into. On the
        way out (returned, raised or cancelled) the call is folded into
        the worker counters and the wait phase left, at one clock reading;
        ``wait_ms`` is the wait's wall by that reading. Held across the
        await by design: it is ledger state, not a profiler span.

        ``own`` are the requests whose prompt chunk a ``prefill_wait``'s
        call holds: the wait is their own prefill and every other
        request's time behind a prefill, and one the worker stamped
        ``t_first_token`` on has its first token at this wait's close."""
        if self._t is None:
            # Not running (a test drives ``_step`` directly): no counter
            # moves and no request is credited, but the flight record still
            # wants the burst's wall — two readings of the ledger's clock,
            # the pair the flight recorder's own clock used to give.
            t0 = self._clock()
            try:
                yield
            finally:
                self.wait_ms = 1e3 * (self._clock() - t0)
            return
        self._advance(self._clock(), name)
        t0 = self._t
        call = self._call = WorkerCall(self._clock, t0)
        token = worker_call.set(call)
        for req in own:
            self._n_own += req.waits.life == _PREFILLING
        try:
            yield
        finally:
            worker_call.reset(token)
            self._call = None
            if self._t is not None:
                now = self._fold(self._ms, call)
                self._advance(now, "other")
                ms = self.wait_ms = 1e3 * (now - t0)
                for req in own:
                    w = req.waits
                    if w.life == _PREFILLING:
                        w.ttft_own_prefill += ms
                        if req.t_first_token is not None:
                            self._close(w, now)
                            w.t_first_loop, w.life = now, _DECODING
                            self._n_dec += 1
                            self.first_tokens += 1
            self._n_own = 0

    # -- the same wall, from the requests -------------------------------------

    def _clocks(self, t: float) -> tuple[float, float, float]:
        """The loop's wall up to ``t`` (a reading inside the open segment)
        spent in prefill waits, in decode waits, and in anything else."""
        ms = self._ms
        part = [ms["prefill_wait"], ms["decode_wait"],
                ms["parked"] + ms["admit"] + ms["emit"] + ms["other"]]
        if self._t is not None:
            part[_CLOCK_OF.get(self._cur, 2)] += 1e3 * (t - self._t)
        return part[0], part[1], part[2]

    def _credit_requests(self, t: float) -> None:
        """The open segment's wall since the totals were last credited, up
        to ``t``: once for every request in a slot, under its bucket."""
        ms, self._t_req = 1e3 * (t - self._t_req), t
        tot, pre, dec = self._req_ms, self._n_pre, self._n_dec
        if self._cur == "prefill_wait":
            tot["ttft_own_prefill"] += ms * self._n_own
            tot["ttft_behind_prefill"] += ms * (pre - self._n_own)
            tot["decode_behind_prefill"] += ms * dec
        elif self._cur == "decode_wait":
            tot["ttft_behind_decode"] += ms * pre
            tot["decode_in_decode"] += ms * dec
        else:
            tot["ttft_loop"] += ms * pre
            tot["decode_loop"] += ms * dec

    def _close(self, w: ReqWaits, t: float) -> None:
        """``w``'s life before or after its first token ends at ``t``: its
        buckets are the three clocks' growth since its last event."""
        p, d, rest = now = self._clocks(t)
        p0, d0, rest0 = w._mark
        if w.life == _PREFILLING:
            # The calls that held its own chunk are in ``p`` too. (``max``:
            # the two sides add the same walls in different orders, and may
            # differ in their last bits.)
            w.ttft_behind_prefill = max(0.0, p - p0 - w.ttft_own_prefill)
            w.ttft_behind_decode = d - d0
            w.ttft_loop = rest - rest0
            self._n_pre -= 1
        else:
            w.decode_behind_prefill = p - p0
            w.decode_in_decode = d - d0
            w.decode_loop = rest - rest0
            self._n_dec -= 1
        w._mark = now

    def admitted(self, req, t: float) -> None:
        """``req`` took a slot at ``t``. Nothing while the loop is not
        running: such a request is never credited."""
        w = req.waits
        if self._t is None or w.life != _QUEUED:
            return
        self._credit_requests(t)
        w.life, w._mark = _PREFILLING, self._clocks(t)
        self._n_pre += 1

    def left(self, req, t: float | None = None) -> None:
        """``req`` gave its slot back at ``t`` (finished, cancelled, or
        failed with the engine: then there is no ``t_done`` and the
        ledger reads its clock; a ledger stopped under it ends the
        request's wall where it stopped)."""
        w = req.waits
        if w.life not in (_PREFILLING, _DECODING):
            return
        if self._t is None:
            t = self._t_req
        else:
            t = self._clock() if t is None else t
            self._credit_requests(t)
        self._close(w, t)
        w.life = _LEFT

    def stats(self) -> dict[str, float]:
        """Flat monotone counters, milliseconds, the open segment (and the
        call in flight) counted up to now; the requests' totals up to the
        last boundary, where they were credited."""
        ms = dict(self._ms)
        if self._t is not None:
            now = (self._fold(ms, self._call) if self._call is not None
                   else self._clock())
            ms[self._cur] += 1e3 * (now - self._t)
        out = {f"sched_{k}_ms_total": round(v, 3) for k, v in ms.items()}
        for k, v in self._req_ms.items():
            out[f"req_{k}_ms_total"] = round(v, 3)
        out["req_first_tokens_total"] = self.first_tokens
        out["req_decode_tokens_total"] = self.decode_tokens
        return out
