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

A counter can have PARTS (ISSUE 41's ``fetch_first``, ISSUE 56's rest): a
part is a kind of its own that the fold adds to its parent as well as
keeping under its own name (``_PART_OF``), so the four worker counters
still sum to the two waits and a part never exceeds its parent.
``worker_other`` has five, named by ``obs.device.part`` on the worker
(``WORKER_PARTS``); ``hop`` has two, by direction: ``hop_out`` from the
wait's opening to the worker's first span, ``hop_back`` from the close of
its outermost span to the fold — finished work waiting for the event loop.

Beside the wall, the threads' own CPU (``time.thread_time``), each read on
its own thread: the worker's inside ``dispatch`` and inside
``worker_other``, at the switches that pass from one of the two to the
other or out of both, published with the call the loop folds (closed
segments only: the loop never reads another thread's clock), and the loop
thread's while the ledger runs — the scheduler AND the HTTP work that
shares the thread — at ``start``, ``stop`` and each ``stats()`` taken on
that thread. A phase whose wall grew and whose CPU did not was not
running.

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

from .device import WORKER_PARTS, phase, worker_call

LOOP_PHASES = ("parked", "admit", "prefill_wait", "decode_wait", "emit",
               "other")
WORKER_PHASES = ("hop", "dispatch", "fetch", "worker_other")
# A part's parent: the loop folds a part into its parent AND keeps it as a
# sub-counter. ``fetch_first`` is the read of prefill's first token; the
# hand-off by direction; the worker's remainder by what it did.
_PART_OF = {"fetch_first": "fetch", "hop_out": "hop", "hop_back": "hop",
            **{"worker_" + p: "worker_other" for p in WORKER_PARTS}}
# What a worker span can be booked under (``obs.device.worker_kind``).
_WORKER_KINDS = WORKER_PHASES + tuple(_PART_OF)
# The worker's own CPU, by the counter the kind's wall is in; ``fetch`` is
# a blocked thread and ``hop`` is no thread's: neither has one.
CPU_PHASES = ("dispatch", "worker_other")
_CPU_OF = {k: _PART_OF.get(k, k) for k in _WORKER_KINDS
           if _PART_OF.get(k, k) in CPU_PHASES}
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


class WorkerCall:
    """One worker-thread call's wall, split by kind: the kind that is open,
    when it opened on the wall's clock and on the worker's CPU clock, the
    seconds closed per kind, the CPU seconds closed per ``CPU_PHASES``.
    The worker writes at each switch, the loop reads; the lock makes a
    clock reading and the state it belongs to one step: without it a
    reader could book a span's first moments (the worker between its clock
    read and its publish) under the span before, and step back at the next
    reading. It is held for a few statements, never across a call.

    The call opens in ``hop_out`` (the loop made it); every switch after
    that is the worker's, reads the worker's CPU clock where it passes from
    one CPU counter's kinds to another's, and a switch back to the opening
    kind is the way back, ``hop_back``."""

    __slots__ = ("_clock", "_cpu_clock", "_lock", "_kind", "_t", "_cpu_t",
                 "_acc", "_cpu")

    def __init__(self, clock: Callable[[], float], t0: float,
                 cpu_clock: Callable[[], float] = time.thread_time):
        self._clock = clock
        self._cpu_clock = cpu_clock
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._kind, self._t = "hop_out", t0
        self._cpu_t = 0.0       # the worker's CPU clock where a counter opened
        self._acc = dict.fromkeys(_WORKER_KINDS, 0.0)
        self._cpu = dict.fromkeys(CPU_PHASES, 0.0)

    def switch(self, kind: str) -> str:
        """Close the current kind's segment and open ``kind``'s; returns
        the kind that was current (to restore on the way out). Worker
        thread only."""
        if kind == "hop_out":
            kind = "hop_back"
        with self._lock:
            now = self._clock()
            prev = self._kind
            self._acc[prev] += now - self._t
            was, now_in = _CPU_OF.get(prev), _CPU_OF.get(kind)
            if was != now_in:
                # The CPU clock is a system call (5.5-5.8 us on the chip's
                # host): read where the CPU's counter changes, not at a
                # part's edges inside ``worker_other``.
                cpu = self._cpu_clock()
                if was is not None:
                    self._cpu[was] += cpu - self._cpu_t
                self._cpu_t = cpu
            self._kind, self._t = kind, now
        return prev

    def split(self) -> tuple[float, dict[str, float], dict[str, float]]:
        """``(now, seconds per kind, CPU seconds per CPU_PHASES)``. The
        wall counts the open segment, so it sums to ``now`` minus the
        wait's start; the CPU counts closed segments only (the reader is
        another thread)."""
        with self._lock:
            now = self._clock()
            acc, cpu = dict(self._acc), dict(self._cpu)
            acc[self._kind] += now - self._t
        return now, acc, cpu


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

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 cpu_clock: Callable[[], float] = time.thread_time):
        self._clock = clock
        self._cpu_clock = cpu_clock
        # A part ("fetch_first", "hop_out", "worker_state") is a part of
        # its parent, not a phase beside it; the "*_cpu" are CPU beside a
        # wall, not walls.
        self._ms = dict.fromkeys(
            LOOP_PHASES + _WORKER_KINDS + ("loop_cpu",)
            + tuple(k + "_cpu" for k in CPU_PHASES), 0.0)
        self._cur = "other"
        self._t: float | None = None        # None = the loop is not running
        # The loop's thread, and its CPU clock where ``loop_cpu`` was last
        # brought up to date.
        self._tid: int | None = None
        self._cpu_t = 0.0
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
            self._tid, self._cpu_t = threading.get_ident(), self._cpu_clock()

    def stop(self) -> None:
        if self._t is not None:
            self._advance(self._clock(), "other")
            self._read_loop_cpu()
            self._t = None

    def _read_loop_cpu(self) -> None:
        """Bring ``loop_cpu`` up to now, if the caller is the loop's thread
        (a thread reads no clock but its own). Not at the boundaries: the
        CPU clock is a system call, and a step has ten."""
        if self._t is not None and threading.get_ident() == self._tid:
            cpu = self._cpu_clock()
            self._ms["loop_cpu"] += 1e3 * (cpu - self._cpu_t)
            self._cpu_t = cpu

    @staticmethod
    def _fold(ms: dict[str, float], call: WorkerCall) -> float:
        """Add ``call``'s split, up to now, to the worker counters of
        ``ms``, a part under its parent as well as under its own name;
        returns that now."""
        now, acc, cpu = call.split()
        for k, s in acc.items():
            if s:
                ms[k] += 1e3 * s
                if k in _PART_OF:
                    ms[_PART_OF[k]] += 1e3 * s
        for k, s in cpu.items():
            ms[k + "_cpu"] += 1e3 * s
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
        call = self._call = WorkerCall(self._clock, t0, self._cpu_clock)
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
        last boundary, where they were credited; the loop's CPU up to now
        when the reader is the loop's thread, else up to its last reading."""
        self._read_loop_cpu()
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
