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
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Iterator

from .device import phase, worker_call

LOOP_PHASES = ("parked", "admit", "prefill_wait", "decode_wait", "emit",
               "other")
WORKER_PHASES = ("hop", "dispatch", "fetch", "worker_other")
_KINDS = {k: i for i, k in enumerate(WORKER_PHASES)}


def _credit(acc: tuple, kind: str, seconds: float) -> tuple:
    """``acc`` (seconds per WORKER_PHASES) with ``seconds`` more on
    ``kind``, as a new tuple."""
    i = _KINDS[kind]
    return acc[:i] + (acc[i] + seconds,) + acc[i + 1:]


class WorkerCall:
    """One worker-thread call's wall, split four ways. ``live`` is
    ``(kind, t_mark, seconds per WORKER_PHASES)``, replaced whole at each
    switch by the worker and read whole by the loop. The lock makes a
    clock reading and the tuple it belongs to one step: without it a
    reader could book a span's first moments (the worker between its clock
    read and its publish) under the span before, and step back at the
    next reading. It is held for two statements, never across a call."""

    __slots__ = ("_clock", "_lock", "live")

    def __init__(self, clock: Callable[[], float], t0: float):
        self._clock = clock
        self._lock = threading.Lock()
        self.live = ("hop", t0, (0.0, 0.0, 0.0, 0.0))  # guarded-by: _lock

    def switch(self, kind: str) -> str:
        """Close the current kind's segment and open ``kind``'s; returns
        the kind that was current (to restore on the way out)."""
        with self._lock:
            prev, t_mark, acc = self.live
            now = self._clock()
            self.live = (kind, now, _credit(acc, prev, now - t_mark))
        return prev

    def split(self) -> tuple[float, tuple[float, float, float, float]]:
        """``(now, seconds per WORKER_PHASES)`` with the open segment
        counted: the four sum to ``now`` minus the wait's start."""
        with self._lock:
            kind, t_mark, acc = self.live
            now = self._clock()
        return now, _credit(acc, kind, now - t_mark)


class SchedLedger:
    """The loop's wall since ``start()``, partitioned. Every method runs
    on the engine's event-loop thread."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._ms = dict.fromkeys(LOOP_PHASES + WORKER_PHASES, 0.0)
        self._cur = "other"
        self._t: float | None = None        # None = the loop is not running
        self._call: WorkerCall | None = None

    def start(self) -> None:
        if self._t is None:
            self._t, self._cur = self._clock(), "other"

    def stop(self) -> None:
        if self._t is not None:
            self._advance(self._clock(), "other")
            self._t = None

    @staticmethod
    def _fold(ms: dict[str, float], call: WorkerCall) -> float:
        """Add ``call``'s split, up to now, to the worker counters of
        ``ms``; returns that now."""
        now, acc = call.split()
        for k, s in zip(WORKER_PHASES, acc):
            ms[k] += 1e3 * s
        return now

    def _advance(self, now: float, phase_: str) -> str:
        self._ms[self._cur] += 1e3 * (now - self._t)
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
    def wait(self, name: str) -> Iterator[None]:
        """Around ``await asyncio.to_thread(...)``: the loop is in the wait
        phase ``name`` and the worker has a call to account into. On the
        way out (returned, raised or cancelled) the call is folded into
        the worker counters and the wait phase left, at one clock reading.
        Held across the await by design: it is ledger state, not a
        profiler span."""
        if self._t is None:
            yield
            return
        self._advance(self._clock(), name)
        call = self._call = WorkerCall(self._clock, self._t)
        token = worker_call.set(call)
        try:
            yield
        finally:
            worker_call.reset(token)
            self._call = None
            if self._t is not None:
                self._advance(self._fold(self._ms, call), "other")

    def stats(self) -> dict[str, float]:
        """Flat monotone counters, milliseconds, the open segment (and the
        call in flight) counted up to now."""
        ms = dict(self._ms)
        if self._t is not None:
            now = (self._fold(ms, self._call) if self._call is not None
                   else self._clock())
            ms[self._cur] += 1e3 * (now - self._t)
        return {f"sched_{k}_ms_total": round(v, 3) for k, v in ms.items()}
