"""Scheduler flight recorder: a fixed-size ring of per-step and
per-lifecycle records emitted by the engine loop (ISSUE 7).

PR 4's span trees answer "where did request X spend its time" and the
``/metrics`` plane answers "what are the aggregates" — but the scheduler's
*decisions* (batch composition, burst depth, clamp engagements, page
pressure, admission order) were computed every step and then thrown away
into EMAs. This module keeps the last ``capacity`` of them, cheap enough
to leave on in production:

* **Preallocated, allocation-free appends.** The ring is one numpy
  structured array plus a fixed-length Python list for request-id
  references; an append is a handful of scalar stores into preexisting
  storage — no dict/list/object construction on the step path. Request
  ids are only attached to *lifecycle* records (admit/finish/shed — per
  request, not per step), and storing a reference into a preallocated
  list slot is a pointer write.
* **Single-writer, no locks.** Every append happens on the engine's
  event-loop thread (the scheduler), marked ``# guarded-by: loop`` and
  enforced by the runtime sanitizer (the recorder is on its instrumented
  class list). Readers — the ``GET /v1/api/flight`` handler and the
  stats bridge — also run on the loop, so there is no cross-thread
  access at all.
* **Sequence numbers cross-link the planes.** Every record carries a
  monotonically increasing ``seq``; a request's admit/finish seqs are
  stamped onto its GenRequest and surfaced as span attributes in the
  ``/v1/api/trace/{id}`` tree, so an operator can jump from one
  request's trace to the exact scheduler steps that served it (and
  ``tools/flight_report.py`` renders both on one Perfetto timeline).

``snapshot()`` (the read side) allocates freely — it runs per HTTP read,
not per step.
"""
from __future__ import annotations

import math
import time
from typing import Any, Callable

import numpy as np

DEFAULT_CAPACITY = 4096

# Record kinds.
STEP = 1          # one scheduler iteration that did work
ADMIT = 2         # request got a slot (queue-wait + prefix-hit accounting)
FINISH = 3        # request left its slot (any reason, incl. cancel)
SHED = 4          # admission refused on a full queue (gateway 429 path)
EVICT = 5         # prefix-cache eviction under page pressure
PROF = 6          # profiler capture start/stop (ISSUE 8): rid = trace dir
SUPERVISOR = 7    # engine lifecycle transition (ISSUE 14): flag = state
PREFILL = 8       # one compiled prefill dispatch (ISSUE 26): what it ran

KIND_NAMES = {STEP: "step", ADMIT: "admit", FINISH: "finish",
              SHED: "shed", EVICT: "evict", PROF: "profile",
              SUPERVISOR: "supervisor", PREFILL: "prefill"}

# SUPERVISOR flag values: index into this tuple = the state entered.
# Mirrors reliability/supervisor.py LIFECYCLE_STATES (order matters —
# the flight-report goldens pin the rendered names).
SUPERVISOR_STATES = ("starting", "serving", "draining", "restarting",
                     "failed", "stopped")

# PROF flag values (capture lifecycle).
PROF_START = 1
PROF_STOP = 2

# Pool tags (ISSUE 13, disaggregated serving): which scheduler pool
# emitted the record. 0 = the unified (single-pool) scheduler — the
# value every pre-disagg ring carries, so unified snapshots are
# byte-identical to the pre-pool format (the field is only emitted
# when nonzero).
POOL_UNIFIED = 0
POOL_PREFILL = 1
POOL_DECODE = 2
POOL_NAMES = {POOL_UNIFIED: "unified", POOL_PREFILL: "prefill",
              POOL_DECODE: "decode"}

# STEP flag bits: what the scheduler iteration actually ran.
F_PREFILL = 1     # >=1 prefill chunk dispatched
F_DECODE = 2      # a decode burst ran
F_SPEC = 4        # the burst was speculative
F_BUSY = 8        # burst depth picked under the busy (interleave) policy
F_CLAMPED = 16    # the prefill-aware TTFT clamp shortened this burst

_DTYPE = np.dtype([
    ("seq", np.int64),          # monotonically increasing record number
    ("t", np.float64),          # record END time (tracer clock domain)
    ("dur_ms", np.float32),     # covered wall time (0 for point events)
    ("kind", np.uint8),
    ("flag", np.uint8),         # STEP: F_* bits; FINISH: reason code
    ("slot", np.int16),         # lifecycle records; -1 = n/a
    ("depth", np.int16),        # decode burst depth (STEP) / group K
                                # (PREFILL: rows in the compiled call)
    ("tokens", np.int32),       # tokens emitted (STEP) / generated (FINISH)
                                # / prompt tokens in the call (PREFILL)
    ("chunks", np.int16),       # prefill chunk dispatches this step
                                # (PREFILL: KV pages the kernel walked)
    ("active", np.int16),       # running requests after the step
                                # (PREFILL: query positions a row-block
                                # of the attention kernel)
    ("free_slots", np.int16),   # (PREFILL: KV heads a program of it)
    ("queued", np.int16),       # admission queue depth (+ parked head)
    ("free_pages", np.int32),   # paged pool headroom; -1 = dense layout
                                # (PREFILL: lowest start position)
    ("fitted_ms", np.float32),  # engine's fitted per-step time (NaN unset)
    ("val", np.float32),        # kind-specific: decode-burst wall ms
                                # (STEP), queue-wait ms (ADMIT), pages
                                # evicted (EVICT), chunk bucket (PREFILL)
    ("spec_acc", np.int32),     # SPEC steps: accepted draft tokens this
                                # burst (tokens - spec_acc = what a plain
                                # burst of the same depth would have made)
                                # (PREFILL: highest start position)
    ("pool", np.uint8),         # POOL_* tag; 0 = unified scheduler
])

FINISH_REASONS = ("stop", "length", "cancelled", "error")


def step_kind(flag: int) -> str:
    """The human name of a STEP record's composition."""
    pf, dc = bool(flag & F_PREFILL), bool(flag & F_DECODE)
    if pf and dc:
        return "mixed"
    if pf:
        return "prefill"
    if dc:
        return "spec" if flag & F_SPEC else "decode"
    return "idle"


class FlightRecorder:
    """Fixed-capacity ring of scheduler records. Single-writer (the engine
    loop); appended fields are all ``guarded-by: loop``."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 clock: Callable[[], float] = time.monotonic):
        self.capacity = max(16, int(capacity))
        self.clock = clock
        self._buf = np.zeros(self.capacity, _DTYPE)     # guarded-by: loop
        # Column views cached once: a structured-array field lookup
        # (buf["seq"]) is a per-call dict hit + view construction — on
        # the step path that was most of the append cost. The views
        # alias _buf's memory, so snapshot() reads stay coherent.
        self._cols = {name: self._buf[name] for name in _DTYPE.names}
        # Request-id references for lifecycle records, parallel to _buf.
        # Preallocated: an append stores a reference into an existing
        # slot, never grows the list.
        self._rid = [None] * self.capacity              # guarded-by: loop
        self._seq = 0                                   # guarded-by: loop
        # Lifecycle balance counters: every admitted request must leave a
        # FINISH record (the chaos tests assert admits == finishes — a
        # "leaked" flight record is a request the scheduler lost track of).
        self._admits = 0                                # guarded-by: loop
        self._finishes = 0                              # guarded-by: loop
        self._sheds = 0                                 # guarded-by: loop

    # -- hot path (engine loop only) ----------------------------------------
    def record(self, kind: int, *, dur_ms: float = 0.0, flag: int = 0,
               slot: int = -1, depth: int = 0, tokens: int = 0,
               chunks: int = 0, active: int = 0, free_slots: int = 0,
               queued: int = 0, free_pages: int = -1,
               fitted_ms: float = math.nan, val: float = 0.0,
               spec_acc: int = 0, pool: int = 0,
               rid: str | None = None, t: float | None = None) -> int:
        """Append one record; returns its sequence number. Scalar stores
        into preallocated storage only — no per-record allocation. ``t``
        is the record's end time when it is not now (a PREFILL record is
        written loop-side after the worker's dispatch ended)."""
        i = self._seq % self.capacity
        cols = self._cols
        cols["seq"][i] = self._seq
        cols["t"][i] = self.clock() if t is None else t
        cols["dur_ms"][i] = dur_ms
        cols["kind"][i] = kind
        cols["flag"][i] = flag
        cols["slot"][i] = slot
        cols["depth"][i] = depth
        cols["tokens"][i] = tokens
        cols["chunks"][i] = chunks
        cols["active"][i] = active
        cols["free_slots"][i] = free_slots
        cols["queued"][i] = queued
        cols["free_pages"][i] = free_pages
        cols["fitted_ms"][i] = fitted_ms
        cols["val"][i] = val
        cols["spec_acc"][i] = spec_acc
        cols["pool"][i] = pool
        self._rid[i] = rid
        seq = self._seq
        self._seq += 1
        if kind == ADMIT:
            self._admits += 1
        elif kind == FINISH:
            self._finishes += 1
        elif kind == SHED:
            self._sheds += 1
        return seq

    # -- read side (also loop-thread; allocates freely) ---------------------
    @property
    def seq(self) -> int:
        """Next sequence number (== total records ever appended)."""
        return self._seq

    @property
    def evicted(self) -> int:
        """Records overwritten by ring wrap — flight loss under load."""
        return max(0, self._seq - self.capacity)

    def snapshot(self, since: int = -1) -> list[dict[str, Any]]:
        """Records with ``seq > since`` still resident, oldest first."""
        lo = max(self._seq - self.capacity, since + 1, 0)
        out: list[dict[str, Any]] = []
        for s in range(lo, self._seq):
            i = s % self.capacity
            row = self._buf[i]
            kind = int(row["kind"])
            d: dict[str, Any] = {
                "seq": int(row["seq"]),
                "t": float(row["t"]),
                "kind": KIND_NAMES.get(kind, str(kind)),
            }
            dur = float(row["dur_ms"])
            if dur:
                d["dur_ms"] = round(dur, 3)
            if kind == STEP:
                flag = int(row["flag"])
                d["step_kind"] = step_kind(flag)
                d["busy"] = bool(flag & F_BUSY)
                d["clamped"] = bool(flag & F_CLAMPED)
                if row["depth"]:
                    d["burst_depth"] = int(row["depth"])
                if row["chunks"]:
                    d["prefill_chunks"] = int(row["chunks"])
                d["tokens"] = int(row["tokens"])
                d["active"] = int(row["active"])
                d["free_slots"] = int(row["free_slots"])
                d["queued"] = int(row["queued"])
                if row["free_pages"] >= 0:
                    d["free_pages"] = int(row["free_pages"])
                if flag & F_SPEC:
                    # Accepted draft tokens this burst: the speculation
                    # win over a plain burst of the same depth.
                    d["spec_accepted"] = int(row["spec_acc"])
                dv = float(row["val"])
                if dv:
                    d["decode_wall_ms"] = round(dv, 3)
                    if row["depth"]:
                        d["measured_step_ms"] = round(
                            dv / int(row["depth"]), 3)
                fitted = float(row["fitted_ms"])
                if not math.isnan(fitted):
                    d["fitted_step_ms"] = round(fitted, 3)
            elif kind == ADMIT:
                d["slot"] = int(row["slot"])
                d["queue_wait_ms"] = round(float(row["val"]), 3)
                d["cached_tokens"] = int(row["tokens"])
                d["queued"] = int(row["queued"])
            elif kind == FINISH:
                d["slot"] = int(row["slot"])
                reason = int(row["flag"])
                d["reason"] = (FINISH_REASONS[reason]
                               if reason < len(FINISH_REASONS) else "?")
                d["tokens"] = int(row["tokens"])
            elif kind == EVICT:
                d["pages_evicted"] = int(row["val"])
                if row["free_pages"] >= 0:
                    d["free_pages"] = int(row["free_pages"])
            elif kind == PREFILL:
                # One compiled prefill dispatch (ISSUE 26): its shape and
                # where in their prompts its rows started; [t - dur_ms, t]
                # is the jitted call on the worker thread. What a
                # paged-prefill roofline needs per call.
                d["rows"] = int(row["depth"])
                d["bucket"] = int(row["val"])
                d["tokens"] = int(row["tokens"])
                d["pos_lo"] = int(row["free_pages"])
                d["pos_hi"] = int(row["spec_acc"])
                d["pages_walked"] = int(row["chunks"])
                if row["active"]:
                    # The attention kernel's block (ISSUE 48): positions a
                    # row-block x KV heads a program. A dense cache runs
                    # no paged kernel and names none.
                    d["block"] = "%dx%d" % (row["active"],
                                            row["free_slots"])
            elif kind == PROF:
                # Profiler capture boundary (ISSUE 8): the rid carries
                # the capture's trace directory, so a Perfetto timeline
                # built from this ring cross-links to the XLA capture
                # that covered these seqs.
                d["phase"] = ("start" if int(row["flag"]) == PROF_START
                              else "stop")
            elif kind == SUPERVISOR:
                # Engine lifecycle transition (ISSUE 14): the state the
                # engine ENTERED; rid carries the transition reason so
                # an incident reads off the ring without joining logs.
                flag = int(row["flag"])
                d["state"] = (SUPERVISOR_STATES[flag]
                              if flag < len(SUPERVISOR_STATES) else "?")
            pool = int(row["pool"])
            if pool:
                # Disagg pool tag (ISSUE 13). Omitted for the unified
                # scheduler so pre-pool snapshot consumers (and the
                # flight-report goldens) see the exact old shape.
                d["pool"] = POOL_NAMES.get(pool, str(pool))
            rid = self._rid[i]
            if rid:
                # The rid slot is kind-polymorphic: SUPERVISOR records
                # store the transition reason there (no request owns a
                # lifecycle event).
                d["reason" if kind == SUPERVISOR else "request_id"] = rid
            out.append(d)
        return out

    def steps_overlapping(self, t0: float, t1: float,
                          flag_mask: int = F_DECODE) -> float:
        """Total milliseconds of resident STEP records matching
        ``flag_mask`` that overlap the window ``[t0, t1]`` — the SLO
        attribution plane's "how much of this request's prefill window
        went to decode contention" query (obs/slo.py)."""
        if t1 <= t0:
            return 0.0
        lo = max(self._seq - self.capacity, 0)
        total = 0.0
        buf = self._buf
        for s in range(lo, self._seq):
            i = s % self.capacity
            if int(buf["kind"][i]) != STEP:
                continue
            if not (int(buf["flag"][i]) & flag_mask):
                continue
            end = float(buf["t"][i])
            # The decode burst's own wall (val) when recorded — a mixed
            # step's prefill share must not count as decode contention;
            # the burst runs last in the step, so it ends ~at the record.
            width = float(buf["val"][i]) or float(buf["dur_ms"][i])
            start = end - width / 1000.0
            ov = min(end, t1) - max(start, t0)
            if ov > 0:
                total += ov * 1000.0
        return total

    def stats(self) -> dict[str, Any]:
        """Counters for the stats()/metrics bridge and the leak check."""
        return {
            "flight_seq": self._seq,
            "flight_capacity": self.capacity,
            "flight_evicted_total": self.evicted,
            "flight_admits": self._admits,
            "flight_finishes": self._finishes,
            "flight_sheds": self._sheds,
        }
