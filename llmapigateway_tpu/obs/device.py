"""Device observability plane (ISSUE 8): HBM memory ledger, per-kernel
roofline cost registry, phase annotations, and XLA compile-event telemetry.

The host-side planes (PR 4 metrics/tracing, PR 7 flight recorder + SLO
attribution) stop at the dispatch boundary: the engine reported ONE
aggregate ``roofline_fraction`` and nothing said which compiled kernel is
off the HBM roof, how much HBM each subsystem actually holds, or when XLA
silently recompiled mid-serving. This module is the device-side substrate:

* :class:`HbmLedger` — static accounting of what the engine *intends* to
  hold in device memory (parameter bytes per dtype, KV-pool bytes from
  page geometry, penalty/spec auxiliaries), reconciled at scrape time
  against (a) the bytes the engine's live buffers actually occupy
  (``tracked`` — array metadata only, no device sync) and (b) the
  runtime's own ``device.memory_stats()`` where the backend provides one
  (TPU does; CPU returns None). Exported as ``gateway_engine_hbm_*``
  gauges, with a configurable headroom watermark that feeds the PR 3
  shed path so admission reacts to memory pressure, not just slots.
* :class:`KernelRegistry` — one row per compiled executable variant
  (prefill-chunk buckets, decode bursts per depth/sampler, spec bursts)
  carrying ``lower().compile().cost_analysis()`` FLOPs + bytes (resolved
  off-thread — an AOT lower can cost seconds at 8B scale) joined with
  the walls the engine measures at dispatch and with the PR 7 flight
  ring's per-step records. ``GET /v1/api/roofline`` serves the table and
  names the single worst kernel — the "pick the next kernel target"
  reading ROADMAP item 3 asks for.
* :func:`phase` — host-side ``jax.profiler.TraceAnnotation`` markers
  (``prefill`` / ``decode`` / ``spec.verify``) so on-demand captures
  from ``server/profiler_api.py`` segment by scheduler phase in
  Perfetto, plus a thread-local phase tag the compile monitor reads to
  attribute a recompile to the kernel that triggered it. (The *in-
  program* markers — ``decode.attention`` / ``decode.mlp`` /
  ``sampling`` — are ``jax.named_scope`` calls in models/ and engine/:
  pure trace-time metadata, zero runtime cost.)
* :class:`XlaCompileMonitor` — a ``jax.monitoring`` listener counting
  backend compiles and their wall time per phase, bridged to the
  ``gateway_engine_xla_compile_*`` series and recorded as ``xla.compile``
  spans on the active request trace (contextvars propagate through
  ``asyncio.to_thread``, so a mid-serving recompile lands inside the
  request that paid for it). Surprise recompiles are a silent TTFT
  killer; this makes them a reading.

Thread model: the engine's worker thread records kernel walls and phase
tags; scrape-time readers run on the event loop. ``KernelRegistry`` and
``XlaCompileMonitor`` therefore guard their state with plain locks
(scalar adds — no allocation worth worrying about); ``HbmLedger`` is
read-mostly and computes its snapshots from immutable statics plus
callables the engine provides.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import logging
import math
import threading
import time
from typing import Any, Callable

logger = logging.getLogger(__name__)

__all__ = [
    "HbmLedger", "KernelRegistry", "XlaCompileMonitor", "phase", "part",
    "current_phase", "install_compile_monitor", "compile_monitor",
    "device_memory_stats", "worst_kernel",
]


# ---------------------------------------------------------------------------
# Device memory probing
# ---------------------------------------------------------------------------

def device_memory_stats(devices: list | None = None) -> dict[str, int] | None:
    """Aggregate ``memory_stats()`` over ``devices`` (default: this
    process's addressable devices). Returns ``{bytes_in_use, peak_bytes,
    bytes_limit}`` summed across devices, or None when the backend
    exposes no allocator stats (CPU) or JAX is unavailable (proxy-only
    deployments). Never raises — a stats probe must not take down a
    scrape."""
    try:
        import jax
        devs = devices if devices is not None else jax.local_devices()
        in_use = peak = limit = 0
        seen = False
        for d in devs:
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if not ms:
                continue
            seen = True
            in_use += int(ms.get("bytes_in_use", 0))
            peak += int(ms.get("peak_bytes_in_use",
                               ms.get("bytes_in_use", 0)))
            limit += int(ms.get("bytes_limit", 0))
        if not seen:
            return None
        return {"bytes_in_use": in_use, "peak_bytes": peak,
                "bytes_limit": limit}
    except Exception:
        return None


class HbmLedger:
    """Static HBM accounting for one engine, reconciled against live state.

    ``weights`` / ``kv_pool`` / ``aux`` / ``spec`` are the byte totals the
    engine computes ONCE from its checkpoint dtypes and cache geometry
    (they never change after init). ``tracked_fn`` returns what the
    engine's live device buffers occupy right now (sum of array
    ``nbytes`` — metadata only); ``mem_fn`` probes the runtime allocator
    (injectable for tests and for backends without one), TTL-cached so a
    per-admission watermark check costs a clock read."""

    def __init__(self, *, weights: int, kv_pool: int, aux: int = 0,
                 spec: int = 0, page_bytes: int = 0,
                 kv_pools: dict[str, int] | None = None,
                 tracked_fn: Callable[[], int] | None = None,
                 mem_fn: Callable[[], dict | None] | None = None,
                 mem_ttl_s: float = 0.5,
                 clock: Callable[[], float] = time.monotonic):
        self.weights = int(weights)
        self.kv_pool = int(kv_pool)
        self.aux = int(aux)
        self.spec = int(spec)
        self.page_bytes = int(page_bytes)   # K+V bytes of ONE physical page
        # ``kv_pool`` by cache group ("global", "window4096"): one pool a
        # group of softmax layers that keep the same KV.
        self.kv_pools = dict(kv_pools or {})
        self.tracked_fn = tracked_fn
        self.mem_fn = mem_fn or device_memory_stats
        self.mem_ttl_s = mem_ttl_s
        self._clock = clock
        self._mem_cache: dict | None = None
        self._mem_stamp = -math.inf

    @property
    def static_total(self) -> int:
        return self.weights + self.kv_pool + self.aux + self.spec

    def device_memory(self) -> dict | None:
        """The runtime allocator's view, TTL-cached (the watermark check
        runs per admission)."""
        now = self._clock()
        if now - self._mem_stamp >= self.mem_ttl_s:
            try:
                self._mem_cache = self.mem_fn()
            except Exception:
                self._mem_cache = None
            self._mem_stamp = now
        return self._mem_cache

    def headroom_fraction(self) -> float | None:
        """Free fraction of the device memory limit (None when the backend
        reports no allocator stats — the watermark is inert there)."""
        mem = self.device_memory()
        if not mem or not mem.get("bytes_limit"):
            return None
        limit = mem["bytes_limit"]
        return max(0.0, (limit - mem.get("bytes_in_use", 0)) / limit)

    def snapshot(self, *, prefix_resident_pages: int = 0) -> dict[str, Any]:
        """Flat ``hbm_*`` fields for the engine's ``stats()`` dict (the
        obs collector bridges them onto ``gateway_engine_hbm_*``)."""
        out: dict[str, Any] = {
            "hbm_weights_bytes": self.weights,
            "hbm_kv_pool_bytes": self.kv_pool,
            "hbm_aux_bytes": self.aux,
            "hbm_ledger_bytes": self.static_total,
        }
        # A pool a cache group where the model has several, or one of a
        # kind of its own (a latent pool).
        if len(self.kv_pools) > 1 or "latent" in self.kv_pools:
            out["hbm_kv_pools_bytes"] = dict(self.kv_pools)
        if self.spec:
            out["hbm_spec_bytes"] = self.spec
        if self.page_bytes and prefix_resident_pages:
            out["hbm_prefix_resident_bytes"] = (
                prefix_resident_pages * self.page_bytes)
        if self.tracked_fn is not None:
            try:
                out["hbm_tracked_bytes"] = int(self.tracked_fn())
            except Exception:       # a sick buffer must not break stats()
                logger.debug("hbm tracked-bytes probe failed", exc_info=True)
        mem = self.device_memory()
        if mem:
            out["hbm_device_in_use_bytes"] = mem["bytes_in_use"]
            out["hbm_device_peak_bytes"] = mem["peak_bytes"]
            if mem.get("bytes_limit"):
                out["hbm_device_limit_bytes"] = mem["bytes_limit"]
                out["hbm_headroom_ratio"] = round(
                    max(0.0, (mem["bytes_limit"] - mem["bytes_in_use"])
                        / mem["bytes_limit"]), 4)
        return out


# ---------------------------------------------------------------------------
# Per-kernel roofline cost registry
# ---------------------------------------------------------------------------

class _Kernel:
    __slots__ = ("name", "kind", "variant", "calls", "steps", "wall_ms",
                 "walled_steps", "flops", "xla_bytes", "cost_fn",
                 "cost_error")

    def __init__(self, name: str, kind: str, variant: dict | None):
        self.name = name
        self.kind = kind
        self.variant = dict(variant or {})
        self.calls = 0
        self.steps = 0
        self.wall_ms = 0.0
        self.walled_steps = 0
        self.flops: float | None = None      # per invocation (cost_analysis)
        self.xla_bytes: float | None = None  # per invocation (cost_analysis)
        self.cost_fn: Callable[[], Any] | None = None
        self.cost_error: str | None = None


def _cost_numbers(analysis: Any) -> tuple[float | None, float | None]:
    """(flops, bytes accessed) out of whatever shape ``cost_analysis()``
    returns on this backend (dict on some, list-of-dicts on others)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    if not isinstance(analysis, dict):
        return None, None
    flops = analysis.get("flops")
    nbytes = analysis.get("bytes accessed")
    return (float(flops) if flops is not None else None,
            float(nbytes) if nbytes is not None else None)


class KernelRegistry:
    """Counts, measured walls, and static XLA costs per compiled kernel.

    The engine registers a kernel the first time it dispatches the
    variant (prefill bucket × K, decode depth × sampler, spec depth) and
    records every later dispatch with :meth:`record` — a lock-guarded
    handful of scalar adds. ``cost_fn`` closures (AOT
    ``lower().compile().cost_analysis()``) resolve ON DEMAND via
    :meth:`resolve_costs`: re-lowering an 8B program can cost seconds,
    which must never land on the step path or the event loop — the
    roofline endpoint drains pending closures in ``asyncio.to_thread``
    at read time, the bench drains synchronously after each rung. (An
    always-on background resolver was tried and reverted: a thread
    compiling XLA programs concurrently with engine churn / interpreter
    teardown segfaulted the process.)"""

    def __init__(self):
        self._lock = threading.Lock()
        self._kernels: dict[str, _Kernel] = {}      # guarded-by: _lock
        self._pending: list[str] = []               # guarded-by: _lock

    def needs(self, name: str) -> bool:
        """True when the kernel is not yet registered — the caller then
        pays the (one-time) aval-capture cost to build its cost_fn."""
        with self._lock:
            return name not in self._kernels

    def register(self, name: str, kind: str, *, variant: dict | None = None,
                 cost_fn: Callable[[], Any] | None = None) -> None:
        """Idempotent; first registration wins."""
        with self._lock:
            if name in self._kernels:
                return
            k = _Kernel(name, kind, variant)
            k.cost_fn = cost_fn
            self._kernels[name] = k
            if cost_fn is not None:
                self._pending.append(name)

    def record(self, name: str, *, steps: int = 1,
               wall_ms: float | None = None) -> None:
        """One dispatch of ``name`` covering ``steps`` device steps.
        ``wall_ms`` only when the caller measured an honest wall for this
        dispatch (lag-one pipelining makes some walls lies — those calls
        still count, they just don't contribute to the step-time
        estimate)."""
        with self._lock:
            k = self._kernels.get(name)
            if k is None:
                k = _Kernel(name, "unknown", None)
                self._kernels[name] = k
            k.calls += 1
            k.steps += steps
            if wall_ms is not None:
                k.wall_ms += wall_ms
                k.walled_steps += steps

    # -- cost resolution (on demand, caller's thread) -----------------------
    def resolve_costs(self) -> None:
        """Drain pending cost_fns synchronously. Callers keep it off hot
        paths and off the event loop (the roofline endpoint wraps it in
        ``asyncio.to_thread``); concurrent callers are safe — the queue
        pop is lock-guarded and each closure runs at most once."""
        while True:
            with self._lock:
                if not self._pending:
                    return
                name = self._pending.pop(0)
                k = self._kernels.get(name)
                fn = k.cost_fn if k is not None else None
            if fn is None:
                continue
            try:
                # Tag the resolver's own AOT compiles so the compile
                # monitor attributes them to cost analysis, not to a
                # serving phase (they are expected, not "recompiles").
                with phase("cost_analysis", annotate=False):
                    flops, nbytes = _cost_numbers(fn())
            except Exception as e:
                flops = nbytes = None
                err = f"{type(e).__name__}: {e}"[:200]
                logger.debug("cost_analysis failed for %s", name,
                             exc_info=True)
            else:
                err = None
            with self._lock:
                if k is not None:
                    k.flops, k.xla_bytes = flops, nbytes
                    k.cost_error = err
                    k.cost_fn = None        # drop the captured avals

    def costs_pending(self) -> int:
        with self._lock:
            return len(self._pending)

    # -- read side ----------------------------------------------------------
    def stats(self) -> dict[str, Any]:
        with self._lock:
            return {"kernel_variants": len(self._kernels),
                    "kernel_costs_pending": len(self._pending)}

    def table(self, *, bytes_per_step_fn: Callable[[str], int | None]
              | None = None, peak_gbps: float = 0.0,
              flight: list[dict] | None = None) -> list[dict[str, Any]]:
        """One row per kernel: invocation counts, measured walls (engine
        dispatch walls joined with flight-ring step records where the
        variant is identifiable), per-step HBM bytes (the engine's
        bytes-touched model via ``bytes_per_step_fn``, with the raw
        ``cost_analysis`` numbers alongside), achieved GB/s, and roofline
        fraction. Sorted by share of measured step time, largest first."""
        with self._lock:
            kernels = [(k.name, k.kind, dict(k.variant), k.calls, k.steps,
                        k.wall_ms, k.walled_steps, k.flops, k.xla_bytes)
                       for k in self._kernels.values()]
        fj = _flight_join(flight) if flight else {}
        rows: list[dict[str, Any]] = []
        effective: dict[str, float] = {}
        for (name, kind, variant, calls, steps, wall_ms, walled_steps,
             flops, xla_bytes) in kernels:
            row: dict[str, Any] = {
                "kernel": name, "kind": kind, "calls": calls,
                "steps": steps, "wall_ms": round(wall_ms, 3),
            }
            if variant:
                row.update({f"variant_{k}": v for k, v in variant.items()})
            step_ms = (wall_ms / walled_steps) if walled_steps else None
            # Flight join: the ring's decode walls are the authoritative
            # per-step measurement for decode/spec variants (recorded by
            # the scheduler with the same clock the SLO plane uses) —
            # engine-side lag-one walls only land on steady pairs, so a
            # variant that ran once still gets a measured wall here.
            fkey = (kind, variant.get("depth"))
            fw = fj.get(fkey)
            eff_wall = wall_ms
            if fw is not None and fw["steps"]:
                row["flight_steps"] = fw["steps"]
                row["flight_wall_ms"] = round(fw["wall_ms"], 3)
                step_ms = fw["wall_ms"] / fw["steps"]
                eff_wall = max(eff_wall, fw["wall_ms"])
            if step_ms is not None:
                row["step_ms"] = round(step_ms, 4)
            effective[name] = eff_wall
            if flops is not None:
                row["xla_flops_per_call"] = flops
            if xla_bytes is not None:
                row["xla_bytes_per_call"] = xla_bytes
            nbytes = None
            if bytes_per_step_fn is not None:
                nbytes = bytes_per_step_fn(kind)
            if nbytes is None and xla_bytes is not None and steps:
                # No engine model for this kind: fall back to the XLA
                # static analysis, per step of one invocation.
                per_call_steps = max(1, steps // max(1, calls))
                nbytes = xla_bytes / per_call_steps
            if nbytes is not None:
                row["hbm_bytes_per_step"] = int(nbytes)
                if step_ms:
                    gbps = nbytes / (step_ms / 1e3) / 1e9
                    row["achieved_gbps"] = round(gbps, 3)
                    if peak_gbps > 0:
                        row["roofline_fraction"] = round(gbps / peak_gbps, 3)
            rows.append(row)
        # Step-time shares over the EFFECTIVE walls (flight-joined where
        # available): what fraction of all measured device time each
        # kernel took — the ranking column of the worst-kernel pick.
        total_wall = sum(effective.values())
        if total_wall > 0:
            for row in rows:
                row["pct_of_step_time"] = round(
                    100.0 * effective[row["kernel"]] / total_wall, 1)
        rows.sort(key=lambda r: -r.get("pct_of_step_time", 0.0))
        return rows


def _flight_join(records: list[dict]) -> dict[tuple, dict]:
    """Aggregate flight STEP records by (kind, burst depth): decode walls
    and step counts per identifiable kernel variant. ``step_kind`` names
    from obs/flight.py; a mixed step's ``decode_wall_ms`` covers only its
    decode burst, so prefill interleave doesn't pollute the join."""
    out: dict[tuple, dict] = {}
    for r in records:
        if r.get("kind") != "step":
            continue
        depth = r.get("burst_depth")
        wall = r.get("decode_wall_ms")
        if not depth or not wall:
            continue
        kind = "spec" if r.get("step_kind") == "spec" else "decode"
        slot = out.setdefault((kind, depth), {"steps": 0, "wall_ms": 0.0})
        slot["steps"] += depth
        slot["wall_ms"] += wall
    return out


def worst_kernel(rows: list[dict], min_share_pct: float = 5.0
                 ) -> str | None:
    """The single kernel furthest below the HBM roof among those taking a
    meaningful share of step time — ROADMAP item 3's "next kernel
    target". Falls back to the worst fraction at any share."""
    scored = [r for r in rows if "roofline_fraction" in r]
    if not scored:
        return None
    major = [r for r in scored
             if r.get("pct_of_step_time", 0.0) >= min_share_pct]
    pick = min(major or scored, key=lambda r: r["roofline_fraction"])
    return pick["kernel"]


# ---------------------------------------------------------------------------
# Phase annotations
# ---------------------------------------------------------------------------

_phase_local = threading.local()

# The scheduler wait in flight, as its worker thread sees it (obs/phases.py
# sets it around each ``asyncio.to_thread``, which copies the context).
# None on every other thread and for direct callers of a worker function.
worker_call: contextvars.ContextVar[Any] = contextvars.ContextVar(
    "sched_worker_call", default=None)


# What a worker call does with the wall that is neither its jitted call
# nor a blocking read, as :func:`part` names it: ``state`` the upload of the
# slots' host mirrors, ``tables`` of the page tables, ``rng`` the key split,
# ``args`` a prefill chunk's host arrays, ``mirrors`` the host bookkeeping
# after a dispatch or a read (snapshots, counters, the kernel registry).
WORKER_PARTS = ("state", "tables", "rng", "args", "mirrors")


@functools.lru_cache(maxsize=256)     # a few dozen names, asked every span
def worker_kind(span: str) -> str | None:
    """Which of the scheduler's worker counters (obs/phases.py) a span's
    wall belongs to. ``sched.fetch.*`` is a blocking device→host read,
    named by what it reads: ``.first`` prefill's first token (no program
    is queued behind it, so the device idles: kept apart as a part of
    ``fetch``), ``.burst`` the lag-one burst's tokens, ``.spec`` the
    lag-one speculative burst's, ``.sync`` the synchronous paths';
    ``prefill`` / ``decode`` / ``spec.*`` are the jitted calls with their
    argument build; ``sched.<call>.<part>`` is a named part of the call's
    own wall (``WORKER_PARTS``: kept apart as a part of ``worker_other``);
    any other ``sched.*`` span entered on the worker is its outermost one,
    where its own wall begins."""
    if span == "sched.fetch.first":
        return "fetch_first"
    if span.startswith("sched.fetch"):
        return "fetch"
    if span in ("prefill", "decode") or span.startswith("spec."):
        return "dispatch"
    if span.startswith("sched."):
        last = span.rpartition(".")[2]
        return "worker_" + last if last in WORKER_PARTS else "worker_other"
    return None


def current_phase() -> str:
    """The phase tag of the calling thread ("" outside any phase) — what
    the compile monitor stamps as a compile event's cause."""
    return getattr(_phase_local, "name", "")


# ``jax.profiler``, imported at the first annotation (False: no JAX here, a
# proxy-only deployment): a span costs no import-machinery call after it.
_profiler: Any = None


def _annotation(name: str):
    """An entered ``jax.profiler.TraceAnnotation``, or None where the
    profiler is unavailable — the tag still applies."""
    global _profiler
    if _profiler is None:
        try:
            import jax.profiler
            _profiler = jax.profiler
        except Exception:
            _profiler = False
    if not _profiler:
        return None
    try:
        ctx = _profiler.TraceAnnotation(name)
        ctx.__enter__()
        return ctx
    except Exception:
        return None


def part(name: str):
    """:func:`phase` over a named part of the enclosing worker call (one of
    ``WORKER_PARTS``), named after the call so that an idle gap's key and a
    compile's tag say both: ``sched.decode_burst.state``,
    ``sched.prefill_group.args``. Under no ``sched.*`` span (a direct call
    of a worker function's callee) it is ``sched.<name>``."""
    parent = current_phase()
    return phase(f"{parent}.{name}" if parent.startswith("sched.")
                 else "sched." + name)


@contextlib.contextmanager
def phase(name: str, annotate: bool = True):
    """Tag the calling thread with a scheduler phase and (when ``annotate``)
    emit a ``jax.profiler.TraceAnnotation`` so captures segment by phase
    on the profiler's clock. The tag always applies — compile attribution
    must work for ``cost_analysis``, the one caller that turns the
    annotation off. Inside a scheduler wait the span's wall is also the
    worker counter ``worker_kind`` names, so a span and its counter cannot
    disagree."""
    prev = getattr(_phase_local, "name", "")
    _phase_local.name = name
    call = worker_call.get()
    kind = worker_kind(name) if call is not None else None
    prev_kind = call.switch(kind) if kind is not None else None
    ctx = _annotation(name) if annotate else None
    try:
        yield
    finally:
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception:
                logger.debug("TraceAnnotation exit failed", exc_info=True)
        if prev_kind is not None:
            call.switch(prev_kind)
        _phase_local.name = prev


# ---------------------------------------------------------------------------
# XLA compile-event monitor
# ---------------------------------------------------------------------------

# The jax.monitoring event fired once per backend (XLA) compile, with its
# wall seconds: the expensive one, and the only one that implies a new
# executable (it fires on a persistent-cache hit too, the read inside it).
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# What a first call pays BEFORE (or without) a backend compile: tracing the
# function to a jaxpr, lowering it, reading the persistent cache. A retrace
# whose lowering is already cached fires the first alone — invisible to
# xla_compile_total, and a 32-layer program holds every Python thread
# while it traces. (jax 0.9: jax/_src/dispatch.py, jax/_src/compiler.py;
# compile_time_saved_sec is a saving, not a wall, and is left out.)
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_TRACE_EVENTS = frozenset({
    _TRACE_EVENT,
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
})


class XlaCompileMonitor:
    """Process-wide compile counters, by the phase tag active on the
    compiling thread. ``jax.monitoring`` listeners cannot be unregistered
    individually, so this is a singleton installed once per process;
    tests snapshot/diff the counters instead of resetting them."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._lock = threading.Lock()
        self._clock = clock
        self._by_phase: dict[str, list] = {}     # guarded-by: _lock
        self._total = 0                          # guarded-by: _lock
        self._total_s = 0.0                      # guarded-by: _lock
        self._last: dict[str, Any] | None = None  # guarded-by: _lock
        self._trace_by_phase: dict[str, list] = {}  # guarded-by: _lock
        self._traces = 0                         # guarded-by: _lock
        self._trace_s = 0.0                      # guarded-by: _lock

    def on_trace(self, event: str, dur_s: float) -> None:
        """A trace, lowering or cache-read event: seconds under the phase
        that paid for them, counted once per traced function (a nested
        jit's trace lies inside its caller's and counts again)."""
        ph = current_phase() or "startup"
        with self._lock:
            slot = self._trace_by_phase.setdefault(ph, [0, 0.0])
            if event == _TRACE_EVENT:
                slot[0] += 1
                self._traces += 1
            slot[1] += dur_s
            self._trace_s += dur_s

    def on_compile(self, dur_s: float) -> None:
        ph = current_phase() or "startup"
        with self._lock:
            slot = self._by_phase.setdefault(ph, [0, 0.0])
            slot[0] += 1
            slot[1] += dur_s
            self._total += 1
            self._total_s += dur_s
            self._last = {"phase": ph, "seconds": round(dur_s, 4),
                          "t": self._clock()}
        # A compile inside a serving phase is a RECOMPILE the request
        # paid for: attach it to the active trace (contextvars propagate
        # through asyncio.to_thread, so the engine's worker-thread
        # dispatches carry the request context) and log it — the silent
        # TTFT killer, made loud.
        if ph not in ("", "startup", "cost_analysis"):
            try:
                from .trace import record_span
                now = time.monotonic()
                record_span("xla.compile", layer="engine",
                            start=now - dur_s, end=now, phase=ph,
                            seconds=round(dur_s, 4))
            except Exception:
                logger.debug("compile-span attach failed", exc_info=True)
            logger.info("xla recompile during %s: %.2fs", ph, dur_s)

    def stats(self) -> dict[str, Any]:
        with self._lock:
            out: dict[str, Any] = {
                "xla_compile_total": self._total,
                "xla_compile_seconds": round(self._total_s, 4),
                "xla_compile_by_phase": {
                    ph: {"count": c, "seconds": round(s, 4)}
                    for ph, (c, s) in sorted(self._by_phase.items())},
            }
            if self._last is not None:
                out["xla_compile_last"] = dict(self._last)
            out["xla_trace_total"] = self._traces
            out["xla_trace_ms_total"] = round(1e3 * self._trace_s, 3)
            out["xla_trace_by_phase"] = {
                ph: {"count": c, "ms": round(1e3 * s, 3)}
                for ph, (c, s) in sorted(self._trace_by_phase.items())}
            return out


_monitor: XlaCompileMonitor | None = None
_monitor_lock = threading.Lock()


def compile_monitor() -> XlaCompileMonitor:
    """The process-wide monitor (created lazily; install separately)."""
    global _monitor
    with _monitor_lock:
        if _monitor is None:
            _monitor = XlaCompileMonitor()
        return _monitor


_installed = False


def install_compile_monitor() -> XlaCompileMonitor:
    """Register the jax.monitoring listener once per process (listeners
    cannot be removed, so double-registration would double-count)."""
    global _installed
    mon = compile_monitor()
    with _monitor_lock:
        if _installed:
            return mon
        _installed = True
    try:
        from jax import monitoring

        def listener(name: str, dur_s: float, **kw) -> None:
            if name == _COMPILE_EVENT:
                mon.on_compile(dur_s)
            elif name in _TRACE_EVENTS:
                mon.on_trace(name, dur_s)
        monitoring.register_event_duration_secs_listener(listener)
    except Exception:       # proxy-only deployment without JAX
        logger.debug("jax.monitoring unavailable; compile telemetry off",
                     exc_info=True)
    return mon
