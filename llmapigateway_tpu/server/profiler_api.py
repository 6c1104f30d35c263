"""Observability API: engine stats and on-demand device trace capture.

The reference has no tracing/profiling beyond a per-request UUID and
duration log (SURVEY.md §5 "Tracing / profiling" — ``request_logging.py:23``,
``:85-86``); the TPU build adds the device-side story the reference never
needed: ``jax.profiler`` trace capture (viewable in TensorBoard/Perfetto)
plus live serving-engine stats (slots, queue depth, paged-KV occupancy),
since TTFT/throughput are north-star metrics here (BASELINE.md).

Endpoints (wired in server/app.py):

* ``GET  /v1/api/engine-stats`` — per-local-provider engine stats + device
  inventory. Cheap; safe to poll.
* ``GET  /v1/api/roofline`` — the roofline slice of those stats (achieved
  GB/s from the engine's bytes-touched model × measured step time, burst
  depth / prefill-aware clamp counters, queue wait), one row per local
  engine — what the bench ladder and the stats UI read to track the
  0.478→1.0 HBM-roofline trajectory (ISSUE 2). Since ISSUE 8 each engine
  block also carries the PER-KERNEL cost table (one row per compiled
  executable variant: calls, measured step walls joined from the flight
  ring, cost_analysis FLOPs/bytes, achieved GB/s, roofline fraction),
  the name of the single worst kernel, and the HBM ledger. Cheap; safe
  to poll.
* ``POST /v1/api/profiler/trace?duration_ms=N`` — capture a profiler trace
  of the next N ms of live traffic into ``<logs_dir>/profiles/<name>``;
  returns the directory path. SINGLE-FLIGHT: a concurrent capture gets
  409 immediately (``jax.profiler`` state is process-global — a second
  ``start_trace`` would corrupt the first). Capture boundaries are
  stamped into each engine's flight ring (``profile`` records), so a
  Perfetto view of the capture cross-links to the exact scheduler seqs
  it covered; old trace dirs are pruned to ``MAX_TRACE_DIRS``.
"""
from __future__ import annotations

import asyncio
import logging
import shutil
import threading
import time
from pathlib import Path
from typing import Any

from aiohttp import web

logger = logging.getLogger(__name__)

_trace_lock = asyncio.Lock()

MAX_TRACE_MS = 30_000
DEFAULT_TRACE_MS = 2_000
# Bounded retention: a capture can be hundreds of MB; keep the newest N
# trace dirs and delete the rest after each successful capture.
MAX_TRACE_DIRS = 8

# Device-inventory probe state. jax.devices() initializes the backend on
# first call, which blocks for as long as the runtime takes to come up.
# A stats poll must not block with it: exactly ONE daemon thread probes,
# requests wait a bounded time, and an unfinished probe is reported as
# status "initializing" instead of holding the endpoint.
_dev_state: dict[str, Any] = {"status": "unprobed", "devices": [],
                              "probe_started": 0.0}
_dev_lock = threading.Lock()

DEVICE_PROBE_WAIT_S = 5.0


def _start_device_probe() -> None:
    with _dev_lock:
        # "ok" is cached for the process lifetime; "initializing" means a
        # probe thread is still out (possibly hung — never stack more).
        # An "unavailable" FAILURE is retried on the next poll: transient
        # causes (another process briefly holding the TPU runtime) heal.
        if _dev_state["status"] in ("initializing", "ok"):
            return
        _dev_state["status"] = "initializing"
        _dev_state["probe_started"] = time.monotonic()

    def work():
        try:
            import jax
            devs = [{"id": d.id, "platform": d.platform,
                     "kind": d.device_kind} for d in jax.devices()]
            with _dev_lock:
                _dev_state.update(status="ok", devices=devs)
        except Exception as e:      # proxy-only deployment without JAX
            with _dev_lock:
                _dev_state.update(status=f"unavailable: {e!r:.120}",
                                  devices=[])
    threading.Thread(target=work, daemon=True,
                     name="engine-stats-device-probe").start()


def _local_engines(gw) -> list[tuple[str, Any]]:
    out = []
    for name, prov in gw.registry.instantiated():
        engine = getattr(prov, "engine", None)
        if engine is not None:
            out.append((name, engine))
    return out


async def get_engine_stats(request: web.Request) -> web.Response:
    gw = request.app["gateway"]
    engines = {name: eng.stats() for name, eng in _local_engines(gw)}
    _start_device_probe()
    # Wait only while the probe is *young*: once a thread has been out
    # longer than the wait budget, every subsequent poll returns
    # "initializing" immediately instead of each burning the full 5 s.
    # (.get: tests monkeypatch _dev_state.)
    deadline = _dev_state.get("probe_started",
                              time.monotonic()) + DEVICE_PROBE_WAIT_S
    while (_dev_state["status"] == "initializing"
           and time.monotonic() < deadline):
        await asyncio.sleep(0.05)
    return web.json_response({
        "engines": engines,
        "devices": _dev_state["devices"],
        "device_status": _dev_state["status"],
    })


# The roofline slice of an engine's stats() dict: bandwidth model,
# step-time gauge, burst-depth controller, and admission-wait counters.
ROOFLINE_KEYS = (
    "achieved_gbps", "roofline_fraction", "hbm_bytes_per_step",
    "decode_ms_per_step", "decode_tok_s",
    "burst_depth_last", "burst_busy_clamps", "burst_depth_hist",
    "burst_step_ms_fit", "burst_fixed_cost_ms", "burst_walls_ms",
    "queue_wait_ms_ema", "queue_wait_ms_max", "queue_waits",
    "running", "queued", "pages_per_block",
)


async def get_roofline(request: web.Request) -> web.Response:
    """Per-engine roofline/scheduler counters — stats() filtered to the
    fields an operator (or the bench ladder) plots over time — plus the
    ISSUE 8 per-kernel table: which compiled executable is furthest off
    the HBM roof, with how much of the step time. The decode/spec rows'
    ``hbm_bytes_per_step`` use the same bytes-touched model as the
    aggregate, so the table and the aggregate reconcile by
    construction; the ``xla_*`` columns carry the raw cost_analysis."""
    gw = request.app["gateway"]
    engines = {}
    for name, eng in _local_engines(gw):
        s = eng.stats()
        block = {k: s[k] for k in ROOFLINE_KEYS if k in s}
        if hasattr(eng, "kernel_table"):
            from ..obs.device import worst_kernel
            kernels = getattr(eng, "kernels", None)
            if kernels is not None and kernels.costs_pending():
                # AOT lower+compile for cost_analysis can cost seconds
                # at 8B scale — pay it off-loop, once per new variant,
                # at read time (this endpoint is on-demand diagnostics).
                await asyncio.to_thread(kernels.resolve_costs)
            rows = eng.kernel_table()
            block["kernels"] = rows
            worst = worst_kernel(rows)
            if worst is not None:
                block["worst_kernel"] = worst
        block["hbm"] = {k: v for k, v in s.items()
                        if k.startswith("hbm_")}
        engines[name] = block
    return web.json_response({"engines": engines})


def _prune_trace_dirs(profiles_dir: Path,
                      keep: int = MAX_TRACE_DIRS) -> list[str]:
    """Delete all but the newest ``keep`` capture dirs (names sort
    chronologically). Synchronous — called via ``asyncio.to_thread``."""
    try:
        dirs = sorted((d for d in profiles_dir.iterdir() if d.is_dir()),
                      key=lambda d: d.name)
    except OSError:
        return []
    deleted: list[str] = []
    for d in (dirs[:-keep] if keep > 0 else dirs):
        try:
            shutil.rmtree(d)
            deleted.append(d.name)
        except OSError:
            logger.warning("failed to prune trace dir %s", d)
    return deleted


def _stamp_flight(gw, flag: int, rid: str) -> dict[str, int]:
    """Record a PROF capture-boundary into every local engine's flight
    ring and return engine → seq. Runs on the event loop — the ring's
    single-writer thread for an in-process gateway — so a capture's
    covered seq window is readable from ``GET /v1/api/flight``."""
    from ..obs.flight import PROF
    seqs: dict[str, int] = {}
    for name, eng in _local_engines(gw):
        rec = getattr(eng, "flight", None)
        if rec is not None:
            seqs[name] = rec.record(PROF, flag=flag, rid=rid)
    return seqs


async def capture_trace(request: web.Request) -> web.Response:
    try:
        import jax
    except Exception:
        return web.json_response(
            {"detail": "jax unavailable in this deployment"}, status=501)

    try:
        duration_ms = int(request.query.get("duration_ms", DEFAULT_TRACE_MS))
    except ValueError:
        return web.json_response({"detail": "duration_ms must be an integer"},
                                 status=400)
    duration_ms = max(100, min(duration_ms, MAX_TRACE_MS))

    # Single-flight guard: ``jax.profiler`` trace state is process-global,
    # so a second concurrent capture must 409 instead of queueing behind
    # the lock (the caller asked for a capture of NOW, not of whenever
    # the current one ends — and a queued start_trace against a profiler
    # mid-teardown has corrupted global state in practice). No awaits
    # between the check and the acquire, so two handlers cannot both
    # pass; acquire() on an uncontended asyncio.Lock is synchronous.
    if _trace_lock.locked():
        return web.json_response(
            {"detail": "a trace capture is already running"}, status=409)
    await _trace_lock.acquire()
    try:
        gw = request.app["gateway"]
        logs_dir = Path(gw.settings.logs_dir or "logs")
        profiles_dir = logs_dir / "profiles"
        out_dir = profiles_dir / time.strftime("trace-%Y%m%d-%H%M%S")
        out_dir.mkdir(parents=True, exist_ok=True)
        logger.info("profiler: capturing %d ms trace to %s",
                    duration_ms, out_dir)
        # start/stop_trace do blocking work (stop serializes the whole
        # device trace to disk — can be hundreds of MB) — keep it off the
        # event loop so in-flight SSE streams don't stall.
        try:
            await asyncio.to_thread(jax.profiler.start_trace, str(out_dir))
        except Exception as e:
            # Profiler already active outside this endpoint (an operator's
            # manual start_trace, or a crashed capture) — surface it as a
            # conflict instead of corrupting that session's state.
            logger.warning("profiler start failed: %r", e)
            return web.json_response(
                {"detail": f"profiler start failed: {e!r:.200}"},
                status=409)
        # Capture boundaries into the flight rings (ISSUE 8): the seqs
        # returned here bracket exactly the scheduler records the XLA
        # capture covers — the Perfetto cross-link between planes.
        from ..obs.flight import PROF_START, PROF_STOP
        start_seqs = _stamp_flight(gw, PROF_START, out_dir.name)
        try:
            # Sleep while live traffic runs under the trace; the engine
            # loop and in-flight requests keep executing on the loop.
            await asyncio.sleep(duration_ms / 1000.0)
        finally:
            stop_seqs = _stamp_flight(gw, PROF_STOP, out_dir.name)
            await asyncio.to_thread(jax.profiler.stop_trace)
        pruned = await asyncio.to_thread(_prune_trace_dirs, profiles_dir)
    finally:
        _trace_lock.release()

    return web.json_response({
        "trace_dir": str(out_dir),
        "duration_ms": duration_ms,
        # Per-engine [start_seq, stop_seq] windows into /v1/api/flight.
        "flight_seqs": {name: [start_seqs.get(name), stop_seqs.get(name)]
                        for name in set(start_seqs) | set(stop_seqs)},
        "pruned_trace_dirs": pruned,
        "hint": "view with: tensorboard --logdir <trace_dir> "
                "(Profile tab) or upload to ui.perfetto.dev; "
                "flight_seqs bracket the scheduler records the capture "
                "covers (tools/flight_report.py renders both planes)",
    })
