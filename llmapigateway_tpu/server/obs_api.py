"""Unified observability endpoints (ISSUE 4).

* ``GET /metrics`` — the whole gateway in Prometheus text format: HTTP
  middleware, router, providers (incl. breaker state), and engine series
  in one scrape. Unauthenticated (like ``/health``): scrapers cannot
  attach bearer headers, and nothing here carries payload data.
* ``GET /v1/api/trace/{request_id}`` — one request's span tree from the
  tracer's ring buffer (gateway → router attempt N → provider call →
  engine phases). Flatten with ``tools/trace_report.py``.

The engine/breaker bridge lives here too: a scrape-time collector maps
each instantiated local engine's existing ``stats()`` dict (and each
breaker's snapshot) onto gauges — the roofline endpoint, bench, and
health endpoint keep reading the same sources unchanged.
"""
from __future__ import annotations

import logging

from aiohttp import web

from ..obs.metrics import GatewayMetrics
from ..obs.phases import LOOP_PHASES, WORKER_PHASES

logger = logging.getLogger(__name__)

# stats() key → GatewayMetrics attribute (plus a unit transform).
_ENGINE_GAUGES = (
    # (stats key, metrics attr, scale)
    ("running", "engine_running_requests_total", 1.0),
    ("queued", "engine_queued_requests_total", 1.0),
    ("free_slots", "engine_free_slots_total", 1.0),
    ("shed_total", "engine_sheds_total", 1.0),
    ("burst_busy_clamps", "engine_burst_clamps_total", 1.0),
    ("free_pages", "engine_kv_free_pages_total", 1.0),
    ("prefix_hits_total", "engine_prefix_cache_hit_total", 1.0),
    ("prefix_misses_total", "engine_prefix_cache_miss_total", 1.0),
    ("prefix_cached_tokens_total", "engine_prefix_cached_tokens_total", 1.0),
    ("prefix_resident_pages", "engine_prefix_resident_pages_total", 1.0),
    ("prefix_pinned_refs", "engine_prefix_pinned_refs_total", 1.0),
    ("hbm_bytes_per_step", "engine_step_hbm_bytes", 1.0),
    ("roofline_fraction", "engine_roofline_ratio", 1.0),
    ("queue_wait_ms_ema", "engine_queue_wait_seconds", 1e-3),
    ("decode_ms_per_step", "engine_decode_step_seconds", 1e-3),
    ("achieved_gbps", "engine_hbm_bandwidth_bytes", 1e9),
    # Speculative acceptance telemetry + flight-recorder loss (ISSUE 7).
    ("spec_proposed", "engine_spec_proposed_total", 1.0),
    ("spec_accepted", "engine_spec_accepted_total", 1.0),
    ("spec_suspended_slots", "engine_spec_suspended_slots", 1.0),
    ("flight_evicted_total", "engine_flight_ring_evicted_total", 1.0),
    # HBM memory ledger (ISSUE 8): static accounting, live buffer bytes,
    # and the runtime allocator's view (device_* keys only exist where
    # the backend exposes memory_stats — TPU yes, CPU no).
    ("hbm_weights_bytes", "engine_hbm_weights_bytes", 1.0),
    ("hbm_kv_pool_bytes", "engine_hbm_kv_pool_bytes", 1.0),
    ("hbm_aux_bytes", "engine_hbm_aux_bytes", 1.0),
    ("hbm_spec_bytes", "engine_hbm_spec_bytes", 1.0),
    ("hbm_ledger_bytes", "engine_hbm_ledger_bytes", 1.0),
    ("hbm_tracked_bytes", "engine_hbm_tracked_bytes", 1.0),
    ("hbm_prefix_resident_bytes", "engine_hbm_prefix_resident_bytes", 1.0),
    ("hbm_device_in_use_bytes", "engine_hbm_device_in_use_bytes", 1.0),
    ("hbm_device_peak_bytes", "engine_hbm_device_peak_bytes", 1.0),
    ("hbm_device_limit_bytes", "engine_hbm_device_limit_bytes", 1.0),
    ("hbm_headroom_ratio", "engine_hbm_headroom_ratio", 1.0),
    ("watermark_sheds", "engine_watermark_sheds_total", 1.0),
    # Disaggregated serving (ISSUE 13): engine-level handoff/clamp
    # counters; the per-pool block fans out via _POOL_GAUGES below.
    ("disagg_handoffs", "engine_disagg_handoffs_total", 1.0),
    ("disagg_handoff_pages", "engine_disagg_handoff_pages_total", 1.0),
    ("disagg_clamps", "engine_disagg_clamps_total", 1.0),
    # Engine supervision (ISSUE 14): lifecycle state + restart budget.
    ("supervisor_state_code", "engine_supervisor_state_ratio", 1.0),
    ("supervisor_restarts_total", "engine_supervisor_restarts_total", 1.0),
    ("supervisor_heartbeat_age_seconds",
     "engine_supervisor_heartbeat_age_seconds", 1.0),
    ("supervisor_backoff_seconds", "engine_supervisor_backoff_seconds", 1.0),
)

# stats()["pools"][pool] key → GatewayMetrics attribute (plus scale),
# one series per (engine, pool) label pair.
_POOL_GAUGES = (
    ("slots", "engine_pool_slots_total", 1.0),
    ("free_slots", "engine_pool_free_slots_total", 1.0),
    ("running", "engine_pool_running_total", 1.0),
    ("admits", "engine_pool_admits_total", 1.0),
    ("sheds", "engine_pool_sheds_total", 1.0),
    ("predicted_ttft_ms", "engine_pool_predicted_ttft_seconds", 1e-3),
    ("predicted_tpot_ms", "engine_pool_predicted_tpot_seconds", 1e-3),
    ("occupancy_ratio", "engine_pool_occupancy_ratio", 1.0),
)


def make_stats_collector(gw) -> "callable":
    """The scrape-time bridge from pull-model telemetry (engine ``stats()``
    dicts, breaker snapshots) into the metrics plane. Registered by
    GatewayApp; unregistered on close so test apps don't stack up."""
    metrics: GatewayMetrics = gw.metrics

    def collect() -> None:
        for name, prov in gw.registry.instantiated():
            engine = getattr(prov, "engine", None)
            if engine is None:
                continue
            try:
                stats = engine.stats()
            except Exception:
                logger.debug("engine stats() failed for %s", name,
                             exc_info=True)
                continue
            for key, attr, scale in _ENGINE_GAUGES:
                val = stats.get(key)
                if isinstance(val, (int, float)):
                    getattr(metrics, attr).labels(engine=name).set(
                        val * scale)
            pools = stats.get("pools")
            if isinstance(pools, dict):
                for pool_name, pstats in pools.items():
                    if not isinstance(pstats, dict):
                        continue
                    for key, attr, scale in _POOL_GAUGES:
                        val = pstats.get(key)
                        if isinstance(val, (int, float)):
                            getattr(metrics, attr).labels(
                                engine=name, pool=pool_name).set(
                                    val * scale)
            for ph in LOOP_PHASES + WORKER_PHASES:
                val = stats.get(f"sched_{ph}_ms_total")
                if isinstance(val, (int, float)):
                    metrics.engine_sched_phase_ms_total.labels(
                        engine=name, phase=ph).set(val)
            total = stats.get("total_pages")
            free = stats.get("free_pages")
            if isinstance(total, (int, float)) and total > 0 \
                    and isinstance(free, (int, float)):
                metrics.engine_kv_occupancy_ratio.labels(engine=name).set(
                    max(0.0, 1.0 - free / total))
            proposed = stats.get("spec_proposed")
            accepted = stats.get("spec_accepted")
            if isinstance(proposed, (int, float)) and proposed > 0 \
                    and isinstance(accepted, (int, float)):
                metrics.engine_spec_acceptance_ratio.labels(
                    engine=name).set(accepted / proposed)
            # Per-slot adaptive drafting: each measured slot's live EMA
            # ratio (the floor's comparand), keyed by slot label.
            ratios = stats.get("spec_slot_acceptance")
            if isinstance(ratios, dict):
                for slot, ratio in ratios.items():
                    if isinstance(ratio, (int, float)):
                        metrics.engine_spec_slot_acceptance_ratio.labels(
                            engine=name, slot=str(slot)).set(ratio)
        # SLO goodput (ISSUE 7): met / (met + violated) per engine,
        # derived at scrape time from the counters the local provider
        # increments at stream end — the violated side sums across its
        # attribution phases.
        met_by_engine = {key[0]: child.value
                         for key, child in metrics.slo_met_total.children()}
        violated_by_engine: dict[str, float] = {}
        for key, child in metrics.slo_violated_total.children():
            violated_by_engine[key[0]] = (
                violated_by_engine.get(key[0], 0.0) + child.value)
        for eng in set(met_by_engine) | set(violated_by_engine):
            met = met_by_engine.get(eng, 0.0)
            tot = met + violated_by_engine.get(eng, 0.0)
            if tot > 0:
                metrics.slo_goodput_ratio.labels(engine=eng).set(met / tot)
        # Per-pool goodput (ISSUE 13): same derivation keyed by the pool
        # that served the request's decode — the pooled-vs-unified
        # scoreboard the disagg A/B reads.
        pool_met = {key: child.value
                    for key, child in metrics.slo_pool_met_total.children()}
        pool_violated = {
            key: child.value
            for key, child in metrics.slo_pool_violated_total.children()}
        for key in set(pool_met) | set(pool_violated):
            met = pool_met.get(key, 0.0)
            tot = met + pool_violated.get(key, 0.0)
            if tot > 0:
                metrics.slo_pool_goodput_ratio.labels(
                    engine=key[0], pool=key[1]).set(met / tot)
        metrics.trace_ring_evicted_total.set(gw.tracer.evicted_total)
        # XLA compile telemetry (ISSUE 8): process-wide monitor, one
        # series per triggering phase — a non-startup phase here is a
        # recompile some live request paid for.
        try:
            from ..obs.device import compile_monitor
            cm = compile_monitor().stats()
            for ph, slot in cm.get("xla_compile_by_phase", {}).items():
                metrics.engine_xla_compile_total.labels(phase=ph).set(
                    slot["count"])
                metrics.engine_xla_compile_seconds.labels(phase=ph).set(
                    slot["seconds"])
        except Exception:
            logger.debug("xla compile bridge failed", exc_info=True)
        if gw.breakers is not None:
            for name, snap in gw.breakers.snapshot().items():
                metrics.provider_breaker_open_ratio.labels(
                    provider=name).set(snap.get("state_code", 0.0))
                metrics.provider_breaker_opens_total.labels(
                    provider=name).set(snap.get("opens", 0))
        # Write-behind usage recorder (ISSUE 14): queue depth + drop
        # counter — a nonzero drop rate means the ledger is lossy under
        # the current incident load.
        recorder = getattr(gw, "usage_recorder", None)
        if recorder is not None:
            rstats = recorder.stats()
            metrics.usage_recorder_queued.set(
                rstats["usage_recorder_queued"])
            metrics.usage_recorder_flushed_total.set(
                rstats["usage_recorder_flushed_total"])
            metrics.usage_recorder_dropped_total.set(
                rstats["usage_recorder_dropped_total"])

    return collect


async def get_metrics_text(request: web.Request) -> web.Response:
    gw = request.app["gateway"]
    text = gw.metrics.render()
    return web.Response(
        text=text,
        headers={"Content-Type":
                 "text/plain; version=0.0.4; charset=utf-8"})


async def get_flight(request: web.Request) -> web.Response:
    """``GET /v1/api/flight?since=<seq>`` — the scheduler flight
    recorder's resident records, per local engine (ISSUE 7). ``since``
    returns only records newer than that sequence number, so a poller
    tails the ring without re-reading it; each engine block carries its
    ring counters (seq / capacity / evicted) so loss is visible. Convert
    to a Perfetto-loadable Chrome trace with ``tools/flight_report.py``."""
    gw = request.app["gateway"]
    try:
        since = int(request.query.get("since", -1))
    except ValueError:
        return web.json_response(
            {"detail": "since must be an integer sequence number"},
            status=400)
    engines = {}
    for name, prov in gw.registry.instantiated():
        engine = getattr(prov, "engine", None)
        recorder = getattr(engine, "flight", None)
        if recorder is None:
            continue
        engines[name] = {"records": recorder.snapshot(since),
                         **recorder.stats()}
    if not engines:
        return web.json_response(
            {"detail": "no local engine with an active flight recorder "
                       "(flight_ring_size 0, or no local provider "
                       "instantiated yet)"},
            status=404)
    return web.json_response({"engines": engines})


async def get_trace(request: web.Request) -> web.Response:
    gw = request.app["gateway"]
    request_id = request.match_info["request_id"]
    doc = gw.tracer.get(request_id)
    if doc is None:
        return web.json_response(
            {"detail": f"no trace for request id {request_id!r} (ring "
                       f"buffer holds the most recent "
                       f"{gw.tracer.capacity} requests)"},
            status=404)
    return web.json_response(doc)
