"""Dependency-free metrics plane: Counter / Gauge / Histogram with labels,
Prometheus text-format exposition, and scrape-time collectors.

The gateway's telemetry was scattered — engine ``stats()`` dicts, breaker
snapshots, per-request logs — with no single scrapeable surface (ISSUE 4).
This module is the one registry every layer registers into; ``GET /metrics``
(server/obs_api.py) serves :meth:`MetricsRegistry.render`. No prometheus
client dependency: the text format is simple, and owning the encoder lets
tests pin the grammar exactly (tests/test_metrics.py).

Conventions (enforced by the graftlint ``metric-discipline`` rule):

* names are snake_case and end with a unit suffix — ``_seconds``,
  ``_bytes``, ``_total``, or ``_ratio``;
* latency histograms share :data:`LATENCY_BUCKETS_S` so dashboards can
  aggregate across layers.

Collectors bridge pull-model sources (engine ``stats()``, breaker
snapshots) into gauges at scrape time, so the existing roofline endpoint
and bench accounting keep reading the same underlying dicts unchanged.

Thread-safety: one lock guards registration, sample mutation, and
rendering — providers record from the event loop, but nothing stops an
operator thread from scraping concurrently, and a torn histogram (count
bumped, sum not yet) would fail the exposition-consistency tests.
"""
from __future__ import annotations

import logging
import math
import threading
from typing import Any, Callable, Iterable

logger = logging.getLogger(__name__)

# Shared latency ladder (seconds): spans SSE frame gaps (~ms) through the
# 300 s transport cap.
LATENCY_BUCKETS_S = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                     0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0)


def _escape_label_value(value: str) -> str:
    return (value.replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _escape_help(text: str) -> str:
    return text.replace("\\", r"\\").replace("\n", r"\n")


def _format_value(value: float) -> str:
    if value == math.inf:
        return "+Inf"
    if value == -math.inf:
        return "-Inf"
    if value != value:                       # NaN
        return "NaN"
    if float(value).is_integer() and abs(value) < 1e15:
        return str(int(value))
    return repr(float(value))


def _format_labels(labelnames: tuple[str, ...], labelvalues: tuple[str, ...],
                   extra: tuple[tuple[str, str], ...] = ()) -> str:
    pairs = list(zip(labelnames, labelvalues)) + list(extra)
    if not pairs:
        return ""
    body = ",".join(f'{k}="{_escape_label_value(v)}"' for k, v in pairs)
    return "{" + body + "}"


class _Child:
    """One labeled sample of a metric (or the single sample of an unlabeled
    one). Mutation goes through the registry lock."""

    def __init__(self, lock: threading.Lock):
        self._lock = lock
        self._value = 0.0               # guarded-by: _lock

    def inc(self, amount: float = 1.0) -> None:
        with self._lock:
            self._value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.inc(-amount)

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> float:
        return self._value


class _CounterChild(_Child):
    def inc(self, amount: float = 1.0) -> None:
        if amount < 0:
            raise ValueError("counters only go up; use a gauge")
        super().inc(amount)


class _HistogramChild:
    def __init__(self, lock: threading.Lock, buckets: tuple[float, ...]):
        self._lock = lock
        self.buckets = buckets
        self._counts = [0] * (len(buckets) + 1)   # guarded-by: _lock (+Inf last)
        self._sum = 0.0                           # guarded-by: _lock
        self._count = 0                           # guarded-by: _lock

    def observe(self, value: float) -> None:
        with self._lock:
            for i, bound in enumerate(self.buckets):
                if value <= bound:
                    self._counts[i] += 1
                    break
            else:
                self._counts[-1] += 1
            self._sum += value
            self._count += 1

    def snapshot(self) -> tuple[list[int], float, int]:
        with self._lock:
            return list(self._counts), self._sum, self._count


class Metric:
    """One metric family: name, help, type, label schema, children."""

    kind = "untyped"

    def __init__(self, name: str, help: str, labelnames: tuple[str, ...],
                 lock: threading.Lock):
        self.name = name
        self.help = help
        self.labelnames = labelnames
        self._lock = lock
        self._children: dict[tuple[str, ...], Any] = {}   # guarded-by: _lock

    def _make_child(self):
        return _Child(self._lock)

    def labels(self, **labelvalues: str) -> Any:
        if set(labelvalues) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {tuple(labelvalues)}")
        key = tuple(str(labelvalues[ln]) for ln in self.labelnames)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._make_child()
                self._children[key] = child
        return child

    def _default_child(self):
        if self.labelnames:
            raise ValueError(f"{self.name} is labeled; call .labels() first")
        return self.labels()

    def children(self) -> list[tuple[tuple[str, ...], Any]]:
        """Snapshot of (labelvalues, child) pairs — what scrape-time
        collectors that DERIVE series (e.g. the SLO goodput ratio) read
        instead of reparsing the exposition."""
        with self._lock:
            return list(self._children.items())

    # Unlabeled convenience passthroughs.
    def inc(self, amount: float = 1.0) -> None:
        self._default_child().inc(amount)

    def dec(self, amount: float = 1.0) -> None:
        self._default_child().dec(amount)

    def set(self, value: float) -> None:
        self._default_child().set(value)

    def observe(self, value: float) -> None:
        self._default_child().observe(value)

    def samples(self) -> list[str]:
        lines = []
        with self._lock:
            children = sorted(self._children.items())
        for key, child in children:
            lines.append(f"{self.name}{_format_labels(self.labelnames, key)} "
                         f"{_format_value(child.value)}")
        return lines


class Counter(Metric):
    kind = "counter"

    def _make_child(self):
        return _CounterChild(self._lock)

    def set(self, value: float) -> None:
        raise TypeError("counters only inc(); use a gauge for set()")


class Gauge(Metric):
    kind = "gauge"


class Histogram(Metric):
    kind = "histogram"

    def __init__(self, name: str, help: str, labelnames: tuple[str, ...],
                 lock: threading.Lock,
                 buckets: tuple[float, ...] = LATENCY_BUCKETS_S):
        super().__init__(name, help, labelnames, lock)
        self.buckets = tuple(sorted(buckets))

    def _make_child(self):
        return _HistogramChild(self._lock, self.buckets)

    def samples(self) -> list[str]:
        lines = []
        with self._lock:
            children = sorted(self._children.items())
        for key, child in children:
            counts, total, count = child.snapshot()
            cumulative = 0
            for bound, n in zip(self.buckets, counts):
                cumulative += n
                le = _format_value(bound)
                lines.append(
                    f"{self.name}_bucket"
                    f"{_format_labels(self.labelnames, key, (('le', le),))} "
                    f"{cumulative}")
            cumulative += counts[-1]
            lines.append(
                f"{self.name}_bucket"
                f"{_format_labels(self.labelnames, key, (('le', '+Inf'),))} "
                f"{cumulative}")
            lines.append(f"{self.name}_sum"
                         f"{_format_labels(self.labelnames, key)} "
                         f"{_format_value(total)}")
            lines.append(f"{self.name}_count"
                         f"{_format_labels(self.labelnames, key)} {count}")
        return lines


class MetricsRegistry:
    """Instrument factory + exposition encoder.

    Re-registering an existing name returns the existing instrument when
    type and label schema match (layers register idempotently at import /
    construction time) and raises otherwise.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: dict[str, Metric] = {}        # guarded-by: _lock
        self._collectors: list[Callable[[], None]] = []   # guarded-by: _lock

    def _register(self, cls, name: str, help: str,
                  labelnames: Iterable[str], **kwargs) -> Any:
        labelnames = tuple(labelnames)
        with self._lock:
            existing = self._metrics.get(name)
            if existing is not None:
                if (type(existing) is not cls
                        or existing.labelnames != labelnames):
                    raise ValueError(
                        f"metric {name!r} already registered with a "
                        f"different type or label schema")
                return existing
            metric = cls(name, help, labelnames, self._lock, **kwargs)
            self._metrics[name] = metric
            return metric

    def counter(self, name: str, help: str,
                labelnames: Iterable[str] = ()) -> Counter:
        return self._register(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str,
              labelnames: Iterable[str] = ()) -> Gauge:
        return self._register(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str,
                  labelnames: Iterable[str] = (),
                  buckets: tuple[float, ...] = LATENCY_BUCKETS_S) -> Histogram:
        return self._register(Histogram, name, help, labelnames,
                              buckets=buckets)

    # -- scrape-time collectors (engine stats / breaker snapshot bridges) ----
    def register_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn not in self._collectors:
                self._collectors.append(fn)

    def unregister_collector(self, fn: Callable[[], None]) -> None:
        with self._lock:
            if fn in self._collectors:
                self._collectors.remove(fn)

    def render(self) -> str:
        """The Prometheus text-format exposition (version 0.0.4)."""
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception:       # a sick engine must never break /metrics
                logger.debug("metrics collector failed", exc_info=True)
        with self._lock:
            metrics = sorted(self._metrics.values(), key=lambda m: m.name)
        out: list[str] = []
        for m in metrics:
            out.append(f"# HELP {m.name} {_escape_help(m.help)}")
            out.append(f"# TYPE {m.name} {m.kind}")
            out.extend(m.samples())
        return "\n".join(out) + "\n"


class GatewayMetrics:
    """Every instrument of the gateway's four layers, pre-registered so the
    exposition carries HELP/TYPE for the full schema from first scrape.
    Layers hold attribute references — no name lookups on the hot path."""

    def __init__(self, registry: MetricsRegistry | None = None):
        self.registry = registry or MetricsRegistry()
        r = self.registry

        # -- http (server/middleware.py) --------------------------------------
        self.http_requests_total = r.counter(
            "gateway_http_requests_total",
            "HTTP requests completed, by route template and final status.",
            ("method", "path", "status"))
        self.http_in_flight = r.gauge(
            "gateway_http_requests_in_flight_total",
            "HTTP requests currently being served.")
        self.http_request_duration_seconds = r.histogram(
            "gateway_http_request_duration_seconds",
            "End-to-end HTTP request wall time (streamed responses include "
            "the full stream drain).",
            ("method", "path"))

        # -- router (routing/router.py) ---------------------------------------
        self.router_attempts_total = r.counter(
            "gateway_router_attempts_total",
            "Provider attempts dispatched by the fallback state machine.",
            ("provider",))
        self.router_fallbacks_total = r.counter(
            "gateway_router_fallbacks_total",
            "Attempted targets that failed and were fallen past to a later "
            "target in the chain.")
        self.router_breaker_skips_total = r.counter(
            "gateway_router_breaker_skips_total",
            "Targets skipped instantly because their circuit breaker was "
            "open.",
            ("provider",))
        self.router_deadline_expired_total = r.counter(
            "gateway_router_deadline_expired_total",
            "Requests terminated 504 because their deadline budget ran out.")
        self.router_sheds_total = r.counter(
            "gateway_router_sheds_total",
            "Requests shed 429 because every target was overloaded or "
            "breaker-open.")

        # -- providers (recorded at the router call-site; covers remote_http
        #    and local uniformly) ---------------------------------------------
        self.provider_attempt_duration_seconds = r.histogram(
            "gateway_provider_attempt_duration_seconds",
            "Wall time of one provider attempt up to commit (remote: SSE "
            "priming; local: first token).",
            ("provider",))
        self.provider_errors_total = r.counter(
            "gateway_provider_errors_total",
            "Failed provider attempts by error kind (timeout / overload / "
            "http / error).",
            ("provider", "kind"))
        self.provider_timeouts_total = r.counter(
            "gateway_provider_timeouts_total",
            "Provider attempts that hit their deadline-capped transport "
            "timeout.",
            ("provider",))
        self.provider_breaker_open_ratio = r.gauge(
            "gateway_provider_breaker_open_ratio",
            "Circuit-breaker state per provider: 0 closed, 0.5 half-open, "
            "1 open.",
            ("provider",))
        self.provider_breaker_opens_total = r.gauge(
            "gateway_provider_breaker_opens_total",
            "Lifetime open transitions per provider breaker.",
            ("provider",))

        # -- engine (providers/local.py records; gauges bridge stats()) -------
        self.engine_ttft_seconds = r.histogram(
            "gateway_engine_ttft_seconds",
            "Local-engine time to first token (submit to first sampled "
            "token).",
            ("engine",))
        self.engine_time_between_tokens_seconds = r.histogram(
            "gateway_engine_time_between_tokens_seconds",
            "Gap between consecutive streamed deltas from the local engine.",
            ("engine",))
        self.engine_running_requests_total = r.gauge(
            "gateway_engine_running_requests_total",
            "Requests holding an engine slot.", ("engine",))
        self.engine_queued_requests_total = r.gauge(
            "gateway_engine_queued_requests_total",
            "Requests waiting for engine admission.", ("engine",))
        self.engine_free_slots_total = r.gauge(
            "gateway_engine_free_slots_total",
            "Free decode slots.", ("engine",))
        self.engine_queue_wait_seconds = r.gauge(
            "gateway_engine_queue_wait_seconds",
            "EMA of submit-to-admission wait.", ("engine",))
        self.engine_decode_step_seconds = r.gauge(
            "gateway_engine_decode_step_seconds",
            "Measured per-step decode time (EMA over steady bursts).",
            ("engine",))
        self.engine_sheds_total = r.gauge(
            "gateway_engine_sheds_total",
            "Admissions refused on a full queue (gateway mapped to 429).",
            ("engine",))
        self.engine_burst_clamps_total = r.gauge(
            "gateway_engine_burst_clamps_total",
            "Busy decode bursts clamped below decode_burst_busy by the "
            "prefill-aware TTFT cap.", ("engine",))
        self.engine_kv_free_pages_total = r.gauge(
            "gateway_engine_kv_free_pages_total",
            "Free pages in the paged-KV pool.", ("engine",))
        # Radix prefix cache (ISSUE 6). Monotonic engine-side totals are
        # bridged as gauges like engine_sheds_total (the engine owns the
        # counter; scrape-time set() keeps restarts honest).
        self.engine_prefix_cache_hit_total = r.gauge(
            "gateway_engine_prefix_cache_hit_total",
            "Admitted requests whose prompt prefix was served from the "
            "radix KV cache.", ("engine",))
        self.engine_prefix_cache_miss_total = r.gauge(
            "gateway_engine_prefix_cache_miss_total",
            "Admitted requests with no resident prompt prefix.",
            ("engine",))
        self.engine_prefix_cached_tokens_total = r.gauge(
            "gateway_engine_prefix_cached_tokens_total",
            "Prompt tokens whose prefill was skipped via the radix KV "
            "cache.", ("engine",))
        self.engine_prefix_resident_pages_total = r.gauge(
            "gateway_engine_prefix_resident_pages_total",
            "KV pages currently pinned by the radix prefix cache.",
            ("engine",))
        self.engine_prefix_pinned_refs_total = r.gauge(
            "gateway_engine_prefix_pinned_refs_total",
            "In-flight request references pinning resident prefix blocks "
            "against eviction.", ("engine",))
        self.engine_kv_occupancy_ratio = r.gauge(
            "gateway_engine_kv_occupancy_ratio",
            "Paged-KV pool occupancy (allocated / allocatable).", ("engine",))
        # Speculative-decoding acceptance telemetry (ROADMAP item 3 stub;
        # ISSUE 7 satellite): bridged from the engine's spec_proposed /
        # spec_accepted stats like the prefix-cache totals.
        self.engine_spec_proposed_total = r.gauge(
            "gateway_engine_spec_proposed_total",
            "Draft tokens proposed by speculative decoding.", ("engine",))
        self.engine_spec_accepted_total = r.gauge(
            "gateway_engine_spec_accepted_total",
            "Draft tokens accepted by the verify forward.", ("engine",))
        self.engine_spec_acceptance_ratio = r.gauge(
            "gateway_engine_spec_acceptance_ratio",
            "Accepted over proposed draft tokens (lifetime).", ("engine",))
        # Per-slot adaptive drafting (spec_acceptance_floor): how many
        # slots are currently benched, plus each measured slot's live
        # EMA-derived acceptance ratio — the quantity the floor compares
        # against ((ema - 1) / k, in [0, 1]).
        self.engine_spec_suspended_slots = r.gauge(
            "gateway_engine_spec_suspended_slots_total",
            "Slots with drafting suspended by spec_acceptance_floor.",
            ("engine",))
        self.engine_spec_slot_acceptance_ratio = r.gauge(
            "gateway_engine_spec_slot_acceptance_ratio",
            "Per-slot EMA acceptance ratio ((ema-1)/k) feeding the "
            "adaptive drafting floor.", ("engine", "slot"))
        # Flight recorder (ISSUE 7): ring position and wrap loss.
        self.engine_flight_ring_evicted_total = r.gauge(
            "gateway_engine_flight_ring_evicted_total",
            "Flight-recorder records lost to ring wrap.", ("engine",))
        # Engine supervision (ISSUE 14): lifecycle + restart telemetry.
        self.engine_supervisor_state_ratio = r.gauge(
            "gateway_engine_supervisor_state_ratio",
            "Engine lifecycle state: 0 serving, 0.25 starting, 0.5 "
            "draining, 0.75 restarting, 0.9 stopped, 1 failed.",
            ("engine",))
        self.engine_supervisor_restarts_total = r.gauge(
            "gateway_engine_supervisor_restarts_total",
            "Supervised engine restarts since the last healthy stretch "
            "(resets after sustained clean serving).", ("engine",))
        self.engine_supervisor_heartbeat_age_seconds = r.gauge(
            "gateway_engine_supervisor_heartbeat_age_seconds",
            "Seconds since the scheduler loop last stamped its "
            "heartbeat.", ("engine",))
        self.engine_supervisor_backoff_seconds = r.gauge(
            "gateway_engine_supervisor_backoff_seconds",
            "Backoff the NEXT supervised restart attempt would wait.",
            ("engine",))

        # Write-behind usage recorder (ISSUE 14; db/recorder.py).
        self.usage_recorder_queued = r.gauge(
            "gateway_usage_recorder_queued_total",
            "Usage rows waiting in the write-behind queue.")
        self.usage_recorder_flushed_total = r.gauge(
            "gateway_usage_recorder_flushed_total",
            "Usage rows flushed to the ledger by the background "
            "recorder.")
        self.usage_recorder_dropped_total = r.gauge(
            "gateway_usage_recorder_dropped_total",
            "Usage rows dropped because the write-behind queue was "
            "full.")

        # -- HBM memory ledger (ISSUE 8; obs/device.py). Static accounting
        #    from checkpoint dtypes + cache geometry, the live buffers'
        #    metadata bytes, and the runtime allocator's own view where
        #    the backend exposes one (TPU; CPU reports none). -------------
        self.engine_hbm_weights_bytes = r.gauge(
            "gateway_engine_hbm_weights_bytes",
            "Resident parameter bytes (scales included) per the ledger.",
            ("engine",))
        self.engine_hbm_kv_pool_bytes = r.gauge(
            "gateway_engine_hbm_kv_pool_bytes",
            "KV-pool bytes from page geometry × cache dtype (incl. int8 "
            "scale planes).", ("engine",))
        self.engine_hbm_aux_bytes = r.gauge(
            "gateway_engine_hbm_aux_bytes",
            "Auxiliary device buffers: penalty counts, page table.",
            ("engine",))
        self.engine_hbm_spec_bytes = r.gauge(
            "gateway_engine_hbm_spec_bytes",
            "Speculative-decoding device buffers (token-history twin).",
            ("engine",))
        self.engine_hbm_ledger_bytes = r.gauge(
            "gateway_engine_hbm_ledger_bytes",
            "Total bytes the ledger expects resident (weights + KV pool "
            "+ aux + spec).", ("engine",))
        self.engine_hbm_tracked_bytes = r.gauge(
            "gateway_engine_hbm_tracked_bytes",
            "Bytes the engine's live device buffers actually occupy "
            "(array metadata; reconciles against the ledger).",
            ("engine",))
        self.engine_hbm_prefix_resident_bytes = r.gauge(
            "gateway_engine_hbm_prefix_resident_bytes",
            "KV-pool bytes held by radix-prefix-cache resident pages.",
            ("engine",))
        self.engine_hbm_device_in_use_bytes = r.gauge(
            "gateway_engine_hbm_device_in_use_bytes",
            "Runtime allocator bytes_in_use summed over the engine's "
            "local devices.", ("engine",))
        self.engine_hbm_device_peak_bytes = r.gauge(
            "gateway_engine_hbm_device_peak_bytes",
            "Runtime allocator peak_bytes_in_use summed over the "
            "engine's local devices.", ("engine",))
        self.engine_hbm_device_limit_bytes = r.gauge(
            "gateway_engine_hbm_device_limit_bytes",
            "Runtime allocator bytes_limit summed over the engine's "
            "local devices.", ("engine",))
        self.engine_hbm_headroom_ratio = r.gauge(
            "gateway_engine_hbm_headroom_ratio",
            "Free fraction of the device memory limit (the watermark "
            "shed threshold compares against this).", ("engine",))
        self.engine_watermark_sheds_total = r.gauge(
            "gateway_engine_watermark_sheds_total",
            "Admissions shed because device memory headroom fell below "
            "the configured watermark.", ("engine",))
        # XLA compile telemetry (ISSUE 8): process-wide monitor bridged
        # at scrape time; a compile during a serving phase is a
        # recompile some request paid for.
        self.engine_xla_compile_total = r.gauge(
            "gateway_engine_xla_compile_total",
            "Backend (XLA) compiles observed in this process, by the "
            "scheduler phase that triggered them (startup = engine "
            "build / prewarm; cost_analysis = the kernel registry's own "
            "AOT lowers).", ("phase",))
        self.engine_xla_compile_seconds = r.gauge(
            "gateway_engine_xla_compile_seconds",
            "Cumulative backend-compile wall seconds, by phase.",
            ("phase",))
        # The scheduler's time ledger (ISSUE 26; obs/phases.py): the
        # engine loop's wall since it started, partitioned.
        self.engine_sched_phase_ms_total = r.gauge(
            "gateway_engine_sched_phase_ms_total",
            "Milliseconds of the engine loop's wall under each scheduler "
            "phase: parked / admit / prefill_wait / decode_wait / emit / "
            "other sum to the loop's wall; hop / dispatch / fetch / "
            "worker_other sum to the two waits.", ("engine", "phase"))

        # -- SLO / goodput attribution plane (ISSUE 7; obs/slo.py) ------------
        self.slo_met_total = r.counter(
            "gateway_slo_met_total",
            "Requests that met every SLO target they carried.",
            ("engine",))
        self.slo_violated_total = r.counter(
            "gateway_slo_violated_total",
            "Requests that violated an SLO target, by attributed phase "
            "(queued / prefill / decode_contention / decode).",
            ("engine", "phase"))
        self.slo_goodput_ratio = r.gauge(
            "gateway_slo_goodput_ratio",
            "Fraction of SLO-carrying requests that met their targets "
            "(the DistServe goodput numerator over its denominator).",
            ("engine",))
        self.trace_ring_evicted_total = r.gauge(
            "gateway_trace_ring_evicted_total",
            "Request traces pushed out of the trace ring buffer.")
        self.engine_step_hbm_bytes = r.gauge(
            "gateway_engine_step_hbm_bytes",
            "HBM bytes one decode step must stream (weights + live KV).",
            ("engine",))
        self.engine_hbm_bandwidth_bytes = r.gauge(
            "gateway_engine_hbm_bandwidth_bytes",
            "Achieved HBM bandwidth in bytes per second at the measured "
            "step time.", ("engine",))
        self.engine_roofline_ratio = r.gauge(
            "gateway_engine_roofline_ratio",
            "Achieved bandwidth over the configured HBM peak.", ("engine",))

        # -- disaggregated serving plane (ISSUE 13; engine/disagg.py) ---------
        self.engine_pool_slots_total = r.gauge(
            "gateway_engine_pool_slots_total",
            "Batch slots owned by a scheduler pool.", ("engine", "pool"))
        self.engine_pool_free_slots_total = r.gauge(
            "gateway_engine_pool_free_slots_total",
            "Free slots in a scheduler pool.", ("engine", "pool"))
        self.engine_pool_running_total = r.gauge(
            "gateway_engine_pool_running_total",
            "Requests resident in a scheduler pool.", ("engine", "pool"))
        self.engine_pool_admits_total = r.gauge(
            "gateway_engine_pool_admits_total",
            "Admissions placed into a scheduler pool.", ("engine", "pool"))
        self.engine_pool_sheds_total = r.gauge(
            "gateway_engine_pool_sheds_total",
            "Goodput-admission sheds attributed to a pool's predicted "
            "miss.", ("engine", "pool"))
        self.engine_pool_predicted_ttft_seconds = r.gauge(
            "gateway_engine_pool_predicted_ttft_seconds",
            "Admission controller's predicted TTFT through the prefill "
            "pool.", ("engine", "pool"))
        self.engine_pool_predicted_tpot_seconds = r.gauge(
            "gateway_engine_pool_predicted_tpot_seconds",
            "Admission controller's predicted per-token time through the "
            "decode pool.", ("engine", "pool"))
        self.engine_pool_occupancy_ratio = r.gauge(
            "gateway_engine_pool_occupancy_ratio",
            "Fraction of the occupancy window spent in the pool's "
            "dispatches (flight-ring derived).", ("engine", "pool"))
        self.engine_disagg_handoffs_total = r.gauge(
            "gateway_engine_disagg_handoffs_total",
            "Prefill-to-decode KV handoffs (zero-copy refcount "
            "transfers).", ("engine",))
        self.engine_disagg_handoff_pages_total = r.gauge(
            "gateway_engine_disagg_handoff_pages_total",
            "KV pages whose ownership moved across a handoff without a "
            "device copy.", ("engine",))
        self.engine_disagg_clamps_total = r.gauge(
            "gateway_engine_disagg_clamps_total",
            "Admissions flagged TTFT-at-risk (clamped) instead of shed.",
            ("engine",))
        self.slo_pool_met_total = r.counter(
            "gateway_slo_pool_met_total",
            "SLO-met requests by the pool that served their decode.",
            ("engine", "pool"))
        self.slo_pool_violated_total = r.counter(
            "gateway_slo_pool_violated_total",
            "SLO-violating requests by the pool that served their "
            "decode.", ("engine", "pool"))
        self.slo_pool_goodput_ratio = r.gauge(
            "gateway_slo_pool_goodput_ratio",
            "Per-pool goodput: met over (met + violated) for requests "
            "the pool decoded — the pooled-vs-unified scoreboard.",
            ("engine", "pool"))

    def render(self) -> str:
        return self.registry.render()


_default: GatewayMetrics | None = None
_default_lock = threading.Lock()


def get_metrics() -> GatewayMetrics:
    """The process-wide instrument set. Layers built outside the app wiring
    (the local provider factory) record here; GatewayApp serves it."""
    global _default
    with _default_lock:
        if _default is None:
            _default = GatewayMetrics()
        return _default
