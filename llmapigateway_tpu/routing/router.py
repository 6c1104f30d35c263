"""Routing engine: rule resolution, rotation, retry/fallback state machine.

Behavior parity with the reference's routing loop — which lives inline in its
API handler (``api/v1/chat.py:41-198``) — lifted into a service object so the
HTTP layer stays thin (SURVEY.md §7 step 2). Extended with the reliability
layer (ISSUE 3): per-request deadline budgets (retry sleeps and remaining
attempts clamped; exhaustion → 504 with partial-attempt detail),
per-provider circuit breakers (open breakers are skipped instantly — a dead
upstream stops costing its timeout on every request), fast-exit on
non-retryable errors (same-target retries of a hopeless attempt are
skipped), and overload shedding (an all-overload/all-open chain → 429 with
a Retry-After the client can act on). Reference semantics preserved:

* Rule lookup by gateway model name; unknown models become a synthetic
  single-target chain on the configured fallback provider with the model name
  passed through (``chat.py:48-59``).
* Rotation: persisted per-(client-key, gateway-model) round-robin start index
  with circular reorder of the chain (``chat.py:64-78``); DB errors degrade
  to index 0. The sqlite call is offloaded, never blocking the event loop
  (the reference blocks — ``chat.py:67``).
* Per-target retry loop: ``retry_count`` extra attempts, sleeping
  ``retry_delay`` seconds when ``0 < delay < 120`` (``chat.py:127,191-194``).
* Payload build per attempt: model rewrite to the provider-real name,
  OpenRouter ``usage.include`` auto-injection, ``custom_body_params`` /
  ``custom_headers`` merge, ``HTTP-Referer``/``X-Title`` headers
  (``chat.py:103-123``); OpenRouter ``provider.order`` pinning, and the
  ``use_provider_order_as_fallback`` sub-provider loop (``chat.py:137-139,
  158-189``).
* Every attempt gets a **fresh deep-copied payload** — deliberately fixing
  the reference quirk where a failure mutates ``messages`` to ``"<REMOVED>"``
  and retries send no real messages (``chat.py:150``; SURVEY.md §2a "Quirk").
* All targets exhausted → a terminal error the server maps to HTTP 503
  (``chat.py:196-198``).
"""
from __future__ import annotations

import asyncio
import copy
import logging
import time
from dataclasses import dataclass, field
from typing import Any, Callable

from ..config.loader import ConfigLoader, resolve_api_key
from ..config.schemas import FallbackModelRule, ModelFallbackConfig, ProviderDetails
from ..db.rotation import RotationDB
from ..obs import trace as obs_trace
from ..obs.metrics import GatewayMetrics, get_metrics
from ..providers.base import (
    CompletionError,
    CompletionRequest,
    JSONCompletion,
    Provider,
    StreamingCompletion,
    UsageObserver,
)
from ..providers.remote_http import RemoteHTTPProvider
from ..reliability.breaker import BreakerRegistry, counts_as_breaker_failure
from ..reliability.deadline import Deadline

logger = logging.getLogger(__name__)

MAX_RETRY_DELAY_S = 120.0        # honored window (chat.py:191)


class ProviderRegistry:
    """Builds/caches Provider instances from the live config.

    Instances are reused until the provider's config entry changes. ``local``
    providers are constructed through a pluggable factory so the gateway can
    run (and be tested) without importing JAX.
    """

    # Grace period before closing a reconfigured provider's pooled client:
    # must outlive the longest possible in-flight request (300 s timeout).
    RETIRE_AFTER_S = 330.0

    def __init__(self, loader: ConfigLoader,
                 local_factory: Callable[[str, ProviderDetails], Provider] | None = None):
        self._loader = loader
        self._local_factory = local_factory
        # name -> (fingerprint, provider)
        self._cache: dict[str, tuple[str, Provider]] = {}   # guarded-by: _lock
        self._lock = asyncio.Lock()
        self._name_locks: dict[str, asyncio.Lock] = {}      # guarded-by: _lock
        # Retire-task bookkeeping is touched only from loop-side code
        # (create_task callbacks, close()) — never from the _build worker
        # thread; the annotation makes graftlint v2's thread-reachability
        # pass and the runtime sanitizer both enforce that.
        self._retiring: set[asyncio.Task] = set()           # guarded-by: loop
        self._closed = False

    async def get(self, name: str) -> Provider | None:
        details = self._loader.providers.get(name)
        if details is None:
            return None
        fingerprint = details.model_dump_json()
        async with self._lock:
            cached = self._cache.get(name)
            if cached and cached[0] == fingerprint:
                return cached[1]
            name_lock = self._name_locks.setdefault(name, asyncio.Lock())
        # Build outside the registry lock: a local-engine build (checkpoint
        # load + device_put) takes seconds to minutes and must not stall
        # requests to other, already-cached providers. The per-name lock
        # stops two requests double-building the same provider; the build
        # itself runs in a worker thread so the event loop keeps serving.
        async with name_lock:
            async with self._lock:
                cached = self._cache.get(name)
                if cached and cached[0] == fingerprint:
                    return cached[1]
                if cached:
                    # Config changed: in-flight streams may still hold the
                    # old provider's pooled client — close it only after
                    # they can possibly have finished.
                    self._retire(cached[1])
                    del self._cache[name]
            provider = await asyncio.to_thread(self._build, name, details)
            if provider is not None:
                async with self._lock:
                    if self._closed:
                        # Registry shut down while this build was in flight:
                        # don't strand a live provider in a dead cache.
                        await provider.close()
                        return None
                    self._cache[name] = (fingerprint, provider)
            return provider

    def instantiated(self) -> list[tuple[str, Provider]]:
        """Currently-built providers (without forcing any build) — for the
        observability endpoints (server/profiler_api.py)."""
        return [(name, prov) for name, (_, prov) in self._cache.items()]

    def local_providers(self) -> list[Provider]:
        """Already-built providers backed by an in-process engine — the
        drain / SIGTERM surface (ISSUE 14). Builds nothing: a provider
        that never served has nothing to drain."""
        return [prov for _, prov in self.instantiated()
                if getattr(prov, "engine", None) is not None]

    def _retire(self, provider: Provider) -> None:
        async def _close_later() -> None:
            try:
                await asyncio.sleep(self.RETIRE_AFTER_S)
                await provider.close()
            except asyncio.CancelledError:
                await provider.close()
                raise
        task = asyncio.get_running_loop().create_task(_close_later())
        self._retiring.add(task)
        task.add_done_callback(self._retiring.discard)

    def _build(self, name: str, details: ProviderDetails) -> Provider | None:
        if details.type == "local":
            if self._local_factory is None:
                logger.error("provider %s is type=local but no engine factory "
                             "is installed", name)
                return None
            return self._local_factory(name, details)
        return RemoteHTTPProvider(
            name=name, base_url=details.baseUrl or "",
            api_key=resolve_api_key(details))

    async def close(self) -> None:
        async with self._lock:
            self._closed = True
            for task in list(self._retiring):
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
            for _, provider in self._cache.values():
                await provider.close()
            self._cache.clear()


@dataclass
class RouteOutcome:
    """Terminal result of routing one request through the fallback chain."""
    result: StreamingCompletion | JSONCompletion | None
    error: CompletionError | None
    attempts: int = 0
    provider: str = ""
    model: str = ""
    errors: list[str] = field(default_factory=list)


class Router:
    def __init__(self, loader: ConfigLoader, registry: ProviderRegistry,
                 rotation_db: RotationDB, fallback_provider: str = "openrouter",
                 sleep: Callable[[float], Any] | None = None,
                 breakers: BreakerRegistry | None = None,
                 default_timeout_ms: float = 0.0,
                 clock: Callable[[], float] | None = None,
                 metrics: GatewayMetrics | None = None):
        self._loader = loader
        self._registry = registry
        self._rotation = rotation_db
        self._fallback_provider = fallback_provider
        self._sleep = sleep or asyncio.sleep     # injectable for tests
        self._breakers = breakers
        self._default_timeout_ms = default_timeout_ms
        self._clock = clock or time.monotonic    # injectable for tests
        self._metrics = metrics or get_metrics()

    # -- rule resolution -----------------------------------------------------
    def resolve_rule(self, gateway_model: str) -> ModelFallbackConfig:
        rule = self._loader.rules.get(gateway_model)
        if rule is not None:
            return rule
        # Unknown model → passthrough to the fallback provider (chat.py:48-59).
        return ModelFallbackConfig(
            gateway_model_name=gateway_model,
            fallback_models=[FallbackModelRule(
                provider=self._fallback_provider, model=gateway_model)],
            rotate_models=False)

    async def _ordered_targets(self, rule: ModelFallbackConfig,
                               client_key: str) -> list[FallbackModelRule]:
        targets = list(rule.fallback_models)
        if rule.rotate_models and len(targets) > 1:
            start = await self._rotation.next_index_async(
                client_key, rule.gateway_model_name, len(targets))
            targets = targets[start:] + targets[:start]
        return targets

    # -- payload/header construction ------------------------------------------
    @staticmethod
    def _build_attempt(payload: dict[str, Any], target: FallbackModelRule,
                       provider_name: str,
                       pinned_order: list[str] | None,
                       deadline: Deadline | None = None,
                       request_id: str = "",
                       slo=None) -> CompletionRequest:
        attempt = copy.deepcopy(payload)
        attempt["model"] = target.model
        if provider_name.lower() == "openrouter":
            # Ask OpenRouter to report usage/cost (chat.py:114-115).
            attempt.setdefault("usage", {"include": True})
            order = pinned_order if pinned_order is not None else target.providers_order
            if order:
                attempt["provider"] = {"order": list(order),
                                       "allow_fallbacks": False}
        if target.custom_body_params:
            attempt.update(copy.deepcopy(target.custom_body_params))
        headers = {"HTTP-Referer": "https://llmapigateway-tpu.local",
                   "X-Title": "LLM API Gateway (TPU)"}
        if request_id:
            # Propagate the gateway's request id upstream so one id
            # correlates gateway and provider logs (ISSUE 4).
            headers["x-request-id"] = request_id
        if target.custom_headers:
            headers.update(target.custom_headers)
        stream = bool(attempt.get("stream", False))
        return CompletionRequest(payload=attempt, stream=stream,
                                 extra_headers=headers, deadline=deadline,
                                 slo=slo)

    # -- the state machine -----------------------------------------------------
    def _start_deadline(self, rule: ModelFallbackConfig,
                        timeout_ms: float | None) -> Deadline | None:
        """Resolve the request's time budget: explicit client ask (header /
        body, parsed by the HTTP layer) > per-rule ``timeout_ms`` >
        gateway-wide default; 0/None at every level = unbounded."""
        budget_ms = timeout_ms or rule.timeout_ms or self._default_timeout_ms
        if not budget_ms or budget_ms <= 0:
            return None
        return Deadline(budget_ms / 1000.0, clock=self._clock)

    async def dispatch(self, payload: dict[str, Any], client_key: str,
                       observer_factory: Callable[[str, str], UsageObserver],
                       timeout_ms: float | None = None,
                       request_id: str = "",
                       slo=None) -> RouteOutcome:
        """Route one chat-completions payload through the fallback chain.

        ``observer_factory(provider, model)`` builds a fresh usage observer
        per attempt; only the successful attempt's observer sees a complete
        stream, so usage is recorded exactly once. ``timeout_ms`` is the
        client's explicit budget (x-request-timeout-ms header / timeout_ms
        body field), if any. ``request_id`` is propagated on outbound
        provider requests (and labels this request's trace spans). ``slo``
        is the client's SLO-header ask; the rule's ``slo_ttft_ms`` /
        ``slo_tpot_ms`` defaults fill unset fields (obs/slo.py), mirroring
        the deadline precedence chain.
        """
        from ..obs.slo import resolve_slo
        gateway_model = str(payload.get("model", ""))
        rule = self.resolve_rule(gateway_model)
        targets = await self._ordered_targets(rule, client_key)
        deadline = self._start_deadline(rule, timeout_ms)
        slo = resolve_slo(slo, rule)
        m = self._metrics

        outcome = RouteOutcome(result=None, error=None)
        # Terminal-status classification (ISSUE 3): 504 when the budget ran
        # out, 429 when EVERY failure was backpressure (engine/upstream
        # overload or an open breaker) so the client gets a Retry-After it
        # can act on, 503 otherwise.
        n_overload = 0
        n_other = 0
        deadline_hit = False
        retry_hints: list[float] = []

        for target_idx, target in enumerate(targets):
            if deadline is not None and deadline.expired():
                deadline_hit = True
                break
            provider = await self._registry.get(target.provider)
            if provider is None:
                outcome.errors.append(
                    f"provider {target.provider!r} unavailable")
                n_other += 1
                continue

            breaker = (self._breakers.get(target.provider)
                       if self._breakers is not None else None)
            if breaker is not None and not breaker.allow():
                # Open breaker: fall through instantly — no payload build,
                # no network, no retry sleeps for a known-dead upstream.
                cooldown = breaker.cooldown_remaining()
                outcome.errors.append(
                    f"{target.provider}/{target.model}: circuit open "
                    f"(retry in {cooldown:.1f}s)")
                retry_hints.append(cooldown)
                n_overload += 1
                m.router_breaker_skips_total.labels(
                    provider=target.provider).inc()
                obs_trace.record_span(
                    "router.breaker_skip", layer="router",
                    provider=target.provider,
                    cooldown_s=round(cooldown, 2))
                continue

            # Sub-provider fallback: gateway loops OpenRouter upstreams one at
            # a time, each pinned (chat.py:158-189). Otherwise one attempt
            # series with the whole order pinned (chat.py:137-139).
            if target.use_provider_order_as_fallback and target.providers_order:
                sub_orders: list[list[str] | None] = [
                    [sub] for sub in target.providers_order]
            else:
                sub_orders = [None]

            retries = max(0, int(target.retry_count))
            target_done = False          # non-retryable / deadline fast-exit
            target_attempted = False     # any attempt actually sent?
            for attempt_idx in range(retries + 1):
                for sub_order in sub_orders:
                    if deadline is not None and deadline.expired():
                        deadline_hit = True
                        target_done = True
                        if breaker is not None and not target_attempted:
                            # allow() may have reserved the half-open probe;
                            # we never sent it — release, don't leak.
                            breaker.release_probe()
                        break
                    request = self._build_attempt(
                        payload, target, target.provider, sub_order, deadline,
                        request_id=request_id, slo=slo)
                    observer = observer_factory(target.provider, target.model)
                    outcome.attempts += 1
                    target_attempted = True
                    m.router_attempts_total.labels(
                        provider=target.provider).inc()
                    t_attempt = self._clock()
                    with obs_trace.span(
                            "router.attempt", layer="router",
                            provider=target.provider, model=target.model,
                            attempt=outcome.attempts) as att_span:
                        with obs_trace.span(
                                "provider.call", layer="provider",
                                provider=target.provider):
                            result, error = await provider.complete(
                                request, observer)
                        if att_span is not None and error is not None:
                            att_span.attrs["error"] = str(error)[:200]
                    m.provider_attempt_duration_seconds.labels(
                        provider=target.provider).observe(
                            self._clock() - t_attempt)
                    if error is not None:
                        kind = error.kind or (
                            "http" if error.status is not None else "error")
                        m.provider_errors_total.labels(
                            provider=target.provider, kind=kind).inc()
                        if error.kind == "timeout":
                            m.provider_timeouts_total.labels(
                                provider=target.provider).inc()
                    if error is None and result is not None:
                        if breaker is not None:
                            breaker.record_success()
                        outcome.result = result
                        outcome.provider = target.provider
                        outcome.model = target.model
                        return outcome
                    breaker_opened = False
                    if breaker is not None:
                        if counts_as_breaker_failure(error):
                            breaker.record_failure()
                            # This failure tripped (or re-tripped, for a
                            # failed half-open probe) the breaker: the
                            # window has judged this target dead — burning
                            # the remaining same-target retries and sleeps
                            # would be exactly the waste breakers exist to
                            # stop.
                            breaker_opened = breaker.state == "open"
                        else:
                            # Alive-but-rejecting (plain 4xx): not evidence
                            # of an unhealthy upstream.
                            breaker.record_success()
                    if error is not None and error.kind == "overload":
                        n_overload += 1
                        if error.retry_after_s is not None:
                            retry_hints.append(error.retry_after_s)
                    else:
                        n_other += 1
                    detail = str(error) if error else "empty response"
                    sub = f" (upstream={sub_order[0]})" if sub_order else ""
                    outcome.errors.append(
                        f"{target.provider}/{target.model}{sub}: {detail}")
                    logger.warning("attempt failed: %s", outcome.errors[-1])
                    if breaker_opened or (error is not None
                                          and not error.retryable):
                        # Same-target retries of a non-retryable failure
                        # (invalid request, deadline hit) or of a target
                        # whose breaker just opened are pure waste — skip
                        # straight to the next target (ISSUE 3 satellite;
                        # previously burned the full retry loop).
                        target_done = True
                        break
                if target_done:
                    break
                if attempt_idx < retries and 0 < target.retry_delay < MAX_RETRY_DELAY_S:
                    # Clamp the backoff sleep against the remaining budget: a
                    # 119 s retry_delay must never outlive a 2 s deadline.
                    delay = (deadline.clamp(target.retry_delay)
                             if deadline is not None else target.retry_delay)
                    if delay > 0:
                        await self._sleep(delay)
            if deadline_hit:
                break
            if target_attempted and target_idx < len(targets) - 1:
                # Falling past an attempted-and-failed target to the next
                # one in the chain — the fallback-hop counter.
                m.router_fallbacks_total.inc()

        if deadline is not None and (deadline_hit or deadline.expired()):
            budget_ms = deadline.budget_s * 1000.0
            m.router_deadline_expired_total.inc()
            outcome.error = CompletionError(
                detail=(f"deadline of {budget_ms:.0f} ms exhausted after "
                        f"{outcome.attempts} attempt(s): "
                        + ("; ".join(outcome.errors[-5:]) or "no attempts made")),
                status=504, retryable=False, kind="timeout")
        elif n_overload > 0 and n_other == 0 and outcome.errors:
            m.router_sheds_total.inc()
            outcome.error = CompletionError(
                detail="all providers overloaded or shedding: "
                       + "; ".join(outcome.errors[-5:]),
                status=429, retryable=True, kind="overload",
                retry_after_s=max(retry_hints, default=1.0))
        else:
            outcome.error = CompletionError(
                detail="; ".join(outcome.errors[-5:]) or
                       f"no providers available for {gateway_model!r}",
                status=503, retryable=False)
        return outcome
