"""POST /v1/chat/completions — the gateway's core endpoint.

Thin HTTP shim over the routing engine (unlike the reference, whose handler
contains the whole fallback loop — ``api/v1/chat.py:41-198``). A body that is
JSON is parsed as JSON; what is not falls to json5, for parity with the
reference's lenient parsing (``chat.py:41``).
Streaming responses are committed (200, SSE headers) only after routing has
produced a primed stream, so upstream failures still fell back.

Reliability mapping (ISSUE 3): the client's ``x-request-timeout-ms`` header
(or ``timeout_ms`` body field) becomes the request's deadline budget;
exhaustion returns **504** with the partial-attempt log, an all-overloaded /
all-breaker-open chain returns **429** with a numeric ``Retry-After`` from
the engine's telemetry or the breakers' cooldowns, and everything else
keeps the reference's **503**.
"""
from __future__ import annotations

import functools
import json
import logging
import math

import json5
from aiohttp import web

from ..obs import trace as obs_trace
from ..obs.slo import slo_from_headers
from ..providers.base import JSONCompletion, StreamingCompletion
from ..reliability.deadline import budget_ms_from_request
from ..server.usage_capture import UsageCollector
from .middleware import client_api_key

logger = logging.getLogger(__name__)


def _parse_body(body: str):
    """Strict JSON first: ``json`` joins an escaped surrogate pair
    (``"\\ud83d\\ude00"``, what every ``ensure_ascii`` client sends for a
    character beyond U+FFFF) into its character, which json5 0.15 leaves as
    two lone surrogates that no tokenizer can encode — and it parses a
    140 kB prompt in 0.5 ms where json5 holds the event loop for a
    second. Only a body that is not JSON (comments, trailing commas,
    single quotes) is parsed leniently."""
    try:
        return json.loads(body)
    except ValueError:
        return json5.loads(body)


async def chat_completions(request: web.Request) -> web.StreamResponse:
    gw = request.app["gateway"]
    try:
        payload = _parse_body(await request.text())
        if not isinstance(payload, dict):
            raise ValueError("body must be a JSON object")
    except Exception as e:
        return web.json_response(
            {"error": {"message": f"invalid request body: {e}", "code": 400}},
            status=400)

    if "model" not in payload:
        return web.json_response(
            {"error": {"message": "missing required field 'model'", "code": 400}},
            status=400)

    timeout_ms = budget_ms_from_request(request.headers, payload)
    # Per-request SLO ask (ISSUE 7): x-slo-ttft-ms / x-slo-tpot-ms.
    # Rule-level defaults fill unset fields inside dispatch; the outcome
    # (met / violated+attributed) lands on /metrics and the usage row.
    slo = slo_from_headers(request.headers)

    observer_factory = functools.partial(
        _make_collector, payload=payload, gw=gw)

    outcome = await gw.router.dispatch(
        payload, client_api_key(request), observer_factory,
        timeout_ms=timeout_ms, request_id=request.get("request_id", ""),
        slo=slo)

    if outcome.error is not None or outcome.result is None:
        err = outcome.error
        detail = str(err) if err else "no providers succeeded"
        status = err.status if err and err.status in (429, 504) else 503
        headers = {}
        timings = obs_trace.server_timing_header()
        if timings:
            headers["x-gateway-timings"] = timings
        if status == 429:
            # Numeric Retry-After (RFC 9110 delay-seconds) from the engine's
            # step-time/queue-wait telemetry or the breakers' cooldowns.
            headers["Retry-After"] = str(
                max(1, math.ceil(err.retry_after_s or 1.0)))
        message = {
            429: f"Gateway overloaded. {detail}",
            504: f"Request deadline exceeded. {detail}",
        }.get(status, f"All fallback models failed. Last error: {detail}")
        return web.json_response(
            {"error": {"message": message, "code": status,
                       "attempts": outcome.attempts}},
            status=status, headers=headers)

    result = outcome.result
    if isinstance(result, JSONCompletion):
        # Per-phase latency summary for the client (Server-Timing style).
        # Non-streamed only: a streamed response's headers are on the wire
        # before the phases being summarized have happened.
        headers = {}
        timings = obs_trace.server_timing_header()
        if timings:
            headers["x-gateway-timings"] = timings
        return web.json_response(result.data, headers=headers)

    assert isinstance(result, StreamingCompletion)
    headers = {"Content-Type": "text/event-stream",
               "Cache-Control": "no-cache",
               "X-Accel-Buffering": "no",
               "Connection": "keep-alive"}
    # Streamed requests get the timing summary too (ISSUE 7 satellite):
    # the phases known at commit time (routing, provider attempts, the
    # engine's queued/prefill spans — everything up to first token) go in
    # a response-start header; the local provider additionally emits the
    # FULL summary, decode included, as the final usage frame's sibling
    # `gateway_timings` field, where post-commit phases exist.
    timings = obs_trace.server_timing_header()
    if timings:
        headers["x-gateway-timings"] = timings
    # Prepared responses bypass the header middleware; attach the id here.
    if request.get("request_id"):
        headers["x-request-id"] = request["request_id"]
    resp = web.StreamResponse(status=200, headers=headers)
    # The on-wire status for the request-end log, should the stream die
    # mid-flight (the middleware can't see it from a raised exception).
    request["prepared_status"] = 200
    await resp.prepare(request)
    with obs_trace.span("gateway.stream_drain", layer="gateway"):
        try:
            async for frame in result.frames:
                await resp.write(frame)
            await resp.write_eof()
        except ConnectionResetError:
            # Client hung up mid-stream; the provider generator's finally
            # block still fires (usage gets recorded with what was
            # streamed).
            logger.info("client disconnected mid-stream")
            await result.frames.aclose()
    return resp


def _make_collector(provider: str, model: str, *, payload: dict, gw) -> UsageCollector:
    settings = gw.settings
    # The write-behind recorder (ISSUE 14) duck-types UsageDB.insert:
    # stream-end observers enqueue instead of fsyncing SQLite inline.
    # Test-built GatewayApp stand-ins without a recorder fall through
    # to the raw DB.
    return UsageCollector(
        provider=provider, model=model,
        usage_db=getattr(gw, "usage_recorder", None) or gw.usage_db,
        request_payload=payload if settings.log_chat_messages else {},
        logs_dir=settings.logs_dir,
        log_chat_messages=settings.log_chat_messages,
        log_file_limit=settings.log_file_limit)
