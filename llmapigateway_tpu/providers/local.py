"""`local` provider: the in-process TPU engine behind the standard provider
contract.

This is the BASELINE.json north star — ``/v1/chat/completions`` answered by
an in-process JAX/XLA engine with **no remote call in the loop**, while
staying "just another entry in providers.json": same ``(response, error)``
contract as remote providers, so fallback/rotation/usage plumbing applies
unchanged, and engine overload/failure falls back to remote providers
(BASELINE config 5).

Streaming commits only after the first token exists (prefill admission +
first sample) — the local analog of the remote SSE priming trick
(SURVEY.md §7 hard part (3)).
"""
from __future__ import annotations

import asyncio
import logging
import time
import uuid
from typing import Any, AsyncIterator

from ..config.schemas import ProviderDetails
from ..obs import slo as obs_slo
from ..obs import trace as obs_trace
from ..obs.metrics import GatewayMetrics, get_metrics
from ..utils.sse import SSE_DONE, format_sse
from .base import (
    CompletionError,
    CompletionRequest,
    CompletionResult,
    JSONCompletion,
    Provider,
    StreamingCompletion,
    UsageObserver,
)

logger = logging.getLogger(__name__)


class LocalProvider(Provider):
    type = "local"

    def __init__(self, name: str, engine: "InferenceEngine",
                 metrics: GatewayMetrics | None = None):
        self.name = name
        self.engine = engine
        self._metrics = metrics or get_metrics()

    # -- engine-phase tracing --------------------------------------------------
    # The engine loop runs outside the request's task, so its phases are
    # reported post-hoc from the GenRequest's own timestamps (ISSUE 4):
    # queued (submit → slot admission), prefill (admission → first token),
    # then decode/drain recorded at stream end. `parent` is the
    # provider.call span captured while complete() was current.
    # Both spans carry what the request waited behind in them (ISSUE 41,
    # obs/phases.py ``ReqWaits``): the buckets partition admission → the
    # loop's first-token reading → done, so they sum to the spans' walls
    # plus/minus the worker's tail after its first-token stamp.

    def _trace_admission(self, req, parent) -> None:
        if req.t_first_token is None:
            return
        t_admit = req.t_admitted or req.t_submit
        # The flight-recorder cross-link (ISSUE 7): the admit record's
        # sequence number, so an operator can jump from this request's
        # trace to the exact scheduler steps that served it
        # (GET /v1/api/flight / tools/flight_report.py).
        attrs = ({"flight_seq": req.flight_admit_seq}
                 if req.flight_admit_seq >= 0 else {})
        obs_trace.record_span("engine.queued", layer="engine",
                              start=req.t_submit, end=t_admit, parent=parent,
                              **attrs)
        if req.prefix_lookup_ms is not None:
            # Radix prefix lookup (ISSUE 6), ran just before admission
            # stamped t_admitted; cached_tokens is the prefill span the
            # hit skipped (0 = miss).
            obs_trace.record_span(
                "engine.prefix_lookup", layer="engine",
                start=t_admit - req.prefix_lookup_ms / 1000.0, end=t_admit,
                parent=parent, cached_tokens=req.cached_tokens)
        w = req.waits
        waited = ({"own_ms": round(w.ttft_own_prefill, 3),
                   "behind_prefill_ms": round(w.ttft_behind_prefill, 3),
                   "behind_decode_ms": round(w.ttft_behind_decode, 3),
                   "loop_ms": round(w.ttft_loop, 3)}
                  if w.t_first_loop is not None else {})
        obs_trace.record_span("engine.prefill", layer="engine",
                              start=t_admit, end=req.t_first_token,
                              parent=parent,
                              prompt_tokens=len(req.prompt_ids), **waited)
        obs_trace.record_span("engine.first_token", layer="engine",
                              start=req.t_first_token, end=req.t_first_token,
                              parent=parent)
        self._metrics.engine_ttft_seconds.labels(engine=self.name).observe(
            max(0.0, req.t_first_token - req.t_submit))

    def _trace_decode(self, req, parent, error: str | None = None) -> None:
        if req.t_first_token is None:
            return
        end = req.t_done if req.t_done is not None else time.monotonic()
        attrs = {"tokens": len(req.generated)}
        if req.finish_reason:
            attrs["finish_reason"] = req.finish_reason
        if error:
            attrs["error"] = error[:200]
        w = req.waits
        if w.closed and w.t_first_loop is not None:
            attrs.update(in_decode_ms=round(w.decode_in_decode, 3),
                         behind_prefill_ms=round(w.decode_behind_prefill, 3),
                         loop_ms=round(w.decode_loop, 3))
        obs_trace.record_span("engine.decode", layer="engine",
                              start=req.t_first_token, end=end,
                              parent=parent, **attrs)
        now = time.monotonic()
        if req.t_done is not None and now > req.t_done:
            # Emission drained after the engine finished (lag-one bursts +
            # stop-sequence holdback flush through here).
            obs_trace.record_span("engine.drain", layer="engine",
                                  start=req.t_done, end=now, parent=parent)

    # -- request translation ---------------------------------------------------
    def _build_genrequest(self, payload: dict[str, Any]):
        from ..engine.engine import GenRequest
        tok = self.engine.tokenizer
        messages = payload.get("messages") or []
        if not isinstance(messages, list):
            raise ValueError("'messages' must be a list")
        prompt_text = tok.apply_chat_template(messages,
                                              add_generation_prompt=True)
        prompt_ids = tok.encode(prompt_text)
        if tok.bos_id is not None and (not prompt_ids or
                                       prompt_ids[0] != tok.bos_id):
            prompt_ids = [tok.bos_id] + prompt_ids

        stop = payload.get("stop") or []
        if isinstance(stop, str):
            stop = [stop]
        max_tokens = int(payload.get("max_completion_tokens")
                         or payload.get("max_tokens")
                         or self.engine.cfg.max_tokens_default)
        # OpenAI default: temperature=1 (sampled) when omitted; an explicit
        # 0 still means greedy.
        raw_temp = payload.get("temperature")
        temperature = 1.0 if raw_temp is None else float(raw_temp)
        top_p = float(payload.get("top_p", 1.0) or 1.0)
        top_k = int(payload.get("top_k", 0) or 0)
        # OpenAI penalty fields (engine/sampling.py apply_penalties). `or 0.0`
        # also maps explicit null to the no-penalty default.
        presence = float(payload.get("presence_penalty") or 0.0)
        frequency = float(payload.get("frequency_penalty") or 0.0)
        return GenRequest(prompt_ids=prompt_ids, max_tokens=max_tokens,
                          temperature=temperature, top_p=top_p, top_k=top_k,
                          presence_penalty=presence,
                          frequency_penalty=frequency,
                          stop=[s for s in stop if s])

    def _usage(self, req) -> dict[str, Any]:
        n_gen = len(req.generated)
        usage = {"prompt_tokens": len(req.prompt_ids),
                 "completion_tokens": n_gen,
                 "total_tokens": len(req.prompt_ids) + n_gen}
        if req.cached_tokens:
            # OpenAI-compatible prefix-cache accounting: the span of the
            # prompt served from resident KV (prefill skipped). Flows into
            # the usage DB / stats UI via extract_usage_fields.
            usage["prompt_tokens_details"] = {
                "cached_tokens": req.cached_tokens}
        if req.t_first_token is not None:
            usage["ttft_ms"] = round(
                (req.t_first_token - req.t_submit) * 1000.0, 2)
            if req.t_done and n_gen > 1 and req.t_done > req.t_first_token:
                usage["tokens_per_sec"] = round(
                    (n_gen - 1) / (req.t_done - req.t_first_token), 2)
        slo_out = self._slo_outcome(req)
        if slo_out is not None:
            # SLO outcome + attribution (ISSUE 7): rides the usage object
            # into the SSE usage frame AND the usage DB row
            # (extract_usage_fields ingests met/phase).
            usage["slo"] = slo_out
        return usage

    def _slo_outcome(self, req) -> dict[str, Any] | None:
        """Evaluate + record this request's SLO outcome exactly once
        (idempotent via a stash on the request): counters on /metrics,
        violation attributed against the engine's flight recorder."""
        slo = obs_slo.SLOTargets(ttft_ms=req.slo_ttft_ms,
                                 tpot_ms=req.slo_tpot_ms)
        if not slo.defined:
            return None
        cached = getattr(req, "_slo_outcome_cache", None)
        if cached is not None:
            return cached
        engine = getattr(self, "engine", None)
        flight = getattr(engine, "flight", None)
        outcome = obs_slo.evaluate(req, slo, flight)
        if outcome["met"]:
            self._metrics.slo_met_total.labels(engine=self.name).inc()
        else:
            self._metrics.slo_violated_total.labels(
                engine=self.name, phase=outcome["phase"]).inc()
        # Per-pool SLO attribution (ISSUE 13): keyed by the pool that
        # served the request's decode (post-handoff), so a disaggregated
        # engine's goodput splits into per-pool numerators and the
        # unified engine keeps one "unified" series — the
        # pooled-vs-unified scoreboard behind
        # gateway_slo_pool_goodput_ratio.
        from ..obs.flight import POOL_NAMES
        pool = POOL_NAMES.get(getattr(req, "pool", 0), "unified")
        outcome["pool"] = pool
        if outcome["met"]:
            self._metrics.slo_pool_met_total.labels(
                engine=self.name, pool=pool).inc()
        else:
            self._metrics.slo_pool_violated_total.labels(
                engine=self.name, pool=pool).inc()
        req._slo_outcome_cache = outcome
        return outcome

    # -- the provider contract -------------------------------------------------
    async def complete(self, request: CompletionRequest,
                       observer: UsageObserver) -> CompletionResult:
        from ..engine.engine import EngineOverloaded, EngineUnavailable
        payload = request.payload
        model_name = str(payload.get("model", self.name))
        try:
            req = self._build_genrequest(payload)
        except Exception as e:
            return None, CompletionError(f"invalid request for local engine: {e}",
                                         retryable=False)
        # Gateway request id onto the engine request: the flight
        # recorder's admit/finish/shed records carry it, linking
        # scheduler timeline rows back to /v1/api/trace/{id} (ISSUE 7).
        req.request_id = obs_trace.current_request_id() or ""
        if request.slo is not None:
            req.slo_ttft_ms = request.slo.ttft_ms
            req.slo_tpot_ms = request.slo.tpot_ms
        try:
            await self.engine.submit(req)
        except EngineOverloaded as e:
            # Overload is a *failable provider* condition: the router falls
            # back to the next (e.g. remote) target — SURVEY.md §5 — and,
            # when the WHOLE chain is overloaded, sheds with HTTP 429 +
            # Retry-After from the engine's own telemetry (ISSUE 3).
            hint = None
            try:
                hint = self.engine.retry_after_hint_s()
            except Exception:       # stats must never break shedding
                logger.debug("retry-after hint unavailable; shedding "
                             "without one", exc_info=True)
            return None, CompletionError(str(e), status=503,
                                         kind="overload", retry_after_s=hint)
        except EngineUnavailable as e:
            # Engine down/draining/restarting (ISSUE 14): a retryable
            # 503 whose status feeds the breaker's failure window, so a
            # few of these open the breaker and the router skips the
            # local provider at ~0 cost until the supervisor recovers
            # the engine and the half-open probe readmits it.
            return None, CompletionError(
                str(e), status=503, kind="engine_down",
                retry_after_s=getattr(e, "retry_after_s", None))
        except Exception as e:
            logger.exception("engine submit failed")
            return None, CompletionError(f"local engine error: {e}")

        # Wait for the first delta before committing (priming analog): if the
        # engine fails before producing a token, the router can still fall
        # back. A request deadline bounds this wait: on expiry the slot is
        # cancelled (the engine stops decoding and frees it) and the attempt
        # reports kind="timeout" so the router's 504 path takes over.
        deadline = request.deadline
        parent = obs_trace.current_span()
        stream_iter = self.engine.stream(req)
        try:
            if deadline is not None:
                first_delta = await asyncio.wait_for(
                    anext(stream_iter), timeout=max(0.001, deadline.remaining()))
            else:
                first_delta = await anext(stream_iter)
        except StopAsyncIteration:
            return None, CompletionError("engine produced no output")
        except asyncio.TimeoutError:
            # The loop drops cancelled requests at its next admission /
            # decode pass — the slot (or queue position) frees itself.
            req.cancelled = True
            return None, CompletionError(
                "deadline expired before the local engine produced a token",
                kind="timeout", retryable=False)
        if first_delta.error is not None:
            return None, CompletionError(first_delta.error)

        observer.on_first_token()
        self._trace_admission(req, parent)

        if request.stream:
            frames = self._sse_frames(req, stream_iter, first_delta,
                                      model_name, observer,
                                      deadline=deadline, parent=parent)
            return StreamingCompletion(frames=frames, provider=self.name,
                                       model=model_name), None

        # Non-streaming: drain (cancel the engine work if the handler task is
        # cancelled, e.g. the client disconnected while we generate).
        text_parts = [first_delta.text]
        finish = first_delta.finish_reason
        error = first_delta.error
        try:
            if finish is None and error is None:
                async for delta in stream_iter:
                    text_parts.append(delta.text)
                    finish = delta.finish_reason
                    error = delta.error
                    if (finish is None and error is None
                            and deadline is not None and deadline.expired()):
                        # Decode cancellation on budget exhaustion: stop the
                        # slot and report timeout — the router returns 504
                        # (the client asked for a bounded wait, not a
                        # truncated answer).
                        req.cancelled = True
                        observer.on_stream_end("deadline expired")
                        self._trace_decode(req, parent,
                                           error="deadline expired")
                        self._slo_outcome(req)
                        return None, CompletionError(
                            "deadline expired during local decode",
                            kind="timeout", retryable=False)
        except asyncio.CancelledError:
            req.cancelled = True
            raise
        if error is not None:
            observer.on_stream_end(error)
            self._trace_decode(req, parent, error=error)
            self._slo_outcome(req)
            return None, CompletionError(error)
        self._trace_decode(req, parent)
        text = "".join(text_parts)
        usage = self._usage(req)
        observer.on_content_delta(text)
        observer.on_usage(usage)
        observer.on_stream_end()
        body = {
            "id": f"chatcmpl-{uuid.uuid4().hex[:24]}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": model_name,
            "choices": [{"index": 0,
                         "message": {"role": "assistant", "content": text},
                         "finish_reason": finish or "stop"}],
            "usage": usage,
        }
        return JSONCompletion(data=body, provider=self.name,
                              model=model_name), None

    async def _sse_frames(self, req, stream_iter: AsyncIterator,
                          first_delta, model_name: str,
                          observer: UsageObserver,
                          deadline=None, parent=None) -> AsyncIterator[bytes]:
        cid = f"chatcmpl-{uuid.uuid4().hex[:24]}"
        created = int(time.time())
        tbt = self._metrics.engine_time_between_tokens_seconds.labels(
            engine=self.name)

        def chunk(delta_content: str | None, finish: str | None = None,
                  role: str | None = None, usage: dict | None = None,
                  timings: str | None = None) -> bytes:
            delta: dict[str, Any] = {}
            if role:
                delta["role"] = role
            if delta_content:
                delta["content"] = delta_content
            body: dict[str, Any] = {
                "id": cid, "object": "chat.completion.chunk",
                "created": created, "model": model_name,
                "choices": [{"index": 0, "delta": delta,
                             "finish_reason": finish}]}
            if usage is not None:
                body["usage"] = usage
            if timings:
                # Streamed analog of the x-gateway-timings header (ISSUE 7
                # satellite): the FULL per-phase summary — decode included,
                # which no response-start header can carry — as the usage
                # frame's sibling field. Extra top-level keys are ignored
                # by OpenAI-protocol clients.
                body["gateway_timings"] = timings
            return format_sse(body)

        error: str | None = None
        traced = False
        last_t = time.monotonic()
        try:
            yield chunk(None, role="assistant")
            if first_delta.text:
                observer.on_content_delta(first_delta.text)
                yield chunk(first_delta.text)
            finish = first_delta.finish_reason
            if finish is None:
                async for delta in stream_iter:
                    now = time.monotonic()
                    tbt.observe(now - last_t)
                    last_t = now
                    if delta.error is not None:
                        error = delta.error
                        yield format_sse({"error": {"message": error,
                                                    "provider": self.name}})
                        return
                    if (deadline is not None and deadline.expired()
                            and delta.finish_reason is None):
                        # Budget exhausted mid-stream: stop decoding, free
                        # the slot, and end the committed stream with an
                        # in-band error frame (the 200 is long since on the
                        # wire — the 504 path only exists pre-commit).
                        error = "deadline expired mid-stream"
                        req.cancelled = True
                        yield format_sse({"error": {
                            "message": "request deadline expired mid-stream",
                            "provider": self.name, "code": 504}})
                        return
                    if delta.text:
                        observer.on_content_delta(delta.text)
                        yield chunk(delta.text)
                    if delta.finish_reason is not None:
                        finish = delta.finish_reason
            # Close the decode/drain spans BEFORE building the summary so
            # the streamed timing field covers the whole request.
            self._trace_decode(req, parent)
            traced = True
            usage = self._usage(req)
            observer.on_usage(usage)
            yield chunk(None, finish=finish or "stop", usage=usage,
                        timings=obs_trace.server_timing_header() or None)
            yield format_sse(SSE_DONE)
        finally:
            if req.finish_reason is None:
                # Client hung up mid-stream (generator closed early): tell
                # the engine to stop decoding and free the slot.
                req.cancelled = True
            observer.on_stream_end(error)
            if not traced:
                self._trace_decode(req, parent, error=error)
            # Error/disconnect exits skip the usage frame; the SLO outcome
            # must still be counted (idempotent — the success path already
            # recorded it inside _usage).
            self._slo_outcome(req)

    async def list_models(self) -> list[dict[str, Any]] | None:
        return [{"id": self.name, "object": "model", "owned_by": "local_tpu",
                 "context_length": self.engine.S}]

    async def close(self) -> None:
        await self.engine.stop()


def make_local_provider(name: str, details: ProviderDetails) -> LocalProvider:
    """Factory installed into the ProviderRegistry (server/app.py)."""
    from ..engine.engine import InferenceEngine
    assert details.engine is not None
    engine = InferenceEngine(details.engine)
    return LocalProvider(name, engine)
