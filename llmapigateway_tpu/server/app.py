"""Application composition: wire config, DBs, router, providers, HTTP app.

Counterpart of the reference's ``main.py`` (app bootstrap, lifespan state,
middleware order, router mounting, static files, ``/health``, ``/`` redirect
— ``main.py:30-116``), built on aiohttp. One ``GatewayApp`` owns exactly one
ConfigLoader / UsageDB / RotationDB (the reference accidentally creates
duplicates at import time — SURVEY.md §1 "layering reality").
"""
from __future__ import annotations

import asyncio
import logging
from pathlib import Path
from typing import Callable

from aiohttp import web

from ..config.loader import ConfigLoader
from ..config.settings import Settings
from ..db.recorder import UsageRecorder
from ..db.rotation import RotationDB
from ..db.usage import UsageDB
from ..obs.metrics import GatewayMetrics, get_metrics
from ..obs.trace import Tracer
from ..providers.base import Provider
from ..reliability.breaker import BreakerRegistry
from ..routing.router import ProviderRegistry, Router
from . import chat, config_api, models_api, obs_api, profiler_api, stats_api
from .middleware import (
    auth_middleware,
    cors_middleware,
    request_id_header_middleware,
    request_logging_middleware,
)

logger = logging.getLogger(__name__)

STATIC_DIR = Path(__file__).resolve().parent.parent / "static"


class GatewayApp:
    """Holds the gateway's singletons; attached to the aiohttp app as
    ``app["gateway"]``."""

    def __init__(self, settings: Settings, loader: ConfigLoader,
                 local_factory: Callable[..., Provider] | None = None,
                 metrics: GatewayMetrics | None = None,
                 tracer: Tracer | None = None):
        self.settings = settings
        self.loader = loader
        self.usage_db = UsageDB(settings.db_dir or "db")
        # Write-behind usage recording (ISSUE 14): stream-end observers
        # enqueue; one background flusher owns the SQLite writes. The
        # recorder duck-types UsageDB.insert, so chat.py hands it to
        # UsageCollector unchanged; close() drains before the DB closes
        # so process exit never loses completed requests' rows.
        self.usage_recorder = UsageRecorder(self.usage_db)
        self.rotation_db = RotationDB(settings.db_dir or "db")
        self.registry = ProviderRegistry(loader, local_factory=local_factory)
        self.breakers = BreakerRegistry(loader)
        # Observability plane (ISSUE 4): the process-global metrics set by
        # default (the local-provider factory records into it too) and a
        # per-app trace ring buffer.
        self.metrics = metrics or get_metrics()
        self.tracer = tracer or Tracer(
            capacity=max(1, settings.trace_ring_size))
        self.router = Router(
            loader, self.registry, self.rotation_db,
            fallback_provider=settings.fallback_provider,
            breakers=self.breakers,
            default_timeout_ms=settings.default_request_timeout_ms,
            metrics=self.metrics)
        self._stats_collector = obs_api.make_stats_collector(self)
        self.metrics.registry.register_collector(self._stats_collector)

    async def close(self) -> None:
        self.metrics.registry.unregister_collector(self._stats_collector)
        await self.registry.close()
        # Recorder before DB: drain the write-behind queue while the
        # connection is still open (flush-on-shutdown contract).
        await asyncio.to_thread(self.usage_recorder.close)
        self.usage_db.close()
        self.rotation_db.close()

    async def drain_local_engines(self, *, restart: bool = False) -> list:
        """Administrative drain of every local provider's engine
        (ISSUE 14): planned restart / SIGTERM path. Flushes the usage
        recorder afterwards so interrupted streams' partial rows are
        durable before the caller exits or reloads."""
        results = []
        for provider in self.registry.local_providers():
            engine = getattr(provider, "engine", None)
            if engine is None:
                continue
            try:
                results.append(await engine.drain(restart=restart))
            except Exception:
                logger.exception("drain failed for provider %r",
                                 getattr(provider, "name", "?"))
        await asyncio.to_thread(self.usage_recorder.flush)
        return results


async def _health(request: web.Request) -> web.Response:
    return web.json_response({"status": "ok"})


async def _root_redirect(request: web.Request) -> web.Response:
    raise web.HTTPFound("/v1/ui/rules-editor")


def _static_page(filename: str):
    async def handler(request: web.Request) -> web.Response:
        path = STATIC_DIR / filename
        if not path.exists():
            return web.json_response({"detail": f"{filename} not found"}, status=404)
        text = await asyncio.to_thread(path.read_text)
        return web.Response(text=text, content_type="text/html")
    return handler


def build_app(settings: Settings | None = None,
              loader: ConfigLoader | None = None,
              local_factory: Callable[..., Provider] | None = None,
              gateway: GatewayApp | None = None) -> web.Application:
    """Build the aiohttp application. All dependencies injectable for tests."""
    settings = settings or Settings.from_env()
    if loader is None:
        loader = ConfigLoader(settings.config_dir or ".",
                              fallback_provider=settings.fallback_provider)
    gw = gateway or GatewayApp(settings, loader, local_factory=local_factory)

    app = web.Application(middlewares=[
        cors_middleware(settings.allowed_origins),
        request_id_header_middleware(),
        request_logging_middleware(metrics=gw.metrics, tracer=gw.tracer),
        auth_middleware(settings.gateway_api_key),
    ])
    app["gateway"] = gw

    app.router.add_get("/health", _health)
    # Unified metrics plane: every layer's instruments in one Prometheus
    # text-format scrape (ISSUE 4).
    app.router.add_get("/metrics", obs_api.get_metrics_text)
    app.router.add_get("/", _root_redirect)

    # Core OpenAI-compatible API
    app.router.add_post("/v1/chat/completions", chat.chat_completions)
    app.router.add_get("/v1/models", models_api.get_models)
    app.router.add_get("/v1/models/AsOpenCodeFormat",
                       models_api.get_models_as_opencode)
    app.router.add_get("/v1/models/AsGitHubCopilotFormat",
                       models_api.get_models_as_github_copilot)

    # Config editor API (+ UI pages)
    app.router.add_get("/v1/config/models-rules", config_api.get_rules_text)
    app.router.add_post("/v1/config/models-rules", config_api.save_rules)
    app.router.add_get("/v1/config/providers", config_api.get_providers_text)
    app.router.add_post("/v1/config/providers", config_api.save_providers)
    app.router.add_get("/v1/ui/rules-editor", _static_page("rules-editor.html"))
    app.router.add_get("/v1/ui/usage-stats", _static_page("usage-stats.html"))

    # Stats API
    app.router.add_get("/v1/api/usage-stats/{period}", stats_api.get_usage_stats)
    app.router.add_get("/v1/api/usage-records", stats_api.get_usage_records)
    # Reliability: live circuit-breaker state per provider (ISSUE 3)
    app.router.add_get("/v1/api/health/providers", stats_api.get_provider_health)

    # Observability: engine stats + on-demand device trace capture
    app.router.add_get("/v1/api/engine-stats", profiler_api.get_engine_stats)
    app.router.add_get("/v1/api/roofline", profiler_api.get_roofline)
    app.router.add_post("/v1/api/profiler/trace", profiler_api.capture_trace)
    # End-to-end request traces (router → provider → engine span trees).
    app.router.add_get("/v1/api/trace/{request_id}", obs_api.get_trace)
    # Scheduler flight recorder: per-step/lifecycle records (ISSUE 7).
    app.router.add_get("/v1/api/flight", obs_api.get_flight)

    if STATIC_DIR.exists():
        app.router.add_static("/static", STATIC_DIR)

    async def _on_startup(app: web.Application) -> None:
        # Daily retention sweep — the reference defines a 180-day cleanup but
        # never calls it (tokens_usage_db.py:164); here it's actually wired.
        import asyncio

        # Graceful drain on SIGTERM (ISSUE 14): stop engine admissions,
        # let in-flight decodes finish under the drain deadline, flush
        # the usage recorder, then let aiohttp's own shutdown proceed.
        # Best-effort: non-main-thread loops (tests) can't install
        # signal handlers and don't need them.
        import signal

        def _on_sigterm() -> None:
            logger.info("SIGTERM: draining local engines before exit")
            asyncio.get_running_loop().create_task(_drain_and_exit())

        async def _drain_and_exit() -> None:
            try:
                await gw.drain_local_engines(restart=False)
            finally:
                # GracefulExit is a SystemExit: raised from a plain loop
                # callback it propagates out of run_forever and stops
                # web.run_app (a task would swallow it into its result).
                def _exit() -> None:
                    raise web.GracefulExit()
                asyncio.get_running_loop().call_soon(_exit)

        try:
            asyncio.get_running_loop().add_signal_handler(
                signal.SIGTERM, _on_sigterm)
        except (NotImplementedError, RuntimeError, ValueError):
            logger.debug("SIGTERM drain handler not installed",
                         exc_info=True)

        async def _retention_loop() -> None:
            while True:
                removed = await asyncio.to_thread(
                    gw.usage_db.cleanup_old_records, settings.usage_retention_days)
                if removed:
                    logger.info("usage retention: removed %d old rows", removed)
                await asyncio.sleep(24 * 3600)
        app["retention_task"] = asyncio.get_running_loop().create_task(
            _retention_loop())

    async def _on_cleanup(app: web.Application) -> None:
        task = app.get("retention_task")
        if task:
            task.cancel()
        await gw.close()

    app.on_startup.append(_on_startup)
    app.on_cleanup.append(_on_cleanup)
    return app


def run(settings: Settings | None = None) -> None:
    settings = settings or Settings.from_env()
    from ..utils.logging_setup import configure_logging
    configure_logging(settings.logs_dir or "logs", settings.log_level)
    try:
        app = build_app(settings, local_factory=_default_local_factory())
    except Exception as e:
        logger.error("startup failed: %s", e)
        raise SystemExit(1)
    web.run_app(app, host=settings.gateway_host, port=settings.gateway_port,
                access_log=None)


def _default_local_factory():
    """Lazily import the TPU engine provider factory. JAX stays optional
    for proxy-only deployments: where it is not installed, ``type: local``
    providers are rejected. Any OTHER failure importing the engine is a
    broken install and propagates — a warning here would turn it into
    "every local request quietly goes to the fallback chain"."""
    try:
        import jax  # noqa: F401
    except ModuleNotFoundError:
        logger.warning("JAX is not installed; type=local providers will "
                       "be rejected")
        return None
    from ..providers.local import make_local_provider
    return make_local_provider
