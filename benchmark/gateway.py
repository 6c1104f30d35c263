"""The system under test, in THIS process: the gateway app built by
``build_app`` on a localhost port with one ``local`` provider, and the
HTTP client that drives it. The chip belongs to one process, so server,
engine and load generator share it (the pattern ``chip_smoke.py`` proved).

Every request is ``POST /v1/chat/completions`` with ``stream: true``:
auth, router, ``providers/local.py``, SSE and usage capture are all in
the path. The client keeps one ``(time, tokens)`` pair per content frame.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import re
import time
from pathlib import Path
from typing import Any, Callable

from .metrics import RequestLog
from .tokenizer import CharTokenizer

GATEWAY_KEY = "benchmark-key"
PROVIDER = "local"
MODEL = "gw/bench"

# Published (Hugging Face config.json) key -> the program's ModelConfig field.
# A configuration's file extends the table for itself ("preset_fields"), so
# the published sizes of a new architecture are held to its preset as these.
HF_KEYS = {
    "hidden_size": "d_model", "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab_size",
    "sliding_window": "sliding_window", "rope_theta": "rope_theta",
    "rms_norm_eps": "rms_eps", "num_local_experts": "n_experts",
    "num_experts_per_tok": "experts_per_token",
    "max_position_embeddings": "max_seq_len"}

# What may be cut is how MANY layers, experts or vocabulary rows this chip
# holds, never how wide anything is (model-configs guide, section 4). A
# width, by its key: a hidden, intermediate, latent, state or projection
# size, a head size, a rank, a window, a kernel or expansion factor, the
# experts per token. ``vocab_size`` counts rows and is the one ``_size``
# that is none.
_WIDTH = re.compile(r"(_size|_dim|_rank|_width|_window|_factor|_expand)$"
                    r"|_per_tok|^expand$|^d_|head_dim")
_EXPERTS_HELD = re.compile(r"^(n|num)_(routed_|local_)?experts$")
MIN_LAYERS, MIN_EXPERTS, MIN_VOCABULARY_SHARE = 4, 8, 8    # the guide's floors


def is_width(key: str) -> bool:
    return key != "vocab_size" and bool(_WIDTH.search(key))


def _cuts(name: str, config: dict[str, Any], table: dict[str, str],
          base: Any) -> dict[str, Any]:
    """The preset fields that ``reduced`` changes, each cut held to the
    guide's floors: depth keeps whole periods of the layer pattern the
    file states (``layer_kinds``) and four layers or more after the leading
    dense ones; the experts held are 8 or more, the vocabulary an eighth or
    more, and both are what one of ``chips_sharing_a_layer`` chips holds.
    Beside each cut stands the published count (``{"published": n}``); the
    experts held go to the field the entry names (``"held_in"``) while the
    router's field keeps the published count."""
    chips = config.get("chips_sharing_a_layer")
    if not (isinstance(chips, int) and chips >= 1
            and config.get("deployment")):
        raise ValueError(
            f"{name}: a cut needs its deployment beside it: "
            f"'chips_sharing_a_layer' (this chip is one of N that share "
            f"each layer) and 'deployment' in words")
    kinds = config.get("layer_kinds", {})
    changes = {}
    for key, entry in config["reduced"].items():
        if is_width(key):
            raise ValueError(f"{name}: {key} is a width, and no width is cut")
        if key not in table or key not in config:
            raise ValueError(
                f"{name}: {key} is cut, and the file does not give it or "
                f"'preset_fields' does not name its field of the preset")
        held, field = config[key], table[key]
        published = entry.get("published") if isinstance(entry, dict) else None
        if not (isinstance(published, int) and 0 < held < published):
            raise ValueError(f"{name}: {key}={held} is cut and states no "
                             f"published count above it ({entry!r})")
        share = -(-published // chips)
        if field == "n_layers":
            lead = kinds.get("leading_dense", 0)
            period = kinds.get("period", 1)
            if held - lead < max(MIN_LAYERS, period) or (held - lead) % period:
                raise ValueError(
                    f"{name}: depth {held} is not whole periods of {period} "
                    f"layers, {MIN_LAYERS} or more, after {lead} leading")
        elif field == "vocab_size":
            if held * MIN_VOCABULARY_SHARE < published or held != share:
                raise ValueError(
                    f"{name}: {held} of {published} vocabulary rows is not "
                    f"one of {chips} chips' share, an eighth or more")
        elif _EXPERTS_HELD.match(key):
            if held < MIN_EXPERTS or held != share:
                raise ValueError(
                    f"{name}: {held} of {published} experts is not one of "
                    f"{chips} chips' share, {MIN_EXPERTS} or more")
            if getattr(base, field) != published:
                raise ValueError(
                    f"{name}: {key} is published as {published}, preset "
                    f"{config['preset']!r} routes over "
                    f"{field}={getattr(base, field)}")
            field = entry.get("held_in")
            if not (isinstance(field, str) and hasattr(base, field)):
                raise ValueError(
                    f"{name}: preset {config['preset']!r} has no field "
                    f"{field!r} to be told how many experts it holds")
        else:
            raise ValueError(f"{name}: only depth, the experts held and the "
                             f"vocabulary may be cut, not {key}")
        changes[field] = held
    return changes


def resolve_preset(config_name: str, config: dict[str, Any],
                   presets: dict[str, Any] | None = None) -> str:
    """The name of the program preset this configuration runs. The file's
    published sizes must be the preset's (``HF_KEYS`` and the file's own
    ``preset_fields``); where the file lists cuts under ``reduced``
    (``_cuts``), a derived preset is registered under the configuration's
    own name — one ``dataclasses.replace`` into the program's table
    (``presets``: another table, for tests), before the app is built."""
    if presets is None:
        from llmapigateway_tpu.models.config import PRESETS as presets
    base = presets[config["preset"]]
    table = dict(HF_KEYS)
    for key, field in config.get("preset_fields", {}).items():
        if table.setdefault(key, field) != field:
            raise ValueError(f"{config_name}: preset_fields moves {key} from "
                             f"{table[key]!r} to {field!r}")
        if not hasattr(base, field):
            raise ValueError(f"{config_name}: preset {config['preset']!r} "
                             f"has no field {field!r} (for {key})")
    reduced = config.get("reduced", {})
    for key, field in table.items():
        if key not in config or key in reduced:
            continue
        want, have = config[key] or 0, getattr(base, field)
        if want != have:
            raise ValueError(
                f"{config_name}: {key}={want} in the file, preset "
                f"{config['preset']!r} has {field}={have}")
    if not reduced:
        return config["preset"]
    presets[config_name] = dataclasses.replace(
        base, **_cuts(config_name, config, table, base))
    return config_name


class Gateway:
    """The app on a localhost port with its one local provider built, the
    benchmark's tokenizer installed and every request's engine-side record
    kept (``requests``: request id -> ``GenRequest``)."""

    def __init__(self, engine_cfg: dict[str, Any], workdir: Path,
                 local_factory: Callable | None = None) -> None:
        self.engine_cfg = engine_cfg
        self.workdir = Path(workdir)
        self.local_factory = local_factory
        self.requests: dict[str, Any] = {}
        self.timings: dict[str, float] = {}

    async def __aenter__(self) -> "Gateway":
        import aiohttp
        from aiohttp import web
        from llmapigateway_tpu.config.settings import Settings
        from llmapigateway_tpu.server.app import (_default_local_factory,
                                                  build_app)
        root = self.workdir
        root.mkdir(parents=True, exist_ok=True)
        for stale in (root / "db").glob("*"):
            stale.unlink()
        (root / "providers.json").write_text(json.dumps(
            [{PROVIDER: {"type": "local", "engine": self.engine_cfg}}]))
        (root / "models_fallback_rules.json").write_text(json.dumps(
            [{"gateway_model_name": MODEL, "fallback_models": [
                {"provider": PROVIDER, "model": self.engine_cfg["preset"]}]}]))
        t0 = time.monotonic()
        self.settings = Settings.from_env(base_dir=root, env={
            "GATEWAY_API_KEY": GATEWAY_KEY, "FALLBACK_PROVIDER": PROVIDER,
            "CONFIG_DIR": str(root), "DB_DIR": str(root / "db"),
            "LOGS_DIR": str(root / "logs")})
        app = build_app(self.settings, local_factory=(
            self.local_factory or _default_local_factory()))
        self.gw = app["gateway"]
        self._runner = web.AppRunner(app, access_log=None)
        await self._runner.setup()
        site = web.TCPSite(self._runner, "127.0.0.1", 0)
        await site.start()
        port = self._runner.addresses[0][1]
        self.session = aiohttp.ClientSession(
            base_url=f"http://127.0.0.1:{port}",
            headers={"Authorization": f"Bearer {GATEWAY_KEY}"},
            timeout=aiohttp.ClientTimeout(total=None),
            connector=aiohttp.TCPConnector(limit=0))
        self.timings["app_start_s"] = time.monotonic() - t0
        t0 = time.monotonic()
        provider = await self.gw.registry.get(PROVIDER)
        if provider is None or getattr(provider, "engine", None) is None:
            raise RuntimeError("the local provider did not build")
        self.timings["engine_build_s"] = time.monotonic() - t0
        self.engine = provider.engine
        self.tokenizer = CharTokenizer(self.engine.model_cfg.vocab_size)
        self.engine.tokenizer = self.tokenizer
        submit = self.engine.submit

        async def recording_submit(req):
            self.requests[req.request_id] = req
            await submit(req)
        self.engine.submit = recording_submit
        return self

    async def __aexit__(self, *exc: Any) -> None:
        await self.session.close()
        await self._runner.cleanup()        # stops the engine, closes DBs

    async def stream_chat(self, log: RequestLog, content: str,
                          temperature: float = 0.0) -> RequestLog:
        """One streamed chat completion; fills ``log`` as frames arrive.
        A failure is recorded on the log, never raised (cancellation by the
        harness is re-raised after being noted)."""
        body = {"model": MODEL, "max_tokens": log.max_tokens,
                "temperature": temperature, "stream": True,
                "messages": [{"role": "user", "content": content}]}
        clock = time.monotonic
        log.t_send = clock()
        try:
            async with self.session.post(
                    "/v1/chat/completions", json=body,
                    headers={"x-request-id": log.rid}) as resp:
                log.status = resp.status
                if resp.status != 200:
                    log.error = (await resp.text())[:300]
                else:
                    async for raw in resp.content:
                        if not raw.startswith(b"data: "):
                            continue
                        now = clock()
                        data = raw[6:].strip()
                        if data == b"[DONE]":
                            log.done = True
                            continue
                        frame = json.loads(data)
                        if "error" in frame:
                            log.error = json.dumps(frame["error"])[:300]
                            continue
                        if frame.get("usage"):
                            log.usage = frame["usage"]
                        for choice in frame.get("choices", ()):
                            text = choice.get("delta", {}).get("content")
                            if text:
                                log.frames.append((now, len(text)))
                            if choice.get("finish_reason"):
                                log.finish_reason = choice["finish_reason"]
        except asyncio.CancelledError:
            log.cancelled = True
            raise
        except Exception as e:     # boundary: a failed request is a datum
            log.error = f"{type(e).__name__}: {e}"[:300]
        finally:
            log.t_end = clock()
            gen = self.requests.get(log.rid)
            if gen is not None:
                log.t_submit = gen.t_submit
                log.t_admitted = gen.t_admitted
                log.t_first_token = gen.t_first_token
        return log
