#!/usr/bin/env python3
"""The quickest proof that the gateway still starts on the chip.

One process, no child, no lock file, nothing read outside the checkout
but this script's own throw-away config/database directory:

    python chip_smoke.py            # one chip  — the main path
    python chip_smoke.py --chips 4  # four chips — tensor parallelism and
                                    # the one-chip engine it is compared
                                    # with, and no other phase

Default phase: the compiled paged kernels against the jnp reference at
Mistral-7B widths, then ``mistral-7b`` (published widths, int8 weights,
random from a fixed key, ByteTokenizer) served by the real app on a
localhost port and driven through ``/v1/chat/completions`` — non-streamed,
SSE, a burst of 8 whose prompts cross a page, a prefill chunk and the
4096-token window, and one greedy prompt twice. What it then asserts comes
from the engine object, the stats endpoints and SQLite, never from logs.

Every line of standard output is one JSON object; the last is exactly
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Without a TPU —
or when any phase raises or fails an assertion — ``ok`` is false and the
exit code is not 0. Engine logs go to standard error.
"""
from __future__ import annotations

import argparse
import asyncio
import collections
import json
import logging
import os
import random
import re
import sqlite3
import sys
import tempfile
import time
import traceback
from pathlib import Path
from typing import Any, Callable, NamedTuple

GATEWAY_KEY = "chip-smoke-key"
PROVIDER = "local"
MODEL = "gw/smoke"
SEED = 0

# The engine under test: Mistral-7B-v0.1 at its published widths, int8
# weights and int8 KV (what leaves room on a 16 GB chip: chunked prefill
# holds a second copy of the pool while it runs — PERF.md), eight slots of
# 8k context served from the sliding-window page ring. Everything not named
# here is the engine's default.
ENGINE: dict[str, Any] = {
    "preset": "mistral-7b", "quant": "int8", "kv_quant": "int8", "mesh": {},
    "max_batch_size": 8, "max_seq_len": 8192}


class Workload(NamedTuple):
    """Prompt lengths in tokens, chat template included. Every burst length
    ends in a chunk of the full prefill bucket, so the burst compiles one
    prefill shape (times the batched-admission group sizes) and not one per
    tail length."""
    single: int = 48
    burst: tuple[int, ...] = (300, 400, 500, 800, 1000, 1500, 2000, 5600)
    max_tokens: int = 32
    burst_max_tokens: int = 48


# Logits of two builds of the same weights (TP=4 against one chip) differ
# by bf16 rounding the compiler places differently, and W8A8 re-quantizes
# the activations at every matmul, so one bf16 ulp can move an int8 code.
# The bound is on the MAXIMUM over ~3e7 logits of magnitude up to ~6, where
# bf16 values are 0.031 apart: eight of those steps. (Measured on v5e,
# PR 21: 0.089 and 0.093.)
LOGIT_TOL = 0.25
# Kernel against reference on unit-normal inputs, bf16 storage, fp32
# accumulation: the old compiled-kernel test's bound.
KERNEL_TOL = 3e-2


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def last_line(ok: bool, device: dict[str, Any]) -> str:
    """The contract's final line: ``ok`` and exactly three device keys."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}})


def device_facts() -> dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# Compile cache: where it is, how full, and whether this run hit it
# ---------------------------------------------------------------------------

class CacheWatch:
    """Directory and entry count of the persistent compile cache, plus
    JAX's own hit/miss events for this process."""

    def __init__(self) -> None:
        import jax
        import jax.monitoring
        from llmapigateway_tpu.engine.engine import (
            _CACHE_DIR, _enable_compilation_cache)
        # The engine would do this at build; doing it first puts the
        # kernel-parity compiles in the same cache.
        _enable_compilation_cache("")
        # Cache every program, not only those that took JAX's default
        # second to compile: a compile that takes 0.9 s in one run and
        # 1.1 s in the next would otherwise be a new entry in a warm run.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        self.dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
            _CACHE_DIR)
        self.events: collections.Counter = collections.Counter()
        jax.monitoring.register_event_listener(self._on_event)
        self.before = self.entries()

    def _on_event(self, name: str, **_: Any) -> None:
        if name.startswith("/jax/compilation_cache/"):
            self.events[name.rsplit("/", 1)[1]] += 1

    def entries(self) -> set[str]:
        try:
            return {f for f in os.listdir(self.dir) if f.endswith("-cache")}
        except FileNotFoundError:
            return set()

    def report(self) -> dict[str, Any]:
        after = self.entries()
        return {"dir": self.dir, "entries_before": len(self.before),
                "entries_after": len(after), "warm": bool(self.before),
                "hits": self.events["cache_hits"],
                "misses": self.events["cache_misses"],
                # program names (hash cut off) of what this run added
                "new_entries": sorted(collections.Counter(
                    f.rsplit("-", 2)[0] for f in after - self.before
                ).items())}


# ---------------------------------------------------------------------------
# Phase: compiled kernels against the jnp reference
# ---------------------------------------------------------------------------

def kernel_parity(interpret: bool = False, *, H: int = 32, KV: int = 8,
                  Dh: int = 128, page: int = 256, pages_per_slot: int = 32,
                  window: int = 4096, T: int = 256) -> list[dict[str, Any]]:
    """Paged decode and prefill kernels, bf16 and int8 KV, sliding window
    on, against the gather+jnp reference on the same inputs. Slots sit
    below, at and past the window so dead pages and the window floor are
    both in play. Returns one record per case; raises past KERNEL_TOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from llmapigateway_tpu.models.llama import quantize_kv
    from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn

    B, S = 3, page * pages_per_slot
    n_pages = B * pages_per_slot + 1
    rng = np.random.default_rng(SEED)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        B, pages_per_slot).astype(np.int32))
    keys = jax.random.split(jax.random.PRNGKey(SEED), 5)
    pool = [jax.random.normal(k, (n_pages, KV, page, Dh), jnp.bfloat16)
            for k in keys[:2]]
    # Stale lengths: inside one page, just past the window, deep past it.
    decode_at = jnp.asarray([page // 2, window + 3, S - page - 1], jnp.int32)
    prefill_at = jnp.asarray([0, window, S - 2 * page], jnp.int32)
    active = jnp.ones((B,), bool)

    def quantized(side):
        q, s = quantize_kv(side)                  # [P,KV,page,Dh], [P,KV,page]
        return {"q": q, "s": s[:, :, None, :]}

    out = []
    for kv in ("bf16", "int8"):
        pk, pv = (pool if kv == "bf16" else [quantized(p) for p in pool])
        fns = {impl: make_paged_attention_fn(
            table, max_seq=S, impl=impl, window=window,
            interpret=interpret if impl == "pallas" else None)
            for impl in ("pallas", "reference")}
        for kind, t, at in (("decode", 1, decode_at),
                            ("prefill", T, prefill_at)):
            q = jax.random.normal(keys[2], (B, t, H, Dh), jnp.bfloat16)
            kn = jax.random.normal(keys[3], (B, t, KV, Dh), jnp.bfloat16)
            vn = jax.random.normal(keys[4], (B, t, KV, Dh), jnp.bfloat16)
            got = {}
            t0 = time.monotonic()
            for impl, fn in fns.items():
                call = fn.decode if kind == "decode" else (
                    lambda *a, _fn=fn: _fn(*a)[0])
                got[impl] = np.asarray(jax.jit(call)(
                    q, kn, vn, pk, pv, at, active), np.float32)
            err = float(np.max(np.abs(got["pallas"] - got["reference"])))
            rec = {"kernel": f"paged_{kind}", "kv": kv, "window": window,
                   "max_abs_err": err, "finite": bool(
                       np.isfinite(got["pallas"]).all()),
                   "seconds": round(time.monotonic() - t0, 2)}
            emit("kernel_parity", **rec)
            if not (rec["finite"] and err <= KERNEL_TOL):
                raise AssertionError(f"kernel parity failed: {rec}")
            out.append(rec)
    return out


# ---------------------------------------------------------------------------
# Serving through the real app
# ---------------------------------------------------------------------------

class Gateway:
    """The gateway app on a localhost port in THIS process (the chip
    belongs to one process), with its one local provider built."""

    def __init__(self, engine_cfg: dict[str, Any],
                 local_factory: Callable | None = None) -> None:
        self.engine_cfg = engine_cfg
        self.local_factory = local_factory
        self.requests: dict[str, Any] = {}     # request id -> GenRequest

    async def __aenter__(self) -> "Gateway":
        import aiohttp
        from aiohttp import web
        from llmapigateway_tpu.config.settings import Settings
        from llmapigateway_tpu.server.app import (_default_local_factory,
                                                  build_app)
        self._tmp = tempfile.TemporaryDirectory(prefix="chip-smoke-")
        root = Path(self._tmp.name)
        (root / "providers.json").write_text(json.dumps(
            [{PROVIDER: {"type": "local", "engine": self.engine_cfg}}]))
        (root / "models_fallback_rules.json").write_text(json.dumps(
            [{"gateway_model_name": MODEL, "fallback_models": [
                {"provider": PROVIDER, "model": self.engine_cfg["preset"]}]}]))
        # FALLBACK_PROVIDER is the local provider itself: no remote
        # provider exists that could answer in its place.
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
            timeout=aiohttp.ClientTimeout(total=900))
        t0 = time.monotonic()
        provider = await self.gw.registry.get(PROVIDER)
        if provider is None or getattr(provider, "engine", None) is None:
            raise AssertionError("the local provider did not build")
        self.init_s = time.monotonic() - t0
        self.engine = provider.engine
        # Keep each request's engine-side record: the HTTP answer carries
        # text and counts, the token ids live on the GenRequest.
        submit = self.engine.submit

        async def recording_submit(req):
            self.requests[req.request_id] = req
            await submit(req)
        self.engine.submit = recording_submit
        return self

    async def __aexit__(self, *exc: Any) -> None:
        await self.session.close()
        await self._runner.cleanup()        # stops the engine, closes DBs
        self._tmp.cleanup()

    def prompt(self, n_tokens: int, tag: str) -> str:
        """User content whose chat-templated, BOS-prefixed prompt is exactly
        ``n_tokens`` long under the engine's ByteTokenizer."""
        tok = self.engine.tokenizer
        overhead = 1 + len(tok.encode(tok.apply_chat_template(
            [{"role": "user", "content": ""}])))
        rng = random.Random(f"{SEED}:{tag}")
        words = []
        while sum(len(w) + 1 for w in words) < n_tokens:
            words.append("".join(rng.choices("abcdefghijklmnopqrstuvwxyz",
                                             k=rng.randint(2, 9))))
        return " ".join(words)[:n_tokens - overhead].ljust(
            n_tokens - overhead, "x")

    async def chat(self, rid: str, content: str, max_tokens: int,
                   temperature: float = 0.0,
                   stream: bool = False) -> dict[str, Any]:
        body = {"model": MODEL, "max_tokens": max_tokens,
                "temperature": temperature, "stream": stream,
                "messages": [{"role": "user", "content": content}]}
        if temperature > 0:
            body["top_p"] = 0.9
        t0 = time.monotonic()
        rec: dict[str, Any] = {"id": rid, "stream": stream,
                               "temperature": temperature}
        async with self.session.post("/v1/chat/completions", json=body,
                                     headers={"x-request-id": rid}) as resp:
            rec["status"] = resp.status
            if resp.status != 200:
                rec["error"] = (await resp.text())[:300]
            elif stream:
                frames = []
                async for line in resp.content:
                    line = line.decode().strip()
                    if line.startswith("data: "):
                        frames.append(line[len("data: "):])
                rec["done"] = bool(frames) and frames[-1] == "[DONE]"
                chunks = [json.loads(f) for f in frames if f != "[DONE]"]
                rec["frames"] = len(chunks)
                rec["error_frames"] = sum("error" in c for c in chunks)
                usage = [c["usage"] for c in chunks if c.get("usage")]
                rec["usage"] = usage[-1] if usage else None
                rec["finish_reason"] = next(
                    (c["choices"][0]["finish_reason"] for c in reversed(chunks)
                     if c.get("choices")), None)
            else:
                data = await resp.json()
                rec["usage"] = data.get("usage")
                rec["finish_reason"] = data["choices"][0]["finish_reason"]
        rec["seconds"] = round(time.monotonic() - t0, 3)
        gen = self.requests.get(rid)
        rec["tokens"] = list(gen.generated) if gen is not None else None
        return rec


def warm_programs(engine, bucket: int) -> tuple[dict[str, Any], str]:
    """Compile, before traffic, every program the burst can reach: the
    prefill bucket at each batched-admission group size, and both sampler
    variants of the decode step at each burst depth. Which of them a burst
    then uses depends on arrival timing; compiling all of them here keeps
    compile stalls out of the requests and makes the set of cache entries
    the same from run to run. Returns the facts and the compiled text of
    the deep greedy decode burst."""
    t0 = time.monotonic()
    groups = sorted({len(g) for n in range(1, engine.B + 1)
                     for g in engine.prefill_groups(list(range(n)))})
    for k in groups:
        engine.compiled_prefill(bucket, k)
    depths = sorted({1, engine.decode_burst_busy, engine.decode_burst})
    decode_text = ""
    for greedy in (True, False):
        for depth in depths:
            compiled = engine.compiled_decode(greedy, depth)
            if greedy and depth == engine.decode_burst:
                decode_text = compiled.as_text()
    return {"seconds": round(time.monotonic() - t0, 1),
            "prefill_bucket": bucket, "prefill_groups": groups,
            "decode_depths": depths}, decode_text


async def serve_and_query(engine_cfg: dict[str, Any], work: Workload,
                          local_factory: Callable | None = None
                          ) -> dict[str, Any]:
    """Serve ``engine_cfg`` through the app, send the workload, and return
    the per-request records with what the engine, the stats endpoints and
    the usage database say afterwards. Checks everything that does not
    depend on the device (:func:`check_serving`) before returning."""
    async with Gateway(engine_cfg, local_factory) as g:
        eng = g.engine
        c = eng.model_cfg
        report: dict[str, Any] = {
            "engine": {
                "preset": engine_cfg["preset"], "n_layers": c.n_layers,
                "d_model": c.d_model, "n_heads": c.n_heads,
                "n_kv_heads": c.n_kv_heads, "d_ff": c.d_ff,
                "sliding_window": c.sliding_window, "quant": eng.quant,
                "kv_quant": eng.kv_quant, "batch": eng.B, "context": eng.S,
                "page": eng.kv_page, "prefill_chunk": eng.prefill_chunk,
                "pages": eng.allocator.num_pages,
                "ring_pages_per_slot": eng._swa_ring_pages,
                "prefix_cache": eng._prefix_cache is not None,
                "init_s": round(g.init_s, 1)}}
        emit("engine", **report["engine"])
        # The lengths must do what the smoke claims they do, on THIS
        # engine's geometry.
        assert any(n > eng.kv_page for n in work.burst), "no page crossed"
        assert any(n > eng.prefill_chunk for n in work.burst), \
            "no prefill chunk crossed"
        if c.sliding_window:
            assert max(work.burst) > c.sliding_window + eng.kv_page, \
                "no prompt past the window"
            # ... and one sequence longer than a slot's page ring holds,
            # so the ring has to rotate under it.
            assert eng.allocator.pages_needed(
                max(work.burst) + work.burst_max_tokens
            ) > eng._swa_ring_pages > 0, "the page ring never rotates"
        report["warm"], report["decode_text"] = await asyncio.to_thread(
            warm_programs, eng, eng.prefill_chunk)
        emit("first_compile", **report["warm"])

        records = []

        async def one(rid: str, n: int, **kw: Any) -> dict[str, Any]:
            rec = await g.chat(rid, g.prompt(n, kw.pop("tag", rid)), **kw)
            rec["prompt_tokens_asked"] = n
            emit("request", **{k: v for k, v in rec.items()
                               if k != "tokens"},
                 n_tokens=len(rec["tokens"] or ()))
            records.append(rec)
            return rec

        await one("plain", work.single, max_tokens=work.max_tokens)
        await one("sse", work.single, max_tokens=work.max_tokens,
                  stream=True)
        # Eight at once; every second one sampled, so the general sampler
        # serves beside the greedy fast path; the longest one streamed.
        await asyncio.gather(*[
            one(f"burst{i}", n, max_tokens=work.burst_max_tokens,
                temperature=0.7 if i % 2 else 0.0,
                stream=n == max(work.burst))
            for i, n in enumerate(work.burst)])
        # The same greedy prompt twice, one after the other on an idle
        # engine: same programs, same inputs, so the same tokens.
        for rid in ("twin-a", "twin-b"):
            await one(rid, work.single, tag="twin",
                      max_tokens=work.max_tokens)
        report["records"] = records

        async def get(path: str) -> Any:
            async with g.session.get(path) as resp:
                assert resp.status == 200, (path, resp.status)
                return await resp.json()
        report["engine_stats"] = await get("/v1/api/engine-stats")
        report["health"] = await get("/v1/api/health/providers")
        report["stats"] = eng.stats()
        report["kernels"] = sorted(
            row["kernel"] for row in eng.kernel_table())
        await asyncio.to_thread(g.gw.usage_recorder.flush)
        db = sqlite3.connect(Path(g.settings.db_dir) / "tokens_usage.db")
        try:
            report["usage_rows"] = db.execute(
                "SELECT prompt_tokens, completion_tokens, cached_tokens "
                "FROM tokens_usage").fetchall()
        finally:
            db.close()
    check_serving(report, work)
    return report


def check_serving(report: dict[str, Any], work: Workload) -> None:
    """Everything a correct run shows whatever device it ran on."""
    records = {r["id"]: r for r in report["records"]}
    assert len(records) == 4 + len(work.burst)
    for r in records.values():
        assert r["status"] == 200, r
        usage = r["usage"]
        assert usage, f"no usage: {r}"
        assert usage["prompt_tokens"] == r["prompt_tokens_asked"], r
        assert usage["completion_tokens"] >= 1, r
        assert usage["completion_tokens"] == len(r["tokens"]), r
        assert r["finish_reason"] in ("stop", "length"), r
        if r["stream"]:
            assert r["done"] and r["frames"] >= 2, r
            assert r["error_frames"] == 0, r
    assert records["twin-a"]["tokens"] == records["twin-b"]["tokens"], (
        records["twin-a"]["tokens"], records["twin-b"]["tokens"])
    cached = records["twin-b"]["usage"].get(
        "prompt_tokens_details", {}).get("cached_tokens", 0)
    # A sliding window turns the prefix cache off (ROADMAP R3), so the
    # repeat is served cold exactly when the engine says it has no cache.
    assert (cached > 0) == (report["engine"]["prefix_cache"]
                            and work.single > report["engine"]["page"]), cached
    emit("repeat_prompt", identical_tokens=True, cached_tokens=cached,
         prefix_cache=report["engine"]["prefix_cache"])

    st = report["stats"]
    assert st["kv_layout"] == "paged", st["kv_layout"]
    assert st["supervisor_state"] == "serving", st["supervisor_state"]
    assert st["supervisor_restarts_total"] == 0, st
    assert not st["supervisor_last_failure_kind"], st
    assert st["flight_admits"] == st["flight_finishes"] == len(records), st
    assert st["shed_total"] == 0 and st["watermark_sheds"] == 0, st
    assert "prewarm_error" not in st, st["prewarm_error"]
    assert st["running"] == 0 and st["queued"] == 0, st
    health = report["health"]["providers"][PROVIDER]
    assert health["state"] == "closed" and health["opens"] == 0, health
    rows = report["usage_rows"]
    assert len(rows) == len(records), (len(rows), len(records))
    assert all(p > 0 and c > 0 for p, c, _ in rows), rows


def check_device(report: dict[str, Any]) -> None:
    """What only a run on the chip can show: the kernels served, compiled."""
    st = report["stats"]
    assert st["attention"] == "pallas", st["attention"]
    assert "tpu_custom_call" in report["decode_text"], \
        "the compiled decode step holds no kernel"
    devices = report["engine_stats"]["devices"]
    assert devices and devices[0]["platform"] == "tpu", devices
    assert report["engine_stats"]["device_status"] == "ok"


def run_one_chip(cache: CacheWatch) -> None:
    import jax
    kernel_parity()
    t0 = time.monotonic()
    report = asyncio.run(serve_and_query(ENGINE, Workload()))
    check_device(report)
    st = report["stats"]
    emit("served", requests=len(report["records"]),
         tokens_out=sum(len(r["tokens"]) for r in report["records"]),
         seconds=round(time.monotonic() - t0, 1),
         attention=st["attention"], kv_layout=st["kv_layout"],
         kernel_in_decode_step=True, prefill_programs=[
             k for k in report["kernels"] if k.startswith("prefill")],
         decode_programs=[
             k for k in report["kernels"] if k.startswith("decode")],
         supervisor_state=st["supervisor_state"],
         restarts=st["supervisor_restarts_total"], shed=st["shed_total"],
         usage_rows=len(report["usage_rows"]),
         xla_compiles=st["xla_compile_total"],
         xla_compile_seconds=st["xla_compile_seconds"],
         depth_cut=None)
    mem = jax.devices()[0].memory_stats() or {}
    emit("memory", peak_bytes_in_use=mem.get("peak_bytes_in_use"),
         bytes_limit=mem.get("bytes_limit"))
    emit("compile_cache", **cache.report())


# ---------------------------------------------------------------------------
# Four chips: tensor parallelism against one chip
# ---------------------------------------------------------------------------

def forced_logits(engine, token_ids: list[int], pad_to: int):
    """Logits of the engine's own weights, where they sit, over a fixed
    token sequence: the model's plain forward with the jnp attention, no
    serving program involved. Padded to ``pad_to`` so every sequence
    shares one compile (attention is causal: the pad changes nothing
    before it). [len(token_ids), V] float32 on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from llmapigateway_tpu.models import forward_fn, llama
    c = engine.model_cfg
    tokens = np.zeros((1, pad_to), np.int32)
    tokens[0, :len(token_ids)] = token_ids

    def run(params, tokens):
        cache = llama.KVCache.create(c, 1, pad_to, engine.dtype)
        logits, _ = forward_fn(c)(params, c, tokens,
                                  jnp.zeros((1,), jnp.int32), cache)
        return logits[0]
    return np.asarray(jax.jit(run)(engine.params, tokens),
                      np.float32)[:len(token_ids)]


def weight_share(engine) -> dict[str, float]:
    """Fraction of the weight bytes each mesh device holds."""
    import jax
    held: collections.Counter = collections.Counter()
    total = 0
    for leaf in jax.tree.leaves(engine.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[str(shard.device)] += shard.data.nbytes
    return {d: round(n / total, 4) for d, n in sorted(held.items())}


def free_engine(engine) -> None:
    """Give the engine's device memory back now, not when the last
    reference to it happens to die."""
    import jax
    for leaf in jax.tree.leaves((engine.params, engine.cache,
                                 engine._d_counts)):
        leaf.delete()


async def compare_tp(engine_cfg: dict[str, Any], tp_mesh: dict[str, int],
                     lens: tuple[int, ...], n_tokens: int = 16,
                     local_factory: Callable | None = None,
                     one_chip_devices: list | None = None) -> dict[str, Any]:
    """The sharded engine, served through the app, against a one-chip
    engine of the same configuration on the first device — built one after
    the other, the first stopped and freed before the second exists. Same
    greedy prompts: forced logits within LOGIT_TOL, served tokens equal —
    or parting at a step where the margin between the two tokens is inside
    twice the logit difference measured at that very step."""
    import jax
    import numpy as np
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    report: dict[str, Any] = {"prompts": []}
    pad_to = -(-(max(lens) + n_tokens + 1) // 128) * 128
    async with Gateway({**engine_cfg, "mesh": tp_mesh}, local_factory) as g:
        eng = g.engine
        c = eng.model_cfg
        report["tp"] = {
            "mesh": {a: n for a, n in eng.mesh.shape.items() if n > 1},
            "devices": eng.mesh.size, "init_s": round(g.init_s, 1),
            "attention": eng.attention_impl, "batch": eng.B,
            "n_heads": c.n_heads, "n_kv_heads": c.n_kv_heads,
            "head_dim": c.head_dim}
        report["weight_share"] = weight_share(eng)
        report["decode_text"] = await asyncio.to_thread(
            lambda: eng.compiled_decode().as_text())
        for i, n in enumerate(lens):
            # One token more than is compared: the first comes from the
            # prefill, the rest are whole decode bursts.
            rec = await g.chat(f"tp{i}", g.prompt(n, f"tp{i}"),
                               max_tokens=n_tokens + 1)
            assert rec["status"] == 200 and len(rec["tokens"]) >= 1, rec
            ids = list(g.requests[f"tp{i}"].prompt_ids)
            forced = ids + rec["tokens"][:n_tokens]
            report["prompts"].append({
                "ids": ids, "forced": forced, "tp_tokens": rec["tokens"],
                "tp_seconds": rec["seconds"],
                "tp_logits": await asyncio.to_thread(
                    forced_logits, eng, forced, pad_to)})
        st = eng.stats()
        report["tp"]["stats"] = {k: st[k] for k in (
            "supervisor_state", "supervisor_restarts_total", "shed_total",
            "attention", "kv_layout")}
        report["tp"]["memory"] = {
            str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in eng.mesh.devices.flat}
    free_engine(eng)
    del eng, g

    t0 = time.monotonic()
    one = await asyncio.to_thread(
        InferenceEngine, LocalEngineConfig(**{**engine_cfg, "mesh": {}}),
        devices=one_chip_devices or [jax.devices()[0]])
    report["one_chip"] = {"init_s": round(time.monotonic() - t0, 1),
                          "attention": one.attention_impl}
    try:
        for p in report["prompts"]:
            req = GenRequest(prompt_ids=p["ids"], max_tokens=n_tokens + 1)
            await one.submit(req)
            async for delta in one.stream(req):
                assert delta.error is None, delta.error
            p["one_tokens"] = list(req.generated)
            logits = await asyncio.to_thread(
                forced_logits, one, p["forced"], pad_to)
            diff = np.abs(logits - p.pop("tp_logits"))
            p["max_logit_diff"] = float(diff.max())
            # Where the served tokens part, compare the one-chip margin
            # between the two tokens with how far the two builds' logits
            # are apart AT THAT STEP: rounding of that size decides it, or
            # something else did.
            p["first_divergence"] = next(
                (i for i, (a, b) in enumerate(zip(
                    p["tp_tokens"][:n_tokens], p["one_tokens"][:n_tokens]))
                 if a != b), None)
            if p["first_divergence"] is not None:
                i = p["first_divergence"]
                row = len(p["ids"]) - 1 + i
                p["margin_at_divergence"] = float(
                    logits[row, p["one_tokens"][i]]
                    - logits[row, p["tp_tokens"][i]])
                p["logit_diff_at_divergence"] = float(diff[row].max())
        st = one.stats()
        report["one_chip"]["stats"] = {k: st[k] for k in (
            "supervisor_state", "supervisor_restarts_total", "shed_total")}
    finally:
        await one.stop()
    free_engine(one)
    check_compare(report)
    return report


def check_compare(report: dict[str, Any]) -> None:
    for p in report["prompts"]:
        assert p["max_logit_diff"] <= LOGIT_TOL, p["max_logit_diff"]
        if p["first_divergence"] is not None:
            assert abs(p["margin_at_divergence"]) <= \
                2 * p["logit_diff_at_divergence"], p
    for side in ("tp", "one_chip"):
        st = report[side]["stats"]
        assert st["supervisor_state"] in ("serving", "stopped"), st
        assert st["supervisor_restarts_total"] == 0, st
        assert st["shed_total"] == 0, st


def check_sharded_on_device(report: dict[str, Any], n_chips: int) -> None:
    tp = report["tp"]
    assert tp["devices"] == n_chips and tp["mesh"] == {"model": n_chips}, tp
    assert tp["attention"] == "pallas", tp
    assert report["one_chip"]["attention"] == "pallas", report["one_chip"]
    share = report["weight_share"]
    assert len(share) == n_chips, share
    assert all(0.20 <= s <= 0.30 for s in share.values()), share
    text = report["decode_text"]
    assert "all-reduce" in text, "no all-reduce in the sharded decode step"
    # The decode kernel's output covers this chip's share of the KV
    # heads: it ran under shard_map, not replicated and not the reference.
    kv_local = tp["n_kv_heads"] // n_chips
    local = (f"[{tp['batch']},{kv_local},{tp['n_heads'] // tp['n_kv_heads']},"
             f"{tp['head_dim']}]")
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert calls and any(local in ln.split("custom-call(")[0]
                         for ln in calls), \
        f"no kernel with a {local} head-shard output in the decode step"


def run_four_chips(cache: CacheWatch) -> None:
    t0 = time.monotonic()
    # Only greedy decode runs here: no background compile of the sampled
    # variants on a four-chip clock.
    # 500 crosses a page, 1000 a prefill chunk, and both end in a chunk of
    # the full bucket: one prefill program and one decode program a side.
    report = asyncio.run(compare_tp(
        {**ENGINE, "prewarm_sampler_variants": False}, {"model": 4},
        lens=(500, 1000)))
    check_sharded_on_device(report, 4)
    text = report["decode_text"]
    emit("tensor_parallel", **report["tp"],
         weight_share=report["weight_share"],
         all_reduces_in_decode_step=len(re.findall(r"all-reduce(?:-start)?\(",
                                                   text)),
         kernel_under_shard_map=True,
         one_chip=report["one_chip"],
         prompts=[{k: p[k] for k in (
             "max_logit_diff", "first_divergence", "tp_seconds")}
             | {"prompt_tokens": len(p["ids"]),
                "tokens_compared": min(len(p["tp_tokens"]),
                                       len(p["one_tokens"])),
                "margin_at_divergence": p.get("margin_at_divergence"),
                "logit_diff_at_divergence": p.get(
                    "logit_diff_at_divergence")}
             for p in report["prompts"]],
         logit_tolerance=LOGIT_TOL,
         seconds=round(time.monotonic() - t0, 1))
    emit("compile_cache", **cache.report())


# ---------------------------------------------------------------------------

def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the tensor-parallel phase and what it is "
                         "compared with, nothing else")
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")

    import jax
    device = device_facts()
    if not __debug__:       # the checks below are assert statements
        print("chip_smoke: do not run under -O", file=sys.stderr)
        print(last_line(False, device))
        return 1
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX reports "
              f"{device}", file=sys.stderr)
        print(last_line(False, device))
        return 1
    ok = False
    try:
        from importlib.metadata import PackageNotFoundError, version
        import jaxlib
        try:
            libtpu = version("libtpu")
        except PackageNotFoundError:
            libtpu = None
        emit("start", jax=jax.__version__, jaxlib=jaxlib.__version__,
             libtpu=libtpu, device_kind=device["kind"], chips=args.chips)
        cache = CacheWatch()
        emit("compile_cache_start", dir=cache.dir,
             entries=len(cache.before), warm=bool(cache.before))
        (run_four_chips if args.chips == 4 else run_one_chip)(cache)
        ok = True
    except BaseException as e:      # every failure ends in ok:false, rc!=0
        traceback.print_exc()
        emit("failed", error=f"{type(e).__name__}: {e}"[:500])
        if not isinstance(e, Exception):
            print(last_line(False, device))
            raise
    print(last_line(ok, device))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
