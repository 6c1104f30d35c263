"""Benchmark: local-engine decode throughput + TTFT on the real chip.

Prints ONE JSON line at the end:
  {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": N, "extra": {...}}

Robustness contract:
* **Progress on stderr.** Every phase logs `[bench +T s] ...` so a watcher
  sees params-ready / compiled / warmed instead of silence.
* **Partial results.** Each phase (prefill, decode, TTFT-under-load, paged
  variant, attention micro-bench) is independently guarded; a failing
  phase records its error in ``extra`` and the rest still report.

Measures, for a TinyLlama-1.1B-architecture model (random weights —
zero-egress image; decode FLOPs/bandwidth are weight-value-independent):
  1. steady-state decode tok/s + MFU + HBM GB/s + roofline fraction
     through the engine's real hot loop (contiguous KV — the headline
     `value`; prefill compile warmed out of the timing),
  2. p50/p95 TTFT for a request injected while the decode batch is
     saturated (north-star metric #2, BASELINE.md <200 ms),
  2b. the NORTH STAR rung: Llama-3-8B-architecture, int8 weights + int8 KV
     (fits one v5e), bs=32 — decode tok/s + TTFT against the 2k target,
  3. the same decode timing with the paged KV layout, swept over page
     size 128 vs 256 (winner reported),
  3b. a decode-burst 16/24 sweep: TTFT-vs-throughput trade on one chip,
  4. a mid-size preset rung (llama-3b-class) — MFU must rise with width,
  5. a batch-scaling rung (bs=32) — throughput headroom past the
     comparable bs=8 shape,
  6. int8 quantization rungs (same shape as the headline; weights-only
     and weights+KV — decode is weight-bandwidth-bound so int8 weights
     should land near 2×),
  7. a long-context rung (bf16 vs int8 KV at ctx ~2k, where live KV
     bytes rival weight bytes),
  8. a speculative-decoding rung (repetitive-text regime),
  9. an in-model pallas-vs-jnp attention A/B (whole greedy decode step,
     timed as the slope between two fused-scan lengths).

``vs_baseline`` is value / 2000 — the BASELINE.md north-star decode
tok/s/chip target.

Usage: python bench.py [--kv both] [--batch 8] [--steps 200] [--skip-ttft]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

T0 = time.monotonic()


def note(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T0:6.1f}s] {msg}",
          file=sys.stderr, flush=True)


def fail_line(diag: str, extra: dict | None = None) -> None:
    """The one-line failure contract: a parseable JSON line that SAYS what
    went wrong, then a fast nonzero exit."""
    print(json.dumps({
        "metric": "decode_tok_s_chip", "value": 0.0, "unit": "tok/s",
        "vs_baseline": 0.0, "error": diag, "extra": extra or {}}))
    sys.stdout.flush()
    sys.exit(2)


# Shared with the watchdog: phases publish partial results here so a
# hard-timeout still emits everything measured so far.
RESULT: dict = {"metric": "decode_tok_s_chip", "value": 0.0,
                "unit": "tok/s", "vs_baseline": 0.0, "extra": {}}


def _start_watchdog(hard_timeout_s: float) -> None:
    """The soft deadline only checks BETWEEN phases; a device call that
    never returns would hang the run. This daemon timer prints the
    best-so-far one-line JSON and force-exits, so the driver always gets
    a parseable result inside its timeout."""
    import threading

    def fire():
        RESULT["extra"]["watchdog"] = (
            f"hard timeout {hard_timeout_s:.0f}s hit mid-phase (device "
            f"call hung); partial results emitted")
        print(json.dumps(RESULT))
        sys.stdout.flush()
        os._exit(3)

    t = threading.Timer(hard_timeout_s, fire)
    t.daemon = True
    t.start()


def build_engine(args, kv_layout: str, preset: str | None = None,
                 batch: int | None = None, quant: str = "",
                 kv_quant: str = "", burst: int | None = None,
                 seq: int | None = None, num_pages: int = 0,
                 ttft_target: float = 0.0, model_cfg=None,
                 pages_per_block: int = 0, disagg: bool = False):
    import logging
    # The engine logs its init phase breakdown (params-ready seconds etc.)
    # at INFO — surface it so a slow cold start is attributable from the
    # bench log alone (param init/upload vs XLA compile vs cache hit).
    # Package logger only: a root-level basicConfig would mislabel every
    # third-party INFO record as "[engine]".
    pkg = logging.getLogger("llmapigateway_tpu")
    if not pkg.handlers:
        h = logging.StreamHandler(sys.stderr)
        h.setFormatter(logging.Formatter("[engine] %(message)s"))
        pkg.addHandler(h)
        pkg.setLevel(logging.INFO)
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import InferenceEngine
    cfg = LocalEngineConfig(
        preset=preset or args.preset, dtype="bfloat16",
        max_batch_size=batch or args.batch, max_seq_len=seq or args.seq,
        prefill_chunk=min(512, args.prompt_len), quant=quant,
        kv_quant=kv_quant, kv_num_pages=num_pages,
        decode_burst=burst or args.burst, kv_layout=kv_layout,
        ttft_target_ms=ttft_target,
        # Paged: the page IS the paged kernel's DMA block, so page
        # geometry sets its DMA efficiency; the paged_sweep phase
        # re-measures 128-vs-256 every run so the default tracks the
        # hardware (2026-07-31 v5e ladder: 256 wins, 1647.8 vs 1443.7).
        kv_page_size=args.page_size,
        # Multi-page kernel blocking (ISSUE 2): contiguous-page runs per
        # paged-kernel DMA; the paged phase sweeps it alongside page size.
        kv_pages_per_block=pages_per_block or args.pages_per_block,
        # Engine-side roofline telemetry reports against the same chip
        # peak the bench's own accounting uses.
        hbm_peak_gbps=args.peak_gbps,
        # The off-thread sampler pre-compile would churn CPU during the
        # TTFT probes; the bench measures the greedy path only.
        prewarm_sampler_variants=False,
        # Disaggregated two-pool scheduler (ISSUE 13) — the --disagg-ab
        # rung's pooled arm; "always" admission so both arms serve the
        # identical workload (goodput is scored by the rung, not shed).
        disaggregation={"enabled": True, "admission": "always"}
        if disagg else {})
    t0 = time.monotonic()
    engine = InferenceEngine(cfg, model_cfg=model_cfg)
    init_s = time.monotonic() - t0
    note(f"engine init ({kv_layout}): {init_s:.1f}s "
         f"(B={engine.B}, S={engine.S})")
    return engine, round(init_s, 1)


def _model_footprint(engine) -> tuple[int, int]:
    """(n_params, param_bytes) of the engine's loaded weights.

    ``n_params`` counts MODEL parameters (the FLOPs basis): int8 ``{q,s}``
    leaves count only ``q`` (the fp32 scales are bookkeeping, not params),
    and the tied-embedding int8 head copy ``lm_head_q8`` is a cast of
    ``embed``, not extra parameters. ``param_bytes`` counts every byte
    actually resident (scales included) — the per-step HBM read basis."""
    import jax
    import numpy as np
    n = b = 0
    import jax.numpy as jnp
    for path, leaf in jax.tree_util.tree_flatten_with_path(engine.params)[0]:
        keys = [getattr(k, "key", str(k)) for k in path]
        # int4 packs two elements per HBM byte on TPU; host itemsize says 1.
        itemsize = 0.5 if leaf.dtype == jnp.int4 else leaf.dtype.itemsize
        b += int(np.prod(leaf.shape) * itemsize)
        if keys[-1] == "s" or keys[0] == "lm_head_q8":
            continue
        n += int(np.prod(leaf.shape))
    return n, b


def decode_footprint(prompt_len: int, steps: int, warmup: int,
                     burst: int) -> tuple[int, int]:
    """(warmup_steps, total_tokens) of fill_and_time_decode's workload.

    ONE copy of this arithmetic: fill_and_time_decode sizes its paged
    ``allocate()`` from it, and the capacity-crossover phase sizes its
    page reservations and slot count from it — if they drifted apart the
    crossover could under-reserve and silently decode through the trash
    page."""
    burst = max(1, burst)
    tail = steps % burst
    warmup_steps = burst + tail + (max(0, warmup - burst - tail)
                                   // burst) * burst
    return warmup_steps, prompt_len + warmup_steps + steps + 1


def fill_and_time_decode(engine, args, steps: int | None = None) -> dict:
    """Fill every slot via prefill, then time steady-state decode through
    the engine's real hot loop (`_decode_burst`)."""
    import numpy as np
    B, S = engine.B, engine.S
    steps = steps if steps is not None else args.steps
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, engine.model_cfg.vocab_size,
                          size=args.prompt_len).astype(np.int32)
    # Exact decode-step count of warmup + timed loop: the paged reservation
    # must cover every step or the tail would write through the trash page.
    burst = max(1, engine.decode_burst)
    tail = steps % burst
    warmup_steps, total_tokens = decode_footprint(
        len(prompt), steps, args.warmup, burst)
    if total_tokens > S:
        raise RuntimeError(
            f"--seq {S} too small for {len(prompt)} prompt + "
            f"{warmup_steps + steps} decode steps")

    # Fill in K-slot groups (the engine's batched-admission programs —
    # dispatch cost dominates chunk compute, so a 40-slot fill runs ~7
    # dispatches per chunk position instead of 40). engine.prefill_groups
    # is the one copy of the rung-snapping policy, so the fill
    # exercises/warms exactly the programs serving admission uses.
    groups = engine.prefill_groups(list(range(B)))

    # Warm every (bucket, K) prefill program the fill loop will use
    # BEFORE timing — r2 conflated prefill compile with prefill
    # throughput (VERDICT item 5). Walk the exact chunk sequence once
    # per distinct group size (all slots share the chunk sequence).
    # Warm writes land in low slots / the paged trash page and are
    # overwritten by the fill.
    t0 = time.monotonic()
    # K=1 is always warmed: TTFT probes admit through the single-request
    # path, and an uncompiled (bucket, K=1) program would land its
    # compile inside a probe's TTFT measurement.
    for k in sorted({1, *(len(g) for g in groups)}):
        pos = 0
        while pos < len(prompt):
            chunk = prompt[pos:pos + engine.prefill_chunk]
            first, engine.cache = engine._exec_prefill(
                list(range(k)), [pos] * k, [chunk] * k)
            pos += len(chunk)
    np.asarray(first)
    note(f"prefill compile warm: {time.monotonic() - t0:.1f}s")

    t0 = time.monotonic()
    firsts = []
    for slot in range(B):
        if engine.paged:
            if not engine.allocator.allocate(slot, total_tokens):
                raise RuntimeError("paged KV pool too small for bench shape")
            engine._table_dirty = True
    for group in groups:
        pos = 0
        while pos < len(prompt):
            chunk = prompt[pos:pos + engine.prefill_chunk]
            first, engine.cache = engine._exec_prefill(
                group, [pos] * len(group), [chunk] * len(group))
            pos += len(chunk)
        firsts.append(first)
        for slot in group:
            engine.lengths[slot] = len(prompt)
            engine.active[slot] = True
            engine.last_token[slot] = 1
    for first in firsts:
        # Sync AFTER all groups dispatched: a per-group sync would
        # serialize host round trips into the prefill timing.
        np.asarray(first)
    prefill_s = time.monotonic() - t0
    note(f"prefill done: {B}x{args.prompt_len} tok in {prefill_s:.1f}s "
         f"(compile excluded)")

    # Warmup compiles every program the timed loop uses: the fused scan
    # (full bursts) AND the per-step fallback (a non-multiple tail).
    engine._d_dirty = True
    t0 = time.monotonic()
    engine._decode_burst(burst)
    if tail:
        engine._decode_burst(tail)
    for _ in range(max(0, args.warmup - burst - tail) // burst):
        engine._decode_burst(burst)
    note(f"decode warm ({warmup_steps} steps incl. compile): "
         f"{time.monotonic() - t0:.1f}s")

    t0 = time.monotonic()
    done = 0
    while done < steps:
        n = min(burst, steps - done)
        engine._decode_burst(n)
        done += n
    decode_s = time.monotonic() - t0
    tok_s = B * steps / decode_s
    note(f"decode timed: {steps} steps x{B} slots -> {tok_s:.1f} tok/s")

    # Roofline accounting (VERDICT r2 item 1): a decode step reads every
    # weight byte once plus the live KV prefix; FLOPs ≈ 2·params per
    # token. Peaks are CLI-settable (defaults: v5e ≈ 197 bf16 TFLOP/s,
    # 819 GB/s HBM).
    c = engine.model_cfg
    n_params, param_bytes = _model_footprint(engine)
    step_s = decode_s / steps
    avg_live = args.prompt_len + warmup_steps + steps / 2
    # bf16 K/V = 2 B/elem; int8 KV = 1 B/elem + fp32 scale per head_dim.
    kv_elem_bytes = (1 + 4 / c.head_dim) if engine.kv_quant else 2
    kv_bytes = (2 * c.n_layers * B * c.n_kv_heads * avg_live * c.head_dim
                * kv_elem_bytes)              # k+v
    # Int8 engines run their matmuls on the MXU's 2x int8 path (v5e: 394
    # TOPS vs 197 bf16 TFLOPS) — MFU against the bf16 peak would read 2x
    # optimistic next to the bf16 rungs it sits beside.
    peak_tflops = args.peak_tflops * (2.0 if engine.quant else 1.0)
    mfu = 2.0 * n_params * B / step_s / (peak_tflops * 1e12)
    hbm_gbps = (param_bytes + kv_bytes) / step_s / 1e9
    out = {
        "tok_s": round(tok_s, 1),
        "ms_per_decode_step": round(1000.0 * decode_s / steps, 3),
        "prefill_tok_s": round(B * args.prompt_len / prefill_s, 1),
        "n_params_b": round(n_params / 1e9, 3),
        "mfu": round(mfu, 4),
        "mfu_peak_tflops": peak_tflops,
        "hbm_gbps": round(hbm_gbps, 1),
        "roofline_fraction": round(hbm_gbps / args.peak_gbps, 3),
    }
    # Cross-check: the ENGINE's own roofline gauge (stats() bytes-touched
    # model × its steady-pair step-time EMA) next to the bench accounting
    # above — if these two drift, one of the models is lying, and that is
    # worth knowing before trusting either (ISSUE 2 telemetry leg).
    es = engine.stats()
    if "achieved_gbps" in es:
        out["engine_achieved_gbps"] = es["achieved_gbps"]
        if "roofline_fraction" in es:
            out["engine_roofline_fraction"] = es["roofline_fraction"]
    if engine.paged and engine.kv_ppb > 1:
        out["pages_per_block"] = engine.kv_ppb
    # Device-observability rows (ISSUE 8): the rung's HBM peak (runtime
    # allocator where the backend has one, else the ledger's static
    # accounting) and the per-kernel cost table the roofline report's
    # worst-kernel ranking reads (tools/roofline_report.py --kernels).
    # Costs resolve synchronously here — the rung is already timed, and
    # an artifact without FLOPs/bytes columns defeats the table.
    mem = engine.ledger.device_memory()
    out["hbm_peak_bytes"] = (mem or {}).get("peak_bytes",
                                            engine.ledger.static_total)
    engine.kernels.resolve_costs()
    out["kernels"] = engine.kernel_table()
    return out


def reset_slots(engine) -> None:
    """Return a bench-filled engine to a clean scheduler state."""
    engine._pending = None               # drop any in-flight burst
    if engine.spec_k:
        engine._spec_pending = None
        engine._d_hist_fresh = False
    engine.lengths[:] = 0
    engine.active[:] = False
    engine.last_token[:] = 0
    engine._d_dirty = True
    if engine.paged:
        for slot in range(engine.B):
            engine.allocator.release(slot)
        engine._table_dirty = True


def measure_ttft_under_load(engine, args) -> dict:
    """North-star metric #2: p50/p95 time-to-first-token for a request
    injected while the decode batch is saturated — exercises the real
    scheduler (admission, chunked prefill interleave, adaptive burst)."""
    import asyncio
    import numpy as np
    from llmapigateway_tpu.engine.engine import GenRequest

    rng = np.random.default_rng(1)
    V = engine.model_cfg.vocab_size
    # DISTINCT prompts per request: with the radix prefix cache on by
    # default, a repeated prompt would serve probes 2..N warm (prefill
    # skipped) and silently turn this phase's headline into warm TTFT —
    # the shared-prefix rung measures that on purpose; this one stays
    # cold, comparable with the r5b ladder.

    async def run() -> dict:
        await engine.start()
        # Saturate B-1 slots with long-running generations.
        bg = []
        budget = engine.S - args.prompt_len - 8
        for _ in range(max(1, engine.B - 1)):
            r = GenRequest(
                prompt_ids=rng.integers(0, V, args.prompt_len).tolist(),
                max_tokens=budget, temperature=0.0)
            await engine.submit(r)
            bg.append(r)

        async def first_token(r: GenRequest) -> float:
            # Poll the engine's own first-token stamp: text deltas can lag
            # tokens (the incremental detokenizer holds back partial
            # UTF-8/BPE), and TTFT is a token-level metric.
            while r.t_first_token is None and r.finish_reason is None:
                await asyncio.sleep(0.002)
            return r.t_first_token or time.monotonic()

        for r in bg:                      # wait until all are decoding
            await first_token(r)
        note(f"TTFT: {len(bg)} background slots decoding; injecting "
             f"{args.ttft_probes} probes")

        ttfts = []
        for _ in range(args.ttft_probes):
            p = GenRequest(
                prompt_ids=rng.integers(0, V, args.prompt_len).tolist(),
                max_tokens=4, temperature=0.0)
            t_sub = time.monotonic()
            await engine.submit(p)
            t_first = await first_token(p)
            ttfts.append(1000.0 * (t_first - t_sub))
            async for _ in engine.stream(p):     # drain to completion
                pass
        for r in bg:
            r.cancelled = True
        await engine.stop()
        arr = np.asarray(sorted(ttfts))
        return {
            "ttft_p50_ms": round(float(np.percentile(arr, 50)), 1),
            "ttft_p95_ms": round(float(np.percentile(arr, 95)), 1),
            "ttft_probes": len(arr),
            "ttft_load_slots": len(bg),
        }

    return asyncio.run(run())


# The TTFT harness drives the full async scheduler (start/submit/stream/
# stop) inside the bench process; on some builds (the CPU jax wheel in
# this container) that sequence kills the interpreter with SIGSEGV — not
# an exception, so the try/except at every call site cannot save the run
# (PR 10 lost its TTFT arm to this 3/3). Probe the harness ONCE in a
# throwaway subprocess on the tiny preset: if the child dies on a
# signal, every TTFT arm is skipped gracefully and the skip reason lands
# in the artifact instead of the whole bench dying mid-run. A fixed
# build gets its arms back automatically — no hardcoded platform list.
_TTFT_PROBE: dict | None = None


def _ttft_probe_args(args):
    """The probe child's knobs: tiny everything, same code path."""
    import copy
    p = copy.copy(args)
    p.preset, p.batch, p.seq = "tiny-test", 4, 256
    p.prompt_len, p.burst, p.page_size = 64, 8, 64
    p.pages_per_block, p.ttft_probes = 1, 2
    return p


def ttft_harness_probe(args) -> dict:
    """Probe the TTFT harness once per process (cached). The child is
    spawned ONLY on the CPU backend: the segfault it guards against is
    the CPU wheel's, and on an accelerator this process already holds the
    chip — one process owns a chip at a time, so a child that needed it
    would fail or hang."""
    global _TTFT_PROBE
    if _TTFT_PROBE is not None:
        return _TTFT_PROBE
    import jax
    if jax.default_backend() != "cpu":
        _TTFT_PROBE = {"ok": True, "probed": False}
        return _TTFT_PROBE
    import subprocess
    note("probing the TTFT harness in a subprocess (known CPU-build "
         "segfault path)")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--ttft-probe-child"],
            capture_output=True, text=True, timeout=600,
            cwd=os.path.dirname(os.path.abspath(__file__)) or ".")
        rc = proc.returncode
        ok = rc == 0 and "TTFT_PROBE_OK" in proc.stdout
        if ok:
            _TTFT_PROBE = {"ok": True, "probed": True}
        elif rc < 0:
            _TTFT_PROBE = {
                "ok": False, "probed": True,
                "reason": f"TTFT harness killed by signal {-rc} on this "
                          f"jax build (probe subprocess; known CPU-wheel "
                          f"segfault)"}
        else:
            tail = (proc.stderr or proc.stdout or "").strip()[-300:]
            _TTFT_PROBE = {
                "ok": False, "probed": True,
                "reason": f"TTFT harness probe exited rc={rc}: {tail}"}
    except subprocess.TimeoutExpired:
        _TTFT_PROBE = {"ok": False, "probed": True,
                       "reason": "TTFT harness probe timed out (600s)"}
    if not _TTFT_PROBE["ok"]:
        note(f"TTFT arms disabled: {_TTFT_PROBE['reason']}")
    return _TTFT_PROBE


def ttft_probe_child(args) -> int:
    """--ttft-probe-child entry: the exact in-parent TTFT sequence
    (fill/time → reset → harness) on a tiny engine. Prints a sentinel on
    success; a segfault here is a segfault the parent was spared."""
    pargs = _ttft_probe_args(args)
    engine, _ = build_engine(pargs, "paged", preset="tiny-test")
    fill_and_time_decode(engine, pargs, steps=8)
    reset_slots(engine)
    out = measure_ttft_under_load(engine, pargs)
    print(f"TTFT_PROBE_OK {json.dumps(out)}")
    return 0


def run_ttft_arm(engine, args, label: str) -> dict:
    """measure_ttft_under_load behind the harness probe: the TTFT
    fields, or a ``ttft_skipped`` reason block when the harness cannot
    run on this build (the artifact records WHY the arm is absent)."""
    probe = ttft_harness_probe(args)
    if not probe["ok"]:
        note(f"TTFT arm '{label}' skipped: {probe['reason']}")
        return {"ttft_skipped": probe["reason"]}
    reset_slots(engine)
    return measure_ttft_under_load(engine, args)


def shared_prefix_rung(args) -> dict:
    """ISSUE 6 acceptance rung: warm-vs-cold TTFT on a shared-prefix
    workload. Every request carries the same >=--shared-prefix-len-token
    system prefix plus a unique tail; the first request pays full
    prefill, later ones must hit the radix prefix cache. The "prefill
    actually skipped" claim is asserted from ENGINE STATS (cached-token
    totals + FaultPlan prefill-call counts), not wall clock alone."""
    import asyncio
    import numpy as np
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import (FaultPlan, GenRequest,
                                                 InferenceEngine)

    plen = max(32, args.shared_prefix_len)
    tail_len = max(8, args.shared_prefix_tail)
    # Keep the default page geometry when it leaves >= 2 shareable blocks
    # in the prefix; shrink the page only when the operator asked for a
    # prefix too short for it (smoke runs).
    page = min(args.page_size, max(16, plen // 2))
    seq = max(args.seq, plen + tail_len + 64)
    chunk = min(512, max(32, plen // 4))
    cfg = LocalEngineConfig(
        preset=args.preset, dtype="bfloat16", max_batch_size=args.batch,
        max_seq_len=seq, prefill_chunk=chunk, kv_layout="paged",
        kv_page_size=page,
        # Slack past full reservation so insert-on-release can retain the
        # prefix instead of evicting it for the next admission.
        kv_num_pages=(args.batch + 2) * -(-seq // page) + 1,
        decode_burst=max(1, min(args.burst, 8)),
        hbm_peak_gbps=args.peak_gbps, prewarm_sampler_variants=False)
    t0 = time.monotonic()
    engine = InferenceEngine(cfg)
    note(f"shared-prefix engine init: {time.monotonic() - t0:.1f}s "
         f"(page={page}, prefix={plen})")
    if engine._prefix_cache is None:
        raise RuntimeError("prefix cache inactive on the rung's engine")
    engine.fault_plan = FaultPlan()
    rng = np.random.default_rng(17)
    V = engine.model_cfg.vocab_size
    prefix = rng.integers(2, V, size=plen).tolist()

    async def first_token(r: GenRequest) -> float:
        while r.t_first_token is None and r.finish_reason is None:
            await asyncio.sleep(0.002)
        return r.t_first_token or time.monotonic()

    async def one(ids, n_gen=8) -> float:
        r = GenRequest(prompt_ids=ids, max_tokens=n_gen, temperature=0.0)
        t_sub = time.monotonic()
        await engine.submit(r)
        ttft = 1000.0 * (await first_token(r) - t_sub)
        async for _ in engine.stream(r):
            pass
        return ttft

    async def run() -> dict:
        await engine.start()
        # Warm the compiled programs off the measured path: an unrelated
        # full-length prompt (cold-shape prefill buckets + decode scans)
        # and an unrelated short prompt (the warm tail's bucket).
        await one(rng.integers(2, V, size=plen + tail_len).tolist())
        await one(rng.integers(2, V, size=tail_len + 1).tolist())
        calls0 = engine.fault_plan.prefill_calls
        cold_ttft = await one(prefix + rng.integers(2, V,
                                                    size=tail_len).tolist())
        cold_calls = engine.fault_plan.prefill_calls - calls0
        warm = []
        warm_calls = []
        for _ in range(max(1, args.shared_prefix_warm)):
            calls0 = engine.fault_plan.prefill_calls
            warm.append(await one(
                prefix + rng.integers(2, V, size=tail_len).tolist()))
            warm_calls.append(engine.fault_plan.prefill_calls - calls0)
        stats = engine.stats()
        await engine.stop()
        arr = np.asarray(sorted(warm))
        p50 = float(np.percentile(arr, 50))
        out = {
            "prefix_tokens": plen,
            "page_size": page,
            "cold_ttft_ms": round(cold_ttft, 1),
            "warm_ttft_p50_ms": round(p50, 1),
            "warm_ttft_p95_ms": round(float(np.percentile(arr, 95)), 1),
            "warm_requests": len(warm),
            "ttft_speedup": round(cold_ttft / max(1e-9, p50), 2),
            # The structural proof prefill was SKIPPED, not just faster:
            # chunk dispatches per request and the engine's own hit
            # accounting.
            "cold_prefill_calls": cold_calls,
            "warm_prefill_calls_max": max(warm_calls),
            "prefix_hits_total": stats.get("prefix_hits_total", 0),
            "prefix_cached_tokens_total": stats.get(
                "prefix_cached_tokens_total", 0),
        }
        return out

    return asyncio.run(run())


def spec_ladder_rung(args) -> dict:
    """ISSUE 10 acceptance rung: the speculative ladder — draft depth
    0/1/3/7 × bf16/int8-KV on the PAGED layout (the headline config's
    layout; int8+spec is the tentpole composition). Repetitive-text
    regime, the one prompt-lookup drafting exists for, so depth is
    exercised honestly: the batch-mean gates are disabled per arm and the
    measured acceptance rate is reported instead. Each arm records tok/s
    through the engine's real burst loop, accepted-tokens-per-step, the
    acceptance ratio, and its registry worst_kernel() pick (the int8
    rows are what PR 8's roofline named furthest from the HBM roof); the
    int8 arm re-runs its mid depth across pages_per_block 1/2/4 — the
    int8-aware DMA-blocking sweep. TTFT under load runs per spec depth
    on the int8 arm unless --skip-ttft."""
    import numpy as np
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import InferenceEngine
    from llmapigateway_tpu.obs.device import worst_kernel

    # Page geometry: keep the configured page when the context is big
    # enough for a multi-page sweep, shrink for smoke shapes so ppb 2/4
    # can still pack (a 1-page sequence can't block multiple pages).
    page = min(args.page_size, max(16, args.seq // 4))
    depths = (0, 1, 3, 7)

    def one(kvq: str, k: int, ppb: int = 1, ttft: bool = False) -> dict:
        cfg = LocalEngineConfig(
            preset=args.preset, dtype="bfloat16",
            max_batch_size=args.batch, max_seq_len=args.seq,
            prefill_chunk=min(512, args.prompt_len),
            decode_burst=args.burst, kv_layout="paged",
            kv_page_size=page, kv_pages_per_block=ppb, kv_quant=kvq,
            spec_draft_len=k,
            # The ladder measures each depth, not the gate: batch-mean
            # gates off (spec_mixed measures the gated path).
            spec_min_tokens_per_step=0.0, spec_wall_gate=False,
            hbm_peak_gbps=args.peak_gbps, prewarm_sampler_variants=False)
        engine = InferenceEngine(cfg)
        rng = np.random.default_rng(5)
        base = rng.integers(0, engine.model_cfg.vocab_size, 16)
        prompt = np.tile(base, args.prompt_len // 16 + 1)[
            :args.prompt_len].astype(np.int32)
        B, S = engine.B, engine.S
        per_burst = (engine._spec_scan_len * (k + 1) if k
                     else max(1, engine.decode_burst))
        bursts = max(1, min(args.spec_bursts,
                            (S - len(prompt) - 2) // per_burst - 1))
        for slot in range(B):
            if not engine.allocator.allocate(
                    slot, len(prompt) + (bursts + 1) * per_burst + 1):
                raise RuntimeError("spec-ladder paged pool too small")
            engine._table_dirty = True
            first, engine.cache = engine._exec_prefill(slot, 0, prompt)
            engine.lengths[slot] = len(prompt)
            engine.active[slot] = True
            engine.last_token[slot] = int(base[len(prompt) % 16])
            if k:
                engine.hist[slot, :len(prompt)] = prompt
        np.asarray(first)
        engine._d_dirty = True
        # Warm (compiles the scan program), then the timed loop.
        if k:
            engine._spec_burst(engine._spec_scan_len)
        else:
            engine._decode_burst(per_burst)
        t0 = time.monotonic()
        toks = 0
        for _ in range(bursts):
            if k:
                rows = engine._spec_burst(engine._spec_scan_len)
                toks += int(sum((r >= 0).sum() for r in rows))
            else:
                engine._decode_burst(per_burst)
                toks += B * per_burst
        dt = time.monotonic() - t0
        rec = {"tok_s": round(toks / dt, 1), "draft_len": k}
        if ppb > 1 or engine.kv_ppb > 1:
            rec["pages_per_block"] = engine.kv_ppb
        if k:
            st = engine.stats()
            prop, acc = st.get("spec_proposed", 0), st.get("spec_accepted", 0)
            rec["acceptance"] = round(acc / prop, 3) if prop else None
            rec["tokens_per_step"] = round(
                engine._spec_tokens_out / max(1, engine._spec_steps_done), 2)
        # Spend the PR 8 registry: this arm's furthest-below-the-roof
        # kernel — on the int8 arms this ranks the int8 decode/spec
        # variants the kernel work targets. The full table rides along so
        # tools/roofline_report.py --kernels renders the ladder's spec
        # rows (acceptance-adjusted) straight from the artifact.
        engine.kernels.resolve_costs()
        rec["kernels"] = engine.kernel_table()
        wk = worst_kernel(rec["kernels"])
        if wk:
            rec["worst_kernel"] = wk
        if ttft and not args.skip_ttft:
            rec.update(run_ttft_arm(engine, args, f"spec-ladder {kvq}"))
        return rec

    out = {"regime": "repetitive-text (prompt-lookup drafting's target); "
                     "batch-mean gates off, paged layout",
           "shape": f"bs={args.batch} ctx={args.prompt_len} "
                    f"burst={args.burst} page={page}"}
    for label, kvq in (("bf16", ""), ("int8", "int8")):
        arm = {}
        for k in depths:
            arm[f"spec{k}"] = one(kvq, k, ttft=(label == "int8"))
        base_tok = arm["spec0"]["tok_s"]
        for k in depths[1:]:
            arm[f"spec{k}"]["vs_spec_off"] = round(
                arm[f"spec{k}"]["tok_s"] / max(1e-9, base_tok), 3)
        out[label] = arm
    # int8-aware pages_per_block sweep at the mid draft depth: the paged
    # spec verify gathers pages for the deferred self-block, so DMA
    # blocking interacts with drafting only on this arm.
    ppb_sweep = {"1": out["int8"]["spec3"]["tok_s"]}
    for ppb in (2, 4):
        try:
            r = one("int8", 3, ppb=ppb)
            ppb_sweep[str(ppb)] = (r["tok_s"]
                                   if r.get("pages_per_block") == ppb
                                   else "fallback (can't pack)")
        except Exception as e:           # noqa: BLE001 — sweep leg only
            ppb_sweep[str(ppb)] = f"failed: {e!r}"
    numeric = {p: v for p, v in ppb_sweep.items() if isinstance(v, float)}
    if numeric:
        best = max(numeric, key=numeric.get)
        ppb_sweep["best_pages_per_block"] = int(best)
        ppb_sweep["best_tok_s"] = numeric[best]
    out["int8"]["ppb_sweep"] = ppb_sweep
    return out


def scheduler_throughput(engine, args, n_tokens: int = 120) -> float:
    """Steady-state tok/s through the REAL scheduler loop (admission,
    bursts, adaptive gates) with non-repetitive prompts: one warm round
    compiles every program, then a full-batch round is timed from
    all-slots-decoding to completion."""
    import asyncio
    import numpy as np
    from llmapigateway_tpu.engine.engine import GenRequest

    rng = np.random.default_rng(9)
    V = engine.model_cfg.vocab_size

    async def drain(r):
        async for _ in engine.stream(r):
            pass

    async def first_token(r):
        while r.t_first_token is None and r.finish_reason is None:
            await asyncio.sleep(0.002)

    async def run() -> float:
        await engine.start()
        # Warm round: compile prefill/decode (and any spec) programs.
        warm = GenRequest(
            prompt_ids=rng.integers(0, V, args.prompt_len).tolist(),
            max_tokens=2 * max(1, engine.decode_burst), temperature=0.0)
        await engine.submit(warm)
        await drain(warm)
        reqs = [GenRequest(
            prompt_ids=rng.integers(0, V, args.prompt_len).tolist(),
            max_tokens=n_tokens, temperature=0.0) for _ in range(engine.B)]
        for r in reqs:
            await engine.submit(r)
        for r in reqs:
            await first_token(r)
        t0 = time.monotonic()
        await asyncio.gather(*(drain(r) for r in reqs))
        dt = time.monotonic() - t0
        toks = sum(len(r.generated) - 1 for r in reqs)   # post-first-token
        await engine.stop()
        return toks / dt

    return asyncio.run(run())


SLO_TTFT_TARGET_MS = 200.0      # SNIPPETS.md serving targets: the ladder's
SLO_TOK_S_TARGET = 2000.0       # goodput gate (ISSUE 7 satellite)


def slo_fields(tok_s=None, ms_per_step=None, batch=None,
               ttft_p50_ms=None) -> dict:
    """Per-rung SLO/goodput block for the ladder JSON: the SNIPPETS.md
    targets (TTFT < 200 ms; TPOT derived from 2k aggregate tok/s at the
    rung's batch — step time must beat batch/2000 s), which of them the
    rung's measurements meet, and the DistServe-style goodput number —
    the rung's throughput counted ONLY while its latency targets hold
    (0.0 otherwise), so BENCH artifacts track goodput, not raw tok/s."""
    out = {"ttft_target_ms": SLO_TTFT_TARGET_MS,
           "tok_s_target": SLO_TOK_S_TARGET}
    tpot_target = (1000.0 * batch / SLO_TOK_S_TARGET) if batch else None
    if tpot_target is not None:
        out["tpot_target_ms"] = round(tpot_target, 3)
    ttft_ok = (ttft_p50_ms <= SLO_TTFT_TARGET_MS
               if ttft_p50_ms is not None else None)
    tpot_ok = (ms_per_step <= tpot_target
               if ms_per_step is not None and tpot_target else None)
    if ttft_p50_ms is not None:
        out["ttft_p50_ms"] = ttft_p50_ms
    if ms_per_step is not None:
        out["tpot_ms"] = ms_per_step
    out["ttft_ok"] = ttft_ok
    out["tpot_ok"] = tpot_ok
    measured = [v for v in (ttft_ok, tpot_ok) if v is not None]
    good = bool(measured) and all(measured) and tok_s
    out["goodput_tok_s"] = round(tok_s, 1) if good else 0.0
    return out


def flight_ab_rung(args) -> dict:
    """Flight-recorder overhead A/B (ISSUE 7 acceptance): decode tok/s
    through the REAL scheduler loop (the only place the recorder appends)
    with recording on vs off, arms alternated and best-of-N compared so
    scheduler jitter cancels — the recorder's appends are a handful of
    scalar stores per step, so the honest delta is noise-floor."""
    from llmapigateway_tpu.obs.flight import FlightRecorder
    engine, _ = build_engine(args, "contiguous")
    n_tok = max(16, args.flight_ab_tokens)
    recorder = engine.flight or FlightRecorder()
    on_runs, off_runs = [], []

    def one(arm: str) -> None:
        engine.flight = recorder if arm == "on" else None
        (on_runs if arm == "on" else off_runs).append(
            scheduler_throughput(engine, args, n_tokens=n_tok))

    pairs = 0
    while True:
        # Alternate which arm leads each pair: process warm-up drifts
        # monotonically favor whichever arm runs later, and a one-sided
        # order folds that drift into the "overhead".
        for arm in (("on", "off") if pairs % 2 == 0 else ("off", "on")):
            one(arm)
        pairs += 1
        # PAIRED estimator: each pair's runs are adjacent in time, so
        # their ratio cancels slow machine drift; the median of ratios
        # is robust to single-run outliers that make best-of-N compares
        # flap on a loaded host. A measured append is ~2 µs against
        # multi-ms steps, so a large persistent delta would be real —
        # noise washes out with more pairs, a true gap survives them.
        ratios = sorted(a / b for a, b in zip(on_runs, off_runs) if b > 0)
        med = ratios[len(ratios) // 2] if ratios else 1.0
        delta = 100.0 * (1.0 - med)
        if pairs >= max(1, args.flight_ab_repeats) and (
                delta <= 2.0 or pairs >= 2 * max(3, args.flight_ab_repeats)):
            break
    return {
        "tok_s_recorder_on": round(max(on_runs), 1),
        "tok_s_recorder_off": round(max(off_runs), 1),
        # Positive = the recorder cost throughput (median of paired
        # on/off ratios); the acceptance bar is <= 2% (negative values
        # are measurement noise in the on arm's favor).
        "delta_pct": round(delta, 2),
        "records_per_run": recorder.seq,
        "repeats": pairs,
    }


def annot_ab_rung(args) -> dict:
    """Phase-annotation overhead A/B (ISSUE 8 acceptance): decode tok/s
    through the REAL scheduler loop with the host-side TraceAnnotation
    markers on vs off, arms alternated and the paired-median ratio
    compared (the --flight-ab estimator) — the markers are two C-level
    calls per dispatch, so the acceptance bar is ≤1% on decode."""
    engine, _ = build_engine(args, "contiguous")
    n_tok = max(16, args.annot_ab_tokens)
    on_runs, off_runs = [], []

    def one(arm: str) -> None:
        engine.profile_annotations = arm == "on"
        (on_runs if arm == "on" else off_runs).append(
            scheduler_throughput(engine, args, n_tokens=n_tok))

    pairs = 0
    while True:
        for arm in (("on", "off") if pairs % 2 == 0 else ("off", "on")):
            one(arm)
        pairs += 1
        ratios = sorted(a / b for a, b in zip(on_runs, off_runs) if b > 0)
        med = ratios[len(ratios) // 2] if ratios else 1.0
        delta = 100.0 * (1.0 - med)
        if pairs >= max(1, args.annot_ab_repeats) and (
                delta <= 1.0 or pairs >= 2 * max(3, args.annot_ab_repeats)):
            break
    return {
        "tok_s_annotations_on": round(max(on_runs), 1),
        "tok_s_annotations_off": round(max(off_runs), 1),
        # Positive = annotations cost throughput (median of paired
        # on/off ratios); ≤1% is the acceptance bar, negative values are
        # noise in the on arm's favor.
        "delta_pct": round(delta, 2),
        # Best-of comparison: robust against per-run scheduler jitter at
        # toy scale — a true cost shows in BOTH estimators, noise rarely
        # in both directions at once (the smoke asserts the min).
        "delta_best_pct": round(
            100.0 * (1.0 - max(on_runs) / max(off_runs)), 2),
        "repeats": pairs,
    }


def disagg_ab_rung(args) -> dict:
    """Disaggregation A/B (ISSUE 13 acceptance): a mixed prefill-heavy /
    decode-heavy workload through the REAL scheduler, pooled (two-pool
    disaggregated) vs unified, arms alternated with the paired-median
    ratio estimator (the --flight-ab pattern). Each arm reports a
    per-pool ``slo`` block — met/violated/goodput per serving pool —
    plus the engine's pool stats, so the artifact carries the
    pooled-vs-unified ``gateway_slo_goodput_ratio`` scoreboard the
    metrics plane exports live. SLO targets are CALIBRATED from an
    uncounted unified round (p75 of its measured TTFT/TPOT): both arms
    are scored against the same fixed bar, so on any hardware the ratio
    measures scheduling, not the machine."""
    import asyncio
    import numpy as np
    from llmapigateway_tpu.engine.engine import GenRequest
    from llmapigateway_tpu.obs.flight import POOL_NAMES

    engines = {
        "unified": build_engine(args, "paged")[0],
        "pooled": build_engine(args, "paged", disagg=True)[0],
    }
    B = engines["unified"].B
    S = engines["unified"].S
    V = engines["unified"].model_cfg.vocab_size
    n_tok = max(16, args.disagg_ab_tokens)
    # The mixed workload: half the requests are prefill-heavy (long
    # prompt, short generation — TTFT-bound), half decode-heavy (short
    # prompt, long generation — TPOT-bound); interleaved so the unified
    # arm experiences the interference disaggregation exists to remove.
    pf_len = min(2 * args.prompt_len, max(32, (S * 3) // 5))
    dc_len = max(8, args.prompt_len // 4)
    pf_gen = 4
    dc_gen = min(n_tok, S - dc_len - 2)
    workload = {"requests": 2 * B, "prefill_heavy":
                {"prompt_len": pf_len, "max_tokens": pf_gen},
                "decode_heavy":
                {"prompt_len": dc_len, "max_tokens": dc_gen}}

    def mk_requests(rng, targets=None):
        reqs = []
        for i in range(2 * B):
            heavy = i % 2 == 0
            plen, gen = (pf_len, pf_gen) if heavy else (dc_len, dc_gen)
            kw = {}
            if targets:
                kw = {"slo_ttft_ms": targets["ttft_ms"],
                      "slo_tpot_ms": targets["tpot_ms"]}
            # DISTINCT prompts: a shared prefix would warm-hit the radix
            # cache and route direct-to-decode, hiding the handoff path.
            reqs.append(GenRequest(
                prompt_ids=rng.integers(0, V, plen).tolist(),
                max_tokens=gen, temperature=0.0, **kw))
        return reqs

    def outcome(r, targets):
        if r.t_first_token is None:
            return None
        ttft = 1000.0 * (r.t_first_token - r.t_submit)
        n = len(r.generated)
        tpot = (1000.0 * (r.t_done - r.t_first_token) / (n - 1)
                if r.t_done and n > 1 else None)
        met = ttft <= targets["ttft_ms"] and (
            tpot is None or tpot <= targets["tpot_ms"])
        return {"ttft_ms": ttft, "tpot_ms": tpot, "met": met,
                "pool": POOL_NAMES.get(getattr(r, "pool", 0), "unified")}

    def mixed_round(engine, rng, targets=None):
        async def run():
            await engine.start()
            reqs = mk_requests(rng, targets)
            t0 = time.monotonic()
            for r in reqs:
                await engine.submit(r)

            async def drain(r):
                async for _ in engine.stream(r):
                    pass
            await asyncio.gather(*(drain(r) for r in reqs))
            dt = time.monotonic() - t0
            toks = sum(len(r.generated) for r in reqs)
            pool_stats = engine.stats().get("pools")
            await engine.stop()
            return toks / dt, reqs, pool_stats
        return asyncio.run(run())

    rng = np.random.default_rng(13)
    # Warm both arms (compile everything), then calibrate the SLO bar
    # from one more uncounted unified round at p75.
    mixed_round(engines["unified"], rng)
    mixed_round(engines["pooled"], rng)
    _, cal_reqs, _ = mixed_round(engines["unified"], rng)
    cal_ttft = sorted(1000.0 * (r.t_first_token - r.t_submit)
                      for r in cal_reqs if r.t_first_token)
    cal_tpot = sorted(
        1000.0 * (r.t_done - r.t_first_token) / (len(r.generated) - 1)
        for r in cal_reqs
        if r.t_done and r.t_first_token and len(r.generated) > 1)
    targets = {
        "ttft_ms": round(cal_ttft[(3 * len(cal_ttft)) // 4], 1),
        "tpot_ms": round(cal_tpot[(3 * len(cal_tpot)) // 4], 2),
    }

    runs: dict[str, list] = {"unified": [], "pooled": []}
    outcomes: dict[str, list] = {"unified": [], "pooled": []}
    pool_stats: dict[str, dict] = {}
    pairs = 0
    while True:
        order = (("pooled", "unified") if pairs % 2 == 0
                 else ("unified", "pooled"))
        for arm in order:
            tok_s, reqs, pstats = mixed_round(engines[arm], rng, targets)
            runs[arm].append(tok_s)
            outcomes[arm].extend(
                o for o in (outcome(r, targets) for r in reqs) if o)
            if pstats:
                pool_stats[arm] = pstats
        pairs += 1
        ratios = sorted(p / u for p, u in
                        zip(runs["pooled"], runs["unified"]) if u > 0)
        med = ratios[len(ratios) // 2] if ratios else 1.0
        if pairs >= max(1, args.disagg_ab_repeats):
            break

    def slo_block(arm: str) -> dict:
        by_pool: dict[str, dict] = {}
        for o in outcomes[arm]:
            b = by_pool.setdefault(o["pool"], {"met": 0, "violated": 0})
            b["met" if o["met"] else "violated"] += 1
        for b in by_pool.values():
            tot = b["met"] + b["violated"]
            b["goodput_ratio"] = round(b["met"] / tot, 3) if tot else None
        met = sum(1 for o in outcomes[arm] if o["met"])
        tot = len(outcomes[arm])
        return {"requests": tot, "met": met, "violated": tot - met,
                "goodput_ratio": round(met / tot, 3) if tot else None,
                "by_pool": by_pool}

    out = {
        "workload": workload,
        "slo_targets": {**targets,
                        "calibration": "p75 of an uncounted unified "
                                       "round; both arms scored against "
                                       "the same bar"},
        "repeats": pairs,
        # Positive = the pooled arm is faster (median of paired ratios).
        "tok_s_delta_pct": round(100.0 * (med - 1.0), 2),
        "gateway_slo_goodput_ratio": {},
    }
    for arm in ("unified", "pooled"):
        blk = {"tok_s": round(max(runs[arm]), 1), "slo": slo_block(arm)}
        if arm in pool_stats:
            blk["pools"] = pool_stats[arm]
        out[arm] = blk
        out["gateway_slo_goodput_ratio"][arm] = \
            blk["slo"]["goodput_ratio"]
    return out


def failover_ab_rung(args) -> dict:
    """Failover A/B (ISSUE 14 acceptance): a scripted mid-run engine kill
    under load, through the REAL router + breaker + supervised engine.
    Three windows are measured against one request stream: steady
    (healthy local engine), incident (an armed FaultPlan kills the step
    loop mid-decode; in-flight streams get in-band SSE error frames,
    new requests fail over to a remote stub once the breaker opens), and
    recovered (fault cleared, admin stop, cooldown, half-open probe
    readmits the local engine). The scoreboard is the goodput ratio per
    window — the incident window must stay NONZERO because the remote
    arm absorbs — plus the p99 kill→error-frame latency (the PR 3
    mid-stream contract made measurable)."""
    import asyncio
    import tempfile
    from pathlib import Path

    from llmapigateway_tpu.config.loader import ConfigLoader
    from llmapigateway_tpu.db.rotation import RotationDB
    from llmapigateway_tpu.engine.engine import FaultPlan
    from llmapigateway_tpu.providers.base import (
        JSONCompletion, NullUsageObserver, Provider)
    from llmapigateway_tpu.providers.local import LocalProvider
    from llmapigateway_tpu.reliability import BreakerRegistry
    from llmapigateway_tpu.routing.router import Router

    engine = build_engine(args, "paged", disagg=True)[0]
    # A deliberately tiny restart budget: the armed fault keeps raising,
    # burns it, and parks the engine "failed" — a deterministic incident
    # plateau to measure against instead of racing backoff windows.
    engine.supervisor.max_restarts = 2
    engine.supervisor.backoff_ms = 10.0

    class RemoteStub(Provider):
        """The absorbing remote arm: a healthy upstream with a fixed
        small reply latency, so backup-served goodput is attributable."""

        def __init__(self):
            self.name = "backup"
            self.calls = 0

        async def complete(self, request, observer):
            self.calls += 1
            await asyncio.sleep(0.002)
            observer.on_first_token()
            observer.on_stream_end()
            return JSONCompletion(
                data={"choices": [{"message": {"role": "assistant",
                                               "content": "remote"},
                                   "finish_reason": "stop"}]},
                provider=self.name), None

    class Registry:
        def __init__(self, providers):
            self.providers = providers

        async def get(self, name):
            return self.providers.get(name)

    remote = RemoteStub()
    providers = {"local_tpu": LocalProvider("local_tpu", engine),
                 "backup": remote}
    # Short breaker window: the steady window's successes age out during
    # the kill/settle sleep, so the incident's first two 503s open the
    # breaker on a clean failure rate (min_requests=2, rate 1.0).
    WINDOW_S, COOLDOWN_S = 0.8, 0.6
    PROVIDERS = ('[{"local_tpu": {"baseUrl": "http://127.0.0.1:1/v1", '
                 '"apikey": "K", "breaker": {"min_requests": 2, '
                 f'"window_s": {WINDOW_S}, "failure_threshold": 0.5, '
                 f'"cooldown_s": {COOLDOWN_S}}}}}}},\n'
                 ' {"backup": {"baseUrl": "http://127.0.0.1:1/v1", '
                 '"apikey": "K"}}]')
    RULES = ('[{"gateway_model_name": "gw/failover", "fallback_models": ['
             '{"provider": "local_tpu", "model": "local"}, '
             '{"provider": "backup", "model": "backup-model"}]}]')

    def observer_factory(provider, model):
        return NullUsageObserver()

    async def dispatch(router, stream=False, max_tokens=8):
        payload = {"model": "gw/failover",
                   "messages": [{"role": "user", "content": "bench"}],
                   "max_tokens": max_tokens, "temperature": 0.0}
        if stream:
            payload["stream"] = True
        t0 = time.monotonic()
        out = await router.dispatch(payload, "bench-key", observer_factory)
        return out, 1000.0 * (time.monotonic() - t0)

    async def probe_window(router, n, max_tokens=8):
        ok, latencies, served = 0, [], {}
        for _ in range(n):
            out, ms = await dispatch(router, max_tokens=max_tokens)
            latencies.append(ms)
            if out.result is not None:
                ok += 1
                served[out.provider] = served.get(out.provider, 0) + 1
        latencies.sort()
        return {"requests": n, "ok": ok,
                "goodput_ratio": round(ok / n, 3), "served": served,
                "p50_ms": round(latencies[n // 2], 2)}

    async def run():
        with tempfile.TemporaryDirectory() as td:
            tmp = Path(td)
            (tmp / "providers.json").write_text(PROVIDERS)
            (tmp / "models_fallback_rules.json").write_text(RULES)
            loader = ConfigLoader(tmp, fallback_provider="backup")
            router = Router(loader, Registry(providers),
                            RotationDB(tmp / "rotdb"),
                            fallback_provider="backup",
                            breakers=BreakerRegistry(loader))
            await engine.start()

            # -- steady window: healthy local engine serves everything.
            steady = await probe_window(router, 8)

            # -- victims: streams to be killed mid-decode. Dispatched
            # concurrently — on a tiny pool some queue behind the first;
            # the kill is armed as soon as ONE stream commits, so at
            # least one in-band error frame is guaranteed, and the
            # still-queued victims are failed over (or error-framed)
            # instead of serializing the incident.
            victim_tasks = [
                asyncio.create_task(dispatch(router, stream=True,
                                             max_tokens=64))
                for _ in range(3)]
            while not any(t.done() for t in victim_tasks):
                await asyncio.sleep(0.005)
            await asyncio.sleep(0.02)       # let the stream decode a bit
            # -- the kill: every step from here raises; the supervisor
            # retries (restart #1, #2), burns the budget, parks "failed".
            t_kill = time.monotonic()
            engine.fault_plan = FaultPlan(
                fail_step_after=0, fail_step_msg="bench: injected kill")
            victim_outs = [o for o, _ in
                           await asyncio.gather(*victim_tasks)]
            committed = [o.result.frames for o in victim_outs
                         if o.result is not None
                         and hasattr(o.result, "frames")]
            absorbed = sum(1 for o in victim_outs
                           if o.result is not None and
                           o.provider == "backup")
            error_frame_ms: list = []

            async def watch(frames):
                async for frame in frames:
                    if b'"error"' in frame:
                        error_frame_ms.append(
                            1000.0 * (time.monotonic() - t_kill))
                        return

            await asyncio.wait_for(
                asyncio.gather(*(watch(f) for f in committed)), timeout=30)
            # Age the steady successes out of the breaker window so the
            # incident failure rate is clean.
            await asyncio.sleep(WINDOW_S + 0.1)

            incident = await probe_window(router, 8)
            kill_ms = sorted(error_frame_ms)
            incident["killed_streams"] = len(committed)
            incident["victims_failed_over"] = absorbed
            incident["error_frames"] = len(kill_ms)
            if kill_ms:
                incident["p99_error_frame_ms"] = round(
                    kill_ms[min(len(kill_ms) - 1,
                                int(0.99 * len(kill_ms)))], 2)
            # Goodput over the whole window: killed streams count against
            # it, failed-over victims count for it (the remote absorbed).
            total = incident["requests"] + len(victim_outs)
            incident["goodput_ratio"] = round(
                (incident["ok"] + absorbed) / total, 3)
            incident["engine_state"] = engine.supervisor.state

            # -- recovery: clear the fault, admin-stop the parked engine
            # (failed→stopped re-arms auto-start), let the breaker cool
            # down, then let the half-open probe readmit local serving.
            engine.fault_plan = None
            await engine.stop()
            await asyncio.sleep(COOLDOWN_S + 0.1)
            recovered = await probe_window(router, 6)
            stats = engine.stats()
            await engine.stop()
            return steady, incident, recovered, stats

    steady, incident, recovered, stats = asyncio.run(run())
    return {
        "workload": {"probe_max_tokens": 8, "victims": 3,
                     "victim_max_tokens": 64},
        "breaker": {"min_requests": 2, "window_s": WINDOW_S,
                    "failure_threshold": 0.5, "cooldown_s": COOLDOWN_S},
        "steady": steady,
        "incident": incident,
        "recovered": recovered,
        "remote_calls": remote.calls,
        "supervisor": {
            "restarts_total": stats.get("supervisor_restarts_total"),
            "last_failure_kind": stats.get("supervisor_last_failure_kind"),
            "final_state": stats.get("supervisor_state"),
            "flight_admits": stats.get("flight_admits"),
            "flight_finishes": stats.get("flight_finishes"),
        },
    }


def attention_inmodel_ab(args) -> dict:
    """In-model attention A/B: the full greedy fused-scan decode step with
    the Pallas flash attention vs the jnp reference path, on real
    stacked-layer weights (the bench preset).

    Why not a standalone kernel micro: with a loop-invariant SINGLE-layer
    cache, XLA keeps the jnp path's K/V resident in VMEM across chain
    iterations — something a 22-layer serving model can never do — so a
    micro makes the jnp path look ~10× faster than it can be in serving.
    The serving-relevant number is the whole step, measured as the SLOPE
    between two fused-scan lengths (the per-dispatch fixed cost cancels).
    Kernel numerics are still checked directly against the jnp
    reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from llmapigateway_tpu.models import llama
    from llmapigateway_tpu.models.config import get_preset
    from llmapigateway_tpu.models.llama import dense_decode_attention
    from llmapigateway_tpu.ops import (flash_decode_attention,
                                       make_cache_attention_fn)
    from functools import partial

    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not args.attention:
        return {"attention_bench": "skipped (not on tpu)"}

    # Kernel numerics check (direct, one call).
    B, H, KV, Dh, S = args.batch, 32, 4, 64, args.seq
    rng = np.random.default_rng(2)
    q0 = jnp.asarray(rng.standard_normal((B, H, Dh)), jnp.bfloat16)
    kn = jnp.asarray(rng.standard_normal((B, KV, Dh)), jnp.bfloat16)
    vn = jnp.asarray(rng.standard_normal((B, KV, Dh)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, KV, S, Dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, KV, S, Dh)), jnp.bfloat16)
    ns = jnp.full((B,), min(args.prompt_len + args.steps, S - 3), jnp.int32)
    o_p = np.asarray(flash_decode_attention(
        q0, kn, vn, k, v, ns, interpret=not on_tpu), np.float32)
    o_r = np.asarray(dense_decode_attention(
        q0[:, None], kn[:, None], vn[:, None], k, v, ns)[:, 0], np.float32)
    max_err = float(np.max(np.abs(o_p - o_r)))

    # In-model A/B on the bench preset.
    c = get_preset(args.preset)
    params = jax.jit(partial(llama.init_params, c, dtype=jnp.bfloat16))(
        jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    cache = llama.KVCache.create(c, args.batch, args.seq)
    lengths0 = jnp.full((args.batch,), args.prompt_len, jnp.int32)
    active = jnp.ones((args.batch,), bool)
    tokens0 = jnp.zeros((args.batch,), jnp.int32)

    def chain(attn_fn, iters):
        @jax.jit
        def run(params, cache, tokens, lengths):
            def body(carry, _):
                cache, tokens, lengths = carry
                kwargs = {} if attn_fn is None else {"attention_fn": attn_fn}
                logits, cache = llama.forward(
                    params, c, tokens[:, None], lengths, cache,
                    active=active, **kwargs)
                nt = jnp.argmax(logits[:, 0, :], -1).astype(jnp.int32)
                return (cache, nt, lengths + 1), nt
            (cache, tokens, lengths), toks = jax.lax.scan(
                body, (cache, tokens, lengths), None, length=iters)
            return toks, cache
        return run

    def slope_ms(attn_fn, short=16, long=48):
        f_s, f_l = chain(attn_fn, short), chain(attn_fn, long)
        np.asarray(f_s(params, cache, tokens0, lengths0)[0])
        np.asarray(f_l(params, cache, tokens0, lengths0)[0])
        ts = tl = float("inf")
        for _ in range(3):
            t0 = time.monotonic()
            np.asarray(f_s(params, cache, tokens0, lengths0)[0])
            ts = min(ts, time.monotonic() - t0)
            t0 = time.monotonic()
            np.asarray(f_l(params, cache, tokens0, lengths0)[0])
            tl = min(tl, time.monotonic() - t0)
        return max(tl - ts, 1e-9) / (long - short) * 1e3   # ms/step

    ms_pallas = slope_ms(make_cache_attention_fn(
        interpret=None if on_tpu else True))
    ms_ref = slope_ms(None)
    note(f"in-model step A/B: pallas {ms_pallas:.2f} ms/step vs "
         f"jnp {ms_ref:.2f} ms/step (kernel max_err {max_err:.3f})")
    return {
        "attn_max_abs_err": round(max_err, 4),
        "attn_compiled": on_tpu,
        "step_ms_pallas": round(ms_pallas, 3),
        "step_ms_reference": round(ms_ref, 3),
        "attn_speedup": round(ms_ref / max(ms_pallas, 1e-9), 2),
        "attn_ab_note": "whole greedy decode step (fused scan slope), "
                        "pallas vs jnp attention on real stacked weights",
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--burst", type=int, default=32,
                    help="chained decode steps per host sync")
    ap.add_argument("--kv", default="both",
                    choices=["contiguous", "paged", "both"])
    ap.add_argument("--page-size", type=int, default=256,
                    help="paged-KV page size (also the paged kernel's "
                         "DMA block); 256 = the r5b sweep optimum and the "
                         "engine default; the sweep measures the "
                         "alternate too")
    ap.add_argument("--pages-per-block", type=int, default=1,
                    help="multi-page paged-kernel blocking (contiguous-"
                         "page runs per DMA); the paged phase also sweeps "
                         "2/4 so the default tracks the hardware")
    ap.add_argument("--ppb-sweep", type=int, default=1,
                    help="pages_per_block 2/4 sweep in the paged phase "
                         "(0 disables)")
    ap.add_argument("--skip-ttft", action="store_true")
    ap.add_argument("--ttft-probes", type=int, default=5)
    ap.add_argument("--attention", action="store_true",
                    help="force the attention A/B even off-TPU")
    ap.add_argument("--peak-tflops", type=float, default=197.0,
                    help="chip peak bf16 TFLOP/s for MFU (v5e: 197)")
    ap.add_argument("--peak-gbps", type=float, default=819.0,
                    help="chip HBM GB/s for roofline fraction (v5e: 819)")
    ap.add_argument("--second-preset", default="llama-3b-class",
                    help="mid-size preset for the MFU-vs-width rung "
                         "('' disables)")
    ap.add_argument("--second-steps", type=int, default=96)
    ap.add_argument("--scale-batch", type=int, default=32,
                    help="extra decode rung at this batch size (0 disables)")
    ap.add_argument("--scale-steps", type=int, default=64)
    ap.add_argument("--eight-b", type=int, default=1,
                    help="8B-class fully-int8 north-star rung (0 disables)")
    ap.add_argument("--eight-b-preset", default="llama-3-8b",
                    help="north-star rung preset (smoke tests shrink it)")
    ap.add_argument("--eight-b-batch", type=int, default=32)
    ap.add_argument("--eight-b-seq", type=int, default=512)
    ap.add_argument("--eight-b-steps", type=int, default=96)
    ap.add_argument("--swa", type=int, default=1,
                    help="sliding-window A/B rung: the SWA preset with its "
                         "window vs the same architecture unwindowed "
                         "(0 disables)")
    ap.add_argument("--swa-preset", default="mistral-7b")
    ap.add_argument("--swa-seq", type=int, default=8192)
    ap.add_argument("--swa-prompt", type=int, default=7680)
    ap.add_argument("--swa-batch", type=int, default=4)
    ap.add_argument("--swa-steps", type=int, default=32)
    ap.add_argument("--ttft-target", type=float, default=200.0,
                    help="ttft_target_ms for the self-tuning TTFT rung "
                         "(BASELINE: p50 < 200 ms under load)")
    ap.add_argument("--crossover", type=int, default=1,
                    help="equal-HBM capacity-crossover rung: paged admits "
                         "budget/request slots vs dense's budget/max_seq "
                         "(0 disables)")
    ap.add_argument("--crossover-seq", type=int, default=2048,
                    help="max_seq_len both crossover engines are "
                         "provisioned for (the dense reservation unit)")
    ap.add_argument("--burst-sweep", type=int, default=1,
                    help="decode-burst 16/24 TTFT-vs-throughput sweep "
                         "(0 disables; args.burst itself is phase 1+2)")
    ap.add_argument("--quant-rung", type=int, default=1,
                    help="int8 weight-quant decode rung (0 disables)")
    ap.add_argument("--long-ctx", type=int, default=1,
                    help="long-context bf16-vs-int8-KV rung (0 disables)")
    ap.add_argument("--long-seq", type=int, default=4096)
    ap.add_argument("--long-prompt", type=int, default=2048)
    ap.add_argument("--long-batch", type=int, default=4)
    ap.add_argument("--long-steps", type=int, default=64)
    ap.add_argument("--shared-prefix", type=int, default=1,
                    help="shared-prefix radix-cache rung: warm-vs-cold "
                         "TTFT with a common prompt prefix (0 disables)")
    ap.add_argument("--shared-prefix-len", type=int, default=512,
                    help="common prefix length in tokens (the acceptance "
                         "bar measures >=512)")
    ap.add_argument("--shared-prefix-tail", type=int, default=32,
                    help="unique per-request tail tokens after the prefix")
    ap.add_argument("--shared-prefix-warm", type=int, default=6,
                    help="warm requests measured after the cold one")
    ap.add_argument("--spec-draft", type=int, default=3,
                    help="speculative rung draft length (0 disables)")
    ap.add_argument("--spec-bursts", type=int, default=12)
    ap.add_argument("--spec-ladder", type=int, default=1,
                    help="speculative ladder rung: draft 0/1/3/7 x "
                         "bf16/int8-KV on the paged layout, acceptance + "
                         "tok/s + TTFT per arm, int8 ppb 1/2/4 sweep "
                         "(0 disables; publishes BENCH_SPEC_r10)")
    ap.add_argument("--spec-mixed", type=int, default=1,
                    help="mixed-traffic spec rung: gated-spec vs normal on "
                         "random prompts through the scheduler (0 disables)")
    ap.add_argument("--spec-mixed-tokens", type=int, default=120,
                    help="tokens per request in the mixed-traffic rung")
    ap.add_argument("--flight-ab", type=int, default=1,
                    help="flight-recorder overhead A/B through the real "
                         "scheduler: tok/s with recording on vs off "
                         "(0 disables; acceptance bar is <=2%% delta)")
    ap.add_argument("--flight-ab-tokens", type=int, default=96,
                    help="decode tokens per request per A/B arm run")
    ap.add_argument("--flight-ab-repeats", type=int, default=3,
                    help="alternating runs per arm (best-of compared)")
    ap.add_argument("--annot-ab", type=int, default=1,
                    help="phase-annotation overhead A/B through the real "
                         "scheduler: tok/s with TraceAnnotation markers "
                         "on vs off (0 disables; acceptance bar is <=1%% "
                         "delta on decode)")
    ap.add_argument("--annot-ab-tokens", type=int, default=96,
                    help="decode tokens per request per annotation A/B "
                         "arm run")
    ap.add_argument("--annot-ab-repeats", type=int, default=3,
                    help="alternating annotation-A/B runs per arm")
    ap.add_argument("--disagg-ab", type=int, default=1,
                    help="disaggregation A/B through the real scheduler: "
                         "two-pool (prefill/decode) vs unified on a mixed "
                         "prefill-heavy/decode-heavy workload, with "
                         "per-pool SLO goodput per arm (0 disables; "
                         "publishes BENCH_DISAGG_r13)")
    ap.add_argument("--disagg-ab-tokens", type=int, default=48,
                    help="decode tokens per decode-heavy request in the "
                         "disaggregation A/B workload")
    ap.add_argument("--disagg-ab-repeats", type=int, default=3,
                    help="alternating disagg-A/B paired rounds per arm")
    ap.add_argument("--failover-ab", type=int, default=1,
                    help="engine-supervision failover A/B through the "
                         "real router+breaker: scripted mid-run engine "
                         "kill, goodput per steady/incident/recovered "
                         "window + p99 kill-to-error-frame latency "
                         "(0 disables; publishes BENCH_FAILOVER_r14)")
    ap.add_argument("--ttft-probe-child", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--max-seconds", type=float, default=1200.0,
                    help="soft deadline: optional phases are skipped once "
                         "elapsed time passes this, so the one-line JSON "
                         "always lands inside a driver timeout (phases are "
                         "ordered highest-value first: headline+TTFT, "
                         "paged, quant rungs, then the rest)")
    ap.add_argument("--hard-timeout", type=float, default=1600.0,
                    help="watchdog: force-emit partial results and exit if "
                         "a device call hangs mid-phase")
    args = ap.parse_args()

    if args.ttft_probe_child:
        # Subprocess arm of ttft_harness_probe(): run the TTFT harness
        # sequence on a tiny config and report liveness. No watchdog —
        # the parent owns timeouts and reads our rc.
        sys.exit(ttft_probe_child(args))

    _start_watchdog(args.hard_timeout)
    RESULT["metric"] = (f"decode_tok_s_chip ({args.preset}, bs={args.batch}, "
                        f"ctx={args.prompt_len}+{args.steps})")
    extra = RESULT["extra"]
    import jax
    dev = jax.devices()[0]
    extra["device"] = str(dev)
    extra["platform"] = dev.platform
    extra["device_kind"] = dev.device_kind
    extra["device_count"] = len(jax.devices())

    # -- phase 1+2: contiguous engine — headline decode + TTFT ---------------
    value = 0.0
    contig_bf16_tok_s = 0.0
    errors = []
    engine = None
    if args.kv in ("contiguous", "both"):
        try:
            engine, extra["engine_init_s"] = build_engine(args, "contiguous")
            r = fill_and_time_decode(engine, args)
            value = r.pop("tok_s")
            contig_bf16_tok_s = value      # quant rung's like-for-like baseline
            RESULT["value"] = value
            RESULT["vs_baseline"] = round(value / 2000.0, 3)
            extra.update(r)
        except Exception as e:
            errors.append(f"contiguous: {e!r}")
            note(f"FAILED contiguous phase: {e!r}")

    if engine is not None and not args.skip_ttft:
        try:
            extra.update(run_ttft_arm(engine, args, "main"))
        except Exception as e:
            errors.append(f"ttft: {e!r}")
            note(f"FAILED ttft phase: {e!r}")
    if engine is not None:
        del engine

    def over_budget(phase: str) -> bool:
        if time.monotonic() - T0 <= args.max_seconds:
            return False
        note(f"soft deadline {args.max_seconds:.0f}s passed — skipping "
             f"{phase}")
        extra.setdefault("skipped_phases", []).append(phase)
        return True

    def eight_b_args(b8: int) -> argparse.Namespace:
        """The ONE copy of the 8B rung shape — every 8B leg (int8 headline,
        int4, paged) must measure the identical geometry or the reported
        ratios are meaningless."""
        bargs = argparse.Namespace(**vars(args))
        bargs.seq = args.eight_b_seq
        bargs.prompt_len = min(args.prompt_len, 128)
        bargs.batch = b8
        return bargs

    # -- phase 2b: the NORTH STAR — 8B-class fully-int8 on one chip ----------
    # BASELINE.md targets ≥2000 decode tok/s/chip at 7-8B. Llama-3-8B bf16
    # (~16 GB) cannot fit one v5e's HBM, but this framework's int8 weights
    # (~8 GB) + int8 KV do — so THIS rung, not an extrapolation from the
    # 1.1B headline, is the target-scale evidence (VERDICT r3 item 1).
    # Decode at this scale is weight-bandwidth-bound: ~8.05 GB/step at the
    # measured 724 GB/s floor ≈ 11 ms/step, so the 2k target needs the
    # batch=32 shape (tok/s = B/step).
    if args.eight_b and not over_budget("headline_8b"):
        # Batch fallback ladder: losing the whole north-star rung to one
        # RESOURCE_EXHAUSTED would be the worst outcome of a driver run —
        # ~13 GB peak (8 GB int8 weights + bf16-init transient + KV) is
        # expected to fit a 16 GB v5e at bs=32, but if it doesn't, a
        # bs=16 number is far better evidence than an error string.
        for b8 in dict.fromkeys([args.eight_b_batch,
                                 max(1, args.eight_b_batch // 2)]):
            try:
                engine = None
                bargs = eight_b_args(b8)
                engine, init_s = build_engine(
                    bargs, "contiguous", preset=args.eight_b_preset,
                    batch=b8, quant="int8", kv_quant="int8")
                r = fill_and_time_decode(engine, bargs,
                                         steps=args.eight_b_steps)
                r8 = {
                    "preset": args.eight_b_preset, "quant": "int8",
                    "kv_quant": "int8",
                    "batch": b8, "init_s": init_s, **r,
                    "vs_baseline_2k": round(r["tok_s"] / 2000.0, 3),
                }
                if not args.skip_ttft:
                    r8.update(run_ttft_arm(engine, bargs, "headline_8b"))
                extra["headline_8b"] = r8
                note(f"8B north star: {r['tok_s']} tok/s at bs={b8} "
                     f"({r8['vs_baseline_2k']}x the 2k target)")
                break
            except Exception as e:
                errors.append(f"headline_8b(bs={b8}): {e!r}")
                note(f"FAILED 8B phase at bs={b8}: {e!r}")
                oom = "RESOURCE_EXHAUSTED" in str(e) or "memory" in \
                    str(e).lower()
                if not oom:
                    break               # non-OOM errors won't heal at bs/2
            finally:
                engine = None
        # bs-2x scale leg: decode at 8B is weight-bandwidth-bound, so
        # tok/s = B / step_ms and the weight stream per step is a FIXED
        # ~8 GB — doubling the batch nearly doubles tok/s for +~1 GB of
        # int8 KV (measured r5b: bs=32 ran 23.0 ms/step at 392 GB/s,
        # only 0.478 of HBM peak; more rows per step is the cheapest
        # path to the 2k target while the bandwidth gap is worked).
        if "headline_8b" in extra \
                and extra["headline_8b"]["batch"] == args.eight_b_batch \
                and not over_budget("headline_8b_bs2x"):
            # (batch == configured: if the headline took the OOM fallback
            # to batch/2, doubling it would rebuild the exact config that
            # just exhausted HBM.)
            b2 = 2 * extra["headline_8b"]["batch"]
            try:
                engine = None
                bargs = eight_b_args(b2)
                engine, _ = build_engine(
                    bargs, "contiguous", preset=args.eight_b_preset,
                    batch=b2, quant="int8", kv_quant="int8")
                r = fill_and_time_decode(engine, bargs,
                                         steps=args.eight_b_steps)
                extra["headline_8b"]["bs2x_batch"] = b2
                extra["headline_8b"]["bs2x_tok_s"] = r["tok_s"]
                extra["headline_8b"]["bs2x_ms_per_step"] = \
                    r["ms_per_decode_step"]
                extra["headline_8b"]["bs2x_vs_target_2k"] = round(
                    r["tok_s"] / 2000.0, 3)
                note(f"8B north star bs={b2}: {r['tok_s']} tok/s "
                     f"({extra['headline_8b']['bs2x_vs_target_2k']}x the "
                     f"2k target)")
            except Exception as e:
                errors.append(f"headline_8b_bs2x(bs={b2}): {e!r}")
                note(f"FAILED 8B bs2x phase: {e!r}")
            finally:
                engine = None
        # Adaptive-TTFT leg: the target-scale engine with ttft_target_ms
        # driving the burst-depth controller, measured through the REAL
        # scheduler — at 23 ms/step a fixed deep burst holds probes for
        # ~740 ms (r5b measured), so target-scale TTFT stands or falls
        # on this controller. Ships the controller's own diagnostics
        # (fitted slope, fixed cost, depth histogram) so a miss is a
        # reading, not a mystery.
        if "headline_8b" in extra and not over_budget("headline_8b_ttft") \
                and not args.skip_ttft:
            try:
                engine = None
                b8 = extra["headline_8b"]["batch"]
                bargs = eight_b_args(b8)
                engine, _ = build_engine(
                    bargs, "contiguous", preset=args.eight_b_preset,
                    batch=b8, quant="int8", kv_quant="int8",
                    ttft_target=args.ttft_target)
                # Compile every burst-depth rung BEFORE measuring: the
                # adaptive controller wanders depths, and a mid-probe
                # 10-20 s XLA compile would be recorded as that probe's
                # TTFT (AOT from avals; hits the persistent cache).
                engine._warm_decode_variants()
                sched_tok_s = scheduler_throughput(engine, bargs)
                t = run_ttft_arm(engine, bargs, "headline_8b_adaptive")
                diag = {k: v for k, v in engine.stats().items()
                        if k.startswith(("burst_", "queue_wait",
                                         "achieved_gbps",
                                         "roofline_fraction",
                                         "hbm_bytes_per_step"))}
                extra["headline_8b"]["ttft_adaptive"] = {
                    "target_ms": args.ttft_target,
                    "scheduler_tok_s": round(sched_tok_s, 1), **t, **diag}
                if "ttft_p50_ms" in t:
                    note(f"8B ttft_adaptive: p50 {t['ttft_p50_ms']} ms, "
                         f"{sched_tok_s:.1f} tok/s "
                         f"(target {args.ttft_target})")
            except Exception as e:
                errors.append(f"headline_8b_ttft: {e!r}")
                note(f"FAILED 8B ttft phase: {e!r}")
            finally:
                engine = None
        # int4 leg: the same 8B shape with 4-bit layer weights — if the
        # packed-int4 HBM layout delivers, this is the fastest
        # single-chip configuration in the ladder (~5.5 GB/step vs int8's
        # ~9 GB). Reported beside the int8 number, which stays the
        # headline (int4's quality cost is opt-in).
        if "headline_8b" in extra and not over_budget("headline_8b_int4"):
            try:
                engine = None
                b8 = extra["headline_8b"]["batch"]
                bargs = eight_b_args(b8)
                engine, _ = build_engine(
                    bargs, "contiguous", preset=args.eight_b_preset,
                    batch=b8, quant="int4", kv_quant="int8")
                r = fill_and_time_decode(engine, bargs,
                                         steps=args.eight_b_steps)
                extra["headline_8b"]["int4_tok_s"] = r["tok_s"]
                extra["headline_8b"]["int4_vs_int8"] = round(
                    r["tok_s"] / extra["headline_8b"]["tok_s"], 3)
                extra["headline_8b"]["int4_vs_target_2k"] = round(
                    r["tok_s"] / 2000.0, 3)
                note(f"8B north star INT4: {r['tok_s']} tok/s "
                     f"({extra['headline_8b']['int4_vs_int8']}x int8)")
            except Exception as e:
                errors.append(f"headline_8b_int4: {e!r}")
                note(f"FAILED 8B int4 phase: {e!r}")
            finally:
                engine = None
        # BASELINE config 3 — the headline — specifies PAGED KV: run the
        # same fully-int8 shape from the page pool so the target-scale
        # number exists for the configured layout too (VERDICT r4 item 3:
        # a headline config must not silently document a paged tax).
        if "headline_8b" in extra and not over_budget("headline_8b_paged"):
            try:
                engine = None
                b8 = extra["headline_8b"]["batch"]
                bargs = eight_b_args(b8)
                engine, _ = build_engine(
                    bargs, "paged", preset=args.eight_b_preset,
                    batch=b8, quant="int8", kv_quant="int8")
                r = fill_and_time_decode(engine, bargs,
                                         steps=args.eight_b_steps)
                extra["headline_8b"]["paged_tok_s"] = r["tok_s"]
                extra["headline_8b"]["paged_page_size"] = args.page_size
                extra["headline_8b"]["paged_vs_contiguous"] = round(
                    r["tok_s"] / extra["headline_8b"]["tok_s"], 3)
                note(f"8B north star PAGED: {r['tok_s']} tok/s "
                     f"({extra['headline_8b']['paged_vs_contiguous']}x "
                     f"contiguous)")
            except Exception as e:
                errors.append(f"headline_8b_paged: {e!r}")
                note(f"FAILED 8B paged phase: {e!r}")
            finally:
                engine = None

    # -- phase 3: paged engine decode ----------------------------------------
    if args.kv in ("paged", "both"):
        # Page-size sweep (VERDICT r3 item 2): the paged kernel's DMA block
        # IS the page, and the dense kernel's 256-block optimum measurably
        # does NOT transfer (r3: 1500.5 tok/s @128 vs 1322.3 @256) — so the
        # configured size runs first, the alternate second, and the winner
        # is reported so the default can track the hardware, not a guess.
        sweep = {}
        for psize in dict.fromkeys([args.page_size,
                                    128 if args.page_size != 128 else 256]):
            if sweep and over_budget(f"paged_p{psize}"):
                break
            try:
                engine = None      # free any prior engine BEFORE building
                pargs = argparse.Namespace(**vars(args))
                pargs.page_size = psize
                engine, init_s = build_engine(pargs, "paged")
                if "paged_init_s" not in extra:
                    extra["paged_init_s"] = init_s
                r = fill_and_time_decode(engine, pargs)
                sweep[str(psize)] = r["tok_s"]
                if str(args.page_size) == str(psize):
                    extra["paged_tok_s"] = r["tok_s"]
                    extra["paged_ms_per_decode_step"] = r["ms_per_decode_step"]
                    extra["paged_page_size"] = psize
                    if args.kv == "paged" or value == 0.0:
                        value = r["tok_s"]
                del engine
            except Exception as e:
                errors.append(f"paged_p{psize}: {e!r}")
                note(f"FAILED paged phase (page {psize}): {e!r}")
        if sweep:
            best_p = max(sweep, key=sweep.get)
            extra["paged_sweep"] = {**sweep, "best_page_size": int(best_p),
                                    "best_tok_s": sweep[best_p]}
            if contig_bf16_tok_s:
                extra["paged_sweep"]["vs_contiguous"] = round(
                    sweep[best_p] / contig_bf16_tok_s, 3)
        # Multi-page blocking sweep (ISSUE 2 tentpole): same paged shape
        # at pages_per_block 2/4 — each step's HBM→VMEM DMA is ppb×
        # larger and the kernel grid ppb× smaller, numerics unchanged
        # (bit-for-bit vs per-page; tests/test_ops_paged_multipage.py).
        # Reported next to ppb=1 so the DMA-size lever is a measured
        # number on this chip, not a guess.
        if args.ppb_sweep and sweep:
            ppb_sweep = {"1": extra.get("paged_tok_s") or sweep.get(
                str(args.page_size), 0.0)}
            for ppb in (2, 4):
                if over_budget(f"paged_ppb{ppb}"):
                    break
                try:
                    engine = None
                    engine, _ = build_engine(args, "paged",
                                             pages_per_block=ppb)
                    if engine.kv_ppb != ppb:
                        ppb_sweep[str(ppb)] = "fallback (can't pack)"
                        continue
                    r = fill_and_time_decode(engine, args)
                    ppb_sweep[str(ppb)] = r["tok_s"]
                    del engine
                except Exception as e:
                    errors.append(f"paged_ppb{ppb}: {e!r}")
                    note(f"FAILED paged ppb={ppb} phase: {e!r}")
            numeric = {k: v for k, v in ppb_sweep.items()
                       if isinstance(v, float)}
            if numeric:
                best = max(numeric, key=numeric.get)
                ppb_sweep["best_pages_per_block"] = int(best)
                ppb_sweep["best_tok_s"] = numeric[best]
            extra["paged_ppb_sweep"] = ppb_sweep

    # -- phase 3a: shared-prefix radix-cache rung (ISSUE 6) ------------------
    # Warm-vs-cold TTFT with a common >=512-token prefix: the acceptance
    # bar is >=5x lower warm TTFT p50 with the skipped prefill PROVEN from
    # engine stats (cached-token totals + prefill dispatch counts).
    if args.shared_prefix and not over_budget("shared_prefix"):
        try:
            r = shared_prefix_rung(args)
            extra["shared_prefix"] = r
            note(f"shared-prefix: cold TTFT {r['cold_ttft_ms']} ms -> warm "
                 f"p50 {r['warm_ttft_p50_ms']} ms ({r['ttft_speedup']}x, "
                 f"{r['prefix_cached_tokens_total']} tokens served from "
                 f"cache)")
        except Exception as e:
            errors.append(f"shared_prefix: {e!r}")
            note(f"FAILED shared-prefix phase: {e!r}")

    # -- phase 3b: capacity crossover — paged vs dense at EQUAL KV HBM -------
    # BASELINE config 3's real argument for paged KV (VERDICT r4 item 3): a
    # dense engine must RESERVE max_seq_len contiguous tokens per slot, so
    # at a fixed KV byte budget its concurrency is budget/max_seq_len; the
    # paged pool reserves only each request's actual footprint rounded up
    # to pages, so the SAME bytes admit budget/request_pages slots. Decode
    # reads every weight byte once per STEP regardless of batch, so the
    # extra slots convert the same HBM into more total tok/s — even if the
    # per-step paged kernel carries an indirection tax.
    if args.kv == "both" and args.crossover and not over_budget("crossover"):
        x_seq = args.crossover_seq        # the context the service supports
        budget_tokens = args.batch * x_seq
        _, req_tokens = decode_footprint(args.prompt_len, args.steps,
                                         args.warmup, args.burst)
        pages_per_req = -(-req_tokens // args.page_size)
        n_pages = budget_tokens // args.page_size          # equal bytes
        raw = (n_pages - 1) // pages_per_req     # -1: the trash page
        b_paged = min(raw - raw % 8 if raw >= 8 else raw, 64)
        xr = {"kv_budget_tokens": budget_tokens, "max_seq_len": x_seq,
              "request_tokens": req_tokens, "page_size": args.page_size,
              "dense_slots": args.batch, "paged_slots": b_paged}
        if req_tokens > x_seq or b_paged < 1:
            xr["skipped"] = "request footprint >= provisioned context"
        else:
            try:
                xeng, _ = build_engine(args, "contiguous", seq=x_seq)
                xr["dense_tok_s"] = fill_and_time_decode(xeng, args)["tok_s"]
                del xeng
                xeng, _ = build_engine(args, "paged", batch=b_paged,
                                       seq=x_seq, num_pages=n_pages)
                xr["paged_tok_s"] = fill_and_time_decode(xeng, args)["tok_s"]
                del xeng
                xr["paged_vs_dense"] = round(
                    xr["paged_tok_s"] / xr["dense_tok_s"], 3)
            except Exception as e:
                errors.append(f"crossover: {e!r}")
                note(f"FAILED capacity-crossover phase: {e!r}")
        extra["capacity_crossover"] = xr

    # -- phase 4d: int8 weight-quantization rung -----------------------------
    # Same shape as the headline; decode is weight-bandwidth-bound, so int8
    # weights should land near 2× the bf16 tok/s (models/quant.py). Reported
    # alongside (not as) the headline `value` so r2→r3 numbers stay
    # comparable; MFU/GB/s here use the int8 byte footprint.
    if args.quant_rung and not over_budget("quant_int8"):
        try:
            engine = None
            engine, init_s = build_engine(args, "contiguous", quant="int8")
            r = fill_and_time_decode(engine, args)
            extra["quant_int8"] = {
                "tok_s": r["tok_s"],
                "ms_per_decode_step": r["ms_per_decode_step"],
                "mfu": r["mfu"], "hbm_gbps": r["hbm_gbps"],
                "roofline_fraction": r["roofline_fraction"],
                "init_s": init_s,
                # Ratio only against the same-layout (contiguous) bf16
                # number — under --kv paged there is no like-for-like base.
                "speedup_vs_bf16": (round(r["tok_s"] / contig_bf16_tok_s, 2)
                                    if contig_bf16_tok_s else None),
            }
            sp = extra["quant_int8"]["speedup_vs_bf16"]
            note(f"quant int8: {r['tok_s']} tok/s"
                 + (f" ({sp}x bf16)" if sp else ""))
            del engine
        except Exception as e:
            errors.append(f"quant: {e!r}")
            note(f"FAILED quant phase: {e!r}")

    # -- phase 4e: fully-quantized rung (int8 weights + int8 KV cache) -------
    if args.quant_rung and not over_budget("quant_int8_kv8"):
        try:
            engine = None
            engine, init_s = build_engine(args, "contiguous", quant="int8",
                                          kv_quant="int8")
            r = fill_and_time_decode(engine, args)
            extra["quant_int8_kv8"] = {
                "tok_s": r["tok_s"],
                "ms_per_decode_step": r["ms_per_decode_step"],
                "mfu": r["mfu"], "hbm_gbps": r["hbm_gbps"],
                "init_s": init_s,
                "speedup_vs_bf16": (round(r["tok_s"] / contig_bf16_tok_s, 2)
                                    if contig_bf16_tok_s else None),
            }
            note(f"quant int8+kv8: {r['tok_s']} tok/s")
            del engine
        except Exception as e:
            errors.append(f"quant_kv: {e!r}")
            note(f"FAILED quant_kv phase: {e!r}")

    # -- phase 4e2: int4 weight rung (W4A8; models/quant.py weight_bits) -----
    # Layer matmuls at 4-bit (lm_head stays int8) cut the per-step weight
    # stream ~45% past int8 — the question this rung answers is whether
    # XLA's packed-int4 HBM layout converts those bytes into tok/s, or the
    # mixed s8×s4 dot materializes an upcast and gives it back.
    if args.quant_rung and not over_budget("quant_int4"):
        try:
            engine = None
            engine, init_s = build_engine(args, "contiguous", quant="int4",
                                          kv_quant="int8")
            r = fill_and_time_decode(engine, args)
            extra["quant_int4_kv8"] = {
                "tok_s": r["tok_s"],
                "ms_per_decode_step": r["ms_per_decode_step"],
                "mfu": r["mfu"], "hbm_gbps": r["hbm_gbps"],
                "init_s": init_s,
                "speedup_vs_bf16": (round(r["tok_s"] / contig_bf16_tok_s, 2)
                                    if contig_bf16_tok_s else None),
            }
            i8 = extra.get("quant_int8_kv8", {}).get("tok_s")
            if i8:
                extra["quant_int4_kv8"]["speedup_vs_int8"] = round(
                    r["tok_s"] / i8, 2)
            note(f"quant int4+kv8: {r['tok_s']} tok/s")
            del engine
        except Exception as e:
            errors.append(f"quant_int4: {e!r}")
            note(f"FAILED quant_int4 phase: {e!r}")

    # -- phase 4g: decode-burst sweep — TTFT vs throughput (VERDICT item 3) --
    # On one chip a probe's TTFT is bounded by the decode burst already in
    # flight (a dispatched scan can't be preempted), so p50 falls roughly
    # linearly with burst depth; the question is what shallower bursts cost
    # in steady-state tok/s (lag-one pipelining should hide most of the
    # extra host syncs). args.burst (32) is measured by phases 1+2; this
    # sweeps the alternates so the default can be set where TTFT p50 <200 ms
    # at ≤10% throughput cost.
    if args.burst_sweep and not args.skip_ttft:
        bs_out = {}
        for b in (16, 24):
            if b == args.burst or over_budget(f"burst_{b}"):
                continue
            try:
                engine = None
                engine, _ = build_engine(args, "contiguous", burst=b)
                r = fill_and_time_decode(engine, args, steps=max(64, 2 * b))
                t = run_ttft_arm(engine, args, f"burst_{b}")
                bs_out[str(b)] = {"tok_s": r["tok_s"], **t}
                if "ttft_p50_ms" in t:
                    note(f"burst {b}: {r['tok_s']} tok/s, "
                         f"ttft p50 {t['ttft_p50_ms']} ms")
                del engine
            except Exception as e:
                errors.append(f"burst_{b}: {e!r}")
                note(f"FAILED burst-sweep phase ({b}): {e!r}")
        if bs_out:
            # The default burst's row comes from phases 1+2 — only real
            # numbers (a skipped/failed contiguous phase must not plant a
            # 0.0-tok/s row as the default's "measurement").
            if contig_bf16_tok_s and extra.get("ttft_p50_ms") is not None:
                bs_out[str(args.burst)] = {
                    "tok_s": contig_bf16_tok_s,
                    "ttft_p50_ms": extra.get("ttft_p50_ms"),
                    "ttft_p95_ms": extra.get("ttft_p95_ms")}
            extra["burst_sweep"] = bs_out

    # -- phase 4g2: TTFT self-tuning rung (ttft_target_ms) -------------------
    # The engine caps its idle-queue deep burst from its OWN step-time
    # gauge so in-flight exposure spends at most half the target
    # (engine._burst_depth). Measured through the real scheduler — the
    # fill_and_time path calls _decode_burst directly and would bypass
    # the adaptive depth entirely.
    if not args.skip_ttft and not over_budget("ttft_adaptive"):
        try:
            engine = None
            engine, _ = build_engine(args, "contiguous",
                                     ttft_target=args.ttft_target)
            engine._warm_decode_variants()      # all depth rungs, AOT
            sched_tok_s = scheduler_throughput(engine, args)
            t = run_ttft_arm(engine, args, "ttft_adaptive")
            diag = {k: v for k, v in engine.stats().items()
                    if k.startswith("burst_")}
            extra["ttft_adaptive"] = {
                "target_ms": args.ttft_target,
                "scheduler_tok_s": round(sched_tok_s, 1), **t, **diag}
            if "ttft_p50_ms" in t:
                note(f"ttft_adaptive: p50 {t['ttft_p50_ms']} ms, "
                     f"{sched_tok_s:.1f} tok/s "
                     f"(target {args.ttft_target} ms)")
            del engine
        except Exception as e:
            errors.append(f"ttft_adaptive: {e!r}")
            note(f"FAILED ttft_adaptive phase: {e!r}")

    # -- phase 4f: long-context rung (bf16 KV vs int8 KV) --------------------
    # At ctx ~2k+ the live KV bytes rival the weight bytes, so this is the
    # regime where kv_quant's bandwidth halving shows up as tok/s (at the
    # headline's ctx≈330 the KV term is ~3% of traffic and invisible).
    if args.long_ctx and not over_budget("long_ctx"):
        try:
            largs = argparse.Namespace(**vars(args))
            largs.seq, largs.prompt_len, largs.batch = (
                args.long_seq, args.long_prompt, args.long_batch)
            # The preset's max_seq_len (tinyllama: 2048) would clamp
            # engine.S below prompt+decode at these shapes; random-weight
            # perf doesn't care about trained RoPE range, so lift it.
            from llmapigateway_tpu.models.config import get_preset
            lmc = dataclasses.replace(get_preset(args.preset),
                                      max_seq_len=args.long_seq)
            lc = {}
            engine = None
            for label, kvq in (("bf16", ""), ("kv8", "int8")):
                engine = None
                engine, _ = build_engine(largs, "contiguous", kv_quant=kvq,
                                         model_cfg=lmc)
                r = fill_and_time_decode(engine, largs,
                                         steps=args.long_steps)
                lc[label] = {"tok_s": r["tok_s"],
                             "ms_per_decode_step": r["ms_per_decode_step"],
                             "hbm_gbps": r["hbm_gbps"]}
                del engine
            lc["shape"] = (f"bs={args.long_batch} "
                           f"ctx={args.long_prompt}+{args.long_steps}")
            lc["kv8_speedup"] = round(
                lc["kv8"]["tok_s"] / lc["bf16"]["tok_s"], 2)
            extra["long_ctx"] = lc
            note(f"long-ctx {lc['shape']}: bf16 {lc['bf16']['tok_s']} vs "
                 f"kv8 {lc['kv8']['tok_s']} tok/s "
                 f"({lc['kv8_speedup']}x)")
        except Exception as e:
            errors.append(f"long_ctx: {e!r}")
            note(f"FAILED long-ctx phase: {e!r}")

    # -- phase 4f2: sliding-window rung — SWA pays, measured -----------------
    # Mistral-family decode reads O(window) cache bytes via the windowed
    # kernels (flash AND paged); this A/Bs the SAME architecture at the
    # same long-context shape with the window on (preset) vs off
    # (sliding_window=0 — plain full attention), isolating the window's
    # KV-traffic cut from everything else. int8+kv8 so the 7B preset fits
    # one chip at the context where the window matters.
    if args.swa and not over_budget("swa"):
        try:
            from llmapigateway_tpu.models.config import get_preset
            sargs = argparse.Namespace(**vars(args))
            sargs.seq, sargs.prompt_len, sargs.batch = (
                args.swa_seq, args.swa_prompt, args.swa_batch)
            mc = get_preset(args.swa_preset)
            sw = {}
            engine = None
            for label, window in (("windowed", mc.sliding_window),
                                  ("full", 0)):
                engine = None
                mcv = dataclasses.replace(
                    mc, sliding_window=window,
                    max_seq_len=max(mc.max_seq_len, args.swa_seq))
                engine, _ = build_engine(sargs, "contiguous",
                                         preset=args.swa_preset,
                                         quant="int8", kv_quant="int8",
                                         model_cfg=mcv)
                r = fill_and_time_decode(engine, sargs,
                                         steps=args.swa_steps)
                sw[label] = {"tok_s": r["tok_s"],
                             "ms_per_decode_step": r["ms_per_decode_step"]}
                del engine
            sw["shape"] = (f"{args.swa_preset} int8+kv8 bs={args.swa_batch} "
                           f"ctx={args.swa_prompt}+{args.swa_steps} "
                           f"window={mc.sliding_window}")
            sw["window_speedup"] = round(
                sw["windowed"]["tok_s"] / sw["full"]["tok_s"], 2)
            extra["swa"] = sw
            note(f"SWA {sw['shape']}: windowed {sw['windowed']['tok_s']} "
                 f"vs full {sw['full']['tok_s']} tok/s "
                 f"({sw['window_speedup']}x)")
        except Exception as e:
            errors.append(f"swa: {e!r}")
            note(f"FAILED SWA phase: {e!r}")
        finally:
            engine = None           # a failed leg must not hold 7B of HBM

    # -- phase 4: mid-size preset (MFU-vs-width rung) ------------------------
    if args.second_preset and not over_budget("second_preset"):
        try:
            engine = None
            engine, init_s = build_engine(args, "contiguous",
                                          preset=args.second_preset)
            r = fill_and_time_decode(engine, args, steps=args.second_steps)
            r["preset"] = args.second_preset
            r["init_s"] = init_s
            extra["second_preset"] = r
            del engine
        except Exception as e:
            errors.append(f"second_preset: {e!r}")
            note(f"FAILED second-preset phase: {e!r}")

    # -- phase 4b: batch-scaling rung (same model, bs=32) --------------------
    if (args.scale_batch and args.scale_batch != args.batch
            and not over_budget("batch_scale")):
        try:
            engine = None
            engine, init_s = build_engine(args, "contiguous",
                                          batch=args.scale_batch)
            r = fill_and_time_decode(engine, args, steps=args.scale_steps)
            extra["batch_scale"] = {
                "batch": args.scale_batch, "tok_s": r["tok_s"],
                "ms_per_decode_step": r["ms_per_decode_step"],
                "mfu": r["mfu"], "hbm_gbps": r["hbm_gbps"]}
            del engine
        except Exception as e:
            errors.append(f"batch_scale: {e!r}")
            note(f"FAILED batch-scale phase: {e!r}")

    # -- phase 4c: speculative decoding rung ---------------------------------
    if args.spec_draft and not over_budget("speculative"):
        try:
            import numpy as np
            from llmapigateway_tpu.config.schemas import LocalEngineConfig
            from llmapigateway_tpu.engine.engine import InferenceEngine
            cfg = LocalEngineConfig(
                preset=args.preset, dtype="bfloat16",
                max_batch_size=args.batch, max_seq_len=args.seq,
                prefill_chunk=min(512, args.prompt_len),
                decode_burst=args.burst, spec_draft_len=args.spec_draft,
                prewarm_sampler_variants=False)
            engine = None
            engine = InferenceEngine(cfg)
            # Repetitive prompts — the regime speculation exists for (the
            # headline `value` stays the honest non-speculative number).
            rng = np.random.default_rng(5)
            base = rng.integers(0, engine.model_cfg.vocab_size, 16)
            prompt = np.tile(base, args.prompt_len // 16 + 1)[
                :args.prompt_len].astype(np.int32)
            for slot in range(engine.B):
                first, engine.cache = engine._exec_prefill(slot, 0, prompt)
                engine.lengths[slot] = len(prompt)
                engine.active[slot] = True
                engine.last_token[slot] = int(base[0])
                engine.hist[slot, :len(prompt)] = prompt
            np.asarray(first)
            engine._d_dirty = True
            engine._spec_burst(engine._spec_scan_len)       # compile+warm
            t0 = time.monotonic()
            toks = 0
            for _ in range(args.spec_bursts):
                rows = engine._spec_burst(engine._spec_scan_len)
                toks += int(sum((r >= 0).sum() for r in rows))
            dt = time.monotonic() - t0
            extra["speculative"] = {
                "draft_len": args.spec_draft,
                "tokens_per_step": round(
                    engine._spec_tokens_out / max(1, engine._spec_steps_done),
                    2),
                "tok_s": round(toks / dt, 1),
                "note": "repetitive-text regime; headline value is "
                        "non-speculative",
            }
            note(f"speculative: {extra['speculative']['tok_s']} tok/s at "
                 f"{extra['speculative']['tokens_per_step']} accepted "
                 f"tokens/step (draft {args.spec_draft})")
            del engine
        except Exception as e:
            errors.append(f"speculative: {e!r}")
            note(f"FAILED speculative phase: {e!r}")

    # -- phase 4h: mixed-traffic speculative rung ----------------------------
    # VERDICT r3 item 5's "doesn't regress" leg: NON-repetitive prompts
    # through the real scheduler, spec-enabled-with-adaptive-gate vs
    # spec-off. The gate should fall back to normal bursts after the first
    # measured burst, so the ratio should sit near 1.0.
    if args.spec_draft and args.spec_mixed and not over_budget("spec_mixed"):
        try:
            engine = None
            engine, _ = build_engine(args, "contiguous")
            base_tok_s = scheduler_throughput(engine, args,
                                              n_tokens=args.spec_mixed_tokens)
            del engine
            engine = None
            from llmapigateway_tpu.config.schemas import LocalEngineConfig
            from llmapigateway_tpu.engine.engine import InferenceEngine
            cfg = LocalEngineConfig(
                preset=args.preset, dtype="bfloat16",
                max_batch_size=args.batch, max_seq_len=args.seq,
                prefill_chunk=min(512, args.prompt_len),
                decode_burst=args.burst, spec_draft_len=args.spec_draft,
                prewarm_sampler_variants=False)
            engine = InferenceEngine(cfg)
            spec_tok_s = scheduler_throughput(engine, args,
                                              n_tokens=args.spec_mixed_tokens)
            stats = engine.stats()
            extra["spec_mixed"] = {
                "normal_tok_s": round(base_tok_s, 1),
                "spec_gated_tok_s": round(spec_tok_s, 1),
                "ratio": round(spec_tok_s / base_tok_s, 3),
                "gate_open": stats.get("spec_gate_open"),
                "ema_tokens_per_step": stats.get(
                    "spec_ema_tokens_per_step"),
                "note": "random prompts; adaptive gate should disable "
                        "drafting, ratio ≈ 1.0",
            }
            note(f"spec mixed-traffic: {spec_tok_s:.1f} vs "
                 f"{base_tok_s:.1f} tok/s "
                 f"(ratio {extra['spec_mixed']['ratio']})")
            del engine
        except Exception as e:
            errors.append(f"spec_mixed: {e!r}")
            note(f"FAILED spec-mixed phase: {e!r}")

    # -- phase 4h2: speculative ladder (ISSUE 10) ----------------------------
    # Draft depth 0/1/3/7 × bf16/int8-KV on the paged layout — the
    # tentpole composition (int8 + spec) measured end to end, with the
    # int8 arm's pages_per_block sweep and per-arm worst_kernel() picks.
    if args.spec_draft and args.spec_ladder and not over_budget("spec_ladder"):
        try:
            extra["spec_ladder"] = spec_ladder_rung(args)
            i8 = extra["spec_ladder"]["int8"]
            note(f"spec ladder (int8): "
                 + ", ".join(
                     f"k={k} {i8[f'spec{k}']['tok_s']} tok/s"
                     for k in (0, 1, 3, 7)))
        except Exception as e:
            errors.append(f"spec_ladder: {e!r}")
            note(f"FAILED spec-ladder phase: {e!r}")

    # -- phase 4i: flight-recorder overhead A/B (ISSUE 7) --------------------
    if args.flight_ab and not over_budget("flight_ab"):
        try:
            engine = None
            extra["flight_ab"] = flight_ab_rung(args)
            note(f"flight A/B: {extra['flight_ab']['tok_s_recorder_on']} "
                 f"on vs {extra['flight_ab']['tok_s_recorder_off']} off "
                 f"tok/s ({extra['flight_ab']['delta_pct']}% overhead)")
        except Exception as e:
            errors.append(f"flight_ab: {e!r}")
            note(f"FAILED flight A/B phase: {e!r}")
        finally:
            engine = None

    # -- phase 4j: phase-annotation overhead A/B (ISSUE 8) -------------------
    if args.annot_ab and not over_budget("annot_ab"):
        try:
            engine = None
            extra["annotation_ab"] = annot_ab_rung(args)
            note(f"annotation A/B: "
                 f"{extra['annotation_ab']['tok_s_annotations_on']} on vs "
                 f"{extra['annotation_ab']['tok_s_annotations_off']} off "
                 f"tok/s ({extra['annotation_ab']['delta_pct']}% overhead)")
        except Exception as e:
            errors.append(f"annot_ab: {e!r}")
            note(f"FAILED annotation A/B phase: {e!r}")
        finally:
            engine = None

    # -- phase 4k: disaggregation A/B (ISSUE 13) -----------------------------
    if args.disagg_ab and not over_budget("disagg_ab"):
        try:
            engine = None
            extra["disagg_ab"] = disagg_ab_rung(args)
            da = extra["disagg_ab"]
            note(f"disagg A/B: goodput pooled "
                 f"{da['gateway_slo_goodput_ratio']['pooled']} vs unified "
                 f"{da['gateway_slo_goodput_ratio']['unified']}, tok/s "
                 f"delta {da['tok_s_delta_pct']}%")
        except Exception as e:
            errors.append(f"disagg_ab: {e!r}")
            note(f"FAILED disagg A/B phase: {e!r}")
        finally:
            engine = None

    # -- phase 4l: engine-supervision failover A/B (ISSUE 14) ----------------
    if args.failover_ab and not over_budget("failover_ab"):
        try:
            engine = None
            extra["failover_ab"] = failover_ab_rung(args)
            fo = extra["failover_ab"]
            note(f"failover A/B: goodput steady "
                 f"{fo['steady']['goodput_ratio']} / incident "
                 f"{fo['incident']['goodput_ratio']} / recovered "
                 f"{fo['recovered']['goodput_ratio']}, p99 error frame "
                 f"{fo['incident'].get('p99_error_frame_ms')} ms")
        except Exception as e:
            errors.append(f"failover_ab: {e!r}")
            note(f"FAILED failover A/B phase: {e!r}")
        finally:
            engine = None

    # -- phase 5: in-model attention A/B -------------------------------------
    try:
        if not over_budget("attention_ab"):
            extra.update(attention_inmodel_ab(args))
    except Exception as e:
        errors.append(f"attention: {e!r}")
        note(f"FAILED attention phase: {e!r}")

    if errors:
        extra["phase_errors"] = errors
    # One-glance best decode number across precision rungs at the headline
    # shape (the headline `value` stays bf16 so rounds compare like for
    # like; quantized serving is how operators would actually run it).
    candidates = {"bf16": value}
    for name in ("quant_int8", "quant_int8_kv8"):
        if name in extra and isinstance(extra[name], dict):
            candidates[name] = extra[name].get("tok_s", 0.0)
    best = max(candidates, key=candidates.get)
    if candidates[best] > 0:
        extra["best"] = {"config": best, "tok_s": candidates[best],
                         "vs_baseline": round(candidates[best] / 2000.0, 3)}
    # The BASELINE.md north star is ≥2k tok/s/chip AT 7-8B — surface the
    # target-scale number separately from the (1.1B) headline ladder.
    h8 = extra.get("headline_8b", {})
    if h8.get("tok_s"):
        ns_tok_s, ns_batch = h8["tok_s"], h8.get("batch")
        if h8.get("bs2x_tok_s", 0) > ns_tok_s:
            ns_tok_s, ns_batch = h8["bs2x_tok_s"], h8.get("bs2x_batch")
        extra["north_star"] = {
            "config": (f"{h8.get('preset')} int8+kv8 bs={ns_batch} "
                       f"(one chip)"),
            "tok_s": ns_tok_s,
            "vs_target_2k": round(ns_tok_s / 2000.0, 3),
        }
        # TTFT was measured on the BASE-batch engine; label it with its
        # batch so a promoted bs-2x tok/s never borrows a foreign TTFT.
        if ns_batch == h8.get("batch"):
            extra["north_star"]["ttft_p50_ms"] = h8.get("ttft_p50_ms")
        else:
            extra["north_star"]["ttft_p50_ms_at_base_bs"] = \
                h8.get("ttft_p50_ms")
            extra["north_star"]["ttft_base_batch"] = h8.get("batch")
        if "int4_tok_s" in h8:          # opt-in faster configuration
            extra["north_star"]["int4_tok_s"] = h8["int4_tok_s"]
            extra["north_star"]["int4_vs_target_2k"] = \
                h8["int4_vs_target_2k"]
        # BASELINE.md defines the baseline AT 7-8B scale — when the
        # target-scale rung ran, IT is the headline number; the 1.1B
        # ladder stays in extra as the small-model reference.
        RESULT["metric"] = (f"decode_tok_s_chip ({h8.get('preset')} "
                            f"int8+kv8, bs={ns_batch}, "
                            f"ctx=128+{args.eight_b_steps})")
        value = ns_tok_s
    # -- per-rung SLO/goodput fields (ISSUE 7 satellite) ---------------------
    # Every rung that measured both a latency and a throughput number gets
    # the SNIPPETS.md-target SLO block, so BENCH artifacts track GOODPUT
    # (throughput while the targets hold), not just raw tok/s.
    extra["slo"] = slo_fields(
        tok_s=contig_bf16_tok_s or value,
        ms_per_step=extra.get("ms_per_decode_step"),
        batch=args.batch, ttft_p50_ms=extra.get("ttft_p50_ms"))
    if extra.get("paged_tok_s"):
        extra["paged_slo"] = slo_fields(
            tok_s=extra["paged_tok_s"],
            ms_per_step=extra.get("paged_ms_per_decode_step"),
            batch=args.batch)
    if "ttft_adaptive" in extra:
        ta = extra["ttft_adaptive"]
        ta["slo"] = slo_fields(tok_s=ta.get("scheduler_tok_s"),
                               batch=args.batch,
                               ttft_p50_ms=ta.get("ttft_p50_ms"))
    h8s = extra.get("headline_8b")
    if isinstance(h8s, dict) and h8s.get("tok_s"):
        h8s["slo"] = slo_fields(
            tok_s=h8s["tok_s"], ms_per_step=h8s.get("ms_per_decode_step"),
            batch=h8s.get("batch"),
            ttft_p50_ms=(h8s.get("ttft_adaptive") or {}).get(
                "ttft_p50_ms", h8s.get("ttft_p50_ms")))
    RESULT["value"] = value
    RESULT["vs_baseline"] = round(value / 2000.0, 3)
    print(json.dumps(RESULT))


if __name__ == "__main__":
    main()
