"""One run of one cell:

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip, serves the app and drives it. Set-up (engine
build, every program the cell's traffic can reach, the correctness checks,
the trace's lead-in) ends where the window opens; ``setup_s`` is process
start to window open. Earlier lines of standard output are JSON objects
with a ``phase`` key (itemised set-up, generator lateness, sample counts,
early stops, flight-ring evictions, correctness); the LAST line is the
result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
and, traced, ``breakdown``. With ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Without the chips the cell asks for, the run fails: exit code 1 and no
result line. A number from a CPU is never printed under a metric's name.
"""
from __future__ import annotations

import time

_T0 = time.monotonic()          # as near to process start as Python allows

import argparse                 # noqa: E402
import asyncio                  # noqa: E402
import dataclasses              # noqa: E402
import json                     # noqa: E402
import logging                  # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
from pathlib import Path        # noqa: E402
from typing import Any          # noqa: E402

from . import metrics, spec     # noqa: E402

OUT_DIR = "bench_out"           # inside the checkout, git-ignored
TRACE_SECONDS = 4.0             # of the window, from its opening
REHEARSAL_PREFIX = "cpu_rehearsal."


def emit(phase: str, **fields: Any) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def device_facts() -> dict[str, Any]:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_compile_cache() -> None:
    """The program's own fixed cache directory, ``.xla_cache/`` at the
    root of the checkout (or ``JAX_COMPILATION_CACHE_DIR``); every program
    is cached, however quickly it compiled."""
    import jax
    from llmapigateway_tpu.engine.engine import _enable_compilation_cache
    _enable_compilation_cache("")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def has_chips(cell: spec.Cell, device: dict[str, Any]) -> bool:
    return device["platform"] == "tpu" and device["count"] == cell.chips


def memory_peak(devices) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def sample_prompt_tokens(engine) -> int:
    """Length of the correctness sample's prompts: whole prefill chunks
    (two where they fit), so they cross a chunk and a page and add no
    program to warm; for a model with experts, the longest call the
    program routes exactly (``correctness.DISPATCH_EXACT_TOKENS``)."""
    from .correctness import DISPATCH_EXACT_TOKENS, SAMPLE_MAX_TOKENS
    if engine.model_cfg.n_experts:
        return DISPATCH_EXACT_TOKENS
    chunk = engine.prefill_chunk
    return (2 if 2 * chunk + SAMPLE_MAX_TOKENS < engine.S else 1) * chunk


def reachable_programs(cell: spec.Cell, engine) -> dict[str, list[int]]:
    """The prefill buckets the trace's prompt lengths can end in (full
    chunks and every possible tail), the group sizes admission can form,
    and the decode depths: what this cell's traffic can reach, no more."""
    from llmapigateway_tpu.engine.engine import _bucket
    from .correctness import SAMPLE_REQUESTS
    from .traffic import support
    chunk = engine.prefill_chunk
    buckets = {_bucket(min(sample_prompt_tokens(engine), chunk), chunk)}
    for n in support(cell.traffic.prompt_tokens):
        if n >= chunk:
            buckets.add(chunk)
        if n % chunk:
            buckets.add(_bucket(n % chunk, chunk))
    t = cell.traffic
    most = engine.B if t.loop == "open" else min(engine.B, max(
        t.clients, SAMPLE_REQUESTS))
    groups = sorted({len(g) for n in range(1, most + 1)
                     for g in engine.prefill_groups(list(range(n)))})
    depths = sorted({1, engine.decode_burst_busy, engine.decode_burst})
    return {"prefill_buckets": sorted(buckets), "prefill_groups": groups,
            "decode_depths": depths}


def warm_programs(engine, plan: dict[str, list[int]]) -> None:
    """RUN every program of ``plan`` once, the way the scheduler calls
    them, on an idle engine: prefill writes land on the trash page, decode
    bursts run with no slot active. Running (not only compiling ahead)
    fills the jit caches too, so the first real call neither compiles nor
    reads the persistent cache."""
    import numpy as np
    for bucket in plan["prefill_buckets"]:
        for k in plan["prefill_groups"]:
            first, engine.cache = engine._exec_prefill(
                list(range(k)), [0] * k,
                [np.zeros((bucket,), np.int32)] * k)
            np.asarray(first)
    engine._d_dirty = True
    for depth in sorted(plan["decode_depths"], reverse=True):
        engine._decode_burst(depth)
        engine._decode_burst(depth)     # lag-one: lands the one before
    engine._flush_pending()
    engine._d_dirty = True


def write_records(path: Path, logs: list[metrics.RequestLog],
                  t_open: float) -> None:
    """One JSON line per request, times in seconds from window open."""
    with path.open("w") as f:
        for r in logs:
            row = dataclasses.asdict(r)
            row["frames"] = [[round(t - t_open, 4), n] for t, n in r.frames]
            for k in ("t_due", "t_send", "t_end", "t_submit", "t_admitted",
                      "t_first_token"):
                if row[k] is not None:
                    row[k] = round(row[k] - t_open, 4)
            f.write(json.dumps(row) + "\n")


def attn_shape(engine):
    from .roofline import AttnShape
    c = engine.model_cfg
    tp = dict(engine.mesh.shape).get("model", 1)
    int8 = engine.kv_quant == "int8"
    return AttnShape(n_layers=c.n_layers, n_heads=c.n_heads // tp,
                     n_kv_heads=max(1, c.n_kv_heads // tp),
                     head_dim=c.head_dim, window=int(c.sliding_window or 0),
                     kv_bytes=1 if int8 else 2,
                     kv_scale_bytes=4 if int8 else 0)


async def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
                   out: Path, rehearsal: bool = False,
                   local_factory=None, describe_to: Path | None = None
                   ) -> dict[str, Any]:
    """Set up, play the window, reduce. Returns the result object."""
    import jax
    from . import correctness, xplane
    from .gateway import Gateway, resolve_preset
    from .load import Player
    from .reducers import REDUCERS, Measured
    from .roofline import peaks_for

    setup: dict[str, float] = {"imports_s": time.monotonic() - _T0}
    device = device_facts()
    preset = resolve_preset(cell.config_name, cell.config)
    engine_cfg = {**cell.config["engine"], "preset": preset}
    async with Gateway(engine_cfg, out / "gateway", local_factory) as g:
        eng = g.engine
        setup.update(g.timings)
        emit("engine", preset=preset, layers=eng.model_cfg.n_layers,
             slots=eng.B, context=eng.S, page=eng.kv_page,
             chunk=eng.prefill_chunk, quant=eng.quant, kv_quant=eng.kv_quant,
             attention=eng.attention_impl,
             mesh={a: n for a, n in eng.mesh.shape.items() if n > 1})

        t0 = time.monotonic()
        plan = reachable_programs(cell, eng)
        await asyncio.to_thread(warm_programs, eng, plan)
        setup["programs_s"] = time.monotonic() - t0
        emit("programs", **plan, seconds=round(setup["programs_s"], 2),
             xla_compiles=eng.stats()["xla_compile_total"])

        t0 = time.monotonic()
        shape = attn_shape(eng)
        parity = await asyncio.to_thread(
            correctness.kernel_parity, n_heads=shape.n_heads,
            n_kv_heads=shape.n_kv_heads, head_dim=shape.head_dim,
            page=eng.kv_page, window=shape.window, kv_quant=eng.kv_quant,
            interpret=rehearsal, **({"pages_per_slot": 8, "t": 16}
                                    if rehearsal else {}))
        emit("kernel_parity", cases=parity)
        ref = await correctness.served_against_reference(
            g, seed, prompt_tokens=sample_prompt_tokens(eng))
        sample_logs = ref.pop("logs")
        emit("reference", **ref)
        setup["correctness_s"] = time.monotonic() - t0

        trace_dir = out / "trace"
        if trace_dir.exists():
            shutil.rmtree(trace_dir)
        marks: dict[str, Any] = {}
        stop_task: list[asyncio.Task] = []

        async def stop_trace_later() -> None:
            await asyncio.sleep(TRACE_SECONDS)
            marks["t_trace1"] = time.monotonic()
            await asyncio.to_thread(jax.profiler.stop_trace)

        async def on_open() -> None:
            marks["stats_open"] = eng.stats()
            if trace:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                await asyncio.to_thread(
                    jax.profiler.start_trace, str(trace_dir),
                    profiler_options=opts)
                marks["t_trace0"] = time.monotonic()
                stop_task.append(asyncio.ensure_future(stop_trace_later()))

        async def on_close() -> None:
            marks["stats_close"] = eng.stats()

        t_lead = time.monotonic()
        player = Player(g, cell.traffic, seed, seconds, on_open, on_close)
        played = await player.play()
        for task in stop_task:
            await task
        setup["lead_in_s"] = played.t_open - t_lead
        setup_s = played.t_open - _T0
        flight = eng.flight.snapshot() if eng.flight is not None else []
        evicted = eng.flight.evicted if eng.flight is not None else 0
        await asyncio.to_thread(g.gw.usage_recorder.flush)
        rows = correctness.usage_rows(Path(g.settings.db_dir))
        peak = memory_peak(eng.mesh.devices.flat)

    logs = played.logs
    in_window = metrics.overlapping(logs, played.t_open, played.t_close)
    failed = [r for r in in_window if r.failed]
    problems = [p for r in logs + sample_logs
                for p in correctness.response_problems(r)]
    finished = sum(1 for r in logs + sample_logs if r.finished)
    if rows < finished:
        problems.append(f"{finished} responses finished, {rows} usage rows")
    values, counts = metrics.end_to_end(logs, played.t_open, played.t_close)
    values["setup_s"] = setup_s
    compiles = (marks["stats_close"]["xla_compile_total"]
                - marks["stats_open"]["xla_compile_total"])

    emit("setup", **{k: round(v, 3) for k, v in setup.items()},
         setup_s=round(setup_s, 3))
    emit("window", seconds=seconds, requests_overlapping=len(in_window),
         finished_inside=sum(1 for r in logs if r.finished
                             and played.t_open <= r.t_end < played.t_close),
         samples=counts, drained=played.drained,
         early_stops=sum(1 for r in logs if r.finished
                         and r.finish_reason != "length"),
         flight_evicted=evicted, compiles_in_window=compiles,
         usage_rows=rows, problems=problems[:5])
    if played.lateness_ms:
        late = sorted(played.lateness_ms)
        emit("generator", sent=len(late),
             lateness_ms_p50=round(late[len(late) // 2], 3),
             lateness_ms_max=round(late[-1], 3))
    write_records(out / "requests.jsonl", logs, played.t_open)

    correct = (all(c["ok"] for c in parity) and ref["ok"] and not problems
               and played.drained)
    result: dict[str, Any] = {
        "correct": bool(correct), "attempted": len(in_window),
        "failed": len(failed)}
    dev: dict[str, Any] = {**device, "memory_peak_bytes": peak}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
    else:
        reduced = None
        if "t_trace0" in marks:
            window_ns = int(1e9 * (marks["t_trace1"] - marks["t_trace0"]))
            recorded = await asyncio.to_thread(xplane.load, str(trace_dir))
            if describe_to is not None:
                describe_to.write_text(json.dumps(
                    xplane.describe(recorded, 400), indent=1))
            reduced = await asyncio.to_thread(
                xplane.reduce, recorded, window_ns)
        measured = Measured(
            logs=logs, t_open=played.t_open, t_close=played.t_close,
            trace=reduced,
            t_trace=(marks.get("t_trace0", 0.0), marks.get("t_trace1", 0.0)),
            flight=flight, counters_open=marks["stats_open"],
            counters_close=marks["stats_close"], slots=eng.B, shape=shape,
            peaks=({} if rehearsal else peaks_for(device["kind"])),
            peak_hbm_bytes=peak)
        out_metrics = {}
        for lm in cell.per_layer:
            v = REDUCERS[lm.reducer](measured, lm.args)
            if v is not None:
                out_metrics[lm.name] = {"value": v, "unit": lm.unit}
        result["metrics"] = out_metrics
        if reduced is not None and reduced.devices:
            dev["busy_s"] = reduced.busy_ns() / 1e9
            dev["window_s"] = reduced.window_ns / 1e9
            result["breakdown"] = {"device_ops": reduced.top_ops(10),
                                   "idle_gaps": reduced.idle_gaps(10)}
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["device"] = dev
    if rehearsal:
        result["metrics"] = {REHEARSAL_PREFIX + k: v
                             for k, v in result["metrics"].items()}
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", type=Path, default=spec.REPO_ROOT,
                    help=argparse.SUPPRESS)    # tests: another BENCHMARK.json
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help=argparse.SUPPRESS)    # tests: tiny sizes, no chip
    ap.add_argument("--describe-trace", type=Path, default=None,
                    help=argparse.SUPPRESS)    # planes/lines/events, by hand
    args = ap.parse_args(argv)
    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(levelname)s %(name)s: %(message)s")
    # Hanging up at the end of a run makes the server log a reset per open
    # stream; failed requests are counted from the client's side.
    logging.getLogger("aiohttp.server").setLevel(logging.CRITICAL)

    cell = spec.load_cell(args.workload, args.root)
    device = device_facts()
    if not args.rehearse_cpu and not has_chips(cell, device):
        print(f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"reports {device}. No result.", file=sys.stderr)
        return 1
    if args.rehearse_cpu and device["platform"] == "tpu":
        print("benchmark: the rehearsal is for machines without a chip",
              file=sys.stderr)
        return 1

    import jax
    if not args.rehearse_cpu:
        enable_compile_cache()
    out = spec.REPO_ROOT / OUT_DIR / cell.name
    out.mkdir(parents=True, exist_ok=True)
    emit("start", workload=cell.name, seed=args.seed, seconds=args.seconds,
         trace=args.trace, jax=jax.__version__, **device)
    result = asyncio.run(run_cell(
        cell, args.seed, args.seconds, bool(args.trace), out,
        rehearsal=args.rehearse_cpu, describe_to=args.describe_trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
