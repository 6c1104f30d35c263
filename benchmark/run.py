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

The window opens first. The open counter snapshot (``stats()``) is taken
where it opens and the close snapshot where it closes, in both loops, so
a counter's delta covers the window and nothing else. A traced run then
profiles ``TRACE_SECONDS`` from a moment just inside the window
(``device.trace_offset_s`` after its opening: the profiler's start-up).
One worker thread makes both profiler calls and writes a marker span
after the first and before the second (``record_trace``); the reduction
takes the traced span from the markers, on the profiler's own clock, and
clips every event to it (``xplane.reduce``), and the host-clock stamps
read inside the markers bound the client's frames that the kernel
roofline counts. A trace in which the markers are not found gives no
``device_trace`` metric and no ``breakdown``; the ``phase: "trace"`` line
says which it was.

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

from . import ENGINE_INTERFACE, metrics, spec     # noqa: E402

OUT_DIR = "bench_out"           # inside the checkout, git-ignored
TRACE_SECONDS = 4.0             # of the window, from just inside it
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


def sample_prompt_tokens(cell: spec.Cell, engine) -> int:
    """Length of the correctness sample's prompts: whole prefill chunks
    (two where they fit), so they cross a chunk and a page and add no
    program to warm; where the configuration's file says its program is
    exact only up to a length (``correctness.sampling``), that length."""
    from .correctness import SAMPLE_MAX_TOKENS, sampling
    exact = sampling(cell.config_name, cell.config).exact_up_to_tokens
    if exact:
        return exact
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
    buckets = {_bucket(min(sample_prompt_tokens(cell, engine), chunk), chunk)}
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
    missing = [name for name in ENGINE_INTERFACE if not hasattr(engine, name)]
    if missing:
        raise TypeError(
            f"the engine lacks {missing}: the benchmark warms, counts and "
            f"checks through these names, and an engine for another "
            f"architecture has to keep them (benchmark.ENGINE_INTERFACE)")
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


class JaxEvents:
    """JAX's own duration events (tracing a function, lowering it, compiling
    it, reading the compilation cache), stamped as they end. The ``window``
    line lists those inside the window: the program's compile counter does
    not see a retrace whose executable came from the persistent cache, and a
    first call that traces and lowers a 32-layer program holds up every
    Python thread for as long as it takes."""

    def __init__(self) -> None:
        self.events: list[tuple[float, str, str, float]] = []

    def __call__(self, event: str, duration: float, **fields: Any) -> None:
        if event.startswith(("/jax/core/compile", "/jax/compilation_cache")):
            self.events.append((time.monotonic(), event.rsplit("/", 1)[-1],
                                str(fields.get("fun_name", "")), duration))

    def inside(self, t0: float, t1: float, n: int = 8) -> dict[str, Any]:
        """How many ended in ``[t0, t1)``, their seconds in all, and the
        ``n`` longest as ``[seconds from t0, event, function, seconds]``."""
        evs = [e for e in self.events if t0 <= e[0] < t1]
        top = sorted(evs, key=lambda e: -e[3])[:n]
        return {"count": len(evs), "seconds": round(sum(e[3] for e in evs), 3),
                "longest": [[round(t - t0, 2), ev, fn[:40], round(d, 3)]
                            for t, ev, fn, d in top]}


def record_trace(trace_dir: Path, seconds: float) -> tuple[float, float]:
    """Profile ``seconds``, all from ONE thread (a worker's, not the event
    loop's): start the profiler, then open a marker span and read the host
    clock inside it; sleep the seconds out from that stamp; open the other
    marker, read the clock, stop the profiler. Nothing the event loop does
    can come between a profiler call and its stamp."""
    import jax
    from .xplane import MARK_CLOSE, MARK_OPEN
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with jax.profiler.TraceAnnotation(MARK_OPEN):
        t0 = time.monotonic()
    time.sleep(max(0.0, t0 + seconds - time.monotonic()))
    with jax.profiler.TraceAnnotation(MARK_CLOSE):
        t1 = time.monotonic()
    jax.profiler.stop_trace()
    return t0, t1


def trace_facts(reduced) -> tuple[dict[str, Any], dict[str, Any]]:
    """What the result line takes straight from the reduced trace: the
    ``device`` block's ``busy_s`` and ``window_s``, and the ``breakdown``.
    Only from a span the markers bound: a busy time over a window from
    another clock has been more than the window (ledger, PR 24)."""
    if not (reduced.marked and reduced.devices):
        return {}, {}
    return ({"busy_s": reduced.busy_ns() / 1e9,
             "window_s": reduced.window_ns / 1e9},
            {"breakdown": {"device_ops": reduced.top_ops(10),
                           "idle_gaps": reduced.idle_gaps(10)}})


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


def attn_shape(engine, config: dict[str, Any]):
    """The paged kernels' per-chip shape; ``n_layers`` counts the layers
    that call them, which the configuration's file may state."""
    from .roofline import AttnShape
    c = engine.model_cfg
    tp = dict(engine.mesh.shape).get("model", 1)
    int8 = engine.kv_quant == "int8"
    return AttnShape(n_layers=spec.paged_attention_layers(config, c.n_layers),
                     n_heads=c.n_heads // tp,
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
    from . import correctness, reference, xplane
    from .gateway import Gateway, resolve_preset
    from .load import Player
    from .reducers import REDUCERS, Measured
    from .roofline import peaks_for

    setup: dict[str, float] = {"imports_s": time.monotonic() - _T0}
    device = device_facts()
    preset = resolve_preset(cell.config_name, cell.config)
    how = correctness.sampling(cell.config_name, cell.config)
    ref_module = reference.load(cell.config, cell.data)
    scopes = spec.scopes(cell.config)
    engine_cfg = {**cell.config["engine"], "preset": preset}
    async with Gateway(engine_cfg, out / "gateway", local_factory) as g:
        eng = g.engine
        setup.update(g.timings)
        shape = attn_shape(eng, cell.config)
        emit("engine", preset=preset, layers=eng.model_cfg.n_layers,
             paged_layers=shape.n_layers,
             vocabulary=eng.model_cfg.vocab_size, slots=eng.B,
             context=eng.S, page=eng.kv_page,
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
        parity = await asyncio.to_thread(
            correctness.kernel_parity, n_heads=shape.n_heads,
            n_kv_heads=shape.n_kv_heads, head_dim=shape.head_dim,
            page=eng.kv_page, window=shape.window, kv_quant=eng.kv_quant,
            interpret=rehearsal, **({"pages_per_slot": 8, "t": 16}
                                    if rehearsal else {}))
        if hasattr(ref_module, "kernel_checks"):
            parity += await asyncio.to_thread(
                ref_module.kernel_checks, eng, cell.config, rehearsal)
        emit("kernel_parity", cases=parity)
        ref = await correctness.served_against_reference(
            g, seed, sample_prompt_tokens(cell, eng), how, ref_module,
            cell.config)
        sample_logs = ref.pop("logs")
        emit("reference", **ref)
        setup["correctness_s"] = time.monotonic() - t0

        trace_dir = out / "trace"
        if trace_dir.exists():
            shutil.rmtree(trace_dir)
        marks: dict[str, Any] = {}

        def on_open() -> asyncio.Future | None:
            marks["stats_open"] = eng.stats()
            marks["t_stats_open"] = time.monotonic()
            if trace:
                return asyncio.ensure_future(asyncio.to_thread(
                    record_trace, trace_dir, TRACE_SECONDS))

        async def on_close() -> None:
            marks["stats_close"] = eng.stats()
            marks["t_stats_close"] = time.monotonic()

        t_lead = time.monotonic()
        player = Player(g, cell.traffic, seed, seconds, on_open, on_close)
        jax_events = JaxEvents()
        jax.monitoring.register_event_duration_secs_listener(jax_events)
        played = await player.play()
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
         counters_span_s=round(
             marks["t_stats_close"] - marks["t_stats_open"], 4),
         loop_stall_ms=round(1e3 * played.stall[0], 1),
         loop_stall_at_s=round(played.stall[1], 2),
         loop_stall_cpu_s=round(played.stall[2], 3),
         jax_events=jax_events.inside(played.t_open, played.t_close),
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
        t_trace = played.opened
        recorded = await asyncio.to_thread(xplane.load, str(trace_dir))
        if describe_to is not None:
            describe_to.write_text(json.dumps(
                xplane.describe(recorded, 400), indent=1))
        reduced = await asyncio.to_thread(xplane.reduce, recorded, scopes)
        facts, breakdown = trace_facts(reduced)
        dev.update(facts, trace_offset_s=t_trace[0] - played.t_open)
        result.update(breakdown)
        emit("trace", marked=reduced.marked,
             offset_s=round(dev["trace_offset_s"], 4),
             host_clock_s=round(t_trace[1] - t_trace[0], 6),
             profiler_clock_s=reduced.window_ns / 1e9,
             devices=len(reduced.devices), **facts,
             **({"gaps_named_s": sum(e - s for s, e in reduced.gaps()) / 1e9}
                if facts else {}),
             **({} if reduced.marked else {
                 "problem": "the marker spans are not in the trace: no "
                            "device_trace metric, no breakdown"}))
        measured = Measured(
            logs=logs, t_open=played.t_open, t_close=played.t_close,
            trace=reduced if reduced.marked else None, t_trace=t_trace,
            flight=flight, counters_open=marks["stats_open"],
            counters_close=marks["stats_close"], slots=eng.B, shape=shape,
            peaks=({} if rehearsal else peaks_for(device["kind"])),
            peak_hbm_bytes=peak, config=cell.config)
        out_metrics = {}
        for lm in cell.per_layer:
            v = REDUCERS[lm.reducer](measured, lm.args)
            if v is not None:
                out_metrics[lm.name] = {"value": v, "unit": lm.unit}
        result["metrics"] = out_metrics
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
