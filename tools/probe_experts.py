"""Chip probe of the grouped expert product (models/hybrid.py
``experts_grouped``): single calls of ONE expert layer at the three expert
cells' geometries, int8, the stack read at a period index. It times the
function as it is — the live tiles in ONE Pallas kernel
(``ops/grouped_experts.py``), form "kernel" — beside the XLA loop it was
from PR 39 to PR 43 under both of that loop's combines (``loop_form``
"gather" | "add", kept here) and the form before PR 39 (every tile
quantises the rows it gathers and scatter-adds into a float32 carry:
``scatter_form``), and the kernel's stages one by one — layout | quantise |
pack | the kernel alone — each a program of its own, so a stage's time is
what it costs alone. Times are DEVICE times, the median duration of a
program's executions on the profiler's ``XLA Modules`` line (a stage of
20 us would read the host's 0.3 ms a dispatch on the host's clock), with
each form's time by op category beside it. The period index is an ARGUMENT
of every program: a constant one lets XLA slice the period out of the stack
inside the loop, 0.4-0.8 GB copied a tile.
``--setup`` times instead what a prefill program pays BEFORE it runs
(``setup_costs``): trace, lower, compile cold and compile from the
persistent cache, of a period scan over four expert layers, with the loop
and with the kernel.
``chiprun -- python3 tools/probe_experts.py [--setup] [cell ...]``; results
on stdout and in chiprun_out/probe_experts[_setup].json. Fails without a TPU.
"""
from __future__ import annotations

import json
import statistics
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import xplane                                    # noqa: E402
from llmapigateway_tpu.models import hybrid                     # noqa: E402
from llmapigateway_tpu.models.llama import (_GATE_ACTS,         # noqa: E402
                                            swiglu_mlp)
from llmapigateway_tpu.models.quant import (_dynamic_int8, mm,  # noqa: E402
                                            mm_q8)
from llmapigateway_tpu.ops import grouped_experts               # noqa: E402

TILE = hybrid.GROUP_TILE
# One prefill call of each cell: rows x chunk positions, the layer's widths,
# the experts held of those the router scores, the gate.
GEOMETRIES = {
    "smallthinker-21b-mixed": dict(N=1024, D=2560, F=768, held=64,
                                   n_experts=64, k=6, act="relu"),
    "solar-open2-chat-sat": dict(N=2048, D=4096, F=1280, held=40,
                                 n_experts=320, k=8, act="silu"),
    "mistral-small4-longctx": dict(N=2048, D=4096, F=2048, held=32,
                                   n_experts=128, k=4, act="silu"),
}
# Smaller calls of the same cells (fewer rows a call, a last chunk's
# bucket): the three forms alone.
SWEEP = (128, 256, 512, 1024, 1536)
PERIODS, PERIOD = 2, 1
LAYERS = 4                  # expert layers a period body unrolls (--setup)
HBM_BYTES_PER_US = 819e3    # a v5e's 819 GB/s


def program(name: str, fn):
    """``fn`` jitted under a name the trace's module line will carry."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def device_ms(calls: dict, n: int = 5) -> tuple[dict, dict]:
    """calls: name -> (a ``program``, its arguments). Returns the median
    device milliseconds of an execution of each, and its ops' self time by
    category (ms an execution)."""
    for fn, args in calls.values():
        for _ in range(2):
            jax.block_until_ready(fn(*args))
    with tempfile.TemporaryDirectory(prefix="probe_experts_") as where:
        jax.profiler.start_trace(where)
        for fn, args in calls.values():
            for _ in range(n):
                out = fn(*args)
            jax.block_until_ready(out)
        jax.profiler.stop_trace()
        dev = xplane.reduce(xplane.load(where)).devices[0]
    runs = defaultdict(list)
    for start, end, name in dev.modules:
        runs[name].append((end - start) / 1e6)
    ops = defaultdict(lambda: defaultdict(float))
    for op in dev.ops:
        ops[op.program][op.category] += op.self_ns / 1e6
    ms = {name: round(statistics.median(runs[name]), 4) for name in calls}
    parts = {name: {c: round(v / len(runs[name]), 4)
                    for c, v in sorted(ops[name].items(),
                                       key=lambda kv: -kv[1])[:6]}
             for name in calls}
    return ms, parts


def scatter_form(x, probs, lp, per_token, tile=TILE, period=None,
                 act="silu"):
    """``experts_grouped`` as it stood before PR 39."""
    N, D = x.shape
    held = probs.shape[1]
    routed = probs > 0.0
    counts = jnp.sum(routed, axis=0, dtype=jnp.int32)
    tiles = (counts + tile - 1) // tile
    last_tile = jnp.cumsum(tiles)
    n_tiles = -(-N * min(per_token, held) // tile) + held
    rows = n_tiles * tile
    rank = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - 1
    dest = jnp.where(routed, (last_tile - tiles)[None, :] * tile + rank, rows)
    token = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None],
                             (N, held))
    row_token = jnp.full((rows,), N, jnp.int32).at[dest.reshape(-1)].set(
        token.reshape(-1), mode="drop")
    row_weight = jnp.zeros((rows,), jnp.float32).at[dest.reshape(-1)].set(
        probs.reshape(-1), mode="drop")
    tile_expert = jnp.minimum(
        jnp.searchsorted(last_tile, jnp.arange(n_tiles), side="right"),
        held - 1).astype(jnp.int32)
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])

    def body(i, out):
        e = tile_expert[i]
        at = jax.lax.dynamic_slice_in_dim(row_token, i * tile, tile)
        wt = jax.lax.dynamic_slice_in_dim(row_weight, i * tile, tile)
        w = hybrid._at(hybrid._at({key: lp[key] for key in
                                   hybrid.EXPERT_KEYS}, period), e)
        y = swiglu_mlp(x_pad[at], w["wg"], w["wu"], w["wd"], act)
        return out.at[at].add(wt[:, None] * y.astype(jnp.float32))

    out = jax.lax.fori_loop(0, last_tile[-1], body,
                            jnp.zeros((N + 1, D), jnp.float32))
    return out[:N]


def _expert(lp, period, e):
    return hybrid._at(hybrid._at({k: lp[k] for k in hybrid.EXPERT_KEYS},
                                 period), e)


def loop_form(x, idx, w, lp, held, period, act, combine):
    """``experts_grouped`` as it stood from PR 39 to PR 43: an XLA loop over
    the live tiles — a tile gathers its int8 rows, runs three int8 dots
    each rescaled around, and writes its result in expert order
    (``combine`` "gather": the tokens gather after the loop) or adds it
    into a float32 carry ("add")."""
    N, D = x.shape
    lay = hybrid.grouped_layout(idx, held, TILE)
    rows = lay.row_token.shape[0]
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
    src = _dynamic_int8(x_pad)

    def run(i):
        m = _expert(lp, period, lay.tile_expert[i])
        at = jax.lax.dynamic_slice_in_dim(lay.row_token, i * TILE, TILE)
        xq, xs = (a[at] for a in src)
        hidden = (_GATE_ACTS[act](mm_q8(xq, xs, m["wg"], x.dtype))
                  * mm_q8(xq, xs, m["wu"], x.dtype))
        return at, mm(hidden, m["wd"])

    if combine == "add":
        row_weight = jnp.zeros((rows,), jnp.float32).at[
            lay.dest.reshape(-1)].set(w.reshape(-1), mode="drop")

        def add(i, out):
            at, y = run(i)
            wt = jax.lax.dynamic_slice_in_dim(row_weight, i * TILE, TILE)
            return out.at[at].add(wt[:, None] * y.astype(jnp.float32))
        return jax.lax.fori_loop(0, lay.counted[0], add,
                                 jnp.zeros((N + 1, D), jnp.float32))[:N]

    def write(i, ys):
        return jax.lax.dynamic_update_slice_in_dim(ys, run(i)[1], i * TILE, 0)
    ys = jax.lax.fori_loop(0, lay.counted[0], write,
                           jnp.zeros((rows + 1, D), x.dtype))
    return jnp.sum(w[:, :, None] * ys[lay.dest].astype(jnp.float32), axis=1)


def kernel_inputs(x, idx, w, lp, held, period):
    """What ``hybrid._grouped`` hands the kernel, flat."""
    (*layout, src, mats), _ = hybrid.grouped_inputs(
        x, idx, w, {key: lp[key] for key in hybrid.EXPERT_KEYS}, period,
        held, TILE)
    return (*layout, *src, *mats)


def weights(g: dict) -> dict:
    D, F, held = g["D"], g["F"], g["held"]
    keys = jax.random.split(jax.random.PRNGKey(39), 3)
    return {name: {
        "q": jax.random.randint(key, (PERIODS, held, *shape), -127, 128,
                                jnp.int8),
        "s": jnp.full((PERIODS, held, shape[1]), 1.0 / (127 * shape[0]),
                      jnp.float32)}
        for key, name, shape in ((keys[0], "wg", (D, F)),
                                 (keys[1], "wu", (D, F)),
                                 (keys[2], "wd", (F, D)))}


def probe(name: str, g: dict, lp: dict, stages: bool) -> dict:
    """One call of ``g["N"]`` rows: the forms, and with ``stages`` the
    kernel's stages alone."""
    N, D, held, k, act = (g[key] for key in ("N", "D", "held", "k", "act"))
    keys = jax.random.split(jax.random.PRNGKey(N), 2)
    x = jax.random.normal(keys[0], (N, D), jnp.bfloat16)
    top, idx = jax.lax.top_k(
        jax.random.normal(keys[1], (N, g["n_experts"]), jnp.float32), k)
    w = jax.nn.softmax(top, axis=-1)
    probs = jnp.sum(jnp.where(
        idx[:, :, None] == jnp.arange(held), w[:, :, None], 0.0), axis=1)
    period = jnp.int32(PERIOD)

    lay = jax.jit(lambda i: hybrid.grouped_layout(i, held, TILE))(idx)
    tiles, landed = (int(n) for n in lay.counted)
    row = {"cell": name, **g,
           "width_blocks": grouped_experts.width_blocks(
               D, g["F"], 1, grouped_experts.VMEM_LIMIT
               - grouped_experts.FIXED_BYTES
               - grouped_experts.resident_bytes(N, D, 1)),
           "rows_bound": int(lay.row_token.shape[0]),
           "rows_landed": landed, "tiles_live": tiles, "slots": N * k,
           "fill_share_pct": round(100 * landed / (TILE * tiles), 2)}

    # The weights and the period are ARGUMENTS of every program.
    def a_loop(combine):
        return (program(combine, lambda lp, p, x, i, w: loop_form(
            x, i, w, lp, held, p, act, combine)), (lp, period, x, idx, w))
    forms = {
        "scatter_form": (program("scatter_form", lambda lp, p, x, pr:
                                 scatter_form(x, pr, lp, k, period=p,
                                              act=act)),
                         (lp, period, x, probs)),
        "gather": a_loop("gather"),
        "add": a_loop("add"),
        "kernel": (program("kernel", lambda lp, p, x, i, w:
                           hybrid.experts_grouped(
                               x, i, w, lp, held, period=p, act=act)[0]),
                   (lp, period, x, idx, w)),
    }
    if not stages:
        del forms["scatter_form"]
    ref = forms["gather"][0](*forms["gather"][1])
    row["result_scale"] = float(jnp.max(jnp.abs(ref)))
    row["max_abs_diff_from_gather"] = {
        f: float(jnp.max(jnp.abs(fn(*args) - ref)))
        for f, (fn, args) in forms.items() if f != "gather"}
    row["ms"], row["ms_by_category"] = device_ms(forms)
    if not stages:
        del row["ms_by_category"]
    print(json.dumps(row), flush=True)
    if not stages:
        return row

    # -- the kernel's stages, each a program of its own --------------------
    prepare = program("prepare", lambda lp, p, x, i, w: kernel_inputs(
        x, i, w, lp, held, p))
    given = prepare(lp, period, x, idx, w)
    calls = {
        "layout": (program("layout", lambda i: hybrid.grouped_layout(
            i, held, TILE)), (idx,)),
        "quantise": (program("quantise", lambda x: _dynamic_int8(x)), (x,)),
        "pack_rows": (program("pack_rows", lambda *s: grouped_experts
                              .pack_rows(*s)), given[4:6]),
        "prepare": (prepare, (lp, period, x, idx, w)),
        "kernel_alone": (program(
            "kernel_alone", lambda *a: grouped_experts.grouped_experts(
                *a[:4], a[4:6], a[6:], tile=TILE, act=act, dtype=x.dtype)),
            given),
    }
    stages, _ = device_ms(calls)
    out = {"cell": name, "stages_ms": stages,
           "tile_us": round(1e3 * stages["kernel_alone"] / tiles, 2),
           "weights_stream_us": round(
               3 * D * g["F"] / HBM_BYTES_PER_US, 2)}
    print(json.dumps(out), flush=True)
    return {**row, **out}


def setup_costs(name: str, g: dict, lp: dict) -> dict:
    """What ONE prefill program pays in set-up for its expert layers: a
    scan over the periods whose body runs ``LAYERS`` expert layers on
    ``g["N"]`` rows, each layer with matrices of its own — traced, lowered,
    compiled cold, and compiled again from the persistent cache (a warm
    run). "loop": PR 39's XLA loop; "kernel": the Pallas kernel through
    ``experts_grouped``'s one jitted function; "kernel_inline": the same
    kernel traced and lowered at every layer (no inner jit)."""
    N, D, held, k, act = (g[key] for key in ("N", "D", "held", "k", "act"))
    sds = jax.ShapeDtypeStruct
    stacks = tuple(jax.tree.map(lambda a: sds(a.shape, a.dtype), lp)
                   for _ in range(LAYERS))
    args = (stacks, sds((N, D), jnp.bfloat16), sds((N, k), jnp.int32),
            sds((N, k), jnp.float32))
    inline = hybrid._grouped.__wrapped__

    def body(layer):
        def fn(stacks, x, idx, w):
            def period_step(x, p):
                for lp in stacks:
                    x = x + layer(x, idx, w, lp, p).astype(x.dtype)
                return x, None
            return jax.lax.scan(period_step, x, jnp.arange(PERIODS))[0]
        return fn
    layers = {
        "loop": lambda x, i, w, lp, p: loop_form(x, i, w, lp, held, p, act,
                                                 "gather"),
        "kernel": lambda x, i, w, lp, p: hybrid.experts_grouped(
            x, i, w, lp, held, period=p, act=act)[0],
        "kernel_inline": lambda x, i, w, lp, p: inline(
            x, i, w, {key: lp[key] for key in hybrid.EXPERT_KEYS}, p,
            held=held, tile=TILE, act=act)[0],
    }
    row = {"cell": name, "N": N, "layers": LAYERS}
    seen = defaultdict(float)
    jax.monitoring.register_event_listener(
        lambda event, **kw: seen.__setitem__(event, seen[event] + 1))
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: seen.__setitem__(event, seen[event] + secs))
    def staged(fn):
        """Trace, lower, compile: seconds each, and the lowered text. Both
        passes go through here, and still a program that holds the kernel
        gets another cache key at its second lowering in one process
        (PERF.md section 5): run ``--setup`` twice in one call — each
        pass's key repeats from process to process — and read the second
        run's ``warm_*``."""
        t0 = time.perf_counter()
        traced = jax.jit(fn).trace(*args)
        t1 = time.perf_counter()
        lowered = traced.lower()
        t2 = time.perf_counter()
        lowered.compile()
        t3 = time.perf_counter()
        return (t1 - t0, t2 - t1, t3 - t2), lowered.as_text()

    for tag, layer in layers.items():
        fn = body(layer)
        fn.__name__ = fn.__qualname__ = f"setup_{tag}_{N}"
        cold, text = staged(fn)
        jax.clear_caches()
        seen.clear()
        warm, _ = staged(fn)
        events = {event.rsplit("/", 1)[1]: round(n, 3)
                  for event, n in seen.items()
                  if "compilation_cache" in event or "backend_compile" in event}
        row[tag] = {"trace_s": round(cold[0], 3), "lower_s": round(cold[1], 3),
                    "compile_cold_s": round(cold[2], 3),
                    "warm_trace_lower_compile_s": round(sum(warm), 3),
                    "warm_events": events,
                    "kernels_in_module": text.count("tpu_custom_call"),
                    "module_chars": len(text)}
    print(json.dumps(row), flush=True)
    return row


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    only = [a for a in sys.argv[1:] if not a.startswith("--")]
    setup = "--setup" in sys.argv[1:]
    if setup:
        from llmapigateway_tpu.engine.engine import _enable_compilation_cache
        _enable_compilation_cache("")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    rows = []
    for name, g in GEOMETRIES.items():
        if only and name not in only:
            continue
        lp = weights(g)
        if setup:
            rows += [setup_costs(name, {**g, "N": n}, lp)
                     for n in (g["N"], 512)]
            continue
        rows.append(probe(name, g, lp, stages=True))
        rows += [probe(name, {**g, "N": n}, lp, stages=False)
                 for n in SWEEP if n < g["N"]]
    out = {"device": dev.device_kind, "tile": TILE, "rows": rows}
    dest = Path("chiprun_out")
    dest.mkdir(exist_ok=True)
    (dest / ("probe_experts_setup.json" if setup else "probe_experts.json")
     ).write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
