"""Chip probe of the grouped expert product (models/hybrid.py
``experts_grouped``): single calls of ONE expert layer at the three expert
cells' geometries, int8, the stack read at a period index. It times the
function under both of its combines ("gather", "add"; ``combine_form`` says
which a cell runs) beside the form it had until PR 39 (every tile quantises
the rows it gathers and scatter-adds into a float32 carry; kept here,
verbatim, as ``scatter_form``), two forms that were candidates (the rows
gathered into expert order in ONE op before the loop; the loop's add told
that its rows are ascending and unique), and the stages one by one — layout
| quantise | gather | products | write | combine — each a program of its
own, so a stage's time is what it costs alone, not what it costs fused into
its neighbours. Times are DEVICE times, the median duration of a program's
executions on the profiler's ``XLA Modules`` line (a stage of 20 us would
read the host's 0.3 ms a dispatch on the host's clock), with each form's
time by op category beside it. The period index is an ARGUMENT of every
program: a constant one lets XLA slice the period out of the stack inside
the loop, 0.4-0.8 GB copied a tile.
``chiprun -- python3 tools/probe_experts.py [cell ...]``; results on stdout
and in chiprun_out/probe_experts.json. Fails without a TPU.
"""
from __future__ import annotations

import json
import statistics
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark import xplane                                    # noqa: E402
from llmapigateway_tpu.models import hybrid                     # noqa: E402
from llmapigateway_tpu.models.llama import swiglu_mlp           # noqa: E402
from llmapigateway_tpu.models.quant import _dynamic_int8        # noqa: E402

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


def _products(xq, xs, m, act, dtype):
    return hybrid._expert_rows((xq, xs), m, act, dtype)


def loop(lay, src, lp, period, act, dtype, gather: str, write: bool):
    """The tile loop over rows quantised before it. ``gather`` "tile": a
    tile gathers its rows of ``src`` ([N+1, ...]); "bulk": ``src`` is in
    expert order already ([rows, ...]) and a tile slices it. ``write``
    False: the results are summed into one tile (products alone)."""
    rows = lay.row_token.shape[0]
    D = src[0].shape[1]

    def body(i, ys):
        m = _expert(lp, period, lay.tile_expert[i])
        if gather == "tile":
            at = jax.lax.dynamic_slice_in_dim(lay.row_token, i * TILE, TILE)
            xq, xs = (a[at] for a in src)
        else:
            xq, xs = (jax.lax.dynamic_slice_in_dim(a, i * TILE, TILE)
                      for a in src)
        y = _products(xq, xs, m, act, dtype)
        if not write:
            return ys + y
        return jax.lax.dynamic_update_slice_in_dim(ys, y, i * TILE, 0)

    init = jnp.zeros((rows + 1 if write else TILE, D), dtype)
    return jax.lax.fori_loop(0, lay.counted[0], body, init)


def combine(ys, lay, w):
    return jnp.sum(w[:, :, None] * ys[lay.dest].astype(jnp.float32), axis=1)


def bulk_form(x, idx, w, lp, held, period, act):
    """Rows gathered into expert order in one op over the static bound."""
    lay = hybrid.grouped_layout(idx, held, TILE)
    x_pad = jnp.concatenate([x, jnp.zeros((1, x.shape[1]), x.dtype)])
    src = tuple(a[lay.row_token] for a in _dynamic_int8(x_pad))
    return combine(loop(lay, src, lp, period, act, x.dtype, "bulk", True),
                   lay, w)


def add_hinted(x, idx, w, lp, held, period, act):
    """``experts_grouped``'s "add" with the add told that its rows are
    ascending and unique: padding rows get dummy rows N .. N + tile - 1."""
    N, D = x.shape
    lay = hybrid.grouped_layout(idx, held, TILE)
    rows = lay.row_token.shape[0]
    row_weight = jnp.zeros((rows,), jnp.float32).at[lay.dest.reshape(-1)].set(
        w.reshape(-1), mode="drop")
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
    src = _dynamic_int8(x_pad)
    dummy = N + jnp.arange(TILE, dtype=jnp.int32)

    def body(i, out):
        m = _expert(lp, period, lay.tile_expert[i])
        at = jax.lax.dynamic_slice_in_dim(lay.row_token, i * TILE, TILE)
        wt = jax.lax.dynamic_slice_in_dim(row_weight, i * TILE, TILE)
        xq, xs = (a[at] for a in src)
        y = wt[:, None] * _products(xq, xs, m, act, x.dtype
                                    ).astype(jnp.float32)
        return out.at[jnp.where(at < N, at, dummy)].add(
            y, indices_are_sorted=True, unique_indices=True)

    out = jax.lax.fori_loop(0, lay.counted[0], body,
                            jnp.zeros((N + TILE, D), jnp.float32))
    return out[:N]


def layout_by_sort(idx, held):
    """``grouped_layout``'s ``row_token`` with no scatter: the assignments
    sorted by expert, and each row reads its place in that order."""
    N, k = idx.shape
    landed = (idx >= 0) & (idx < held)
    key = jnp.where(landed, idx, held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                     dtype=jnp.int32)
    tiles = (counts + TILE - 1) // TILE
    last_tile = jnp.cumsum(tiles)
    n_tiles = -(-N * min(k, held) // TILE) + held
    tile_expert = jnp.minimum(
        jnp.searchsorted(last_tile, jnp.arange(n_tiles), side="right"),
        held - 1).astype(jnp.int32)
    e = jnp.repeat(tile_expert, TILE)
    r = jnp.arange(n_tiles * TILE, dtype=jnp.int32)
    rank = r - ((last_tile - tiles) * TILE)[e]
    j = (jnp.cumsum(counts) - counts)[e] + rank
    live = (rank < counts[e]) & (r < last_tile[-1] * TILE)
    return jnp.where(live, order[jnp.clip(j, 0, N * k - 1)] // k, N)


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
    candidates that lost and the stages alone."""
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
           "combine_form": hybrid.combine_form(N, k, held, g["n_experts"],
                                               TILE),
           "rows_bound": int(lay.row_token.shape[0]),
           "rows_landed": landed, "tiles_live": tiles, "slots": N * k,
           "fill_share_pct": round(100 * landed / (TILE * tiles), 2)}
    assert (jax.jit(lambda i: layout_by_sort(i, held))(idx)
            == lay.row_token).all()

    # The weights and the period are ARGUMENTS of every program.
    forms = {
        "scatter_form": (program("scatter_form", lambda lp, p, x, pr:
                                 scatter_form(x, pr, lp, k, period=p,
                                              act=act)),
                         (lp, period, x, probs)),
        "gather": (program("gather", lambda lp, p, x, i, w:
                           hybrid.experts_grouped(
                               x, i, w, lp, held, period=p, act=act,
                               combine="gather")[0]),
                   (lp, period, x, idx, w)),
        "add": (program("add", lambda lp, p, x, i, w:
                        hybrid.experts_grouped(
                            x, i, w, lp, held, period=p, act=act,
                            combine="add")[0]),
                (lp, period, x, idx, w)),
        "bulk_form": (program("bulk_form", lambda lp, p, x, i, w:
                              bulk_form(x, i, w, lp, held, p, act)),
                      (lp, period, x, idx, w)),
        "add_hinted": (program("add_hinted", lambda lp, p, x, i, w:
                               add_hinted(x, i, w, lp, held, p, act)),
                       (lp, period, x, idx, w)),
    }
    if not stages:
        del forms["bulk_form"], forms["add_hinted"]
    ref = forms["scatter_form"][0](*forms["scatter_form"][1])
    row["result_scale"] = float(jnp.max(jnp.abs(ref)))
    row["max_abs_diff"] = {
        f: float(jnp.max(jnp.abs(fn(*args) - ref)))
        for f, (fn, args) in forms.items() if f != "scatter_form"}
    row["ms"], row["ms_by_category"] = device_ms(forms)
    if not stages:
        del row["ms_by_category"]
    print(json.dumps(row), flush=True)
    if not stages:
        return row

    # -- the stages, each a program of its own -----------------------------
    x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)])
    quantise = program("quantise", lambda x: _dynamic_int8(x))
    src = quantise(x_pad)
    bulk = program("gather_bulk",
                   lambda s, lay: tuple(a[lay.row_token] for a in s))
    src_sorted = bulk(src, lay)

    def a_loop(tag, gather, write):
        return program(tag, lambda lp, p, s, lay: loop(
            lay, s, lp, p, act, x.dtype, gather, write))
    calls = {
        "layout": (program("layout", lambda i: hybrid.grouped_layout(
            i, held, TILE)), (idx,)),
        "layout_by_sort": (program("layout_by_sort", lambda i:
                                   layout_by_sort(i, held)), (idx,)),
        "quantise": (quantise, (x_pad,)),
        "gather_bulk": (bulk, (src, lay)),
        "loop_gather_products_write": (
            a_loop("loop_gather_products_write", "tile", True),
            (lp, period, src, lay)),
        "loop_products_write": (a_loop("loop_products_write", "bulk", True),
                                (lp, period, src_sorted, lay)),
        "loop_products": (a_loop("loop_products", "bulk", False),
                          (lp, period, src_sorted, lay)),
    }
    ys = calls["loop_gather_products_write"][0](lp, period, src, lay)
    calls["combine"] = (program("combine", combine), (ys, lay, w))
    stages, _ = device_ms(calls)
    stages["gather_in_loop"] = round(stages["loop_gather_products_write"]
                                     - stages["loop_products_write"], 4)
    stages["write"] = round(stages["loop_products_write"]
                            - stages["loop_products"], 4)
    tile_us = {k_: round(1e3 * stages[k_] / row["tiles_live"], 2)
               for k_ in ("gather_in_loop", "loop_products", "write")}
    out = {"cell": name, "stages_ms": stages, "per_tile_us": tile_us}
    print(json.dumps(out), flush=True)
    return {**row, **out}


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    only = sys.argv[1:]
    rows = []
    for name, g in GEOMETRIES.items():
        if only and name not in only:
            continue
        lp = weights(g)
        rows.append(probe(name, g, lp, stages=True))
        rows += [probe(name, {**g, "N": n}, lp, stages=False)
                 for n in SWEEP if n < g["N"]]
    out = {"device": dev.device_kind, "tile": TILE, "rows": rows}
    dest = Path("chiprun_out")
    dest.mkdir(exist_ok=True)
    (dest / "probe_experts.json").write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
