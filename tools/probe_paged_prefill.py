"""Chip probe of the paged prefill kernel (ops/paged_attention.py
``paged_prefill_attention``): milliseconds a layer call — 32 calls in ONE
program on the layer-stacked int8 pool, the layer a traced index, the
host's clock around the program — at the served folds: Mistral 32/8 from
3 584 and from 512, Solar 64/8, SmallThinker 28/4 windowed and global,
Command A+ 128/8 windowed (17 live pages) and global (8k-16k), 512 tokens
a row. Each at the block shape the kernel's rule picks, and at 16 query
heads a KV head also at 32x1, 64x1 and 64x2 (``--shapes``: positions a
row-block x KV heads a program, forced by standing in for
``prefill_block_shape``); beside the time, its share of
``benchmark.roofline.paged_prefill_cost``'s floor and a digest of the
output's bits (a row's result must not depend on the shape, nor on the
tree). ``--tree DIR`` runs another checkout's kernel (a parent unpacked
under ``chiprun_tree/``) with this file, so parent | change is one call:

    chiprun -- bash -c 'python3 tools/probe_paged_prefill.py --tree \\
        chiprun_tree/parent && python3 tools/probe_paged_prefill.py'

Geometries by name on the command line (default: all); results on stdout
and in chiprun_out/probe_paged_prefill[.<tree's name>].json. Fails without
a TPU.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import statistics
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

PAGE, DH, T, CALLS, LAYERS = 256, 128, 512, 32, 2
# name: (query heads, KV heads, window, the rows' start positions).
GEOMETRIES = {
    "mistral-3584": (32, 8, 4096, [3584]),
    "mistral-512": (32, 8, 4096, [512]),
    "solar": (64, 8, 0, [0, 512, 1024, 3584]),
    "smallthinker-window": (28, 4, 4096, [5632, 2048]),
    "smallthinker-global": (28, 4, 0, [13312, 9728]),
    "command-a-window": (128, 8, 4096, [8192, 5632]),
    "command-a-global": (128, 8, 0, [8192, 15872]),
}
# Beside the rule's own shape, where a KV head has 16 query heads.
SHAPES_AT_16 = [(32, 1), (64, 1), (64, 2)]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("geometries", nargs="*", help=", ".join(GEOMETRIES))
    ap.add_argument("--tree", default=str(Path(__file__).resolve().parents[1]),
                    help="the checkout whose kernel runs (default: this one)")
    ap.add_argument("--shapes", default=None,
                    help="BTxHEADS,... in place of 32x1,64x1,64x2 at 128/8; "
                         "given, they run at every geometry named")
    args = ap.parse_args()
    if set(args.geometries) - set(GEOMETRIES):
        ap.error(f"geometries are {list(GEOMETRIES)}")
    sys.path.insert(0, str(Path(args.tree).resolve()))
    from benchmark.roofline import (AttnShape, least_seconds,
                                    paged_prefill_cost, peaks_for)
    from llmapigateway_tpu.ops import paged_attention as pa

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    peaks = peaks_for(dev.device_kind)
    forced = None if args.shapes is None else [
        tuple(int(n) for n in s.split("x")) for s in args.shapes.split(",")]
    rule = pa.prefill_block_shape
    rows_out = []
    for name in args.geometries or GEOMETRIES:
        H, KV, window, starts = GEOMETRIES[name]
        B, NP = len(starts), -(-(max(starts) + T) // PAGE)
        keys = jax.random.split(
            jax.random.PRNGKey(list(GEOMETRIES).index(name)), 5)
        P = B * NP + 1

        def side(kq, ks):
            return {"q": jax.random.randint(kq, (LAYERS, P, KV, PAGE, DH),
                                            -127, 128, jnp.int8),
                    "s": 0.01 + 0.02 * jax.random.uniform(
                        ks, (LAYERS, P, KV, 1, PAGE), jnp.float32)}
        pk, pv = side(keys[0], keys[1]), side(keys[2], keys[3])
        q = jax.random.normal(keys[4], (B, T, H, DH), jnp.bfloat16)
        table = jnp.arange(1, P, dtype=jnp.int32).reshape(B, NP)
        start = jnp.asarray(starts, jnp.int32)
        shape = AttnShape(1, H, KV, DH, window, 1, 4)
        cost = [sum(c) for c in zip(*(paged_prefill_cost(s, T, shape)
                                      for s in starts))]
        floor_s, bound = least_seconds(*cost, peaks)

        own = rule(T, H // KV, KV, PAGE, DH, 2, 1, True, 1)
        shapes = [own] + (forced if forced is not None
                          else SHAPES_AT_16 if H // KV == 16 else [])
        for bt, heads in dict.fromkeys(shapes):
            pa.prefill_block_shape = lambda *a, **k: (bt, heads)
            row = {"geometry": name, "heads": f"{H}/{KV}", "window": window,
                   "starts": starts, "bt": bt, "kv_heads": heads,
                   "rule": (bt, heads) == own}

            # Traced anew a shape: a function of its own, so that no
            # cached trace of another shape's stands in.
            def program(q, pk, pv):
                # 32 calls, each on the layer the loop's index names; the
                # outputs are summed so that none is dropped.
                def call(i, total):
                    out = pa.paged_prefill_attention(
                        q, pk, pv, table, start, layer=i % LAYERS,
                        window=window)
                    return total + out.astype(jnp.float32)
                return jax.lax.fori_loop(
                    0, CALLS, call, jnp.zeros((B, T, H * DH), jnp.float32))
            try:
                f = jax.jit(program)
                jax.block_until_ready(f(q, pk, pv))
                times = []
                for _ in range(5):
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(q, pk, pv))
                    times.append((time.perf_counter() - t0) / CALLS * 1e3)
                one = jax.jit(lambda q, pk, pv: pa.paged_prefill_attention(
                    q, pk, pv, table, start, layer=1, window=window))
                bits = np.asarray(one(q, pk, pv).astype(jnp.float32))
                ms = statistics.median(times)
                row.update(ms=round(ms, 4), ms_min=round(min(times), 4),
                           floor_ms=round(floor_s * 1e3, 4), bound=bound,
                           roofline_pct=round(100 * floor_s / (ms / 1e3), 2),
                           digest=hashlib.sha256(bits.tobytes()
                                                 ).hexdigest()[:16])
            except Exception as e:          # a block the compiler refuses
                row["error"] = repr(e)[:300]
            finally:
                pa.prefill_block_shape = rule
            rows_out.append(row)
            print(json.dumps(row), flush=True)
        del pk, pv, q
    # A row's bits are one, whatever the shape.
    same = all(len({r.get("digest") for r in rows_out
                    if r["geometry"] == g and "digest" in r}) <= 1
               for g in GEOMETRIES)
    print(json.dumps({"bits_equal_across_shapes": same}), flush=True)
    dest = Path(__file__).resolve().parents[1] / "chiprun_out"
    dest.mkdir(exist_ok=True)
    tag = "" if Path(args.tree).resolve() == Path(__file__).resolve(
        ).parents[1] else "." + Path(args.tree).name
    (dest / f"probe_paged_prefill{tag}.json").write_text(json.dumps(
        {"device": dev.device_kind, "tree": args.tree, "rows": rows_out,
         "bits_equal_across_shapes": same}, indent=1))
    return 0 if same else 2


if __name__ == "__main__":
    sys.exit(main())
