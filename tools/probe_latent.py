"""Chip probe of the latent attention kernels (ops/latent_attention.py) at
the served geometry: parity, then the time of one call of the write and of
the attention kernel as a prefill chunk (8k and 28k of context) and as a
decode step (both latent cells' head counts), with its share of the chip's
roofline by benchmark/reference/mistral4.py's cost functions; and the
EXPANDED form of the same chunk as the program could run it without a
kernel of its own (gather the row's pages, rebuild K and V through W_kvb,
one XLA softmax attention in bfloat16). Since PR 57 the chunk and the
decode rows are timed with the softmax update spanning 1, 2 and 4 pages
(``update_span`` replaced for the call: the served rule's pick has
``"served": true``) and once with the update between the two dots stubbed
to a cast (``"kernel": "attend_*_dots_only"``: what the dots, the copies
and the accumulator's add cost alone; its output is no attention).
``--tree DIR`` times another checkout's kernels with this file (a parent
unpacked under ``chiprun_tree/``; one before PR 57 has one row a shape):

    chiprun -- bash -c 'python3 tools/probe_latent.py --tree \\
        chiprun_tree/parent && python3 tools/probe_latent.py'

Results on stdout and in chiprun_out/probe_latent[.<tree's name>].json.
Fails without a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

HERE = Path(__file__).resolve().parents[1]

L, SLOTS, S, PAGE, H, W, WV, T = 12, 8, 32768, 256, 32, 320, 256, 512
NP = S // PAGE
# gigachat35-reason's call: 64 heads over 576 / 512, 32 slots of 80 pages.
H8, W8, WV8, SLOTS8, NP8 = 64, 576, 512, 32, 80


def timed(fn, *args, n=20):
    out = fn(*args)
    jax.block_until_ready(out)
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=str(HERE),
                    help="the checkout whose kernels are timed")
    args = ap.parse_args()
    sys.path.insert(0, str(Path(args.tree).resolve()))
    from benchmark.reference import mistral4 as ref
    from benchmark.roofline import least_seconds, peaks_for
    from llmapigateway_tpu.ops import latent_attention as la
    gather_latent, latent_insert_in_place = (la.gather_latent,
                                             la.latent_insert_in_place)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    peaks = peaks_for(dev.device_kind)
    out = {"device": dev.device_kind, "tree": args.tree}
    out["parity"] = ref.latent_kernel_parity(
        heads=H, width=W, value_width=WV, page=PAGE, interpret=False)
    print(json.dumps(out["parity"]), flush=True)
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    pool = jax.random.normal(keys[0], (L, SLOTS * NP + 1, W, PAGE),
                             jnp.bfloat16)
    table = jnp.arange(1, SLOTS * NP + 1, dtype=jnp.int32).reshape(SLOTS, NP)
    layer = jnp.int32(5)
    rows = []

    def share(ms, flops, nbytes):
        least, side = least_seconds(flops, nbytes, peaks)
        return {"ms": round(ms, 4), "roofline_pct": round(
            100 * least / (ms / 1e3), 2), "bound": side,
            "tflops": round(flops / (ms / 1e3) / 1e12, 2),
            "gbps": round(nbytes / (ms / 1e3) / 1e9, 1)}

    served_span = getattr(la, "update_span", None)
    served_update = getattr(la, "_softmax_update", None)

    def dots_only(scores, m, l):
        return scores, jnp.ones_like(m), m, l

    def attend_rows(kernel, cost, q, pool, tbl, st, wv, lyr, bt=None,
                    served_only=False, **tags):
        """Time ``latent_paged_attention(q, pool, tbl, st)``: a tree from
        before PR 57 as it is; else with the update spanning 1, 2 and 4
        pages and with the dots alone (``served_only``: the rule's own
        pick, nothing replaced). The rule and the update are put back
        after each row."""
        K, T, heads, width = q.shape
        bt = bt or la.latent_block_t(T, heads)
        if served_span is None:
            forms = [(None, False)]
        else:
            served = served_span(bt * heads, 4, PAGE, width, wv, 2)
            forms = [(served, False)] if served_only else [
                (1, False), (2, False), (4, False), (4, True)]
        for span, stub in forms:
            row = {"kernel": kernel + ("_dots_only" if stub else ""),
                   **tags, "bt": bt}
            if span is not None:
                la.update_span = lambda *a, span=span: span
                row.update(span=span, served=not stub and span == served)
            if stub:
                la._softmax_update = dots_only
            f = jax.jit(lambda q, pool, st: la.latent_paged_attention(
                q, pool, tbl, st, value_width=wv, layer=lyr, block_t=bt))
            try:
                row.update(share(timed(f, q, pool, st), *cost))
            except Exception as e:      # a form the compiler refuses
                row["error"] = repr(e)[:200]
            finally:
                if served_span is not None:
                    la.update_span = served_span
                    la._softmax_update = served_update
            rows.append(row)
            print(json.dumps(row), flush=True)

    # -- a prefill chunk: one row of 512 tokens ending at 8k and at 28k ----
    q = (jax.random.normal(keys[1], (1, T, H, W), jnp.float32)
         * W ** -0.5).astype(jnp.bfloat16)
    new = jax.random.normal(keys[2], (1, T, W), jnp.bfloat16)
    for ctx in (8192, 28672):
        start = jnp.asarray([ctx - T], jnp.int32)
        cost = ref.mla_prefill_cost(ctx - T, T, H, W, WV)
        attend_rows("attend_prefill", cost, q, pool, table[:1], start, WV,
                    layer, ctx=ctx)
        for bt in (32, 128):
            attend_rows("attend_prefill", cost, q, pool, table[:1], start,
                        WV, layer, bt=bt, served_only=True, ctx=ctx)
        w = jax.jit(lambda pool, new, st: latent_insert_in_place(
            pool, new, table[:1], st, None, layer=layer), donate_argnums=0)
        pool = w(pool, new, start)
        jax.block_until_ready(pool)
        t0 = time.perf_counter()
        for _ in range(10):
            pool = w(pool, new, start)
        jax.block_until_ready(pool)
        rows.append({"kernel": "write_chunk", "ctx": ctx, "ms": round(
            (time.perf_counter() - t0) / 10 * 1e3, 4)})
        print(json.dumps(rows[-1]), flush=True)

        # The expanded form without a kernel of its own.
        wkvb = jax.random.normal(keys[3], (WV, H, 192), jnp.bfloat16) / 16
        qn = q[..., :64]
        qr = q[..., 256:]

        def gather(pool):
            return gather_latent(pool, table[:1], ctx, layer=layer)

        def rebuild(dense):
            return jnp.einsum("bsc,chx->bshx", dense[..., :WV], wkvb,
                              preferred_element_type=jnp.float32
                              ).astype(jnp.bfloat16)

        def attend(dense, kv):
            scores = (jnp.einsum("bthn,bshn->bhts", qn, kv[..., :64],
                                 preferred_element_type=jnp.float32)
                      + jnp.einsum("bthr,bsr->bhts", qr, dense[..., WV:],
                                   preferred_element_type=jnp.float32))
            q_pos = (ctx - T) + jnp.arange(T)
            seen = jnp.arange(ctx)[None, :] <= q_pos[:, None]
            p = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30),
                               axis=-1).astype(jnp.bfloat16)
            return jnp.einsum("bhts,bshv->bthv", p, kv[..., 64:],
                              preferred_element_type=jnp.float32
                              ).astype(jnp.bfloat16)
        g, r, a = jax.jit(gather), jax.jit(rebuild), jax.jit(attend)
        dense = g(pool)
        kv = r(dense)
        whole = jax.jit(lambda pool: attend(gather(pool),
                                            rebuild(gather(pool))))
        rows.append({"kernel": "expanded_xla", "ctx": ctx,
                     "gather_ms": round(timed(g, pool), 4),
                     "rebuild_ms": round(timed(r, dense), 4),
                     "attend_ms": round(timed(a, dense, kv), 4),
                     "whole_ms": round(timed(whole, pool), 4)})
        print(json.dumps(rows[-1]), flush=True)
        del dense, kv

    # -- a decode step: 8 slots at the cycle's lengths ----------------------
    lens = [8192, 20480, 12288, 28672, 16384, 24576, 8192, 20480]
    start = jnp.asarray(lens, jnp.int32)
    q1 = (jax.random.normal(keys[1], (SLOTS, 1, H, W), jnp.float32)
          * W ** -0.5).astype(jnp.bfloat16)
    new1 = jax.random.normal(keys[2], (SLOTS, 1, W), jnp.bfloat16)
    attend_rows("attend_decode", ref.mla_decode_cost(lens, H, W, WV), q1,
                pool, table, start, WV, layer)
    w = jax.jit(lambda pool, new, st: latent_insert_in_place(
        pool, new, table, st, None, layer=layer), donate_argnums=0)
    pool = w(pool, new1, start)
    jax.block_until_ready(pool)
    t0 = time.perf_counter()
    for _ in range(10):
        pool = w(pool, new1, start)
    jax.block_until_ready(pool)
    rows.append({"kernel": "write_decode", "ms": round(
        (time.perf_counter() - t0) / 10 * 1e3, 4)})
    print(json.dumps(rows[-1]), flush=True)
    del pool

    # -- gigachat35-reason's geometry: a chunk at 2k, a decode step ---------
    pool8 = jax.random.normal(keys[0], (1, SLOTS8 * NP8 + 1, W8, PAGE),
                              jnp.bfloat16)
    table8 = jnp.arange(1, SLOTS8 * NP8 + 1, dtype=jnp.int32).reshape(
        SLOTS8, NP8)
    q8 = (jax.random.normal(keys[1], (1, T, H8, W8), jnp.float32)
          * W8 ** -0.5).astype(jnp.bfloat16)
    for ctx in (2048, 8192):
        attend_rows("attend_prefill_576x64",
                    ref.mla_prefill_cost(ctx - T, T, H8, W8, WV8), q8, pool8,
                    table8[:1], jnp.asarray([ctx - T], jnp.int32), WV8,
                    jnp.int32(0), ctx=ctx)
    lens8 = [512 + 112 * i for i in range(SLOTS8)]          # 512 .. 3,984
    q81 = (jax.random.normal(keys[1], (SLOTS8, 1, H8, W8), jnp.float32)
           * W8 ** -0.5).astype(jnp.bfloat16)
    attend_rows("attend_decode_576x64",
                ref.mla_decode_cost(lens8, H8, W8, WV8), q81, pool8, table8,
                jnp.asarray(lens8, jnp.int32), WV8, jnp.int32(0))

    out["rows"] = rows
    dest = Path("chiprun_out")
    dest.mkdir(exist_ok=True)
    tag = "" if Path(args.tree).resolve() == HERE else "." + Path(
        args.tree).name
    (dest / f"probe_latent{tag}.json").write_text(json.dumps(out, indent=1))
    return 0 if all(c["ok"] for c in out["parity"]) else 2


if __name__ == "__main__":
    sys.exit(main())
