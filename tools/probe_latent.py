"""Chip probe of the latent attention kernels (ops/latent_attention.py) at
the served geometry: parity, then the time of one call of the write and of
the attention kernel as a prefill chunk (8k and 28k of context) and as a
decode step, with its share of the chip's roofline by
benchmark/reference/mistral4.py's cost functions; and the EXPANDED form of
the same chunk as the program could run it without a kernel of its own
(gather the row's pages, rebuild K and V through W_kvb, one XLA softmax
attention in bfloat16). ``chiprun -- python3 tools/probe_latent.py``;
results on stdout and in chiprun_out/probe_latent.json. Fails without a TPU.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.reference import mistral4 as ref          # noqa: E402
from benchmark.roofline import least_seconds, peaks_for  # noqa: E402
from llmapigateway_tpu.ops.latent_attention import (     # noqa: E402
    gather_latent, latent_insert_in_place, latent_paged_attention)

L, SLOTS, S, PAGE, H, W, WV, T = 12, 8, 32768, 256, 32, 320, 256, 512
NP = S // PAGE


def timed(fn, *args, n=10):
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
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    peaks = peaks_for(dev.device_kind)
    out = {"device": dev.device_kind}
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

    # -- a prefill chunk: one row of 512 tokens ending at 8k and at 28k ----
    q = (jax.random.normal(keys[1], (1, T, H, W), jnp.float32)
         * W ** -0.5).astype(jnp.bfloat16)
    new = jax.random.normal(keys[2], (1, T, W), jnp.bfloat16)
    for ctx in (8192, 28672):
        start = jnp.asarray([ctx - T], jnp.int32)
        flops, nbytes = ref.mla_prefill_cost(ctx - T, T, H, W, WV)
        for bt in (32, 64, 128):
            for ppb in (4,):
                f = jax.jit(lambda q, pool, st, bt=bt, ppb=ppb:
                            latent_paged_attention(
                                q, pool, table[:1], st, value_width=WV,
                                layer=layer, block_t=bt, pages_per_step=ppb))
                try:
                    ms = timed(f, q, pool, start)
                except Exception as e:      # a block the compiler refuses
                    rows.append({"kernel": "attend_prefill", "ctx": ctx,
                                 "bt": bt, "ppb": ppb,
                                 "error": repr(e)[:200]})
                    continue
                rows.append({"kernel": "attend_prefill", "ctx": ctx,
                             "bt": bt, "ppb": ppb,
                             **share(ms, flops, nbytes)})
                print(json.dumps(rows[-1]), flush=True)
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
    flops, nbytes = ref.mla_decode_cost(lens, H, W, WV)
    for ppb in (4,):
        f = jax.jit(lambda q, pool, st, ppb=ppb: latent_paged_attention(
            q, pool, table, st, value_width=WV, layer=layer,
            pages_per_step=ppb))
        rows.append({"kernel": "attend_decode", "ppb": ppb,
                     **share(timed(f, q1, pool, start), flops, nbytes)})
        print(json.dumps(rows[-1]), flush=True)
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
    out["rows"] = rows
    dest = Path("chiprun_out")
    dest.mkdir(exist_ok=True)
    (dest / "probe_latent.json").write_text(json.dumps(out, indent=1))
    return 0 if all(c["ok"] for c in out["parity"]) else 2


if __name__ == "__main__":
    sys.exit(main())
