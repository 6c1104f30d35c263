"""Chip probe of learned sparse attention (ops/sparse_attention.py) at the
served geometry of ``keye-vl2-30b-ep4`` (12 layers, 8 slots of 32,768, page
256, 32 query / 4 KV heads of 128, 16 index heads of 64, 2,048 keys kept):
the time of ONE layer's call of each piece, as a decode step over all 8
slots and as a prefill chunk of ``--rows`` rows of 512 — the index scores,
the plain selection (``lax.top_k``: a decode step's list, and that list as
a chunk's mask) beside the chunk kernel that scores and selects in one, the
gathered read, the masked page walk beside the unmasked one.
``chiprun -- python3 tools/probe_sparse_attention.py``; results on stdout
and in chiprun_out/probe_sparse_attention.json. Fails without a TPU."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from llmapigateway_tpu.ops import sparse_attention as sa       # noqa: E402
from llmapigateway_tpu.ops.paged_attention import (            # noqa: E402
    paged_prefill_attention)

L, SLOTS, S, PAGE, H, KV, DH, J, W, K, T = (12, 8, 32768, 256, 32, 4, 128,
                                            16, 64, 2048, 512)
NP = S // PAGE


def timed(fn, *args, n=10):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / n * 1e3, 4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out: dict = {"device": jax.devices()[0].device_kind, "ms": {}}
    ms = out["ms"]
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    pages = SLOTS * NP + 1
    pool_k = jax.random.normal(keys[0], (L, pages, KV, PAGE, DH), jnp.bfloat16)
    pool_v = jax.random.normal(keys[1], (L, pages, KV, PAGE, DH), jnp.bfloat16)
    pool_i = jax.random.normal(keys[2], (L, pages, W, PAGE), jnp.bfloat16)
    table = jnp.arange(1, pages, dtype=jnp.int32).reshape(SLOTS, NP)
    layer = jnp.int32(5)
    for kind, B, tt, ctx in (("decode", SLOTS, 1, 20000),
                             ("prefill", args.rows, T, 16384)):
        fn = sa.SparseAttention(table[:B], S, K, "pallas")
        q = jax.random.normal(keys[3], (B, tt, H, DH), jnp.bfloat16)
        qi = jax.random.normal(keys[4], (B, tt, J, W), jnp.bfloat16)
        w = jax.random.normal(keys[5], (B, tt, J), jnp.float32) / 32
        start = jnp.full((B,), ctx, jnp.int32)
        scores = jax.jit(lambda qi, w, p: fn.scores(qi, w, p, layer))
        ms[f"{kind}.index_scores"] = timed(scores, qi, w, pool_i)
        got = scores(qi, w, pool_i)
        seen = (jnp.arange(S)[None, None, :]
                <= ctx + jnp.arange(tt)[None, :, None])
        seen = jnp.broadcast_to(seen, got.shape)
        plain = jax.jit(lambda s, m: sa.top_mask(s, m, K))
        ms[f"{kind}.top_mask_plain"] = timed(plain, got, seen)
        keep = plain(got, seen)
        out[f"{kind}.kept"] = int(keep.sum(-1).max())
        if kind == "decode":
            listed = jax.jit(lambda s, m: sa.top_positions(
                s[:, 0], m[:, 0], K))
            ms["decode.top_positions"] = timed(listed, got, seen)
            positions, total = listed(got, seen)
            phys = jnp.take_along_axis(table, positions // PAGE, axis=1)
            offset = positions % PAGE
            gathered = jax.jit(lambda q, pk, pv, ph, off, tot:
                               sa.gathered_decode_attention(
                                   q[:, 0], pk, pv, layer, ph, off, tot))
            ms["decode.gather_and_attend"] = timed(
                gathered, q, pool_k, pool_v, phys, offset, total)
            whole = jax.jit(lambda qi, w, q, pool: fn.attend(
                q, pool, layer, start, fn.select(qi, w, pool[2], layer,
                                                 start)))
            ms["decode.select_and_attend"] = timed(
                whole, qi, w, q, (pool_k, pool_v, pool_i))
        else:
            kernel = jax.jit(lambda qi, w, p: sa.index_select(
                qi, w, p, table[:B], start, layer=layer, topk=K))
            ms["prefill.index_select_kernel"] = timed(kernel, qi, w, pool_i)
            same = kernel(qi, w, pool_i).astype(bool) == keep
            out["prefill.kernel_differs_at"] = int((~same).sum())
            for name, mask in (("masked", keep), ("unmasked", None)):
                walk = jax.jit(lambda q, pk, pv, keep=mask:
                               paged_prefill_attention(
                                   q, pk, pv, table[:B], start, layer=layer,
                                   keep=keep))
                ms[f"prefill.walk_{name}"] = timed(walk, q, pool_k, pool_v)
        print(json.dumps(out), flush=True)
    path = Path("chiprun_out/probe_sparse_attention.json")
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
