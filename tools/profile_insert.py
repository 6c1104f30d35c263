"""Microbench KV-insert strategies for the decode step (T=1).

The engine's vmap(dynamic_update_slice) insert lowers to a TPU scatter that
costs ~5.5 ms/step at L22 B8 KV4 S1024 Dh64.
Candidates measured here, each as a scan over L layers like the model's
layer scan, 32-step burst:

  vmap_dus   — current (models/llama.py insert_kv)
  onehot     — masked select over the full cache
  stacked    — ONE dynamic_update_slice per (row) on the [L,...] stacked
               cache outside the layer scan (all layers at once)
  pallas     — aliased pallas kernel writing just the touched lane
"""
from __future__ import annotations

import argparse
import functools
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def note(msg):
    print(msg, file=sys.stderr, flush=True)


def insert_vmap_dus(layer_k, k_new, lengths):
    def insert(cache_row, new_row, offset):
        return jax.lax.dynamic_update_slice(
            cache_row, new_row.transpose(1, 0, 2).astype(cache_row.dtype),
            (0, offset, 0))
    return jax.vmap(insert)(layer_k, k_new, lengths)


def insert_onehot(layer_k, k_new, lengths):
    B, KV, S, Dh = layer_k.shape
    hot = (jnp.arange(S)[None, :] == lengths[:, None])       # [B, S]
    newv = k_new.transpose(0, 2, 1, 3)                        # [B, KV, 1, Dh]
    return jnp.where(hot[:, None, :, None], newv.astype(layer_k.dtype),
                     layer_k)


def _insert_kernel(len_ref, new_ref, cache_ref, out_ref):
    # One program per (b, kv): out block is the 8-row lane containing
    # position lengths[b]; the aliased cache makes every untouched byte
    # free. Read-modify-write the 8 rows, replacing row lengths[b] % 8.
    b = pl.program_id(0)
    off = len_ref[b] % 8
    row = jax.lax.broadcasted_iota(jnp.int32, cache_ref[0, 0].shape, 0)
    out_ref[0, 0] = jnp.where(row == off, new_ref[0, 0], cache_ref[0, 0])


def insert_pallas(layer_k, k_new, lengths):
    B, KV, S, Dh = layer_k.shape
    newv = k_new.transpose(0, 2, 1, 3)                        # [B, KV, 1, Dh]

    def idx(b, h, lens):
        return b, h, lens[b] // 8, 0

    return pl.pallas_call(
        _insert_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, KV),
            in_specs=[
                pl.BlockSpec((1, 1, 1, Dh), lambda b, h, lens: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, 8, Dh), idx),
            ],
            out_specs=pl.BlockSpec((1, 1, 8, Dh), idx),
        ),
        out_shape=jax.ShapeDtypeStruct(layer_k.shape, layer_k.dtype),
        input_output_aliases={2: 0},   # cache input -> output
        interpret=jax.default_backend() != "tpu",
    )(lengths.astype(jnp.int32), jnp.broadcast_to(
        newv.astype(layer_k.dtype), (B, KV, 1, Dh)), layer_k)


def run_scan(name, insert_fn, L, B, KV, S, Dh, burst, reps):
    k_cache = jnp.zeros((L, B, KV, S, Dh), jnp.bfloat16)
    v_cache = jnp.zeros((L, B, KV, S, Dh), jnp.bfloat16)
    k_new = jnp.ones((B, 1, KV, Dh), jnp.bfloat16)
    lengths = jnp.full((B,), 128, jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def burst_fn(k_cache, v_cache, lengths):
        def step(carry, _):
            k_cache, v_cache, lengths = carry

            def layer(x, scanned):
                lk, lv = scanned
                lk = insert_fn(lk, k_new, lengths)
                lv = insert_fn(lv, k_new, lengths)
                # touch something so nothing is DCE'd
                return x + lk[0, 0, 0, 0].astype(jnp.float32), (lk, lv)
            acc, (k_cache, v_cache) = jax.lax.scan(
                layer, jnp.float32(0), (k_cache, v_cache))
            return (k_cache, v_cache, lengths + 1), acc
        (k_cache, v_cache, lengths), accs = jax.lax.scan(
            step, (k_cache, v_cache, lengths), None, length=burst)
        return accs, k_cache, v_cache

    t0 = time.monotonic()
    accs, k_cache, v_cache = burst_fn(k_cache, v_cache, lengths)
    np.asarray(accs)
    compile_s = time.monotonic() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        accs, k_cache, v_cache = burst_fn(k_cache, v_cache, lengths)
        np.asarray(accs)
        best = min(best, time.monotonic() - t0)
    note(f"{name:10s}: {1000*best/burst:8.3f} ms/step "
         f"(compile {compile_s:.1f}s)")


def run_stacked(L, B, KV, S, Dh, burst, reps):
    """All-layers-at-once variant: insert into the [L,...] stacked cache
    OUTSIDE the layer scan — one vmap(DUS) per step instead of per layer
    (the layer scan would read the pre-updated cache; for decode the new
    token IS attended, so the model would need the per-layer k_new handed
    separately — measured here purely for the lowering cost)."""
    k_cache = jnp.zeros((L, B, KV, S, Dh), jnp.bfloat16)
    k_new = jnp.ones((L, B, 1, KV, Dh), jnp.bfloat16)
    lengths = jnp.full((B,), 128, jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def burst_fn(k_cache, lengths):
        def step(carry, _):
            k_cache, lengths = carry

            def insert(cache_row, new_row, offset):
                # cache_row [L, KV, S, Dh]; new_row [L, 1, KV, Dh]
                return jax.lax.dynamic_update_slice(
                    cache_row, new_row.transpose(0, 2, 1, 3),
                    (0, 0, offset, 0))
            k_cache = jax.vmap(insert, in_axes=(1, 1, 0), out_axes=1)(
                k_cache, k_new, lengths)
            return (k_cache, lengths + 1), k_cache[0, 0, 0, 0, 0].astype(
                jnp.float32)
        (k_cache, lengths), accs = jax.lax.scan(
            step, (k_cache, lengths), None, length=burst)
        return accs, k_cache

    t0 = time.monotonic()
    accs, k_cache = burst_fn(k_cache, lengths)
    np.asarray(accs)
    compile_s = time.monotonic() - t0
    best = float("inf")
    for _ in range(reps):
        t0 = time.monotonic()
        accs, k_cache = burst_fn(k_cache, lengths)
        np.asarray(accs)
        best = min(best, time.monotonic() - t0)
    note(f"{'stacked':10s}: {1000*best/burst:8.3f} ms/step "
         f"(k only! x2 for k+v; compile {compile_s:.1f}s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=22)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--burst", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    note(f"backend: {jax.default_backend()}")
    dims = (args.layers, args.batch, args.kv_heads, args.seq, args.head_dim)
    for name, fn in [("vmap_dus", insert_vmap_dus),
                     ("onehot", insert_onehot),
                     ("pallas", insert_pallas)]:
        run_scan(name, fn, *dims, args.burst, args.reps)
    run_stacked(*dims, args.burst, args.reps)


if __name__ == "__main__":
    main()
