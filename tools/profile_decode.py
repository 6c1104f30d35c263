"""Ablation profiler for the decode step (VERDICT r2 item 1).

Times the engine's fused decode-burst scan with components selectively
disabled, on whatever backend is live. Differences between variants
attribute the per-step milliseconds to attention / KV-insert / sampling /
matmuls without needing a device trace. Each variant compiles its own
program; timings exclude compile.

Usage: python tools/profile_decode.py [--preset tinyllama-1.1b]
           [--batch 8] [--seq 1024] [--burst 32] [--reps 3]
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np


def note(msg):
    print(msg, file=sys.stderr, flush=True)


def build(args):
    from llmapigateway_tpu.models import llama
    from llmapigateway_tpu.models.config import get_preset

    c = get_preset(args.preset)
    key = jax.random.PRNGKey(0)
    t0 = time.monotonic()

    def init(k):
        p = llama.init_params(c, k, dtype=jnp.bfloat16)
        if args.quant:
            from llmapigateway_tpu.models.quant import quantize_tree
            p = quantize_tree(p, c, args.quant)
        return p
    params = jax.jit(init)(key)
    jax.block_until_ready(params)
    note(f"params on device in {time.monotonic() - t0:.1f}s"
         + (f" ({args.quant} weights)" if args.quant else ""))
    cache = llama.KVCache.create(c, args.batch, args.seq,
                                 kv_quant="int8" if args.kv_quant else "")
    return c, params, cache


def make_step(c, variant: str, attention_fn=None):
    """One decode step with parts ablated. Variants:
    full          — forward + sample (the engine's real step)
    greedy        — forward + argmax (no sampling machinery)
    nosample      — forward only, next token constant
    noattn        — attention replaced by zeros (no insert, no attention)
    noinsert      — attention over the cache WITHOUT the per-step insert
    nomlp         — mlp replaced by identity
    """
    from llmapigateway_tpu.engine.sampling import sample
    from llmapigateway_tpu.models import llama

    def zero_attn(q, k_new, v_new, layer_k, layer_v, lengths, active=None):
        B, T, H, Dh = q.shape
        return jnp.zeros((B, T, H * Dh), q.dtype), layer_k, layer_v

    def noinsert_attn(q, k_new, v_new, layer_k, layer_v, lengths,
                      active=None):
        out, _, _ = llama.dense_cache_attention(
            q, k_new, v_new, layer_k, layer_v, lengths, active)
        return out, layer_k, layer_v

    attn = attention_fn
    if variant == "noattn":
        attn = zero_attn
    elif variant == "noinsert":
        attn = noinsert_attn

    mlp = None
    if variant == "nomlp":
        def mlp(h, lp):
            return h

    def one_step(params, cache, tokens, lengths, active, samp, key):
        kwargs = {}
        if attn is not None:
            kwargs["attention_fn"] = attn
        if mlp is not None:
            kwargs["mlp_fn"] = mlp
        logits, cache = llama.forward(
            params, c, tokens[:, None], lengths, cache, active=active,
            **kwargs)
        if variant == "full":
            nt = sample(logits[:, 0, :], samp, key)
        elif variant in ("greedy",):
            nt = jnp.argmax(logits[:, 0, :], axis=-1).astype(jnp.int32)
        else:
            nt = tokens
        return nt, jnp.where(active, lengths + 1, lengths), cache

    return one_step


def time_variant(c, params, cache, args, variant, attention_fn=None):
    from llmapigateway_tpu.engine.sampling import SamplingParams

    one_step = make_step(c, variant, attention_fn)
    B = args.batch

    @partial(jax.jit, donate_argnums=(1,))
    def burst(params, cache, tokens, lengths, active, samp, key):
        def body(carry, _):
            cache, tokens, lengths, key = carry
            key, sub = jax.random.split(key)
            nt, nl, cache = one_step(params, cache, tokens, lengths,
                                     active, samp, sub)
            return (cache, nt, nl, key), nt
        (cache, tokens, lengths, key), toks = jax.lax.scan(
            body, (cache, tokens, lengths, key), None, length=args.burst)
        return toks, cache

    tokens = jnp.zeros((B,), jnp.int32)
    lengths = jnp.full((B,), 128, jnp.int32)
    active = jnp.ones((B,), bool)
    samp = SamplingParams(temperature=jnp.full((B,), 0.7, jnp.float32),
                          top_p=jnp.full((B,), 0.95, jnp.float32),
                          top_k=jnp.full((B,), 40, jnp.int32))
    key = jax.random.PRNGKey(1)

    t0 = time.monotonic()
    toks, cache = burst(params, cache, tokens, lengths, active, samp, key)
    np.asarray(toks)
    compile_s = time.monotonic() - t0

    best = float("inf")
    for _ in range(args.reps):
        t0 = time.monotonic()
        toks, cache = burst(params, cache, tokens, lengths, active, samp, key)
        np.asarray(toks)
        best = min(best, time.monotonic() - t0)
    ms_step = 1000.0 * best / args.burst
    note(f"{variant:10s}: {ms_step:8.3f} ms/step   "
         f"(burst {1000*best:.1f} ms, compile {compile_s:.1f}s)")
    return ms_step, cache


def time_weights_stream(c, params, args):
    """Pure weight-streaming roofline probe: a scan over the stacked
    layers running ONLY the seven projection dots (plus the lm_head) at
    the decode step's exact shapes, no attention/cache/norms/sampling.
    The measured ms/step is the best step time these dots can achieve
    on this chip — full-step minus this is glue; this minus
    bytes/HBM-peak is the dots' own streaming inefficiency (the lever
    fused/layout work would pull). Every projection output feeds the
    carry (or an aux scalar) so XLA cannot dead-code any weight read."""
    from llmapigateway_tpu.models.quant import head_matmul, is_quantized, mm

    B = args.batch

    @jax.jit
    def stream_burst(params, x0):
        def one_pass(x):
            def body(carry, lp):
                h, aux = carry
                q = mm(h, lp["wq"])
                k = mm(h, lp["wk"])
                v = mm(h, lp["wv"])
                o = mm(q, lp["wo"])
                g = mm(h, lp["wg"])
                u = mm(h, lp["wu"])
                d = mm(g * u, lp["wd"])
                return (h + o + d, aux + k.sum() + v.sum()), None
            (h, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)),
                                       params["layers"])
            head = params.get("lm_head", params.get("lm_head_q8",
                                                    params["embed"]))
            logits = head_matmul(h[:, None, :], head)
            return h, aux + logits.sum()

        # Burst the passes like the decode variants do — a single pass
        # can be shorter than one dispatch and would time the dispatch,
        # not the dots. The carry feeds forward so no
        # pass can be elided or overlapped away.
        def step(carry, _):
            x, tot = carry
            h, s = one_pass(x)
            return ((h * 1e-3).astype(x.dtype), tot + s), None
        (x, tot), _ = jax.lax.scan(step, (x0, jnp.float32(0)), None,
                                   length=args.burst)
        return tot

    x = jnp.ones((B, c.d_model), jnp.bfloat16)
    out = stream_burst(params, x)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(args.reps):
        t0 = time.monotonic()
        jax.block_until_ready(stream_burst(params, x))
        best = min(best, time.monotonic() - t0)
    best = best / args.burst

    def leaf_bytes(w):
        if is_quantized(w):
            return w["q"].nbytes + w["s"].nbytes
        return w.nbytes
    keys = ("wq", "wk", "wv", "wo", "wg", "wu", "wd")
    nbytes = sum(leaf_bytes(params["layers"][k]) for k in keys)
    head = params.get("lm_head", params.get("lm_head_q8",
                                            params["embed"]))
    nbytes += leaf_bytes(head)
    ms = 1000.0 * best
    gbps = nbytes / best / 1e9
    note(f"{'weights_stream':10s}: {ms:8.3f} ms/step   "
         f"({nbytes / 1e9:.2f} GB of weights -> {gbps:.0f} GB/s achieved)")
    return ms


def time_weights_stream_fused(c, params, args):
    """The same weight bytes as :func:`time_weights_stream`, streamed
    through FUSED projections — wqkv = [wq|wk|wv] and wgu = [wg|wu]
    concatenated on the output axis (6 dots/layer instead of 7, wider
    contiguous streams). The delta vs the unfused probe is the entire
    case for (or against) building fused projections into the model:
    if the dots stream at the same rate either way, the model feature
    buys nothing and is not built."""
    from llmapigateway_tpu.models.quant import head_matmul, is_quantized, mm

    B = args.batch
    lay = params["layers"]

    def cat(ws):
        if is_quantized(ws[0]):
            return {"q": jnp.concatenate([w["q"] for w in ws], axis=-1),
                    "s": jnp.concatenate([w["s"] for w in ws], axis=-1)}
        return jnp.concatenate(ws, axis=-1)

    fused = {"wqkv": cat([lay["wq"], lay["wk"], lay["wv"]]),
             "wo": lay["wo"], "wgu": cat([lay["wg"], lay["wu"]]),
             "wd": lay["wd"]}
    fused = jax.tree.map(jnp.asarray, fused)
    jax.block_until_ready(fused)

    def out_width(w):
        return (w["q"] if is_quantized(w) else w).shape[-1]
    D = out_width(lay["wq"])        # q slice of the fused z
    F = out_width(lay["wg"])        # gate slice of the fused gu

    @jax.jit
    def stream_burst(fused, head, x0):
        def one_pass(x):
            def body(carry, lp):
                h, aux = carry
                z = mm(h, lp["wqkv"])
                q = z[:, :D]
                o = mm(q, lp["wo"])
                gu = mm(h, lp["wgu"])
                d = mm(gu[:, :F] * gu[:, F:], lp["wd"])
                return (h + o + d, aux + z[:, D:].sum()), None
            (h, aux), _ = jax.lax.scan(body, (x, jnp.float32(0)), fused)
            logits = head_matmul(h[:, None, :], head)
            return h, aux + logits.sum()

        def step(carry, _):
            x, tot = carry
            h, s = one_pass(x)
            return ((h * 1e-3).astype(x.dtype), tot + s), None
        (x, tot), _ = jax.lax.scan(step, (x0, jnp.float32(0)), None,
                                   length=args.burst)
        return tot

    head = params.get("lm_head", params.get("lm_head_q8", params["embed"]))
    x = jnp.ones((B, D), jnp.bfloat16)
    out = stream_burst(fused, head, x)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(args.reps):
        t0 = time.monotonic()
        jax.block_until_ready(stream_burst(fused, head, x))
        best = min(best, time.monotonic() - t0)
    ms = 1000.0 * best / args.burst
    note(f"{'fused_stream':10s}: {ms:8.3f} ms/step   "
         f"(wqkv+wgu concatenated, 6 dots/layer)")
    return ms


def time_sort_alone(args, V):
    x = jax.random.normal(jax.random.PRNGKey(0), (args.batch, V), jnp.float32)

    @jax.jit
    def burst_sort(x):
        def body(carry, _):
            s = jnp.sort(carry, axis=-1)[:, ::-1]
            return carry + s[:, :1] * 0, s[:, 0]
        carry, outs = jax.lax.scan(body, x, None, length=args.burst)
        return outs

    out = burst_sort(x)
    np.asarray(out)
    best = float("inf")
    for _ in range(args.reps):
        t0 = time.monotonic()
        np.asarray(burst_sort(x))
        best = min(best, time.monotonic() - t0)
    ms = 1000.0 * best / args.burst
    note(f"{'sort alone':10s}: {ms:8.3f} ms/step   ([B={args.batch}, V={V}])")
    return ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tinyllama-1.1b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--burst", type=int, default=32)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--variants", default="full,greedy,nosample,noinsert,"
                    "noattn,nomlp")
    ap.add_argument("--pallas", action="store_true",
                    help="also run `full` with the pallas attention_fn")
    ap.add_argument("--quant", nargs="?", const="int8", default="",
                    choices=("", "int8", "int4"),
                    help="weight quantization (bare flag = int8)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache")
    args = ap.parse_args()

    note(f"backend: {jax.default_backend()} {jax.devices()}")
    c, params, cache = build(args)

    results = {}
    for v in args.variants.split(","):
        results[v], cache = time_variant(c, params, cache, args, v)
    if args.pallas:
        from llmapigateway_tpu.ops import make_cache_attention_fn
        results["pallas"], cache = time_variant(
            c, params, cache, args, "full",
            attention_fn=make_cache_attention_fn())
    results["weights_stream"] = time_weights_stream(c, params, args)
    del cache                       # free HBM for the fused copies
    results["fused_stream"] = time_weights_stream_fused(c, params, args)
    results["sort_alone"] = time_sort_alone(args, c.vocab_size)

    note("\n--- attribution (ms/step) ---")
    f = results.get("full")
    if f is not None:
        for k, v in results.items():
            if k == "full":
                note(f"full step          : {f:8.3f}")
            elif k in ("sort_alone", "pallas", "weights_stream",
                       "fused_stream"):
                note(f"{k:19s}: {v:8.3f}")
            else:
                note(f"delta full-{k:8s}: {f - v:8.3f}")
    print({k: round(v, 3) for k, v in results.items()})


if __name__ == "__main__":
    main()
