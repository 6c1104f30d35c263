"""Time the streamed sharded checkpoint load at full scale, then serve.

VERDICT r4 item 4: measures what the tiny CPU parity tests can't — wall
clock of the per-parameter streamed load (engine/checkpoint.py pass 2),
peak host RSS during stacking (the design claim: bounded by the largest
stacked parameter, not the checkpoint), int8-at-source preprocessing
cost, and time-to-first-served-token from a cold process.

Run against a real or synthetic checkpoint (tools/
make_synthetic_checkpoint.py):

    python tools/profile_checkpoint_load.py /tmp/synth-8b --quant int8

Emits one JSON line. With JAX_PLATFORMS=cpu the engine still exercises
the identical load/stack/place path on host.
"""
import argparse
import asyncio
import json
import resource
import time

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("model_dir")
    ap.add_argument("--quant", default="", choices=["", "int8", "int4"])
    ap.add_argument("--kv-quant", default="", choices=["", "int8"])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--tokens", type=int, default=32)
    args = ap.parse_args()

    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    rss0 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    t0 = time.monotonic()
    engine = InferenceEngine(LocalEngineConfig(
        model_path=args.model_dir, max_batch_size=args.batch,
        max_seq_len=args.seq, quant=args.quant, kv_quant=args.kv_quant,
        prewarm_sampler_variants=False,
        # No persistent XLA cache: load timing is the point here, not
        # compile timing.
        compilation_cache_dir="off"))
    init_s = time.monotonic() - t0
    rss1 = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss

    async def serve():
        req = GenRequest(prompt_ids=engine.tokenizer.encode(
            "The quick brown fox"), max_tokens=args.tokens, temperature=0.0)
        t = time.monotonic()
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()
        return req, time.monotonic() - t

    req, serve_s = asyncio.run(serve())
    import numpy as np
    n_params = sum(
        int(np.prod(l.shape)) for l in
        __import__("jax").tree_util.tree_leaves(engine.params))
    print(json.dumps({
        "model_dir": args.model_dir,
        "quant": args.quant or "bf16", "kv_quant": args.kv_quant or "bf16",
        "engine_init_s": round(init_s, 1),
        "peak_host_rss_gb": round((rss1 - rss0) / 1e6, 2),
        "n_param_leaf_elems_b": round(n_params / 1e9, 2),
        "generated_tokens": len(req.generated),
        "first_request_s": round(serve_s, 2),
        "text_preview": engine.tokenizer.decode(req.generated)[:60],
    }))


if __name__ == "__main__":
    main()
