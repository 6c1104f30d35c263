"""Time the engine's own _decode_burst, split into dispatch vs fetch, to
locate the gap between the standalone scan (7.5 ms/step) and the bench's
64.5 ms/step (VERDICT r2 item 1)."""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def note(msg):
    print(msg, file=sys.stderr, flush=True)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--attention", default="auto")
    ap.add_argument("--burst", type=int, default=32)
    ap.add_argument("--kv", default="contiguous")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import InferenceEngine
    from llmapigateway_tpu.engine.sampling import SamplingParams

    cfg = LocalEngineConfig(
        preset="tinyllama-1.1b", dtype="bfloat16", max_batch_size=8,
        max_seq_len=1024, prefill_chunk=128, decode_burst=args.burst,
        kv_layout=args.kv, attention=args.attention)
    t0 = time.monotonic()
    engine = InferenceEngine(cfg)
    note(f"engine init: {time.monotonic()-t0:.1f}s")

    rng = np.random.default_rng(0)
    prompt = rng.integers(0, engine.model_cfg.vocab_size, size=128).astype(
        np.int32)
    for slot in range(engine.B):
        if engine.paged:
            engine.allocator.allocate(slot, 1024)
            engine._table_dirty = True
        first, engine.cache = engine._exec_prefill(slot, 0, prompt)
        engine.lengths[slot] = len(prompt)
        engine.active[slot] = True
        engine.last_token[slot] = 1
        np.asarray(first)
    note("prefill done")

    # Warm both programs.
    engine._d_dirty = True
    t0 = time.monotonic()
    engine._decode_burst(args.burst)
    note(f"scan warm (incl compile): {time.monotonic()-t0:.1f}s")

    # Time whole _decode_burst calls.
    for i in range(3):
        t0 = time.monotonic()
        engine._decode_burst(args.burst)
        dt = time.monotonic() - t0
        note(f"_decode_burst({args.burst}) #{i}: {1000*dt:.1f} ms "
             f"({1000*dt/args.burst:.2f} ms/step)")

    # Split: dispatch only vs fetch — use the SAME program _decode_burst
    # picked (greedy: bench slots decode at temperature 0).
    scan_fn = engine._decode_fns[True][1][args.burst]
    table = (engine._device_table(),) if engine.paged else ()
    for i in range(3):
        engine._rng, key = jax.random.split(engine._rng)
        t0 = time.monotonic()
        toks, engine._d_tokens, engine._d_lengths, engine.cache = \
            scan_fn(
                engine.params, engine.cache, *table, engine._d_tokens,
                engine._d_lengths, engine._d_active, engine._d_samp, key)
        t1 = time.monotonic()
        host = np.asarray(toks)
        t2 = time.monotonic()
        note(f"raw scan #{i}: dispatch {1000*(t1-t0):.1f} ms, "
             f"fetch {1000*(t2-t1):.1f} ms, total "
             f"{1000*(t2-t0)/args.burst:.2f} ms/step")

    # Back-to-back dispatches, one final fetch (pipelining check).
    t0 = time.monotonic()
    n = 4
    for i in range(n):
        engine._rng, key = jax.random.split(engine._rng)
        toks, engine._d_tokens, engine._d_lengths, engine.cache = \
            scan_fn(
                engine.params, engine.cache, *table, engine._d_tokens,
                engine._d_lengths, engine._d_active, engine._d_samp, key)
    host = np.asarray(toks)
    dt = time.monotonic() - t0
    note(f"{n} chained bursts + 1 fetch: {1000*dt:.1f} ms "
         f"({1000*dt/(n*args.burst):.2f} ms/step)")


if __name__ == "__main__":
    main()
