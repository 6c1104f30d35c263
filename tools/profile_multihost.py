"""Measure the multi-host command-stream tax (VERDICT r4 item 7).

The bridge (parallel/multihost.py) broadcasts a fixed-shape int32 frame
before every decode burst (slot state + rng key; page tables on paged
engines) and one-or-more frames per prefill chunk. Lockstep tests prove
this is *correct*; this tool measures what it *costs*, on CPU meshes —
the same fabric the 2-process lockstep tests use (Gloo stands in for
ICI/DCN), so the numbers bound the protocol overhead, not real-network
latency.

Method: the same serving workload (B requests × N tokens through the real
async scheduler) runs on a TP=4 mesh twice —

* ``--procs 1``: four host devices in one process, bridge disabled.
* ``--procs 2``: two processes × two devices, the coordinator's
  ``_broadcast`` wrapped to count frames/bytes/seconds.

Per-burst overhead = (2-proc steady decode per burst) − (1-proc), with
the broadcast share reported separately so protocol cost is separable
from the collective-compute cost of simply spanning two processes.

Run: ``python tools/profile_multihost.py`` (driver mode runs both and
prints one comparison JSON line; ~2-3 min on CPU).
"""
import argparse
import json
import os
import socket
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

BURST = 4
MAX_TOKENS = 96
PROMPT = list(range(2, 34))          # 32 tokens, 4 chunks of 8


def _free_port() -> str:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return str(s.getsockname()[1])


def worker(proc_id: int, n_proc: int, port: str) -> None:
    import jax
    if n_proc > 1:
        jax.distributed.initialize(
            coordinator_address=f"localhost:{port}",
            num_processes=n_proc, process_id=proc_id)

    import asyncio

    import numpy as np  # noqa: F401

    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=2,
                            max_seq_len=192, prefill_chunk=8,
                            decode_burst=BURST, mesh={"model": 4},
                            attention="reference",
                            prewarm_sampler_variants=False,
                            compilation_cache_dir="off")
    engine = InferenceEngine(cfg)

    stats = {"frames": 0, "bytes": 0, "broadcast_s": 0.0}
    if engine._bridge.enabled and proc_id == 0:
        orig = engine._bridge._broadcast

        def timed(cmd):
            t0 = time.perf_counter()
            out = orig(cmd)
            stats["broadcast_s"] += time.perf_counter() - t0
            stats["frames"] += 1
            if cmd is not None:
                stats["bytes"] += cmd.nbytes
            return out
        engine._bridge._broadcast = timed

    if proc_id != 0:
        engine.run_follower()
        return

    async def main():
        # Warm round: compile prefill + decode programs outside timing.
        warm = GenRequest(prompt_ids=list(PROMPT), max_tokens=2 * BURST,
                          temperature=0.0)
        await engine.submit(warm)
        async for _ in engine.stream(warm):
            pass
        pre0 = dict(stats)

        reqs = [GenRequest(prompt_ids=list(PROMPT), max_tokens=MAX_TOKENS,
                           temperature=0.0) for _ in range(engine.B)]
        t_sub = time.monotonic()
        for r in reqs:
            await engine.submit(r)
        while any(r.t_first_token is None and r.finish_reason is None
                  for r in reqs):
            await asyncio.sleep(0.002)
        prefill_s = time.monotonic() - t_sub
        pre1 = dict(stats)

        t0 = time.monotonic()
        for r in reqs:
            async for _ in engine.stream(r):
                pass
        decode_s = time.monotonic() - t0
        await engine.stop()

        toks = sum(len(r.generated) - 1 for r in reqs)
        bursts = max(1, toks // (engine.B * BURST))
        out = {
            "procs": n_proc,
            "decode_s": round(decode_s, 3),
            "decode_tokens": toks,
            "bursts": bursts,
            "ms_per_burst": round(1000.0 * decode_s / bursts, 2),
            "prefill_s": round(prefill_s, 3),
            "prefill_frames": pre1["frames"] - pre0["frames"],
            "decode_frames": stats["frames"] - pre1["frames"],
            "decode_broadcast_ms": round(
                1000.0 * (stats["broadcast_s"] - pre1["broadcast_s"]), 1),
            "frame_bytes": (stats["bytes"] // stats["frames"]
                            if stats["frames"] else 0),
        }
        print("MHPROF " + json.dumps(out), flush=True)

    asyncio.run(main())


def run_config(n_proc: int) -> dict:
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count="
                        f"{4 // n_proc}",
           "PYTHONPATH": str(ROOT)}
    port = _free_port()
    procs = [subprocess.Popen(
        [sys.executable, __file__, "--worker", str(i), str(n_proc), port],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(n_proc)]
    result = None
    for i, p in enumerate(procs):
        out, _ = p.communicate(timeout=600)
        if p.returncode != 0:
            raise RuntimeError(f"proc {i} rc={p.returncode}:\n{out[-3000:]}")
        for line in out.splitlines():
            if line.startswith("MHPROF "):
                result = json.loads(line[len("MHPROF "):])
    assert result is not None, "coordinator emitted no MHPROF line"
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", nargs=3, metavar=("ID", "N", "PORT"))
    args = ap.parse_args()
    if args.worker:
        worker(int(args.worker[0]), int(args.worker[1]), args.worker[2])
        return

    solo = run_config(1)
    duo = run_config(2)
    per_burst_tax = round(duo["ms_per_burst"] - solo["ms_per_burst"], 2)
    broadcast_per_burst = round(
        duo["decode_broadcast_ms"] / max(1, duo["decode_frames"]), 2)
    print(json.dumps({
        "solo": solo, "duo": duo,
        "per_burst_tax_ms": per_burst_tax,
        "broadcast_ms_per_decode_frame": broadcast_per_burst,
        "note": "tax = protocol + CPU-Gloo collectives; broadcast share "
                "is the command-stream floor",
    }))


if __name__ == "__main__":
    main()
