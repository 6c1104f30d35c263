"""Subprocess worker for the multi-host serving test (test_multihost.py).

Two of these run concurrently (process 0 = coordinator, 1 = follower) with
a TP=4 mesh spanning both processes' CPU devices. The coordinator drives
the REAL async engine (submit → stream → stop); the follower replays the
broadcast commands. Both record every decode step's sampled tokens; at the
end the coordinator broadcasts its record and each process asserts its own
matches bit-for-bit — proving the two executed identical programs with
identical inputs in lockstep.
"""
import os
import sys

PROC_ID = int(sys.argv[1])
N_PROC = int(sys.argv[2])
PORT = sys.argv[3]
KV_LAYOUT = sys.argv[4] if len(sys.argv) > 4 else "contiguous"
QUANT = sys.argv[5] if len(sys.argv) > 5 else ""
SPEC = int(sys.argv[6]) if len(sys.argv) > 6 else 0

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402

jax.distributed.initialize(coordinator_address=f"localhost:{PORT}",
                           num_processes=N_PROC, process_id=PROC_ID)

import asyncio  # noqa: E402

import numpy as np  # noqa: E402
from jax.experimental import multihost_utils  # noqa: E402

from llmapigateway_tpu.config.schemas import LocalEngineConfig  # noqa: E402
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine  # noqa: E402

MAX_REC = 64

cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=2, max_seq_len=96,
                        prefill_chunk=8, decode_burst=4,
                        mesh={"model": 4}, attention="reference",
                        kv_layout=KV_LAYOUT, kv_page_size=16,
                        quant=QUANT,
                        # int4 is weights-only; the KV cache has no int4
                        # mode — pair it with the int8 cache (the W4A8
                        # serving shape).
                        kv_quant="int8" if QUANT == "int4" else QUANT,
                        spec_draft_len=SPEC)
engine = InferenceEngine(cfg)
assert engine._bridge.enabled, "bridge must be active with 2 processes"

recorded: list[np.ndarray] = []
_orig_exec = engine._exec_decode


def _recording_exec(n_steps, state):
    toks = _orig_exec(n_steps, state)
    recorded.extend(toks)
    return toks


engine._exec_decode = _recording_exec

if SPEC:
    # Record the speculative emitted matrices too — data-dependent
    # advances make these the strongest lockstep evidence.
    _orig_spec = engine._exec_spec

    def _recording_spec(n_steps, state):
        host = _orig_spec(n_steps, state)
        recorded.append(host.reshape(-1))
        return host

    engine._exec_spec = _recording_spec

if PROC_ID == 0:
    async def main():
        # Speculative engines need greedy (temperature 0) and a
        # REPETITIVE prompt so drafting actually accepts; the sampled
        # path keeps exercising the general sampler.
        if SPEC:
            req = GenRequest(prompt_ids=[7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8, 9],
                             max_tokens=16, temperature=0.0)
        else:
            req = GenRequest(prompt_ids=[1, 2, 3, 4, 5], max_tokens=8,
                             temperature=0.8, top_p=0.9)
        await engine.submit(req)
        async for _ in engine.stream(req):
            pass
        await engine.stop()     # SHUTDOWN first — asserts after (a dead
        return req              # coordinator strands the follower)

    req = asyncio.run(main())
    assert len(req.generated) >= 2, req.generated
    if SPEC:
        assert engine._spec_steps_done > 0, "speculation never engaged"
else:
    engine.run_follower()

# All asserts AFTER the final collective: a pre-collective assert would
# kill this process and strand the peer inside broadcast_one_to_all,
# surfacing as an opaque 300s deadlock timeout instead of the message.
flat = np.full((MAX_REC,), -1, np.int32)
mine = np.concatenate(recorded)[:MAX_REC] if recorded else np.zeros(0, np.int32)
flat[:len(mine)] = mine
theirs = np.asarray(multihost_utils.broadcast_one_to_all(flat))
if PROC_ID != 0:
    assert len(mine) > 0, "follower replayed no decode steps"
    np.testing.assert_array_equal(theirs, flat)
    if SPEC:
        assert engine._spec_steps_done > 0, \
            "follower replayed no speculative bursts"
print(f"MULTIHOST_OK proc={PROC_ID} decode_tokens={len(mine)}", flush=True)
