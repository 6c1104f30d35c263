"""A mesh engine against the one-device engine, token for token.

Each feature's test file holds its own case (int8 and int4 weights, int8
KV, a window with speculation, speculation alone, the prefix cache,
multi-page blocks, penalties, the expert axis): it calls :func:`serve`
twice with the same engine settings, once on one device and once on the
mesh, and compares what the requests generated. Greedy, float32, tiny
presets; the requests run together, so slots share every burst."""
import asyncio

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from tests.conftest import cpu_devices

# Two chunks each (32 + 8), so a group of two runs both prefill programs.
PROMPTS = ([3, 1, 4, 1, 5, 9, 2, 6, 5, 3] * 4, list(range(40, 80)))
# For speculation: the tiny presets' greedy continuation of the first
# repeats, so drafts from the history are accepted.
CYCLING = ([3, 1, 4, 1, 5, 9, 2, 6, 5, 3, 5, 8, 9, 7, 9] * 3, PROMPTS[1])


def split_dims(arr) -> tuple[int, ...]:
    """The dims of ``arr`` that are split over devices (an array a jitted
    step returned carries no ``PartitionSpec`` to read them from)."""
    shard = arr.sharding.shard_shape(arr.shape)
    return tuple(d for d, (whole, part) in enumerate(zip(arr.shape, shard))
                 if part < whole)


async def serve(mesh: dict, prompts=PROMPTS, max_tokens: int = 10,
                model_cfg=None, rounds: int = 1, request_kw=None,
                **engine_kw):
    """Serve ``prompts`` together, ``rounds`` times over, on ``mesh`` (as
    many virtual devices as it names; ``{}``: one). Returns (the token
    lists in submission order, the stopped engine)."""
    n = 1
    for size in mesh.values():
        n *= size
    engine_kw.setdefault("preset", "tiny-test")
    engine_kw.setdefault("attention", "reference")
    burst = engine_kw.pop("decode_burst", 4)     # one depth: one scan
    cfg = LocalEngineConfig(
        mesh=mesh, max_batch_size=2, max_seq_len=128, prefill_chunk=32,
        dtype="float32", decode_burst=burst, decode_burst_busy=burst,
        prewarm_sampler_variants=False, compilation_cache_dir="off",
        **engine_kw)
    # Built off the loop: a build is seconds of tracing, and the suite's
    # sanitizer fails a session whose loop stood still for five.
    eng = await asyncio.to_thread(
        InferenceEngine, cfg, model_cfg, cpu_devices()[:n])
    assert dict(eng.mesh.shape) == {"data": 1, "expert": 1, "model": 1,
                                    **mesh}
    out = []

    async def one(ids):
        req = GenRequest(prompt_ids=list(ids), max_tokens=max_tokens,
                         temperature=0.0, **(request_kw or {}))
        await eng.submit(req)
        async for _ in eng.stream(req):
            pass
        assert req.finish_reason == "length", req.finish_reason
        return req.generated
    try:
        for _ in range(rounds):
            out += await asyncio.gather(*(one(p) for p in prompts))
    finally:
        await eng.stop()
    return out, eng
