"""Engines on the page pool where it lies (``attention="pallas"``: the
stacked read and the aliased writes of tests/test_ops_paged_in_place.py
and tests/test_ops_paged_chunk_write.py, interpreted) against engines on
the reference path: the same greedy tokens."""
import asyncio

import jax
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

# preset -> (engine options, tokens answered per request)
ENGINES = {
    # Window 16 on pages of 16, a ring of 5 pages a slot: 90 answered
    # tokens after a 20-token prompt reach logical page 6, so the ring
    # rotates (the test counts the rotations).
    "tiny-mistral-test": (dict(max_batch_size=2, max_seq_len=128,
                               prefill_chunk=16, kv_num_pages=9), 90),
    "tiny-hybrid-test": (dict(max_batch_size=2, max_seq_len=128,
                              prefill_chunk=32, prefill_batch=2,
                              prefix_cache=False), 40),
}


async def _serve(eng, prompts, max_tokens):
    out = []
    for ids in prompts:
        req = GenRequest(prompt_ids=list(ids), max_tokens=max_tokens)
        await eng.submit(req)
        async for _ in eng.stream(req):
            pass
        out.append(list(req.generated))
    return out


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["f32kv", "int8kv"])
@pytest.mark.parametrize("preset", list(ENGINES))
async def test_engines_on_the_kernels_and_on_the_reference_path_agree(
        preset, kv_quant):
    """``attention="pallas"`` (the stacked read and the aliased write,
    interpreted) against ``"reference"`` (per-layer slices, the XLA
    scatter): the same greedy tokens over bursts that cross page edges,
    and ``stats()`` says which path each engine was built on."""
    rng = np.random.default_rng(5)
    prompts = [[int(t) for t in rng.integers(1, 500, n)] for n in (20, 9)]
    options, n_tokens = ENGINES[preset]
    served = {}
    for impl in ("pallas", "reference"):
        # Built off the event loop: a build holds it for seconds.
        eng = await asyncio.to_thread(
            InferenceEngine,
            LocalEngineConfig(preset=preset, dtype="float32",
                              kv_layout="paged", kv_page_size=16,
                              decode_burst=4, decode_burst_busy=2,
                              attention=impl, kv_quant=kv_quant,
                              **options),
            devices=[jax.devices("cpu")[0]])
        try:
            assert eng.stats()["kv_pool_in_place"] is (impl == "pallas")
            assert eng.stats()["attention"] == impl
            rotations = []
            mapped = eng.allocator.ensure_mapped
            eng.allocator.ensure_mapped = \
                lambda *a, **kw: rotations.append(mapped(*a, **kw)) \
                or rotations[-1]
            served[impl] = await _serve(eng, prompts, n_tokens)
            eng.allocator.check_invariants()
            assert any(rotations) is (preset == "tiny-mistral-test")
        finally:
            await eng.stop()
    assert served["pallas"] == served["reference"]
    # (a stream may end early on the tokenizer's end-of-sequence id)
    assert min(len(t) for t in served["pallas"]) >= 40
