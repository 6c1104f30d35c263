"""Engines whose ``prefill_step`` has the page pool as its layer scan's
CARRY (PR 34) against the same engines on per-layer slices of the pool:
the same greedy tokens. The forwards themselves on the carried against the
sliced pool, and ``_sliced``, are tests/test_prefill_pool_carried.py."""
import asyncio

import jax
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.ops import paged_attention as pa
from test_prefill_pool_carried import _sliced

# preset -> (engine options, prompt lengths, tokens answered per request)
CARRIED = {
    # A 70-token prompt on a ring of 5 pages of 16 (window 16): the ring
    # rotates under prefill, chunks of 16 and a ragged last bucket.
    "tiny-mistral-test": (dict(max_batch_size=2, max_seq_len=128,
                               prefill_chunk=16, kv_num_pages=9), (70, 9),
                          12),
    # Two rows a prefill call, each from its own start.
    "tiny-hybrid-test": (dict(max_batch_size=2, max_seq_len=128,
                              prefill_chunk=32, prefill_batch=2,
                              prefix_cache=False), (45, 20), 12),
}


@pytest.mark.parametrize("preset", list(CARRIED))
async def test_engines_on_the_carried_and_on_the_sliced_pool_agree(
        preset, monkeypatch):
    """The engine's ``prefill_step`` with the pool as its layer scan's
    carry against the same engine whose provider lacks ``.prefill_at``
    (per-layer slices, the XLA scatter; the decode programs in place in
    both): the same greedy tokens, int8 pool, prompts submitted together
    so that the hybrid family's prefill call holds two rows."""
    options, lengths, n_tokens = CARRIED[preset]
    rng = np.random.default_rng(9)
    prompts = [[int(t) for t in rng.integers(1, 500, n)] for n in lengths]
    build = pa.make_paged_attention_fn
    calls = {True: 0, False: 0}
    served = {}
    for carried in (True, False):
        def provider(*a, carried=carried, **kw):
            fn = build(*a, **kw)
            calls[carried] += hasattr(fn, "prefill_at")
            return fn if carried else _sliced(fn)
        monkeypatch.setattr(pa, "make_paged_attention_fn", provider)
        eng = await asyncio.to_thread(
            InferenceEngine,
            LocalEngineConfig(preset=preset, dtype="float32",
                              kv_layout="paged", kv_page_size=16,
                              decode_burst=4, decode_burst_busy=2,
                              attention="pallas", kv_quant="int8",
                              **options),
            devices=[jax.devices("cpu")[0]])
        try:
            assert eng.stats()["kv_pool_in_place"]
            reqs = [GenRequest(prompt_ids=list(ids), max_tokens=n_tokens)
                    for ids in prompts]
            for req in reqs:
                await eng.submit(req)
            for req in reqs:
                async for _ in eng.stream(req):
                    pass
            served[carried] = [list(req.generated) for req in reqs]
            eng.allocator.check_invariants()
        finally:
            await eng.stop()
    assert calls[True] and calls[False]
    assert served[True] == served[False]
    assert min(len(t) for t in served[True]) >= 8
