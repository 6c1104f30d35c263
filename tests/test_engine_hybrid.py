"""The hybrid family through ``InferenceEngine``: the state rules (a)-(d) of
the recurrent block beside the page pool, the build-time refusals, the
counters. Served tokens are judged as the benchmark judges them: at every
generated position the reference's logit of the token the engine SERVED
lies within a bound of the reference's own maximum (float32 engine and
float32 reference: the order of the sums, 1e-3 is generous)."""
import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as ref
from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.models.config import get_preset

from test_model_hybrid import file_of

GAP_TOL = 1e-3
BASE = dict(preset="tiny-hybrid-test", max_batch_size=4, max_seq_len=128,
            prefill_chunk=32, prefill_batch=2, dtype="float32",
            kv_layout="paged", kv_page_size=16, prefix_cache=False,
            decode_burst=4, decode_burst_busy=2)


def _mk_engine(model_cfg=None, devices=None, **kw):
    return InferenceEngine(LocalEngineConfig(**{**BASE, **kw}), model_cfg,
                           devices=devices or [jax.devices("cpu")[0]])


@pytest.fixture(scope="module")
def engine(stop_engine):
    eng = _mk_engine()
    yield eng
    stop_engine(eng)


def prompt(n: int, seed: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(1, 500, n)]


async def generate(eng, ids, max_tokens=8) -> GenRequest:
    req = GenRequest(prompt_ids=list(ids), max_tokens=max_tokens)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


async def worst_gap(eng, req: GenRequest) -> float:
    """How far below the reference's best logit the served tokens sit
    (the reference runs off the event loop: seconds of tracing)."""
    return await asyncio.to_thread(_worst_gap, eng, req)


def _worst_gap(eng, req: GenRequest) -> float:
    c = eng.model_cfg
    seq = np.asarray(list(req.prompt_ids) + req.generated[:-1], np.int32)
    rows = ref.logits(eng.params, ref.sizes(c, file_of(c)), seq,
                      last=len(req.generated))
    return max(float(row.max() - row[t])
               for row, t in zip(rows, req.generated))


def dirty(eng) -> None:
    """Fill every slot's state block and conv tail with garbage."""
    eng.cache = eng.cache._replace(
        state=tuple(jnp.full_like(s, 9.0) for s in eng.cache.state),
        conv=tuple(jnp.full_like(t, -5.0) for t in eng.cache.conv))


async def test_a_dirty_block_a_padded_bucket_and_an_uneven_group(engine):
    """(a) every slot's block holds garbage, and a finished request leaves
    its state behind for the next on its slot ((d): release does no state
    work); (b) prompts of 41 and 46 tokens share their chunks' buckets (32,
    then a tail of 9 and 14 in the bucket of 16): a group of uneven
    lengths, both rows padded."""
    dirty(engine)
    for round_ in range(2):         # the second round reuses the slots
        reqs = await asyncio.gather(
            generate(engine, prompt(41, 10 + round_)),
            generate(engine, prompt(46, 20 + round_)))
        for req in reqs:
            assert len(req.generated) == 8
            assert await worst_gap(engine, req) <= GAP_TOL
    st = engine.stats()
    assert st["state_slots"] == 4 and st["moe_experts_held"] == 16
    per_slot = 6 * (4 * 16 * 16 * 4) + 6 * (3 * 3 * 4 * 16 * 4)
    assert st["state_bytes_resident"] == 4 * per_slot
    # Every assignment of a decode step lands on a held expert when all
    # are held; bursts hand the device's totals back beside their tokens.
    assert st["moe_assignments_total"] > 0
    assert st["moe_assignments_local_total"] == st["moe_assignments_total"]
    assert st["moe_assignments_total"] % (4 * 8) == 0   # top-4 x 8 layers


async def test_a_long_prompt_prefills_between_another_slots_bursts(engine):
    """(c) through the scheduler: a 3-chunk prompt is admitted while a
    request decodes, so its slot sits through decode bursts between its
    chunks; both come out as the reference has them."""
    first = asyncio.ensure_future(generate(engine, prompt(20, 1), 40))
    await asyncio.sleep(0.5)
    late = await generate(engine, prompt(90, 2), 6)
    early = await first
    assert await worst_gap(engine, late) <= GAP_TOL
    assert await worst_gap(engine, early) <= GAP_TOL


def test_a_decode_burst_leaves_an_inactive_slots_block_bit_identical(engine):
    """(c) at the programs: slot 2 holds the state of one prefilled chunk
    (it is 'between chunks'), slot 0 decodes a burst of 4 and single
    steps; slot 2's state and tail do not change by a bit, slot 0's do."""
    eng = engine
    eng._flush_pending()
    first, eng.cache = eng._exec_prefill([0, 2], [0, 0], [
        np.asarray(prompt(32, 3), np.int32), np.asarray(prompt(32, 4),
                                                        np.int32)])
    eng.lengths[:] = 0
    eng.active[:] = False
    eng.lengths[0], eng.active[0] = 32, True
    eng.last_token[0] = int(np.asarray(first)[0])
    eng._d_dirty = True

    def rows(slot):
        return [np.asarray(a[:, slot]) for a in eng.cache.state
                + eng.cache.conv]
    parked, moving = rows(2), rows(0)
    assert all(np.abs(a).max() > 0 for a in parked)
    eng._decode_burst(4)
    eng._decode_burst(1)
    eng._flush_pending()
    assert all((a == b).all() for a, b in zip(parked, rows(2)))
    assert not any((a == b).all() for a, b in zip(moving, rows(0)))
    eng.active[:] = False
    eng.lengths[:] = 0
    eng._d_dirty = True


async def test_an_engine_that_holds_half_the_experts():
    c = dataclasses.replace(get_preset("tiny-hybrid-test"),
                            n_experts_held=8, first_expert_held=8)
    eng = await asyncio.to_thread(_mk_engine, model_cfg=c)   # compiles: seconds
    try:
        req = await generate(eng, prompt(50, 7), 12)
        assert await worst_gap(eng, req) <= GAP_TOL
        st = eng.stats()
        share = (st["moe_assignments_local_total"]
                 / st["moe_assignments_total"])
        assert st["moe_experts_held"] == 8 and 0.3 < share < 0.7
    finally:
        await eng.stop()


async def test_the_tiles_of_the_grouped_product_are_counted():
    """A prefill call of more than ``DENSE_MAX_TOKENS`` positions takes the
    grouped product and counts its tiles and the rows they held; a smaller
    call and the decode bursts run every held expert and count none; the
    totals survive a rebuild of the state, as their neighbours do."""
    from llmapigateway_tpu.models import hybrid
    eng = await asyncio.to_thread(_mk_engine, prefill_chunk=128)
    try:
        assert eng.stats()["moe_tiles_run_total"] == 0
        await generate(eng, prompt(20, 5), 12)      # a bucket of 32: dense
        small = eng.stats()
        assert small["moe_assignments_total"] > 0
        assert small["moe_tiles_run_total"] == small["moe_tile_rows_total"] == 0
        await generate(eng, prompt(100, 6), 12)     # one call of 128 rows
        st = eng.stats()
        layers, k = eng.model_cfg.n_layers, eng.model_cfg.experts_per_token
        assert st["moe_tile_rows_total"] == 128 * k * layers    # all held
        least = -(-128 * k // hybrid.GROUP_TILE)
        assert least * layers <= st["moe_tiles_run_total"] \
            <= (least + eng.model_cfg.experts_held) * layers
        assert st["moe_assignments_total"] > small["moe_assignments_total"]
        await generate(eng, prompt(24, 7), 12)      # decode bursts only
        after = eng.stats()
        assert after["moe_assignments_total"] > st["moe_assignments_total"]
        assert after["moe_tiles_run_total"] == st["moe_tiles_run_total"]
        await eng.stop()
        eng._rebuild_state()
        assert int(np.asarray(eng.cache.counters).sum()) == 0
        kept = eng.stats()
        for key in ("moe_tiles_run_total", "moe_tile_rows_total",
                    "moe_assignments_total", "moe_assignments_local_total",
                    "moe_experts_hit_total"):
            assert kept[key] == after[key] > 0
        await generate(eng, prompt(100, 8), 12)
        assert eng.stats()["moe_tiles_run_total"] > after["moe_tiles_run_total"]
    finally:
        await eng.stop()


@pytest.mark.parametrize("change, says", [
    ({"prefix_cache": True}, "prefix_cache: a cached prefix holds KV pages"),
    ({"spec_draft_len": 3}, "spec_draft_len: a rejected draft"),
    ({"mesh": {"model": 2}}, "mesh .*no sharding rule yet"),
    ({"disaggregation": {"enabled": True, "prefill_slots": 1}},
     "disaggregation: a handoff moves pages"),
    ({"model_path": "/nonexistent/checkpoint"},
     "model_path: no checkpoint mapping"),
    ("window", "a sliding window"),
])
def test_what_the_family_cannot_be_served_with_is_refused_at_build(
        change, says):
    model_cfg, devices = None, None
    if change == "window":
        model_cfg, change = dataclasses.replace(
            get_preset("tiny-hybrid-test"), sliding_window=16), {}
    if "mesh" in change:
        devices = jax.devices("cpu")[:2]
    with pytest.raises(ValueError, match=f"'hybrid' family does not "
                                         f"support {says}"):
        _mk_engine(model_cfg=model_cfg, devices=devices, **change)


def test_the_checkpoint_loader_refuses_the_family():
    from llmapigateway_tpu.engine.checkpoint import load_checkpoint
    with pytest.raises(ValueError, match="no checkpoint mapping"):
        load_checkpoint("/nonexistent", get_preset("tiny-hybrid-test"))
