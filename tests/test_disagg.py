"""Prefill/decode disaggregation (ISSUE 13): the two-pool scheduler over
one shared paged KV pool — greedy parity with the unified scheduler,
zero-copy KV handoff (page-id identity, refcount invariants under
cancel churn), direct-to-decode compositions (warm prefix hits, penalty
requests), and the goodput-first admission gate (shed vs clamp)."""
import asyncio

import jax
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import (
    EngineOverloaded, GenRequest, InferenceEngine)
from llmapigateway_tpu.obs.flight import POOL_DECODE, POOL_PREFILL
from tests.mesh_parity import serve


def _cfg(disagg=False, prefill_slots=1, **kw):
    base = dict(preset="tiny-test", max_batch_size=4, max_seq_len=128,
                prefill_chunk=32, dtype="float32", kv_layout="paged",
                kv_page_size=16)
    if disagg:
        base["disaggregation"] = {"enabled": True,
                                  "prefill_slots": prefill_slots}
    base.update(kw)
    return LocalEngineConfig(**base)


def _mk_engine(disagg=False, prefill_slots=1, **kw):
    return InferenceEngine(_cfg(disagg, prefill_slots, **kw),
                           devices=[jax.devices("cpu")[0]])


async def _generate(eng, prompt="hello", max_tokens=8, **kw) -> GenRequest:
    req = GenRequest(prompt_ids=eng.tokenizer.encode(prompt),
                     max_tokens=max_tokens, **kw)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


@pytest.fixture(scope="module")
def pooled_engine(stop_engine):
    """One disaggregated engine shared by the composition tests (tests
    assert counter DELTAS, never absolute values)."""
    eng = _mk_engine(disagg=True)
    yield eng
    stop_engine(eng)


# -- v1 composition gates ----------------------------------------------------

def test_config_rejects_unknown_admission_policy():
    with pytest.raises(ValueError, match="admission"):
        LocalEngineConfig(preset="tiny-test",
                          disaggregation={"enabled": True,
                                          "admission": "vibes"})


def test_prefill_slots_must_leave_decode_slots():
    with pytest.raises(ValueError, match="both pools non-empty"):
        _mk_engine(disagg=True, prefill_slots=4)


def test_spec_decoding_rejected():
    with pytest.raises(ValueError, match="spec_draft_len"):
        _mk_engine(disagg=True, spec_draft_len=3)


# -- greedy parity pooled vs unified -----------------------------------------

@pytest.mark.parametrize("ppb", [1, 2, 4])
async def test_greedy_parity_pooled_vs_unified(ppb):
    """Bit-for-bit: the pooled scheduler (prefill slot != decode slot,
    KV handed off mid-request) must emit exactly the unified scheduler's
    greedy tokens — the handoff moves page ownership, never content."""
    unified = _mk_engine(kv_pages_per_block=ppb)
    pooled = _mk_engine(disagg=True, kv_pages_per_block=ppb)
    prompts = ("the quick brown fox", "a much longer serving prompt " * 2)
    try:
        for prompt in prompts:
            r_uni = await _generate(unified, prompt, max_tokens=6)
            r_pool = await _generate(pooled, prompt, max_tokens=6)
            assert r_pool.generated == r_uni.generated, (ppb, prompt)
            assert r_pool.pool == POOL_DECODE      # finished post-handoff
        assert pooled.stats()["disagg_handoffs"] == len(prompts)
        assert "pools" not in unified.stats()
        # Flight records: the pooled engine tags steps per pool; the
        # unified engine's records never grow a pool key (pre-pool wire
        # format stays byte-identical).
        step_pools = {r.get("pool")
                      for r in pooled.flight.snapshot(-1)
                      if r["kind"] == "step"}
        assert {"prefill", "decode"} <= step_pools
        assert all("pool" not in r for r in unified.flight.snapshot(-1))
    finally:
        await unified.stop()
        await pooled.stop()


async def test_greedy_parity_int8_kv():
    """The handoff composes with quantized KV: the page transfer is
    layout-agnostic (ids move, bytes don't), so int8-KV parity must hold
    pooled-vs-unified just like fp32."""
    unified = _mk_engine(kv_quant="int8")
    pooled = _mk_engine(disagg=True, kv_quant="int8")
    try:
        for prompt in ("int8 kv parity probe", "another distinct prompt"):
            r_uni = await _generate(unified, prompt, max_tokens=6)
            r_pool = await _generate(pooled, prompt, max_tokens=6)
            assert r_pool.generated == r_uni.generated, prompt
        assert pooled.stats()["disagg_handoffs"] == 2
    finally:
        await unified.stop()
        await pooled.stop()


# -- zero-copy handoff -------------------------------------------------------

async def test_handoff_page_identity_and_no_free_list_transit():
    """The acceptance bar's zero-copy assertion: the page ids the prefill
    slot held are EXACTLY the ids the decode slot holds after the
    handoff, and the allocator's free count never moves — no page
    touched a free list, no new page was allocated, so there was nothing
    a device copy could have targeted."""
    eng = _mk_engine(disagg=True)
    alloc = eng.allocator
    orig = alloc.transfer
    observed = []

    def spy(src, dst):
        before = list(alloc._held[src])
        free_before = alloc.free_pages
        pages = orig(src, dst)
        observed.append((src, dst, before, pages,
                         list(alloc._held[dst]),
                         free_before, alloc.free_pages))
        return pages

    alloc.transfer = spy
    try:
        req = await _generate(eng, "page identity probe", max_tokens=6)
        assert req.finish_reason is not None
        ((src, dst, before, returned, after, free_b, free_a),) = observed
        assert src != dst
        assert before == returned == after
        assert free_b == free_a
        assert eng._disagg.handoffs == 1
        assert eng._disagg.handoff_pages == len(returned) > 0
        eng._prefix_cache.check_invariants()
    finally:
        alloc.transfer = orig
        await eng.stop()


async def test_refcount_invariants_under_handoff_cancel_churn():
    """Allocator/table invariants hold across repeated rounds of
    concurrent admissions with cancellations landing mid-prefill (the
    reserved decode slot must come back) and mid-decode (the handed-off
    slot must come back); afterwards both pools are whole again."""
    eng = _mk_engine(disagg=True)
    try:
        for rnd in range(3):
            # A multi-chunk victim to cancel mid-prefill (its reserved
            # decode slot must come back), plus regular traffic with one
            # queued-cancel and rotating mid-decode cancels. Cancelled
            # requests never emit a closing delta (a cancelling client
            # has stopped reading), so they are awaited by finish_reason,
            # not drained.
            victim = GenRequest(
                prompt_ids=eng.tokenizer.encode(
                    f"mid prefill cancel target round {rnd} " * 3),
                max_tokens=12)
            reqs = [GenRequest(
                prompt_ids=eng.tokenizer.encode(f"churn {rnd} item {i}"),
                max_tokens=12) for i in range(5)]
            await eng.submit(victim)
            for r in reqs:
                await eng.submit(r)
            reqs[-1].cancelled = True           # usually still queued
            while victim.slot < 0 and victim.finish_reason is None:
                await asyncio.sleep(0.001)
            victim.cancelled = True             # slot taken: mid-request

            async def drain(r, cancel_mid):
                async for _ in eng.stream(r):
                    if cancel_mid:
                        r.cancelled = True      # a cancelling client also
                        break                   # stops reading the stream

            await asyncio.gather(*(
                drain(r, i % 2 == 0) for i, r in enumerate(reqs[:-1])))
            for r in (victim, reqs[-1], *reqs[:-1]):
                while r.finish_reason is None:
                    await asyncio.sleep(0.005)
            eng._prefix_cache.check_invariants()
        ctl = eng._disagg
        assert sorted(ctl.prefill.free) == list(ctl.prefill.slots)
        assert sorted(ctl.decode.free) == list(ctl.decode.slots)
        assert eng._free_slot_count() == eng.B
        assert not eng._running and not eng._prefilling
        assert ctl.clamp_pending == 0
    finally:
        await eng.stop()


# -- direct-to-decode compositions -------------------------------------------

async def test_warm_prefix_hit_admits_direct_to_decode(pooled_engine):
    """Radix-cache composition: a warm hit whose unmatched tail fits one
    prefill chunk never enters the prefill pool — the matched span is
    mapped (not prefilled) and the request decodes in place, so the
    handoff counter must NOT move."""
    eng = pooled_engine
    prompt = "please summarize the quarterly llama serving report " * 2
    cold = await _generate(eng, prompt, max_tokens=4)
    assert cold.cached_tokens == 0 and cold.pool == POOL_DECODE
    h0 = eng._disagg.handoffs
    d0 = eng._disagg.decode.admits
    p0 = eng._disagg.prefill.admits
    assert h0 >= 1

    warm = await _generate(eng, prompt, max_tokens=4)
    assert warm.cached_tokens > 0
    assert warm.pool == POOL_DECODE
    # decode_slot is reset at release; the slot it held must be a
    # decode-pool slot (it never borrowed one from the prefill pool).
    assert warm.slot in eng._disagg.decode.slots
    assert eng._disagg.handoffs == h0            # prefill pool skipped
    assert eng._disagg.decode.admits == d0 + 1
    assert eng._disagg.prefill.admits == p0
    eng._prefix_cache.check_invariants()


async def test_penalty_request_admits_direct_to_decode(pooled_engine):
    """Sampling-penalty requests build their on-device token-occurrence
    counts during prefill — which must happen on the slot that decodes
    them, so they place direct-to-decode (and bypass the prefix cache,
    as everywhere)."""
    eng = pooled_engine
    h0 = eng._disagg.handoffs
    req = await _generate(eng, "penalized distinct prompt", max_tokens=4,
                          presence_penalty=0.5)
    assert req.finish_reason is not None
    assert req.pool == POOL_DECODE
    assert eng._disagg.handoffs == h0


# -- goodput-first admission -------------------------------------------------

async def test_goodput_shed_raises_with_predicted_tpot(pooled_engine):
    """A request whose TPOT target the fitted decode step time cannot
    meet sheds at submit with the overload exception (the provider maps
    it to 429 + the engine's numeric Retry-After hint); SLO-free traffic
    keeps flowing."""
    eng = pooled_engine
    saved = eng._ema_step_ms_stats
    sheds0 = eng._disagg.goodput_sheds
    pool_sheds0 = eng._disagg.decode.sheds
    eng._ema_step_ms_stats = 500.0
    try:
        req = GenRequest(prompt_ids=eng.tokenizer.encode("shed me"),
                         max_tokens=4, slo_tpot_ms=0.01)
        with pytest.raises(EngineOverloaded, match="TPOT target"):
            await eng.submit(req)
        assert eng._disagg.goodput_sheds == sheds0 + 1
        assert eng._disagg.decode.sheds == pool_sheds0 + 1
        assert 1.0 <= eng.retry_after_hint_s() <= 30.0
        ok = await _generate(eng, "no slo attached", max_tokens=2)
        assert ok.finish_reason is not None
    finally:
        eng._ema_step_ms_stats = saved


async def test_ttft_risk_clamps_instead_of_shedding(pooled_engine):
    """TTFT-only risk admits with the clamp flag (burst depth rides the
    busy interleave until first token) and the flag drops by stream end
    — clamp is a latency trade, not a rejection."""
    eng = pooled_engine
    saved_step = eng._ema_step_ms_stats
    saved_chunk = eng._disagg._chunk_wall_ema_ms
    clamps0 = eng._disagg.clamps
    eng._ema_step_ms_stats = 0.01               # TPOT trivially met
    eng._disagg._chunk_wall_ema_ms = 1000.0     # TTFT predicted awful
    try:
        req = GenRequest(prompt_ids=eng.tokenizer.encode("clamped run"),
                         max_tokens=4, slo_ttft_ms=1.0, slo_tpot_ms=1e6)
        await eng.submit(req)                   # admitted, not shed
        assert req.disagg_clamped is True
        assert eng._disagg.clamp_pending >= 1
        async for _ in eng.stream(req):
            pass
        assert req.finish_reason is not None
        assert req.disagg_clamped is False
        assert eng._disagg.clamps == clamps0 + 1
        assert eng._disagg.clamp_pending == 0
    finally:
        eng._ema_step_ms_stats = saved_step
        eng._disagg._chunk_wall_ema_ms = saved_chunk


async def test_pool_stats_shape_and_prediction_fields(pooled_engine):
    """stats()["pools"] carries the per-pool block the /metrics collector
    fans onto gateway_engine_pool_* (slots/free/running/admits/sheds per
    pool, prediction fields once measured)."""
    eng = pooled_engine
    await _generate(eng, "stats shape probe", max_tokens=3)
    st = eng.stats()
    pools = st["pools"]
    assert set(pools) == {"prefill", "decode"}
    for block in pools.values():
        for key in ("slots", "free_slots", "running", "admits", "sheds"):
            assert isinstance(block[key], int)
    assert pools["prefill"]["slots"] == 1
    assert pools["decode"]["slots"] == eng.B - 1
    assert "occupancy_ratio" in pools["decode"]
    # Prefill dispatch walls were measured above → the TTFT prediction
    # engages (TPOT may stay None until a steady-depth burst fits).
    assert pools["prefill"].get("predicted_ttft_ms", 0) > 0
    assert st["disagg_handoffs"] >= 1
    assert st["disagg_handoff_pages"] >= 1


async def test_disaggregated_pools_on_a_model_mesh_match_one_device():
    """Prefill and decode pools over one SHARDED page pool: a handoff is
    a refcount transfer in the host allocator, so it is the same on a
    mesh — the tokens are the one-device engine's, and handoffs ran."""
    kw = dict(kv_page_size=16,
              disaggregation={"enabled": True, "prefill_slots": 1})
    ref, _ = await serve({}, **kw)
    got, eng = await serve({"model": 2}, **kw)
    assert got == ref
    assert eng.stats()["disagg_handoffs"] >= 2
    eng._prefix_cache.check_invariants()
