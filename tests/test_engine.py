"""Engine tests: generation lifecycle, continuous batching, sampling params,
overload fallback semantics. All on CPU with the tiny random-init presets,
served from the page pool; the lifecycle cases run on both kinds of cache
group the cells serve from — whole contexts (``tiny-test``) and the
window's page ring (``tiny-mistral-test``)."""
import asyncio

import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import (
    Delta, EngineOverloaded, GenRequest, InferenceEngine)

import jax

from tests.dense_reference import greedy_tokens

KINDS = ("tiny-test", "tiny-mistral-test")


def _cfg(**kw) -> LocalEngineConfig:
    base = dict(preset="tiny-test", max_batch_size=2, max_seq_len=128,
                prefill_chunk=32, dtype="float32", kv_page_size=16)
    base.update(kw)
    return LocalEngineConfig(**base)


@pytest.fixture(scope="module", params=KINDS)
def shared_engine(request, stop_engine):
    # Pages of 8: the ring is 9 pages (72 tokens) a slot, so a prompt of
    # 80 tokens recycles pages in prefill and a long answer in decode.
    eng = InferenceEngine(_cfg(preset=request.param, max_batch_size=4,
                               kv_page_size=8, decode_burst=4),
                          devices=[jax.devices("cpu")[0]])
    assert bool(eng._swa_ring_pages) == (request.param == KINDS[1])
    yield eng
    stop_engine(eng)


async def _generate(eng, prompt="hello", max_tokens=8, **kw) -> GenRequest:
    req = GenRequest(prompt_ids=eng.tokenizer.encode(prompt),
                     max_tokens=max_tokens, **kw)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


async def test_basic_generation(engine):
    req = await _generate(engine, "hello world", max_tokens=8)
    assert req.finish_reason in ("stop", "length")
    assert 1 <= len(req.generated) <= 8
    assert req.t_first_token is not None


async def test_deterministic_greedy(engine):
    r1 = await _generate(engine, "same prompt", max_tokens=6)
    r2 = await _generate(engine, "same prompt", max_tokens=6)
    assert r1.generated == r2.generated     # temperature=0 → greedy, stable


async def test_long_prompt_chunked_prefill(engine):
    # Prompt longer than prefill_chunk (32) forces multi-chunk prefill.
    req = await _generate(engine, "x" * 80, max_tokens=4)
    assert req.finish_reason is not None
    assert len(req.prompt_ids) == 80


async def test_generation_ends_at_max_seq_len(engine):
    """An answer that would run past the cache ends with "length" at the
    last position the cache holds (a ring has recycled pages by then)."""
    recycled = sum(g.recycled for g in engine.kv_groups)
    req = await _generate(engine, "y" * 40, max_tokens=10_000)
    assert req.finish_reason == "length"
    assert len(req.prompt_ids) + len(req.generated) == engine.S - 1
    if engine._swa_ring_pages:
        assert sum(g.recycled for g in engine.kv_groups) > recycled


async def test_concurrent_batching(engine):
    """More requests than slots: continuous batching must complete all,
    with no token loss or cross-request corruption."""
    prompts = [f"prompt number {i} " * 3 for i in range(7)]
    reqs = await asyncio.gather(*[
        _generate(engine, p, max_tokens=5) for p in prompts])
    for req in reqs:
        assert req.finish_reason is not None
        assert len(req.generated) >= 1
    # Greedy determinism across batch shapes: same prompt solo == batched.
    solo = await _generate(engine, prompts[0], max_tokens=5)
    assert solo.generated == reqs[0].generated


@pytest.mark.parametrize("preset", KINDS)
def test_prefill_group_matches_single_calls(preset):
    """One K=2 batched-prefill program call (per-slot page-table rows
    sliced inside the program) must leave the engine AND allocator in the
    same state as two K=1 calls (same pool, mirrors, first tokens) — the
    correctness that licenses batched admission (K queued prefills in one
    dispatch). Driven at the _prefill_chunk_group level so the grouping is
    deterministic, not scheduler-timing-dependent."""
    import numpy as np

    def build():
        return InferenceEngine(_cfg(preset=preset, max_batch_size=4,
                                    prefill_chunk=16, decode_burst=4),
                               devices=[jax.devices("cpu")[0]])

    def reqs_for(eng):
        out = []
        for slot, text in ((0, "batched admission parity alpha"),
                           (2, "a different second prompt beta")):
            req = GenRequest(prompt_ids=eng.tokenizer.encode(text),
                             max_tokens=4)
            req.slot = slot
            req.prefill_pos = 0
            eng.kv_groups.allocate(slot, len(req.prompt_ids) + 4)
            eng._table_dirty = True
            out.append(req)
        return out

    eng_b, eng_s = build(), build()
    rb, rs = reqs_for(eng_b), reqs_for(eng_s)
    done_b = eng_b._prefill_chunk_group(rb)      # one K=2 program
    done_s = [eng_s._prefill_chunk_group([r])[0] for r in rs]  # two K=1
    assert done_b == done_s
    for a, b in zip(rb, rs):
        assert a.generated == b.generated        # first tokens
    np.testing.assert_array_equal(eng_b.lengths, eng_s.lengths)
    np.testing.assert_array_equal(eng_b.active, eng_s.active)
    np.testing.assert_array_equal(eng_b.allocator.table,
                                  eng_s.allocator.table)
    assert eng_b.allocator.free_pages == eng_s.allocator.free_pages
    for side in ("k", "v"):
        for la, lb in zip(jax.tree.leaves(getattr(eng_b.cache, side)),
                          jax.tree.leaves(getattr(eng_s.cache, side))):
            a, b = np.asarray(la).copy(), np.asarray(lb).copy()
            # Page 0 is the trash page: bucket-pad positions of BOTH
            # rows scatter there, so its garbage is order-dependent BY
            # DESIGN (one K=2 program vs two K=1 programs write it in
            # different orders). Real pages must still match exactly.
            a[:, 0], b[:, 0] = 0, 0
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("preset", KINDS)
async def test_batched_admission_matches_sequential(build_engine, preset):
    """End-to-end: concurrent submissions (batched admission engages
    opportunistically when same-bucket prefills are queued together)
    produce the exact greedy tokens of each prompt decoded alone by the
    dense forward (on a ring the rows of one group rotate their pages
    each for itself)."""
    prompts = [f"batched admission parity {i} " * 2 for i in range(4)]
    eng = build_engine(_cfg(preset=preset, max_batch_size=4, prefill_chunk=16,
                            kv_page_size=8, decode_burst=4, prefill_batch=4),
                       devices=[jax.devices("cpu")[0]])
    reqs = await asyncio.gather(*[
        _generate(eng, p, max_tokens=6) for p in prompts])
    for req in reqs:
        assert req.generated == greedy_tokens(eng, req.prompt_ids, 6)


@pytest.mark.parametrize("preset", KINDS)
async def test_cancel_one_of_grouped_admissions(build_engine, preset):
    """Cancelling one request while its neighbors prefill in the same
    batched-admission group must not disturb the survivors (tokens
    intact) and must free the cancelled slot, and its pages, for reuse."""
    eng = build_engine(_cfg(preset=preset, max_batch_size=4, prefill_chunk=8,
                            kv_page_size=8, decode_burst=4, prefill_batch=4),
                       devices=[jax.devices("cpu")[0]])
    solo = await _generate(eng, "survivor prompt", max_tokens=5)

    victim = GenRequest(
        prompt_ids=eng.tokenizer.encode("victim prompt " * 6),
        max_tokens=5)
    await eng.submit(victim)
    survivor_task = asyncio.ensure_future(
        _generate(eng, "survivor prompt", max_tokens=5))
    await asyncio.sleep(0)          # let both enter the scheduler
    victim.cancelled = True
    survivor = await survivor_task
    assert survivor.generated == solo.generated
    # The cancelled slot returns to the pool (no slot leak).
    for _ in range(200):
        if len(eng._free_slots) == eng.B:
            break
        await asyncio.sleep(0.05)
    assert len(eng._free_slots) == eng.B


@pytest.mark.parametrize("preset", KINDS)
async def test_pipelined_bursts_match_sync_engine(build_engine, preset):
    """Lag-one burst pipelining (decode_burst > 1) must produce the exact
    greedy tokens of one token a step (the dense forward), across budgets
    that land on, before, and after a burst boundary (a ring's recycle
    floor trails the burst in flight by the burst's depth)."""
    piped = build_engine(_cfg(preset=preset, kv_page_size=8, decode_burst=4),
                         devices=[jax.devices("cpu")[0]])
    for mt in (3, 4, 5, 9):          # around burst=4 boundaries
        got = await _generate(piped, "pipelined parity", max_tokens=mt)
        assert got.generated == greedy_tokens(piped, got.prompt_ids, mt), mt
        assert len(got.generated) <= mt


@pytest.mark.parametrize("kv_quant", ["", "int8"])
async def test_tp_serving_engages_sharded_pallas_kernels(
        caplog, build_engine, kv_quant):
    """VERDICT r2 stretch item: on a multi-chip mesh with
    attention="pallas", real serving must route through the paged kernels
    under the providers' ``shard_map`` (interpret-mode on CPU) — pinned by
    the engine's log line — and produce the dense forward's exact greedy
    tokens. The int8 variant exercises the wrapper's per-leaf {q,s}
    specs."""
    import logging

    from tests.conftest import cpu_devices

    devs = cpu_devices()[:4]
    mesh_cfg = {"data": 2, "model": 2}    # KV=2 % 2 == 0 → manual axes

    with caplog.at_level(logging.INFO,
                         logger="llmapigateway_tpu.engine.engine"):
        eng = build_engine(
            _cfg(decode_burst=2, attention="pallas", mesh=mesh_cfg,
                 kv_quant=kv_quant), devices=devs)
    logs = " ".join(r.getMessage() for r in caplog.records)
    assert "attention=pallas" in logs, logs
    # Not in place under a mesh: the providers wrap the kernels in
    # shard_map (ops/paged_attention.py pool_in_place).
    assert eng.stats()["attention"] == "pallas"
    assert not eng.kv_pool_in_place
    got = await _generate(eng, "sharded pallas parity", max_tokens=6)
    assert got.generated == greedy_tokens(eng, got.prompt_ids, 6)
    assert got.finish_reason == "length"


@pytest.mark.parametrize("preset", KINDS)
async def test_pipelined_slot_reuse_no_token_bleed(build_engine, preset):
    """A slot released and re-admitted while a burst is in flight must not
    leak the dead request's tokens into the new one (epoch guard in
    _flush_entry). Staggered max_tokens force mid-flight releases."""
    eng = build_engine(_cfg(preset=preset, kv_page_size=8, decode_burst=4),
                       devices=[jax.devices("cpu")[0]])
    # 6 requests over 2 slots with varied budgets → several release +
    # re-admit cycles racing in-flight bursts.
    reqs = await asyncio.gather(*[
        _generate(eng, f"bleed check {i}", max_tokens=2 + (i % 3) * 3)
        for i in range(6)])
    for i, req in enumerate(reqs):
        assert req.finish_reason is not None
        assert 1 <= len(req.generated) <= 2 + (i % 3) * 3
        assert all(t >= 0 for t in req.generated), req.generated
    # Determinism: same prompt again solo gives the same tokens.
    again = await _generate(eng, "bleed check 0", max_tokens=2)
    assert again.generated == reqs[0].generated


async def test_engine_serves_qwen2_family(build_engine):
    """Qwen2 (llama block + QKV bias) serves end-to-end through the engine,
    random-init — exercises bias init/forward in both prefill and the
    deferred-decode path."""
    from llmapigateway_tpu.models.config import ModelConfig
    cfg = ModelConfig(family="qwen2", vocab_size=256, d_model=64, n_layers=2,
                      n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=128,
                      tie_embeddings=True, attn_bias=True)
    eng = build_engine(_cfg(preset=None, max_seq_len=64, prefill_chunk=16),
                       model_cfg=cfg, devices=[jax.devices("cpu")[0]])
    req = await _generate(eng, "qwen bias", max_tokens=5)
    assert req.finish_reason is not None and len(req.generated) >= 1


async def test_prompt_too_long_is_overload(engine):
    req = GenRequest(prompt_ids=list(range(4000)), max_tokens=4)
    with pytest.raises(EngineOverloaded):
        await engine.submit(req)


async def test_stop_string(engine):
    # Byte tokenizer: model output is pseudo-random bytes; use a stop string
    # unlikely to appear, then an empty generation path via max_tokens=1.
    req = await _generate(engine, "abc", max_tokens=1)
    assert req.finish_reason in ("stop", "length")
    assert len(req.generated) == 1


async def test_sampling_with_temperature(engine):
    """Temperature sampling runs (shape/mask path) and respects max_tokens."""
    req = await _generate(engine, "hi", max_tokens=5, temperature=0.9,
                          top_p=0.9, top_k=40)
    assert req.finish_reason is not None
    assert len(req.generated) <= 5


def test_stats(engine):
    s = engine.stats()
    assert s["batch_size"] == 4 and s["running"] == 0


@pytest.mark.parametrize("preset", KINDS)
async def test_prefill_near_cache_boundary_no_overrun(build_engine, preset):
    """Regression: with S not a multiple of the prefill bucket, the final
    padded chunk must be clamped to S - pos — a write past the last page
    (a ring maps pages only as far as the chunk reaches) would land on
    another token's place. The first token after a boundary-straddling
    prompt must be the dense forward's."""
    import numpy as np
    eng = build_engine(_cfg(preset=preset, max_batch_size=1, max_seq_len=100,
                            kv_page_size=8),
                       devices=[jax.devices("cpu")[0]])
    prompt_ids = list(np.arange(2, 97).astype(int) % 500)   # 95 tokens:
    # chunks at pos 0/32/64 → last bucket would pad to 32 but 64+32 = 96 < 100
    # is fine; use 97 tokens so last chunk starts at 96 with bucket 8 > 100-96.
    prompt_ids = prompt_ids + [7, 9]                         # 97 tokens

    req = GenRequest(prompt_ids=list(prompt_ids), max_tokens=2,
                     temperature=0.0)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    # The first token comes straight off prefill.
    assert req.generated[:1] == greedy_tokens(eng, prompt_ids, 1)


async def test_stop_flushes_waiting_consumers(build_engine):
    """stop() must emit terminal deltas for queued requests so no consumer
    hangs (review finding)."""
    cfg = _cfg(max_batch_size=1, max_seq_len=64, prefill_chunk=16)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    req = GenRequest(prompt_ids=[1, 2, 3], max_tokens=4)
    # Enqueue without letting the loop run, then stop: the stream must
    # terminate with an error delta rather than hang.
    eng._queue.put_nowait(req)
    await eng.stop()
    delta = await asyncio.wait_for(req.out_queue.get(), timeout=2)
    assert delta.error is not None


async def test_ttft_under_load_first_token_within_bounded_steps(build_engine):
    """North-star TTFT regression (VERDICT r1 item 6): while the decode
    batch is saturated with a long-running request, a newly admitted
    request's first token must arrive within a couple of scheduler
    iterations (the adaptive burst policy drops to burst=1 when work is
    pending), not after the running request drains."""
    from llmapigateway_tpu.engine.engine import FaultPlan

    cfg = _cfg(prefill_chunk=16, decode_burst=8)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    plan = FaultPlan()              # counters only, no injected faults
    eng.fault_plan = plan
    bg = GenRequest(prompt_ids=list(range(2, 18)), max_tokens=100)
    await eng.submit(bg)
    while bg.t_first_token is None:
        await asyncio.sleep(0.005)

    probe = GenRequest(prompt_ids=list(range(3, 15)), max_tokens=2)
    bursts_at_submit = plan.decode_calls
    await eng.submit(probe)
    while probe.t_first_token is None and probe.finish_reason is None:
        await asyncio.sleep(0.005)
    assert probe.t_first_token is not None
    # Saturation was real: the background request was still generating.
    assert bg.finish_reason is None
    # Bounded interleave: at most the in-flight burst + one shallow
    # (burst=1) round before the probe's prefill completes.
    assert plan.decode_calls - bursts_at_submit <= 3, \
        f"probe waited {plan.decode_calls - bursts_at_submit} bursts"
    bg.cancelled = True
    async for _ in eng.stream(probe):
        pass


def test_ttft_target_caps_idle_burst_depth(build_engine):
    """With ttft_target_ms set, the idle-queue deep burst depth is capped
    by the engine's fitted step time (half the target), snapping DOWN
    to a compiled scan depth; busy depth and the no-model warmup are
    unaffected. (VERDICT r4 item 2: TTFT exposure is the in-flight
    burst — a fixed deep depth is only right for one step time.)"""
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=32,
               decode_burst_busy=4, ttft_target_ms=100.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    # The 3/4, 1/2 and 1/4 rungs are compiled alongside deep and busy.
    assert set(eng._burst_depths) == {4, 8, 16, 24, 32}
    # No samples yet: run configured depth (the first bursts measure it).
    assert eng._burst_depth(busy=False) == 32
    assert eng._burst_depth(busy=True) == 4
    # 2 ms/step -> 50 ms budget -> cap 25 -> snaps down to the 24 rung.
    eng._burst_walls = {32: 64.0}
    assert eng._burst_depth(busy=False) == 24
    # 3 ms/step -> cap 16.7 -> the 16 rung.
    eng._burst_walls = {32: 96.0}
    assert eng._burst_depth(busy=False) == 16
    # Fast steps: full depth fits the budget.
    eng._burst_walls = {32: 32.0}
    assert eng._burst_depth(busy=False) == 32
    # Slow steps: even the busy depth overruns -> shallowest rung.
    eng._burst_walls = {32: 1280.0}
    assert eng._burst_depth(busy=False) == 4
    # Busy path ignores the target entirely.
    eng._burst_walls = {32: 64.0}
    assert eng._burst_depth(busy=True) == 4


def test_step_time_fit_removes_per_burst_fixed_cost(build_engine):
    """The cap's step-time estimate is the Δwall/Δdepth slope across the
    two largest measured depths, so per-burst fixed cost C cancels. The
    naive wall/d estimate folds C into the step time, which shrinks the
    cap, which shallows the bursts, which inflates the estimate further —
    a death spiral to the minimum compiled depth (observed on v5e:
    372 tok/s through the scheduler vs 1468 at a fixed burst 16, same
    TTFT target). The fit makes the loop self-correcting: shallow-depth
    samples plus ANY second depth recover the true step time."""
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=32,
               decode_burst_busy=4, ttft_target_ms=100.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    # True step 2 ms, fixed cost 40 ms/burst. One shallow depth alone:
    # conservative wall/d = 12 ms -> cap 4 (the spiral's resting point).
    eng._burst_walls = {4: 48.0}
    assert eng._step_ms_estimate() == pytest.approx(12.0)
    assert eng._burst_depth(busy=False) == 4
    # A second depth measured: slope (72-48)/(16-4) = 2 ms — C cancels,
    # the cap recovers (50/2 = 25 -> rung 24) despite C >> step.
    eng._burst_walls = {4: 48.0, 16: 72.0}
    assert eng._step_ms_estimate() == pytest.approx(2.0)
    assert eng._burst_depth(busy=False) == 24
    # Noise guard: a non-positive slope never feeds the cap — with a
    # previously fitted slope on record, that slope carries over...
    eng._burst_walls = {4: 48.0, 16: 40.0}
    assert eng._step_ms_estimate() == pytest.approx(2.0)
    # ...and without one, the conservative amortized bound is the floor
    # (never a negative/zero step time).
    eng._fit_slope = None
    assert eng._step_ms_estimate() == pytest.approx(40.0 / 16)


def test_step_time_fit_ignores_stale_depths(build_engine):
    """A depth that stopped running holds a wall measured under old
    conditions; once its sample ages past the window, the fit must not
    use it (stale w[32] from short-context warmup would UNDERestimate
    the step time after contexts grow — deepening bursts past the ttft
    budget)."""
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=32,
               decode_burst_busy=4, ttft_target_ms=100.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    eng._burst_walls = {32: 80.0, 16: 72.0}
    eng._burst_wall_stamp = {32: 1, 16: 1000}
    eng._burst_wall_n = 1000
    # Both fresh within the window -> two-point fit would give
    # (80-72)/16 = 0.5; with 32 stale (age 999 > 512) only depth 16
    # participates -> conservative 72/16 = 4.5.
    assert eng._step_ms_estimate() == pytest.approx(72.0 / 16)
    # All stale -> the newest entry still provides an estimate.
    eng._burst_wall_n = 2000
    assert eng._step_ms_estimate() == pytest.approx(72.0 / 16)


def test_fitted_slope_survives_depth_aging_out(build_engine):
    """Regression for the ON-CHIP death spiral (r5: 345.7 tok/s vs 1475
    at fixed burst 16, same 200 ms target): once the cap settles at one
    depth, the other depth's wall sample ages past the freshness window
    and the estimate used to degrade to the C-biased one-depth wall/d —
    shrinking the cap further, permanently. The fitted slope must
    PERSIST (TTL'd) across the aging-out, holding the cap at the fitted
    operating point."""
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=32,
               decode_burst_busy=4, ttft_target_ms=200.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    # Chip-like regime: step 4.5 ms, per-burst fixed cost 60 ms.
    wall = lambda d: 60.0 + 4.5 * d
    eng._burst_walls = {16: wall(16), 32: wall(32)}
    eng._burst_wall_stamp = {16: 100, 32: 100}
    eng._burst_wall_n = 100
    assert eng._step_ms_estimate() == pytest.approx(4.5)
    assert eng._burst_depth(busy=False) == 16          # cap 22.2
    # Depth 32 ages out (cap ran 16 for >window bursts). Without slope
    # persistence: est = wall(16)/16 = 8.25 -> cap 12 -> depth 8 (the
    # first turn of the spiral). With it: est stays 4.5, depth stays 16.
    eng._burst_wall_stamp = {16: 1000, 32: 100}
    eng._burst_wall_n = 1000
    assert eng._step_ms_estimate() == pytest.approx(4.5)
    assert eng._burst_depth(busy=False) == 16
    # The fixed-cost diagnostic reads C back out of the freshest wall.
    assert eng._fixed_cost_ms() == pytest.approx(60.0)
    # TTL expiry: a slope fitted thousands of samples ago no longer
    # reflects current conditions -> conservative amortized fallback.
    eng._burst_wall_n = 1000 + eng._SLOPE_TTL + 1
    eng._burst_wall_stamp = {16: eng._burst_wall_n}
    del eng._burst_walls[32]
    assert eng._step_ms_estimate() == pytest.approx(wall(16) / 16)


def test_explore_bursts_keep_second_depth_fresh(build_engine):
    """Every _EXPLORE_EVERY idle bursts the controller runs a steady
    PAIR one compiled rung deeper than the cap's pick, so the slope fit
    always has a second fresh depth (without it, exploration never
    happens once the cap settles, and the fit starves — the other half
    of the spiral fix)."""
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=32,
               decode_burst_busy=4, ttft_target_ms=200.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    eng._burst_walls = {16: 132.0, 32: 204.0}     # step 4.5, C 60
    eng._burst_wall_stamp = {16: 10, 32: 10}
    eng._burst_wall_n = 10
    depths = [eng._burst_depth(busy=False)
              for _ in range(2 * eng._EXPLORE_EVERY + 4)]
    # Steady point is 16; the explore rung is the next compiled depth.
    assert set(depths) == {16, 24}
    # Explore bursts come in back-to-back pairs (a wall sample only
    # records on a steady same-depth pair).
    runs, cur = [], [depths[0], 0]
    for d in depths:
        if d == cur[0]:
            cur[1] += 1
        else:
            runs.append(tuple(cur)); cur = [d, 1]
    runs.append(tuple(cur))
    assert all(n == 2 for d, n in runs if d == 24)
    assert sum(n for d, n in runs if d == 24) == 4   # 2 pairs in 68 calls
    # At the full configured depth there is nothing deeper to explore.
    eng._burst_walls = {32: 96.0}                    # 3 ms/step amortized
    eng._burst_wall_stamp = {32: eng._burst_wall_n}
    eng._fit_slope = None
    eng._explore_pending = 0
    assert all(eng._burst_depth(busy=False) == 32
               for _ in range(eng._EXPLORE_EVERY + 2))
    # Diagnostics: the depth histogram saw every dispatch decision.
    assert eng._depth_hist[24] == 4
    assert eng._depth_hist[16] == 2 * eng._EXPLORE_EVERY
    assert eng._depth_hist[32] == eng._EXPLORE_EVERY + 2


def test_burst_walls_sample_any_steady_depth(build_engine):
    """Every steady same-depth burst pair feeds the per-depth wall model
    (busy stretches at the shallow depth included — the model must not
    go stale under sustained load), and a depth transition never
    samples (its wall mixes two depths)."""
    cfg = _cfg(max_seq_len=96, prefill_chunk=16, decode_burst=8,
               decode_burst_busy=2, ttft_target_ms=100.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    eng.lengths[:] = 4
    eng.active[:] = True
    eng.last_token[:] = 1
    eng._d_dirty = True
    # First burst at 4: transition (no prior same-depth burst) -> no
    # sample; second at 4: steady pair -> samples depth 4.
    eng._decode_burst(4)
    assert eng._burst_walls == {}
    eng._decode_burst(4)
    assert set(eng._burst_walls) == {4}
    # Depth change: the first 8-burst is a transition, the second lands
    # the 8-sample — now two depths, the fit is live.
    eng._decode_burst(8)
    assert set(eng._burst_walls) == {4}
    eng._decode_burst(8)
    assert set(eng._burst_walls) == {4, 8}
    assert eng._step_ms_estimate() is not None
    assert eng._ema_step_ms_stats is not None


def test_no_ttft_target_keeps_fixed_depths(build_engine):
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=8,
               decode_burst_busy=2)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    assert set(eng._burst_depths) == {2, 8}
    eng._burst_walls = {8: 400.0}        # samples present, target unset
    assert eng._burst_depth(busy=False) == 8
    assert eng._burst_depth(busy=True) == 2


def test_prefill_aware_clamp_caps_busy_depth(build_engine):
    """ISSUE 2 tentpole (scheduler leg): while an admission waits, a busy
    burst may spend at most a QUARTER of the TTFT budget — at target
    scale (23 ms/step, r5b) the configured busy depth alone holds every
    prefill chunk behind a ~100-400 ms scan, compounding into the
    measured 742.8 ms p50. The clamp snaps below ``decode_burst_busy``
    (to the synchronous burst=1 path if nothing compiled fits) and
    leaves idle-queue depth untouched — fixed-burst TTFT without the
    fixed-burst throughput tax."""
    cfg = _cfg(max_seq_len=64, prefill_chunk=16, decode_burst=32,
               decode_burst_busy=16, ttft_target_ms=100.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    # No step-time sample yet: busy runs the configured busy depth.
    assert eng._burst_depth(busy=True) == 16
    assert eng._busy_clamps == 0
    # Fitted 2 ms/step: busy budget 25 ms -> cap 12.5 -> snaps to 8.
    eng._burst_walls = {32: 96.0, 16: 64.0}
    assert eng._burst_depth(busy=True) == 8
    assert eng._busy_clamps == 1
    # Idle depth is NOT reduced by the busy clamp (cap 50/2 = 25 -> 24).
    assert eng._burst_depth(busy=False) == 24
    # Pathologically slow steps: nothing compiled fits a quarter budget
    # -> burst=1 (synchronous path), still correct. (Drop the persisted
    # slope fit — this models a cold engine whose only evidence is the
    # one slow amortized wall.)
    eng._burst_walls = {32: 3200.0}
    eng._fit_slope = None
    assert eng._burst_depth(busy=True) == 1
    # Fast steps: the configured busy depth already fits -> unclamped.
    eng._burst_walls = {32: 32.0, 16: 16.0}   # 1 ms/step, cap 25
    clamps = eng._busy_clamps
    assert eng._burst_depth(busy=True) == 16
    assert eng._busy_clamps == clamps
    # Without a target the busy depth is never clamped (legacy behavior).
    eng.ttft_target_ms = 0.0
    eng._burst_walls = {32: 3200.0}
    assert eng._burst_depth(busy=True) == 16
    # The chosen depth and clamp count surface in stats.
    s = eng.stats()
    assert s["burst_depth_last"] == 16
    assert s["burst_busy_clamps"] >= 1


async def test_queue_wait_and_clamp_surface_in_stats_under_load(build_engine):
    """Engine-level scheduler leg of the acceptance: with a TTFT target
    and slow measured steps, a probe admitted against a saturated batch
    rides clamped (burst=1) interleaves — queue wait stays bounded and
    the stats counters (queue_wait, busy clamps, burst depth) read back
    end-to-end."""
    from llmapigateway_tpu.engine.engine import FaultPlan

    cfg = _cfg(prefill_chunk=16, decode_burst=8, decode_burst_busy=8,
               ttft_target_ms=100.0)
    eng = build_engine(cfg, devices=[jax.devices("cpu")[0]])
    plan = FaultPlan()
    eng.fault_plan = plan
    bg = GenRequest(prompt_ids=list(range(2, 18)), max_tokens=100)
    await eng.submit(bg)
    while bg.t_first_token is None:
        await asyncio.sleep(0.005)
    # Pretend the model measured SLOW (100 ms/step): every busy
    # burst must clamp below the configured busy depth of 8. The
    # probe's prompt spans THREE prefill chunks so clamped decode
    # rounds actually interleave mid-prefill (a one-chunk prompt
    # admits and finishes inside a single scheduler step).
    eng._burst_walls = {8: 800.0}
    eng._burst_wall_stamp = {8: eng._burst_wall_n}
    eng._fit_slope = None
    probe = GenRequest(prompt_ids=list(range(3, 43)), max_tokens=2)
    bursts_at_submit = plan.decode_calls
    await eng.submit(probe)
    while probe.t_first_token is None and probe.finish_reason is None:
        await asyncio.sleep(0.005)
    assert probe.t_first_token is not None
    assert bg.finish_reason is None          # saturation was real
    # Bounded interleave: at most the burst in flight at submit time
    # plus one clamped round per prefill chunk (the probe spans 3).
    # Anything above that means decode rounds ran unclamped between
    # chunks — the starvation this clamp exists to prevent.
    assert plan.decode_calls - bursts_at_submit <= 4, \
        f"probe waited {plan.decode_calls - bursts_at_submit} bursts"
    s = eng.stats()
    assert s["burst_busy_clamps"] >= 1
    assert s["queue_waits"] >= 2             # bg + probe admissions
    assert s["queue_wait_ms_max"] >= s["queue_wait_ms_ema"] > 0
    bg.cancelled = True
    async for _ in eng.stream(probe):
        pass


def test_engine_refuses_to_build_in_one_process_of_several(monkeypatch):
    """The engine serves from ONE process that drives every device of its
    mesh: a process started as one of several (``jax.distributed``) is
    refused at build, before anything is placed."""
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(jax, "process_index", lambda: 1)
    with pytest.raises(ValueError, match="the engine serves from one "
                       "process; this one is 1 of 2"):
        InferenceEngine(LocalEngineConfig(preset="tiny-test"))


def test_engine_build_refuses_an_unknown_mesh_axis(build_engine):
    """Past the configuration's own check (a ``mesh`` assigned after
    validation, a caller that builds the mesh itself): the mesh builder
    refuses the axis too, so nothing is silently served on one chip."""
    cfg = LocalEngineConfig(preset="tiny-test")
    cfg.mesh = {"pipe": 2}
    with pytest.raises(ValueError, match="unknown mesh axis 'pipe'"):
        build_engine(cfg, devices=jax.devices("cpu")[:2])
