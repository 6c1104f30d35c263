"""ISSUE 6: radix prefix cache over the paged KV pool.

* Bit-for-bit parity: the same request served cold (full prefill) and
  warm (prefix hit + tail prefill) produces identical tokens/deltas,
  across the pages_per_block × precision matrix. Geometry aligns chunk
  boundaries with block boundaries so warm tail chunks run the exact
  programs the cold run compiled — bitwise-identical logits, not just
  "close".
* The skipped work is asserted STRUCTURALLY (prefill dispatch counts +
  engine cached-token stats), not from wall clock.
* Allocator churn invariants under fork/COW/refcount: randomized
  insert/evict/cancel sequences leak no pages and double-free none,
  including mid-stream cancellation through the real engine.
* Eviction is LRU-by-leaf with refcount pinning: in-flight requests can
  never lose a mapped page.
"""
import asyncio

import jax
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import (FaultPlan, GenRequest,
                                             InferenceEngine)
from llmapigateway_tpu.engine.paged import PageAllocator
from llmapigateway_tpu.engine.prefix_cache import RadixPrefixCache
from tests.mesh_parity import serve, split_dims

PAGE = 16


def _mk_engine(**kw):
    base = dict(preset="tiny-test", max_batch_size=2, max_seq_len=128,
                prefill_chunk=PAGE, dtype="float32", kv_layout="paged",
                kv_page_size=PAGE)
    base.update(kw)
    return InferenceEngine(LocalEngineConfig(**base),
                           devices=[jax.devices("cpu")[0]])


async def _gen(eng, ids, max_tokens=6, **kw) -> GenRequest:
    req = GenRequest(prompt_ids=list(ids), max_tokens=max_tokens, **kw)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


def _prompt(n, seed=3):
    rng = np.random.default_rng(seed)
    return rng.integers(2, 500, size=n).tolist()


@pytest.fixture(scope="module")
def warm_engine(stop_engine):
    eng = _mk_engine()
    yield eng
    stop_engine(eng)


# -- parity: cold vs warm, over the ppb × precision matrix --------------------

@pytest.mark.parametrize("ppb,kv_quant", [
    (1, ""), (2, ""), (4, ""), (1, "int8"), (2, "int8"), (4, "int8")])
async def test_cold_vs_warm_bit_for_bit(ppb, kv_quant):
    """Acceptance: identical greedy tokens AND text deltas cold vs warm,
    parametrized over pages_per_block 1/2/4 × bf16/int8-KV. Chunk size ==
    block size, so the warm tail prefill re-runs exactly the cold run's
    compiled chunk programs — bit-for-bit logits by construction."""
    eng = _mk_engine(kv_pages_per_block=ppb, kv_quant=kv_quant,
                     dtype="bfloat16", prefill_chunk=PAGE * ppb,
                     max_seq_len=256)      # 3 blocks fit even at ppb=4
    try:
        assert eng.kv_ppb == ppb
        assert eng._prefix_cache is not None
        assert eng._prefix_cache.block_tokens == PAGE * ppb
        ids = _prompt(3 * PAGE * ppb + 5, seed=ppb * 10 + len(kv_quant))
        cold = await _gen(eng, ids)
        warm = await _gen(eng, ids)
        assert cold.generated == warm.generated
        assert cold.text == warm.text
        assert cold.cached_tokens == 0
        assert warm.cached_tokens == 3 * PAGE * ppb
        s = eng.stats()
        assert s["prefix_hits_total"] == 1
        assert s["prefix_misses_total"] == 1
        assert s["prefix_cached_tokens_total"] == warm.cached_tokens
        eng._prefix_cache.check_invariants()
    finally:
        await eng.stop()


async def test_warm_request_skips_prefill_dispatches(warm_engine):
    """The matched span's prefill FLOPs are skipped, asserted from the
    engine's own dispatch counters (FaultPlan) — warm runs only the tail
    chunk."""
    eng = warm_engine
    eng.fault_plan = FaultPlan()
    ids = _prompt(4 * PAGE + 3, seed=7)
    try:
        cold = await _gen(eng, ids)
        cold_calls = eng.fault_plan.prefill_calls
        warm = await _gen(eng, ids)
        warm_calls = eng.fault_plan.prefill_calls - cold_calls
        assert cold.generated == warm.generated
        assert cold_calls == 5           # ceil(67 / 16) chunks
        assert warm_calls == 1           # 64 matched -> 3-token tail
        assert warm.cached_tokens == 4 * PAGE
        assert warm.prefix_lookup_ms is not None
    finally:
        eng.fault_plan = None


async def test_multi_turn_insert_covers_generated_tokens(warm_engine):
    """Insert-on-release indexes prompt + generated KV, so a follow-up
    turn (prior prompt + prior completion + new text) hits past the
    original prompt boundary."""
    eng = warm_engine
    ids = _prompt(2 * PAGE + 4, seed=11)
    first = await _gen(eng, ids, max_tokens=PAGE + 4)
    follow = ids + first.generated + _prompt(8, seed=12)
    second = await _gen(eng, follow)
    # Everything up to the last fully-written block of turn one is
    # reusable: >= floor((prompt + generated - 1) / block) blocks.
    reusable = (len(ids) + len(first.generated) - 1) // PAGE * PAGE
    assert second.cached_tokens >= reusable
    eng._prefix_cache.check_invariants()


async def test_penalty_requests_bypass_cache(warm_engine):
    """Penalty sampling needs the full-prompt token counts that prefill
    rebuilds — those requests run cold even with a resident prefix."""
    ids = _prompt(2 * PAGE + 2, seed=21)
    await _gen(warm_engine, ids)
    warm = await _gen(warm_engine, ids, presence_penalty=0.5)
    assert warm.cached_tokens == 0
    assert warm.finish_reason is not None


async def test_prefix_cache_flag_off():
    eng = _mk_engine(prefix_cache=False)
    try:
        assert eng._prefix_cache is None
        ids = _prompt(2 * PAGE + 2)
        await _gen(eng, ids)
        warm = await _gen(eng, ids)
        assert warm.cached_tokens == 0
        assert "prefix_hits_total" not in eng.stats()
    finally:
        await eng.stop()


async def test_mid_stream_cancellation_churn():
    """Cancellation at every lifecycle stage (queued / mid-prefill /
    mid-decode) with insert-on-release active: no leaked or double-freed
    pages, and the indexed KV stays warm-servable."""
    eng = _mk_engine(kv_num_pages=4 * 8 + 1, max_batch_size=2)
    try:
        ids = _prompt(4 * PAGE + 2, seed=31)

        async def cancel_after(req, n_deltas):
            # Client-hangup shape: stop consuming after flagging (a
            # cancelled slot finishes with emit=False — no terminal
            # delta arrives).
            seen = 0
            async for _ in eng.stream(req):
                seen += 1
                if seen >= n_deltas:
                    req.cancelled = True
                    break

        # Mid-decode cancel.
        r1 = GenRequest(prompt_ids=list(ids), max_tokens=40)
        await eng.submit(r1)
        await cancel_after(r1, 2)
        # Cancel while queued (before any admission pass can run).
        r2 = GenRequest(prompt_ids=list(ids), max_tokens=4)
        r2.cancelled = True
        await eng.submit(r2)
        # A clean warm request over whatever the cancelled one indexed.
        r3 = await _gen(eng, ids)
        assert r3.finish_reason in ("stop", "length")
        for _ in range(20):              # let releases drain
            if not eng._running:
                break
            await asyncio.sleep(0.05)
        eng._prefix_cache.check_invariants()
        total = eng.allocator.num_pages - 1
        assert (eng.allocator.free_pages
                + eng._prefix_cache.resident_pages == total)
    finally:
        await eng.stop()


# -- allocator + cache churn invariants (no engine) ---------------------------

def _mk_pool(ppb=1, num_pages=65, page=8, batch=6, max_seq=128):
    alloc = PageAllocator(num_pages=num_pages, page_size=page, batch=batch,
                          max_seq=max_seq, pages_per_block=ppb)
    cache = RadixPrefixCache(alloc, block_tokens=page * ppb)
    return alloc, cache


@pytest.mark.parametrize("ppb", [1, 4])
def test_randomized_fork_cow_refcount_churn(ppb):
    """Randomized admit(with shared prefix)/release(with insert)/cancel/
    evict sequences: the refcount invariants hold after every op and the
    pool conserves pages exactly (nothing leaked, nothing double-freed)."""
    rng = np.random.default_rng(42 + ppb)
    page = 8
    alloc, cache = _mk_pool(ppb=ppb, num_pages=64 + ppb, page=page,
                            batch=6, max_seq=128)
    bt = cache.block_tokens
    allocatable = alloc.free_pages
    # A small universe of token streams so prefixes actually collide
    # (fork points at every depth).
    streams = [list((np.arange(128) * m + m) % 97 + 2) for m in range(5)]
    live: dict[int, tuple] = {}          # slot -> (ids, total, nodes)
    for _ in range(400):
        op = rng.random()
        free_slots = [s for s in range(6) if s not in live]
        if op < 0.45 and free_slots:
            slot = int(rng.choice(free_slots))
            ids = streams[int(rng.integers(len(streams)))]
            total = int(rng.integers(bt, 120))
            matched, pages, nodes = cache.match(ids[:total])
            if not alloc.can_admit(total, shared_pages=len(pages)):
                short = alloc.fresh_shortfall(total,
                                              shared_pages=len(pages))
                cache.evict(short)
            if alloc.can_admit(total, shared_pages=len(pages)):
                assert alloc.allocate(slot, total, shared_pages=pages)
                live[slot] = (ids, total, nodes)
            else:
                cache.release_nodes(nodes)
        elif op < 0.8 and live:
            slot = int(rng.choice(list(live)))
            ids, total, nodes = live.pop(slot)
            if rng.random() < 0.7:       # completed: insert-on-release
                n_ok = int(rng.integers(0, total + 1))
                cache.insert(ids, min(n_ok, total),
                             alloc.table[slot])
            cache.release_nodes(nodes)   # cancelled or completed: unpin
            alloc.release(slot)
        else:
            cache.evict(int(rng.integers(1, 16)))
        cache.check_invariants()
    for slot in list(live):
        ids, total, nodes = live.pop(slot)
        cache.release_nodes(nodes)
        alloc.release(slot)
    cache.check_invariants()
    cache.evict(10 ** 6)
    assert cache.resident_pages == 0
    assert alloc.free_pages == allocatable
    assert not alloc._ref


def test_eviction_is_lru_by_leaf_and_pins_in_flight():
    alloc, cache = _mk_pool(num_pages=33, page=8, batch=4, max_seq=64)
    a = list(range(2, 34))               # 4 blocks
    b = list(range(50, 82))
    for seq in (a, b):
        assert alloc.allocate(0, len(seq))
        cache.insert(seq, len(seq), alloc.table[0])
        alloc.release(0)
    assert cache.resident_blocks == 8
    # Touch A's chain (pins it) — eviction must consume B's leaves first.
    matched, pages, nodes = cache.match(a + [1])
    assert matched == 32 and len(nodes) == 4
    freed = cache.evict(2)
    assert freed >= 2
    m2, _, n2 = cache.match(a + [1])
    assert m2 == 32                      # pinned chain untouched
    cache.release_nodes(n2)
    # Unpinned now, but interior nodes still only evict leaf-first:
    # drain everything and confirm exact conservation.
    cache.release_nodes(nodes)
    cache.evict(10 ** 6)
    assert cache.resident_pages == 0
    cache.check_invariants()
    assert alloc.free_pages == 32


def test_match_caps_one_token_short_of_prompt():
    """A fully-resident prompt still leaves >= 1 tail token to prefill
    (the engine samples the first output inside that program), which is
    also what keeps every written block private (COW at the fork)."""
    alloc, cache = _mk_pool(num_pages=33, page=8, batch=2, max_seq=64)
    seq = list(range(2, 34))             # exactly 4 blocks
    assert alloc.allocate(0, len(seq))
    cache.insert(seq, len(seq), alloc.table[0])
    alloc.release(0)
    matched, pages, nodes = cache.match(seq)
    assert matched == 24                 # NOT 32: last block left private
    cache.release_nodes(nodes)
    matched, _, nodes = cache.match(seq + [99])
    assert matched == 32                 # one extra token -> full share
    cache.release_nodes(nodes)
    cache.check_invariants()


def test_shared_pages_must_be_whole_groups():
    alloc, _ = _mk_pool(ppb=4, num_pages=36, page=8, batch=2, max_seq=128)
    assert alloc.allocate(0, 64)
    with pytest.raises(ValueError, match="whole groups"):
        alloc.allocate(1, 64, shared_pages=alloc.table[0][:2].tolist())
    with pytest.raises(ValueError, match="not live"):
        alloc.allocate(1, 64, shared_pages=[28, 29, 30, 31])


async def test_prefix_cache_on_a_model_mesh_matches_one_device():
    """The prefix cache over a pool whose heads are split on `model`: the
    second round of the same two prompts maps resident pages (whole
    blocks of each prompt) and generates what one device generates."""
    ref, _ = await serve({}, kv_page_size=16, rounds=2)
    got, eng = await serve({"model": 2}, kv_page_size=16, rounds=2)
    assert got == ref and got[:2] == got[2:]
    stats = eng.stats()
    assert stats["prefix_hits_total"] == 2
    assert stats["prefix_cached_tokens_total"] == 64       # 32 + 32
    assert split_dims(eng.cache.k) == (2,)           # KV heads
    eng._prefix_cache.check_invariants()
