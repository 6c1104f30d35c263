"""Cache groups (engine/paged.py ``CacheGroups``; ROADMAP R6): the layers
of a model grouped by the KV they must keep — a ring of pages for the
windowed group, the whole context for the global one — behind the
admission calls of one allocator, and the engine serving the SmallThinker
family from two of them. Served tokens are judged as the benchmark judges
them: the reference's logit of the token SERVED against the reference's own
maximum (float32 engine and float32 reference: the order of the sums, 1e-3
is generous)."""
import asyncio

import jax
import numpy as np
import pytest

from benchmark.reference import smallthinker as ref
from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.engine.paged import (CacheGroup, CacheGroups,
                                            PageAllocator)

from test_model_smallthinker import TINY, file_of

GAP_TOL = 1e-3
# Window 16, page 8, chunk 16, bursts of 4: a ring of ceil((16 + 4 + 16) /
# 8) + 2 = 7 pages a slot (56 tokens) against 16 for the whole context.
BASE = dict(preset="tiny-smallthinker-test", max_batch_size=3,
            max_seq_len=128, prefill_chunk=16, prefill_batch=2,
            dtype="float32", kv_layout="paged", kv_page_size=8,
            prefix_cache=False, decode_burst=4, decode_burst_busy=2,
            attention="reference")
RING, WHOLE = 7, 16


def _mk_engine(devices=None, **kw):
    return InferenceEngine(LocalEngineConfig(**{**BASE, **kw}),
                           devices=devices or [jax.devices("cpu")[0]])


@pytest.fixture(scope="module")
def engine(stop_engine):
    eng = _mk_engine()
    yield eng
    stop_engine(eng)


def prompt(n: int, seed: int) -> list[int]:
    return [int(t) for t in np.random.default_rng(seed).integers(3, 500, n)]


async def generate(eng, ids, max_tokens=8) -> GenRequest:
    req = GenRequest(prompt_ids=list(ids), max_tokens=max_tokens)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


def _worst_gap(eng, req: GenRequest) -> float:
    seq = np.asarray(list(req.prompt_ids) + req.generated[:-1], np.int32)
    rows = ref.logits(eng.params, ref.sizes(TINY, file_of(TINY)), seq,
                      last=len(req.generated))
    return max(float(row.max() - row[t])
               for row, t in zip(rows, req.generated))


# -- the allocators behind one admission --------------------------------------

def two_groups(global_pages=2 * WHOLE + 1, ring_pages=2 * RING + 1):
    """Two slots of 128 tokens, page 8: a global group of 2 layers and a
    windowed one of 6 whose slots hold a ring of 7 pages."""
    return CacheGroups([
        CacheGroup(2, 0, 0, PageAllocator(global_pages, 8, 2, 128)),
        CacheGroup(6, 16, RING, PageAllocator(ring_pages, 8, 2, 128))])


def test_admission_takes_every_group_or_none():
    groups = two_groups(global_pages=WHOLE + 5)     # one whole context + 4
    glob, ring = (g.allocator for g in groups)
    assert groups.can_admit(128) and groups.allocate(0, 128)
    assert (glob.free_pages, ring.free_pages) == (4, RING)
    for g in groups:
        g.dirty = False
    # The ring group has room for a second slot, the global one has not:
    # nothing is taken from either, no table is marked.
    assert ring.can_admit(128, RING) and not glob.can_admit(128)
    assert not groups.can_admit(128) and not groups.allocate(1, 128)
    assert (glob.free_pages, ring.free_pages) == (4, RING)
    assert not ring.table[1].any() and not any(g.dirty for g in groups)
    assert groups.fresh_shortfall(128) == WHOLE - 4
    # A short request fits both: 4 pages of the global group, and of the
    # ring no more than the request needs.
    assert groups.allocate(1, 32)
    assert (glob.free_pages, ring.free_pages) == (0, RING - 4)
    assert all(g.dirty for g in groups)
    groups.check_invariants()
    groups.release(0)
    groups.release(1)
    assert (glob.free_pages, ring.free_pages) == (WHOLE + 4, 2 * RING)
    groups.check_invariants()


def test_rotation_turns_the_ring_and_leaves_the_global_table_alone():
    groups = two_groups()
    glob, ring = groups.groups
    assert groups.allocate(0, 128) and groups.allocate(1, 40)
    whole = glob.allocator.table.copy()
    assert np.count_nonzero(whole[0]) == WHOLE
    assert np.count_nonzero(ring.allocator.table[0]) == RING
    for g in groups:
        g.dirty = False
    # A chunk that ends inside the mapped pages changes nothing.
    groups.rotate(0, last_pos=47, floor_pos=32)
    assert not glob.dirty and not ring.dirty and ring.recycled == 0
    # Positions 56..71 lie on logical pages 7 and 8: the two oldest pages,
    # wholly below position 40 - 16 + 1, are re-targeted onto them.
    held = set(ring.allocator.table[0][ring.allocator.table[0] > 0])
    groups.rotate(0, last_pos=71, floor_pos=40)
    row = ring.allocator.table[0]
    assert ring.dirty and ring.recycled == 2 and not glob.dirty
    assert not row[:2].any() and row[7] and row[8]
    assert set(row[row > 0]) == held                # the same 7 pages
    assert (glob.allocator.table == whole).all()
    assert (ring.allocator.table[1][:5] > 0).all()  # the other slot's row
    groups.check_invariants()
    # A page the window still reads is never taken: the floor holds.
    with pytest.raises(RuntimeError):
        groups.rotate(0, last_pos=127, floor_pos=40)
    groups.release(0)
    groups.release(1)
    groups.check_invariants()
    assert ring.allocator.free_pages == 2 * RING
    assert [g.stats()["pages_per_slot"] for g in groups] == [WHOLE, RING]


def test_one_group_configurations_run_through_the_same_calls():
    """Mistral is one windowed group, a model without a window one global
    group: the same object, the same admission."""
    ring = CacheGroups([CacheGroup(2, 16, RING,
                                   PageAllocator(RING + 1, 8, 1, 128))])
    assert ring.allocate(0, 128) and not ring.can_admit(8)
    ring.rotate(0, last_pos=71, floor_pos=40)
    assert ring.groups[0].recycled == 2
    whole = CacheGroups([CacheGroup(2, 0, 0,
                                    PageAllocator(WHOLE + 1, 8, 1, 128))])
    assert whole.allocate(0, 128, shared_pages=())
    whole.rotate(0, last_pos=127, floor_pos=100)    # no ring: a no-op
    assert whole.groups[0].recycled == 0 and not len(whole) - 1
    for groups in (ring, whole):
        groups.release(0)
        groups.check_invariants()


# -- the engine on two groups -------------------------------------------------

def test_the_engine_builds_two_groups_and_names_them(engine):
    st = engine.stats()
    token = 2 * 2 * 16 * 4                      # K+V, 2 heads of 16, f32
    assert st["kv_groups"] == [
        {"kind": "kv", "layers": 2, "window": 0, "token_bytes": token,
         "pages": 3 * WHOLE, "pages_free": 3 * WHOLE,
         "pages_per_slot": WHOLE},
        {"kind": "kv", "layers": 6, "window": 16, "token_bytes": token,
         "pages": 3 * RING, "pages_free": 3 * RING, "pages_per_slot": RING}]
    assert st["kv_ring_recycled_total"] == 0
    assert st["moe_experts_held"] == 8
    assert st["hbm_kv_pools_bytes"] == {
        "global": 2 * (3 * WHOLE + 1) * 8 * token,
        "window16": 6 * (3 * RING + 1) * 8 * token}
    assert st["hbm_kv_pool_bytes"] == sum(st["hbm_kv_pools_bytes"].values())
    assert len(engine.cache.k) == 2
    assert engine.cache.k[0].shape[:2] == (2, 3 * WHOLE + 1)
    assert engine.cache.k[1].shape[:2] == (6, 3 * RING + 1)
    assert st["kv_pool_in_place"] is False      # the "reference" kernels
    # One-group engines report the same keys.
    one = _mk_engine(preset="tiny-mistral-test").stats()
    assert [g["window"] for g in one["kv_groups"]] == [16]
    assert "hbm_kv_pools_bytes" not in one


async def test_contexts_past_window_and_ring_are_served_as_the_reference(
        engine):
    """Three requests at once on three slots: 100 and 70 tokens of prompt
    pass the ring's 56 tokens in prefill, a third passes it while decoding;
    every served token stands at the reference's maximum, the rings turned,
    the global group kept every page, and every slot left both groups."""
    at_start = engine.stats()
    before = at_start["kv_ring_recycled_total"]
    reqs = await asyncio.gather(
        generate(engine, prompt(100, 1), 12),
        generate(engine, prompt(70, 2), 20),
        generate(engine, prompt(50, 3), 30))
    for req in reqs:
        assert len(req.generated) == req.max_tokens
        assert await asyncio.to_thread(_worst_gap, engine, req) <= GAP_TOL
    st = engine.stats()
    # Pages past the ring: ceil(112 / 8) - 7, ceil(90 / 8) - 7 and
    # ceil(80 / 8) - 7 at the least (a burst maps a little ahead).
    assert st["kv_ring_recycled_total"] - before >= 7 + 5 + 3
    # The paged prefill kernel's walk, counted in BOTH groups (ISSUE 37):
    # chunks of 16 and a last bucket of 8 a prompt, one row-block each;
    # the global group walks up to the chunk's last page, the windowed
    # one from its window's floor, and a grid with a page axis stepped
    # through both tables of 16.
    from llmapigateway_tpu.ops.paged_attention import prefill_pages_walked
    chunks = [(pos, 16 if n - pos >= 16 else 8)
              for n in (100, 70, 50) for pos in range(0, n, 16)]
    walked = sum(prefill_pages_walked([pos], t, t, 8, window, 16)[0]
                 for pos, t in chunks for window in (0, 16))
    assert st["prefill_kv_pages_walked_total"] \
        - at_start["prefill_kv_pages_walked_total"] == walked
    assert st["prefill_kv_pages_table_total"] \
        - at_start["prefill_kv_pages_table_total"] == len(chunks) * 2 * 16
    assert 2 * walked < len(chunks) * 2 * 16
    assert [g["pages_free"] for g in st["kv_groups"]] == [3 * WHOLE, 3 * RING]
    engine.kv_groups.check_invariants()
    assert st["moe_assignments_total"] == st["moe_assignments_local_total"] > 0
    assert st["moe_assignments_total"] % (3 * 8) == 0   # top-3 x 8 layers
    assert 0 < st["moe_experts_hit_total"] <= st["moe_assignments_total"]


async def test_a_cancelled_request_leaves_both_groups(engine):
    req = GenRequest(prompt_ids=prompt(90, 4), max_tokens=30)
    await engine.submit(req)
    async for _ in engine.stream(req):
        if len(req.generated) >= 4:
            req.cancelled = True
    for _ in range(100):
        if not engine.active.any():
            break
        await asyncio.sleep(0.05)
    st = engine.stats()
    assert [g["pages_free"] for g in st["kv_groups"]] == [3 * WHOLE, 3 * RING]
    engine.kv_groups.check_invariants()
    after = await generate(engine, prompt(60, 5), 6)    # the slot is sound
    assert await asyncio.to_thread(_worst_gap, engine, after) <= GAP_TOL


def test_the_references_own_check_runs_on_an_idle_engine_and_cleans_up(
        engine):
    """``served_past_window`` (benchmark/reference/smallthinker.py) as the
    harness calls it in set-up: 64 tokens here ((7 + 3) pages of 8 in
    chunks of 16), pages re-targeted, the state as the warm-up left it."""
    case = ref.served_past_window(engine, file_of(TINY))
    assert case["ok"] and case["tokens"] == 80 and case["positions"] == 9
    assert case["ring_pages_recycled"] >= 3 and case["max_abs_err"] <= GAP_TOL
    assert not engine.active.any() and not engine.lengths.any()
    assert engine._d_dirty
    engine.kv_groups.check_invariants()
    assert all(g["pages_free"] == g["pages"]
               for g in engine.stats()["kv_groups"])


def test_a_global_layer_on_the_ring_fails_the_references_check():
    """The mechanism the check holds: were the global layers served from
    the ring too (one group, every layer windowed pages), their pages below
    the window would be re-targeted and the served tokens would fall far
    below the reference's maximum."""
    import dataclasses
    wrong = dataclasses.replace(TINY, window_layout=(1, 1, 1, 1))
    eng = InferenceEngine(LocalEngineConfig(**BASE), wrong,
                          devices=[jax.devices("cpu")[0]])
    assert len(eng.kv_groups) == 1
    case = ref.served_past_window(eng, file_of(TINY))
    assert not case["ok"] and case["max_abs_err"] > 100 * GAP_TOL


def test_the_in_place_pool_path_is_on_for_both_groups():
    """With the Pallas kernels both stacked pools ride the in-place
    protocol (``.decode_at`` / ``.prefill_at`` / ``.insert_all``): read
    from the engine's own statement, and from the lowered ``prefill_step``,
    which holds one aliased chunk write a layer kind and returns both
    pools where it took them."""
    eng = _mk_engine(attention="pallas", max_batch_size=2)
    assert eng.stats()["kv_pool_in_place"] is True
    state, key = eng._state_avals()
    import jax.numpy as jnp

    def row(dtype, *shape):
        return jax.ShapeDtypeStruct((1, *shape), dtype)
    text = eng._prefill_fn.lower(
        *state, row(jnp.int32, 16), row(jnp.int32), row(jnp.int32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32), key).as_text()
    # The four pool sides are donated and come back in place.
    assert text.count("tf.aliasing_output") >= 4 + 2    # + counts, counters
    for name in ("attn.global", "attn.window", "moe.experts"):
        assert name in eng._prefill_fn.lower(
            *state, row(jnp.int32, 16), row(jnp.int32), row(jnp.int32),
            row(jnp.int32), row(jnp.float32), row(jnp.float32),
            row(jnp.int32), row(jnp.float32), row(jnp.float32),
            key).as_text(debug_info=True)


# -- what the family refuses at build -----------------------------------------

@pytest.mark.parametrize("change, says", [
    ({"prefix_cache": True}, "prefix_cache: the ring re-targets"),
    ({"spec_draft_len": 3}, "spec_draft_len: the verify path reads one pool"),
    ({"mesh": {"model": 2}}, "mesh .*the page ring runs on one device"),
    ({"disaggregation": {"enabled": True, "prefill_slots": 1}},
     "disaggregation: a handoff cannot move a ring slot"),
    ({"model_path": "/nonexistent/checkpoint"},
     "model_path: no checkpoint mapping"),
])
def test_what_the_family_cannot_be_served_with_is_refused_at_build(
        change, says):
    devices = jax.devices("cpu")[:2] if "mesh" in change else None
    with pytest.raises(ValueError, match=f"'smallthinker' family does not "
                                         f"support {says}"):
        _mk_engine(devices=devices, **change)


def test_the_checkpoint_loader_refuses_the_family():
    from llmapigateway_tpu.engine.checkpoint import load_checkpoint
    with pytest.raises(ValueError, match="no checkpoint mapping for the "
                                         "'smallthinker' family"):
        load_checkpoint("/nonexistent", TINY)


# -- a cache group of the LATENT kind (ISSUE 38) -------------------------------
# The Mistral-Small-4 family: one global group whose pool is ONE latent pool
# (ops/latent_attention.py), behind the same allocator, tables and admission.

from benchmark.reference import mistral4 as mref  # noqa: E402

from test_model_mistral4 import TINY as MLA  # noqa: E402
from test_model_mistral4 import file_of as mla_file  # noqa: E402

LATENT = {**BASE, "preset": "tiny-mistral4-test"}


@pytest.fixture(scope="module")
def latent(stop_engine):
    eng = InferenceEngine(LocalEngineConfig(**LATENT),
                          devices=[jax.devices("cpu")[0]])
    yield eng
    stop_engine(eng)


def test_a_latent_group_is_a_kind_not_a_window():
    """The new kind keeps pages, tables and admission and changes the
    pool's shape alone; it has no window and no ring."""
    group = CacheGroup(4, 0, 0, PageAllocator(33, 8, 2, 128), kind="latent",
                       token_bytes=160)
    groups = CacheGroups([group])
    assert groups.allocate(0, 128) and groups.allocate(1, 128)
    assert not groups.can_admit(8)
    groups.rotate(0, 99, 80)                    # nothing to rotate
    assert group.recycled == 0
    groups.check_invariants()
    groups.release(0)
    groups.check_invariants()
    assert group.stats() == {
        "kind": "latent", "layers": 4, "window": 0, "token_bytes": 160,
        "pages": 32, "pages_free": 16, "pages_per_slot": 16}
    for bad in ({"window": 16}, {"ring_pages": 7}):
        args = {"window": 0, "ring_pages": 0, **bad}
        with pytest.raises(ValueError, match="keeps the whole context"):
            CacheGroup(4, args["window"], args["ring_pages"],
                       PageAllocator(33, 8, 2, 128), kind="latent")
    with pytest.raises(ValueError, match="unknown cache group kind"):
        CacheGroup(4, 0, 0, PageAllocator(33, 8, 2, 128), kind="dense")


def test_the_engine_builds_one_latent_group_and_accounts_for_it(latent):
    st = latent.stats()
    token = MLA.latent_width * 4                # one row, float32
    assert st["kv_groups"] == [
        {"kind": "latent", "layers": 4, "window": 0, "token_bytes": token,
         "pages": 3 * WHOLE, "pages_free": 3 * WHOLE,
         "pages_per_slot": WHOLE}]
    pool = latent.cache.k[0]
    assert latent.cache.v == () and pool.shape == (
        4, 3 * WHOLE + 1, MLA.latent_width, 8)
    assert st["hbm_kv_pools_bytes"] == {
        "latent": 4 * (3 * WHOLE + 1) * 8 * token}
    assert st["hbm_kv_pool_bytes"] == pool.size * 4
    assert (st["mla_decode_keys_total"], st["mla_prefill_keys_total"]) == (
        0, 0)
    assert st["moe_experts_held"] == 16


async def test_requests_of_mixed_lengths_serve_at_the_references_maximum(
        latent):
    """Four requests on three slots, through the scheduler, chunked
    prefill, bursts and the latent pool; three of them pass the tiny
    rotary's original context of 32."""
    before = latent.stats()
    done = await asyncio.gather(*[
        generate(latent, prompt(n, seed), 12)
        for seed, n in ((1, 70), (2, 33), (3, 20), (4, 90))])
    sizes = mref.sizes(MLA, mla_file(MLA))
    for req in done:
        assert len(req.generated) == 12
        seq = np.asarray(list(req.prompt_ids) + req.generated[:-1], np.int32)
        # Off the event loop: the reference compiles a program a length.
        rows = await asyncio.to_thread(mref.logits, latent.params, sizes,
                                       seq, len(req.generated))
        assert max(float(row.max() - row[t]) for row, t in zip(
            rows, req.generated)) <= GAP_TOL
    after = latent.stats()
    # Every prompt token and every decoded token but a request's last
    # attended its context once a layer: sum over positions of (p + 1),
    # plus what a prefill bucket pads its tail chunk with.
    exact = sum(sum(range(1, len(r.prompt_ids) + 11 + 1)) for r in done)
    counted = (after["mla_prefill_keys_total"] + after["mla_decode_keys_total"]
               - before["mla_prefill_keys_total"]
               - before["mla_decode_keys_total"])
    assert exact <= counted <= 1.35 * exact
    latent.kv_groups.check_invariants()
    assert all(g["pages_free"] == g["pages"] for g in after["kv_groups"])


def test_served_past_the_original_context_on_an_idle_engine(latent):
    """``served_past_8192`` (benchmark/reference/mistral4.py) as the
    harness calls it in set-up: 48 tokens here (the tiny original context
    of 32 and a chunk of 16), 8 decode steps, every key counted, the
    state as the warm-up left it."""
    case = mref.served_past_8192(latent, mla_file(MLA))
    assert case["ok"] and (case["tokens"], case["positions"]) == (48, 9)
    assert case["keys_attended"] == 56 * 57 // 2
    assert case["max_abs_err"] <= GAP_TOL
    assert not latent.active.any() and not latent.lengths.any()
    assert latent._d_dirty
    latent.kv_groups.check_invariants()


@pytest.fixture(scope="module")
def latent_kernels(stop_engine):
    """The latent engine with the Pallas kernels (interpreted here)."""
    eng = InferenceEngine(
        LocalEngineConfig(**{**LATENT, "attention": "pallas",
                             "max_batch_size": 2}),
        devices=[jax.devices("cpu")[0]])
    yield eng
    stop_engine(eng)


def test_the_latent_pool_rides_the_in_place_path_of_both_programs(
        latent_kernels):
    """With the Pallas kernels the pool is donated, carried through the
    layer scan of ``prefill_step`` AND of the decode programs, written by
    an aliased custom call under ``kv.latent_insert`` and attended under
    ``attention.latent``, all inside ``attn.mla``."""
    eng = latent_kernels
    assert eng.stats()["kv_pool_in_place"] is True
    state, key = eng._state_avals()
    import jax.numpy as jnp

    def row(dtype, *shape):
        return jax.ShapeDtypeStruct((1, *shape), dtype)
    lowered = eng._prefill_fn.lower(
        *state, row(jnp.int32, 16), row(jnp.int32), row(jnp.int32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32),
        row(jnp.int32), row(jnp.float32), row(jnp.float32), key)
    # The pool, the penalty counts and the counters come back in place.
    assert lowered.as_text().count("tf.aliasing_output") >= 3
    text = lowered.as_text(debug_info=True)
    for name in ("attn.mla", "kv.latent_insert", "attention.latent",
                 "moe.experts", "moe.shared"):
        assert name in text


async def test_the_steps_a_prefill_walks_are_counted_whole_and_all(
        latent_kernels, engine):
    """``mla_prefill_steps_total`` / ``_whole_total``: per layer, what the
    attention kernel's programs walk over a prompt's chunks and the steps
    among them attended in one straight line, by the kernel's own rule
    (``latent_steps_walked``: pages of 8, 4 a step, chunks of 16); a model
    without a latent pool counts neither."""
    from llmapigateway_tpu.ops.latent_attention import latent_steps_walked
    eng = latent_kernels
    before = eng.stats()
    assert (before["mla_prefill_steps_total"],
            before["mla_prefill_steps_whole_total"]) == (0, 0)
    req = await generate(eng, prompt(70, 5), 2)
    assert len(req.generated) == 2
    after = eng.stats()
    chunks = latent_steps_walked(range(0, 70, 16), 16, 16, 8, WHOLE)
    assert chunks == (1 + 1 + 2 + 2 + 3, 0 + 0 + 1 + 1 + 2)
    assert (after["mla_prefill_steps_total"],
            after["mla_prefill_steps_whole_total"]) == chunks
    await generate(engine, prompt(40, 6), 2)
    plain = engine.stats()
    assert plain.get("mla_prefill_steps_total", 0) == 0
    assert plain.get("mla_prefill_steps_whole_total", 0) == 0


@pytest.mark.parametrize("change, says", [
    ({"kv_quant": "int8"}, "kv_quant 'int8': the latent pool is bfloat16"),
    ({"prefix_cache": True}, "prefix_cache: the radix cache shares K/V"),
    ({"spec_draft_len": 3}, "spec_draft_len: the verify path reads a K"),
    ({"mesh": {"model": 2}}, "mesh .*the latent pool has one key head"),
    ({"disaggregation": {"enabled": True, "prefill_slots": 1}},
     "disaggregation: a handoff of latent pages"),
    ({"model_path": "/nonexistent/checkpoint"},
     "model_path: no checkpoint mapping"),
])
def test_what_the_latent_family_cannot_be_served_with_is_refused_at_build(
        change, says):
    devices = jax.devices("cpu")[:2] if "mesh" in change else None
    with pytest.raises(ValueError, match=f"'mistral4' family does not "
                                         f"support {says}"):
        InferenceEngine(LocalEngineConfig(**{**LATENT, **change}),
                        devices=devices or [jax.devices("cpu")[0]])


# -- the RING as group 0 (ISSUE 44) --------------------------------------------
# The Command A+ family: three windowed layers THEN a global one a period, so
# the first cache group is the ring and the whole-context group the second.
# Whatever wants "the whole-context group" finds it by its window.

from benchmark.reference import command_a_plus as cref  # noqa: E402

from test_model_cohere2 import TINY as COHERE  # noqa: E402
from test_model_cohere2 import file_of as cohere_file  # noqa: E402

RING_FIRST = {**BASE, "preset": "tiny-cohere2-test"}


@pytest.fixture(scope="module")
def ring_first(stop_engine):
    eng = InferenceEngine(LocalEngineConfig(**RING_FIRST),
                          devices=[jax.devices("cpu")[0]])
    yield eng
    stop_engine(eng)


def ring_then_global(global_pages=2 * WHOLE + 1, ring_pages=2 * RING + 1):
    return CacheGroups([
        CacheGroup(6, 16, RING, PageAllocator(ring_pages, 8, 2, 128)),
        CacheGroup(2, 0, 0, PageAllocator(global_pages, 8, 2, 128))])


def test_the_whole_context_group_is_found_by_its_window_not_its_place():
    ring, glob = ring_then_global().groups
    assert ring_then_global().whole_context.window == 0
    assert two_groups().whole_context is not two_groups().groups[1]
    assert two_groups().whole_context.window == 0
    # A model of windowed layers alone: the only group there is.
    only = CacheGroups([ring])
    assert only.whole_context is ring
    latent = CacheGroups([CacheGroup(
        4, 0, 0, PageAllocator(5, 8, 1, 32), kind="latent", token_bytes=80)])
    assert latent.whole_context.kind == "latent"


def test_admission_takes_both_or_neither_with_the_ring_first():
    groups = ring_then_global(global_pages=WHOLE + 5)
    ring, glob = (g.allocator for g in groups)
    assert groups.allocate(0, 128)
    assert (ring.free_pages, glob.free_pages) == (RING, 4)
    for g in groups:
        g.dirty = False
    # The ring (group 0) has room for a second slot, the global group has
    # not: nothing is taken from either.
    assert ring.can_admit(128, RING) and not glob.can_admit(128)
    assert not groups.can_admit(128) and not groups.allocate(1, 128)
    assert (ring.free_pages, glob.free_pages) == (RING, 4)
    assert not ring.table[1].any() and not any(g.dirty for g in groups)
    assert groups.fresh_shortfall(128) == WHOLE - 4
    # Rotation turns group 0 and leaves the global table alone.
    whole = glob.table.copy()
    groups.rotate(0, last_pos=71, floor_pos=40)
    assert groups.groups[0].recycled == 2 and groups.groups[0].dirty
    assert not groups.groups[1].dirty and (glob.table == whole).all()
    groups.release(0)
    groups.check_invariants()


def test_the_engine_builds_the_ring_first_and_names_the_pools_by_window(
        ring_first):
    eng, st = ring_first, ring_first.stats()
    token = 2 * 2 * 16 * 4                      # K+V, 2 heads of 16, f32
    assert st["kv_groups"] == [
        {"kind": "kv", "layers": 6, "window": 16, "token_bytes": token,
         "pages": 3 * RING, "pages_free": 3 * RING, "pages_per_slot": RING},
        {"kind": "kv", "layers": 2, "window": 0, "token_bytes": token,
         "pages": 3 * WHOLE, "pages_free": 3 * WHOLE,
         "pages_per_slot": WHOLE}]
    assert st["hbm_kv_pools_bytes"] == {
        "window16": 6 * (3 * RING + 1) * 8 * token,
        "global": 2 * (3 * WHOLE + 1) * 8 * token}
    assert st["hbm_kv_pool_bytes"] == sum(st["hbm_kv_pools_bytes"].values())
    assert eng.cache.k[0].shape[:2] == (6, 3 * RING + 1)
    assert eng.cache.k[1].shape[:2] == (2, 3 * WHOLE + 1)
    # The single-table view is the whole-context group's, wherever it lies.
    assert eng.allocator is eng.kv_groups.groups[1].allocator
    assert (st["free_pages"], st["total_pages"]) == (3 * WHOLE, 3 * WHOLE)
    assert eng.ledger.page_bytes == 2 * 8 * token
    assert eng._swa_ring_pages == RING
    assert st["moe_experts_held"] == 16
    assert (st["attn_decode_keys_global_total"],
            st["attn_decode_keys_window_total"]) == (0, 0)
    # The tied head: one matrix in the tree.
    assert "lm_head" not in eng.params and "lm_head_q8" not in eng.params
    # Both counters stand in every paged engine's stats, at zero where the
    # model has no group of the kind.
    assert "attn_decode_keys_window_total" in _mk_engine().stats()


def _cohere_gap(eng, req: GenRequest) -> float:
    seq = np.asarray(list(req.prompt_ids) + req.generated[:-1], np.int32)
    rows = cref.logits(eng.params, cref.sizes(COHERE, cohere_file(COHERE)),
                       seq, last=len(req.generated))
    return max(float(row.max() - row[t])
               for row, t in zip(rows, req.generated))


async def test_ring_first_contexts_past_the_ring_are_served_as_the_reference(
        ring_first):
    """Three requests at once: 100 and 70 tokens of prompt pass the ring's
    56 tokens in prefill, a third passes it while decoding; every served
    token stands at the reference's maximum, the rings turned, the global
    group kept every page, every slot left both groups — and the decode
    keys of both kinds are counted, monotone, a windowed layer's never
    past its window."""
    engine = ring_first
    at_start = engine.stats()
    reqs = await asyncio.gather(
        generate(engine, prompt(100, 1), 12),
        generate(engine, prompt(70, 2), 20),
        generate(engine, prompt(50, 3), 30))
    for req in reqs:
        assert len(req.generated) == req.max_tokens
        assert await asyncio.to_thread(_cohere_gap, engine, req) <= GAP_TOL
    st = engine.stats()
    assert st["kv_ring_recycled_total"] \
        - at_start["kv_ring_recycled_total"] >= 7 + 5 + 3
    assert [g["pages_free"] for g in st["kv_groups"]] == [3 * RING, 3 * WHOLE]
    engine.kv_groups.check_invariants()
    # A request of n prompt tokens and m answers decodes m - 1 steps (the
    # first token is the prefill's), step i at n + i + 1 keys; bursts may
    # run a few steps past a request's end, so these are lower bounds.
    glob = st["attn_decode_keys_global_total"] \
        - at_start["attn_decode_keys_global_total"]
    win = st["attn_decode_keys_window_total"] \
        - at_start["attn_decode_keys_window_total"]
    least = sum(n + i + 1 for n, m in ((100, 12), (70, 20), (50, 30))
                for i in range(m - 1))
    steps = (12 - 1) + (20 - 1) + (30 - 1)
    assert glob >= least and win >= 16 * steps
    assert win * (least // steps) <= 16 * glob      # window 16 a step
    assert st["moe_assignments_total"] % (4 * 8) == 0   # top-4 x 8 layers
    again = engine.stats()
    assert again["attn_decode_keys_global_total"] >= \
        st["attn_decode_keys_global_total"]


def test_the_cohere_references_own_check_counts_the_decode_keys(ring_first):
    """``served_past_window`` (benchmark/reference/command_a_plus.py) as the
    harness calls it in set-up: 80 tokens ((7 + 3) pages of 8 in chunks of
    16) and 8 decode steps; the two counters give exactly the keys of its
    steps, the window's in the ring group."""
    case = cref.served_past_window(ring_first, cohere_file(COHERE))
    assert case["ok"] and case["tokens"] == 80 and case["positions"] == 9
    assert case["ring_pages_recycled"] >= 3 and case["max_abs_err"] <= GAP_TOL
    assert case["decode_keys"] == {"global": sum(range(81, 89)),
                                   "window": 8 * 16}
    assert not ring_first.active.any() and not ring_first.lengths.any()
    assert all(g["pages_free"] == g["pages"]
               for g in ring_first.stats()["kv_groups"])


def test_the_parallel_block_rides_the_in_place_path_under_its_scopes(
        stop_engine):
    eng = InferenceEngine(
        LocalEngineConfig(**{**RING_FIRST, "attention": "pallas",
                             "max_batch_size": 2}),
        devices=[jax.devices("cpu")[0]])
    try:
        assert eng.stats()["kv_pool_in_place"] is True
        state, key = eng._state_avals()
        import jax.numpy as jnp

        def row(dtype, *shape):
            return jax.ShapeDtypeStruct((1, *shape), dtype)
        lowered = eng._prefill_fn.lower(
            *state, row(jnp.int32, 16), row(jnp.int32), row(jnp.int32),
            row(jnp.int32), row(jnp.float32), row(jnp.float32),
            row(jnp.int32), row(jnp.float32), row(jnp.float32), key)
        assert lowered.as_text().count("tf.aliasing_output") >= 4 + 2
        text = lowered.as_text(debug_info=True)
        for name in ("block.norm", "attn.global", "attn.window",
                     "moe.experts", "moe.shared"):
            assert name in text
    finally:
        stop_engine(eng)
