"""The cross-decoder family ("phi4flash" at ``tiny-phi4flash-test``: 8
layers, window 16) through ``InferenceEngine``: recurrent state BESIDE a
ring group AND a global group of ONE layer that other layers read. A slot's
life (admit into both groups or neither, prefill in chunks while the ring
recycles and the global group grows, the upper half on one row a prompt —
counted —, decode, release, re-admission onto a slot whose state block is
another request's), the counters, the ledger's bytes, and every refusal at
build with its reason. Served tokens are judged as the benchmark judges
them: at every generated position the reference's logit of the token the
engine SERVED lies within a bound of the reference's own maximum (float32
engine and float32 reference: the order of the sums, 1e-3 is generous)."""
import asyncio
import re

import jax
import numpy as np
import pytest

from benchmark.reference import phi4_flash as ref
from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

from test_model_phi4_flash import file_of

GAP_TOL = 1e-3
# Window 16, page 16, chunk 32, bursts of 4: a ring of ceil((16 + 4 + 32) /
# 16) + 2 = 6 pages a slot (96 tokens) against 16 for the whole context.
BASE = dict(preset="tiny-phi4flash-test", max_batch_size=4, max_seq_len=256,
            prefill_chunk=32, prefill_batch=2, dtype="float32",
            kv_layout="paged", kv_page_size=16, prefix_cache=False,
            decode_burst=4, decode_burst_busy=4)
RING, WHOLE = 6, 16


def _mk_engine(devices=None, **kw):
    return InferenceEngine(LocalEngineConfig(**{**BASE, **kw}), None,
                           devices=devices or [jax.devices("cpu")[0]])


@pytest.fixture(scope="module")
def engine(stop_engine):
    eng = _mk_engine()
    eng.tokenizer.eos_ids = set()   # random weights: every answer runs out
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


def _worst_gap(eng, req: GenRequest) -> float:
    c = eng.model_cfg
    seq = np.asarray(list(req.prompt_ids) + req.generated[:-1], np.int32)
    rows = ref.logits(eng.params, ref.sizes(c, file_of(c)), seq,
                      last=len(req.generated))
    return max(float(row.max() - row[t])
               for row, t in zip(rows, req.generated))


def test_the_engine_serves_the_folded_geometry_from_two_groups(engine):
    """The preset keeps the published heads; the engine serves the fold. A
    ring of 2 layers FIRST, a global group of ONE layer, ONE state block of
    3 scan layers [3, slots, 8, 128] float32 and its conv tails beside
    them; the ledger names both pools and counts the state."""
    c = engine.model_cfg
    assert (c.n_heads, c.n_kv_heads, c.head_dim) == (4, 1, 32)
    assert c.served() is c
    st = engine.stats()
    ring, whole = st["kv_groups"]
    assert (ring["layers"], ring["window"], ring["pages_per_slot"]) == (
        2, 16, RING)
    assert (whole["layers"], whole["window"], whole["pages_per_slot"]) == (
        1, 0, WHOLE)
    assert ring["token_bytes"] == whole["token_bytes"] == 2 * 1 * 32 * 4
    assert engine.allocator is engine.kv_groups.groups[1].allocator
    (state,), (conv,) = engine.cache.state, engine.cache.conv
    assert state.shape == (3, 4, 8, 128) and state.dtype == np.float32
    assert conv.shape == (3, 4, 3, 128)
    assert st["state_bytes_resident"] == state.nbytes + conv.nbytes
    assert set(st["hbm_kv_pools"]) == {"window16", "global"} \
        if "hbm_kv_pools" in st else True
    assert st["hbm_kv_pool_bytes"] == sum(
        a.size * a.dtype.itemsize
        for side in (engine.cache.k, engine.cache.v) for a in side)


def test_admission_takes_both_groups_or_neither(engine):
    """The global group has room for 4 whole contexts; with three taken and
    a fourth that needs more than is left, nothing is taken from the ring
    either."""
    groups = engine.kv_groups
    ring, whole = (g.allocator for g in groups)
    free = (ring.free_pages, whole.free_pages)
    for slot in range(3):
        assert groups.allocate(slot, 256)
    assert whole.free_pages == free[1] - 3 * WHOLE
    assert ring.free_pages == free[0] - 3 * RING
    assert groups.allocate(3, 200)          # 13 of the 16 pages left
    assert not groups.can_admit(100)
    groups.release(3)
    taken = (ring.free_pages, whole.free_pages)
    for g in groups:
        g.dirty = False
    # Now the ring has room and the global group has too little.
    held = whole.free_pages
    assert groups.allocate(3, 16 * (held - 1))
    groups.release(3)
    assert (ring.free_pages, whole.free_pages) == taken
    for slot in range(3):
        groups.release(slot)
    assert (ring.free_pages, whole.free_pages) == free
    groups.check_invariants()


async def test_a_slots_life_beside_state_ring_and_one_shared_layer(engine):
    """Two rounds of three requests on four slots — prompts of 97, 150 and
    41 tokens (chunks of 32, padded tails, an uneven group; two of them past
    the ring's 96 tokens, all past the window), 16 decoded tokens each — the
    second round on slots whose state blocks and pages are the first
    round's. Every served token stands at the reference's maximum. The ring
    recycled while the global group grew; every prompt row but ONE a prompt
    stopped at the full layer's K/V; the cross reads are two layers' (the
    full layer and the one cross layer) of every step's context."""
    before = engine.stats()
    lengths = (97, 150, 41)
    for round_ in range(2):
        reqs = await asyncio.gather(*[
            generate(engine, prompt(n, 10 * n + round_), 16)
            for n in lengths])
        for req in reqs:
            assert len(req.generated) == 16
            gap = await asyncio.to_thread(_worst_gap, engine, req)
            assert gap <= GAP_TOL
    st = engine.stats()
    grew = {k: st[k] - before[k] for k in (
        "prefill_rows_stopped_total", "cross_decode_keys_read_total",
        "lin_decode_state_updates_total", "kv_ring_recycled_total",
        "attn_decode_keys_global_total", "attn_decode_keys_window_total")}
    # The prompts' tokens less ONE a prompt: the upper half ran on 6 rows.
    assert grew["prefill_rows_stopped_total"] == 2 * (sum(lengths) - 3)
    # 6 requests x 15 decode steps (the first token is the prefill's), and
    # what a burst ran past a request's last token: at most 3 steps each.
    steps = 2 * len(lengths) * 15
    assert steps * 3 <= grew["lin_decode_state_updates_total"] \
        <= (steps + 6 * 3) * 3
    least = 2 * sum(sum(range(n + 1, n + 16)) for n in lengths)
    assert grew["attn_decode_keys_global_total"] >= least
    assert grew["cross_decode_keys_read_total"] == \
        2 * grew["attn_decode_keys_global_total"]
    assert least <= grew["attn_decode_keys_global_total"] \
        <= least + 6 * 3 * (150 + 19)
    assert steps * 16 <= grew["attn_decode_keys_window_total"] \
        <= (steps + 6 * 3) * 16
    assert grew["kv_ring_recycled_total"] > 0
    assert all(g["pages_free"] == g["pages"] for g in st["kv_groups"])
    assert "moe_assignments_total" not in st


async def test_what_correct_compares_past_the_ring(engine):
    """The benchmark's ``served_past_window`` on this engine as the harness
    calls it in set-up: ONE request of 160 tokens (five chunks: past the
    ring's 96) on an idle engine, every generated position held to the
    reference, the counters to the steps' contexts. (What the planted faults
    move is tests/test_model_phi4_flash.py's: at this width attention is
    all but even, and a fault that moves every logit by 1e-2 leaves the
    served token the wrong reference's maximum too.)"""
    c = engine.model_cfg
    got = await asyncio.to_thread(ref.served_past_window, engine, file_of(c))
    assert got["tokens"] == 160 and got["positions"] == ref.LONG_ANSWER
    assert got["prefill_rows_stopped_total"] == 159
    assert got["kv_ring_recycled_total"] > 0 and got["released"]
    assert got["ok"] and got["max_abs_err"] <= GAP_TOL
    assert not engine.active.any() and engine._d_dirty


async def test_the_references_checks_run_on_the_engine(engine):
    """``kernel_checks`` whole, interpreted: the paged kernels at the served
    fold over the whole context, one cross layer's read, the chunk form of
    the scan with its state carried, the long request; ``controlled_checks``
    refuses a bfloat16 state by the scan's arithmetic and by the engine's
    own block, which is float32 (no served token can show one: the gap
    stays inside its bound)."""
    c = engine.model_cfg
    cases = await asyncio.to_thread(ref.kernel_checks, engine, file_of(c),
                                    True)
    assert [case["kernel"] for case in cases] == [
        "paged_decode_full", "paged_prefill_full", "cross_read_decode",
        "ssm_scan_chunked", "served_past_window"]
    assert all(case["ok"] for case in cases), cases
    assert cases[2]["max_abs_err"] < 1e-2   # bfloat16 inputs: 3e-3 here
    assert cases[4]["state_dtype"] == "float32"
    scan, served = await asyncio.to_thread(
        ref.controlled_checks, engine, file_of(c), ref.CONTROLS["bf16_state"])
    assert not scan["ok"] and scan["max_abs_err"] > 10 * ref.SCAN_TOL
    assert not served["ok"] and served["state_dtype"] == "float32"
    assert served["max_abs_err"] <= GAP_TOL


REFUSED = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant 'int8'",
                 "two softmax maps are SUBTRACTED"),
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache",
                     "the memory layer's state at its end"),
    "spec": (dict(spec_draft_len=3), "spec_draft_len",
             "this family's upper half runs on one row"),
    "mesh": (dict(mesh={"model": 2}), "mesh .*",
             "the folded K/V heads and the scan's channels"),
    "disaggregation": (dict(disaggregation={"enabled": True,
                                            "prefill_slots": 1}),
                       "disaggregation",
                       "not the ring, the state block and the memory"),
    "model_path": (dict(model_path="/nonexistent/checkpoint"), "model_path",
                   "no checkpoint mapping for this family"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_every_refusal_at_build_states_its_reason(what):
    over, label, reason = REFUSED[what]
    devices = jax.devices("cpu")[:2] if "mesh" in over else None
    with pytest.raises(ValueError) as err:
        _mk_engine(devices=devices, **over)
    said = str(err.value)
    assert re.search(f"'phi4flash' family does not support {label}: ", said)
    assert reason in said, said
    # The window itself is NOT refused beside this family's state.
    assert "the page ring is not wired" not in said
