"""A family with a latent page pool AND a block of recurrent state a slot
("gigachat3_5" at ``tiny-gigachat35-test``) through ``InferenceEngine``: a
slot's life with both kinds of storage (admit, prefill in chunks, decode
while its latent pages grow, release, reuse from zero state), the counters,
and every refusal at build with its reason — for a feature both kinds
refuse, BOTH reasons. Served tokens are judged as the benchmark judges
them: at every generated position the reference's logit of the token the
engine SERVED lies within a bound of the reference's own maximum (float32
engine and float32 reference: the order of the sums, 1e-3 is generous; a
stale state block or a lost latent page misses by ~1)."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gigachat35 as ref
from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

from test_model_gigachat35 import file_of

GAP_TOL = 1e-3
BASE = dict(preset="tiny-gigachat35-test", max_batch_size=4, max_seq_len=128,
            prefill_chunk=32, prefill_batch=2, dtype="float32",
            kv_layout="paged", kv_page_size=16, prefix_cache=False,
            decode_burst=4, decode_burst_busy=2)


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


async def worst_gap(eng, req: GenRequest) -> float:
    return await asyncio.to_thread(_worst_gap, eng, req)


async def test_a_slots_life_with_latent_pages_and_state(engine):
    """Every slot's state block and conv tail hold garbage. Two rounds of
    three requests on four slots (the second round REUSES slots whose
    blocks hold the first round's state: release does no state work, a
    prefill from position 0 starts from zero): prompts of 41, 70 and 41
    tokens — chunks of 32, padded tails, an uneven group — and 24 decoded
    tokens each, so a slot's latent pages grow DURING decode (41 + 24
    crosses pages 3 and 4 of 16) beside its fixed state. Every served token
    stands at the reference's maximum; the pool is whole again after."""
    engine.cache = engine.cache._replace(
        state=tuple(jnp.full_like(s, 9.0) for s in engine.cache.state),
        conv=tuple(jnp.full_like(t, -5.0) for t in engine.cache.conv))
    before = engine.stats()
    for round_ in range(2):
        reqs = await asyncio.gather(
            generate(engine, prompt(41, 10 + round_), 24),
            generate(engine, prompt(70, 20 + round_), 24),
            generate(engine, prompt(41, 30 + round_), 24))
        for req in reqs:
            assert len(req.generated) == 24
            assert await worst_gap(engine, req) <= GAP_TOL
    st = engine.stats()
    (group,) = st["kv_groups"]
    assert (group["kind"], group["layers"], group["window"]) == (
        "latent", 2, 0)
    assert group["token_bytes"] == (32 + 8) * 4          # float32 here
    assert group["pages_free"] == group["pages"]        # every slot released
    # 8 linear layers (2 leading + 2 x 3) of [4 heads, 16, 16] float32 and a
    # tail of 3 x 128 channels, 4 slots.
    per_slot = 8 * (4 * 16 * 16 * 4) + 8 * (3 * 128 * 4)
    assert st["state_bytes_resident"] == 4 * per_slot
    assert st["state_slots"] == 4
    # 6 requests x 23 decode steps (the first token is the prefill's), and
    # what a burst ran past a request's last token: at most 3 steps each.
    steps = 6 * 23
    updates = (st["lin_decode_state_updates_total"]
               - before["lin_decode_state_updates_total"])
    assert updates % 8 == 0 and steps <= updates // 8 <= steps + 6 * 3
    keys = sum(sum(range(n + 1, n + 24)) for n in (41, 70, 41)) * 2
    seen = st["mla_decode_keys_total"] - before["mla_decode_keys_total"]
    assert keys <= seen <= keys + 6 * 3 * (70 + 27)
    assert st["moe_assignments_total"] > 0


async def test_a_long_prompt_prefills_between_another_slots_bursts(engine):
    """A 3-chunk prompt is admitted while a request decodes: its slot sits
    through decode bursts between its chunks — its state and its latent
    pages untouched by them — and both come out as the reference has them."""
    first = asyncio.ensure_future(generate(engine, prompt(20, 1), 40))
    await asyncio.sleep(0.5)
    late = await generate(engine, prompt(90, 2), 6)
    early = await first
    assert await worst_gap(engine, late) <= GAP_TOL
    assert await worst_gap(engine, early) <= GAP_TOL


def test_a_burst_leaves_an_inactive_slots_storage_bit_identical(engine):
    """At the programs: slot 2 holds one prefilled chunk (state, tails and
    two latent pages), slot 0 decodes a burst of 4: slot 2's blocks and its
    pages do not change by a bit, slot 0's do."""
    eng = engine
    eng._flush_pending()
    assert eng.kv_groups.allocate(0, 64) and eng.kv_groups.allocate(2, 64)
    first, eng.cache = eng._exec_prefill([0, 2], [0, 0], [
        np.asarray(prompt(32, 3), np.int32),
        np.asarray(prompt(32, 4), np.int32)])
    pages = np.asarray(eng.allocator.table)[2, :2]

    def held(slot):
        blocks = jax.tree.map(lambda a: np.asarray(a[:, slot]),
                              (eng.cache.state, eng.cache.conv))
        return jax.tree.leaves(blocks) + [np.asarray(eng.cache.k[0][:, pages])]
    idle, busy = held(2), held(0)[:-1]
    eng.lengths[0], eng.active[0] = 32, True
    eng.last_token[0] = int(np.asarray(first)[0])
    eng._d_dirty = True
    eng._decode_burst(4)
    eng._flush_pending()
    for a, b in zip(idle, held(2)):
        assert np.array_equal(a, b)
    assert not all(np.array_equal(a, b) for a, b in zip(busy, held(0)[:-1]))
    eng.active[0], eng.lengths[0], eng.last_token[0] = False, 0, 0
    eng.kv_groups.release(0)
    eng.kv_groups.release(2)
    eng._d_dirty = True


REFUSED = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant 'int8'", [
        "the latent pool is bfloat16"]),
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache", [
        "a cached prefix holds KV pages but not the recurrent state",
        "has no rule yet for sharing latent pages"]),
    "spec": (dict(spec_draft_len=3), "spec_draft_len", [
        "a rejected draft cannot be rolled out of the recurrent state",
        "the verify path reads a K and a V pool, not a latent one"]),
    "mesh": (dict(mesh={"model": 2}), "mesh .*", [
        "the state block and the held experts have no sharding rule",
        "the latent pool has one key head"]),
    "disaggregation": (dict(disaggregation={"enabled": True,
                                            "prefill_slots": 1}),
                       "disaggregation", [
        "a handoff moves pages between slots, not the state block",
        "a handoff of latent pages between pools is not wired"]),
    "model_path": (dict(model_path="/nonexistent/checkpoint"), "model_path", [
        "no checkpoint mapping for this family"]),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_every_refusal_at_build_states_each_kinds_reason(what):
    """ONE list for a family with a latent group AND state: the union of
    what either kind refuses, and for a feature both refuse both reasons."""
    over, label, reasons = REFUSED[what]
    devices = jax.devices("cpu")[:2] if "mesh" in over else None
    with pytest.raises(ValueError) as err:
        _mk_engine(devices=devices, **over)
    said = str(err.value)
    import re
    assert re.search(f"'gigachat3_5' family does not support {label}: ", said)
    for reason in reasons:
        assert reason in said, (reason, said)
    assert said.count("; and ") == len(reasons) - 1


def test_a_window_beside_state_is_refused_too():
    import dataclasses
    from llmapigateway_tpu.models.config import get_preset
    windowed = dataclasses.replace(get_preset("tiny-gigachat35-test"),
                                   sliding_window=16)
    with pytest.raises(ValueError, match="does not support a sliding window: "
                                         "the page ring is not wired"):
        InferenceEngine(LocalEngineConfig(**BASE), windowed,
                        devices=[jax.devices("cpu")[0]])
