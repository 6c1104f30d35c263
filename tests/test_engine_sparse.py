"""A family whose page pool has an index-key side ("keye_vl2" at
``tiny-keye-vl2-test``: 24 keys kept a query) through ``InferenceEngine``:
a slot's life (admit, prefill in chunks past the selection, decode while
its pages grow, release, re-admission onto pages whose index keys are
another request's), the two counters, the ledger's bytes, and every refusal
at build with its reason. Served tokens are judged as the benchmark judges
them: at every generated position the reference's logit of the token the
engine SERVED lies within a bound of the reference's own maximum (float32
engine and float32 reference: the order of the sums, 1e-3 is generous; a
query that kept a stale key misses by ~0.1-1)."""
import asyncio
import re

import jax
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as ref
from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine

from test_model_keye_vl2 import file_of

GAP_TOL = 1e-3
TOPK = 24
BASE = dict(preset="tiny-keye-vl2-test", max_batch_size=4, max_seq_len=192,
            prefill_chunk=32, prefill_batch=2, dtype="float32",
            kv_layout="paged", kv_page_size=16, prefix_cache=False,
            decode_burst=4, decode_burst_busy=4)


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


async def test_a_slots_life_with_an_index_side(engine):
    """Two rounds of three requests on four slots — prompts of 97, 150 and
    41 tokens (chunks of 32, padded tails, an uneven group; two of them
    four to six times the 24 keys kept), 16 decoded tokens each, so pages
    grow during decode — the second round on pages whose index keys are the
    first round's (release does no work on them; a key past a query's
    position is never selected). Every served token stands at the
    reference's maximum; decode read exactly min(context, 24) rows a step;
    the pool is whole again after."""
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
    (group,) = st["kv_groups"]
    assert (group["kind"], group["layers"], group["window"]) == ("kv", 4, 0)
    # K and V of 2 heads of 16 and an index key of 8, float32 here.
    assert group["token_bytes"] == (2 * 2 * 16 + 8) * 4
    assert group["pages_free"] == group["pages"]
    # The ledger counts the index side with K and V (and the trash page).
    assert st["hbm_kv_pool_bytes"] == (
        group["layers"] * (group["pages"] + 1) * 16 * group["token_bytes"])
    assert st["hbm_kv_pool_bytes"] == sum(
        a.size * a.dtype.itemsize for side in (
            engine.cache.k, engine.cache.v, engine.cache.index)
        for a in side)
    # 6 requests x 15 decode steps (the first token is the prefill's), and
    # what a burst ran past a request's last token: at most 3 steps each.
    scored = (st["dsa_decode_keys_scored_total"]
              - before["dsa_decode_keys_scored_total"])
    kept = (st["dsa_decode_keys_selected_total"]
            - before["dsa_decode_keys_selected_total"])
    steps = 2 * len(lengths) * 15
    assert kept % TOPK == 0 and steps <= kept // TOPK <= steps + 6 * 3
    least = 2 * sum(sum(range(n + 1, n + 16)) for n in lengths)
    assert least <= scored <= least + 6 * 3 * (150 + 19)
    assert kept < 0.4 * scored
    assert "mla_decode_keys_total" not in st
    assert st["attn_decode_keys_global_total"] == \
        before["attn_decode_keys_global_total"]


@pytest.mark.parametrize("control", [None, "dense_attention",
                                     "lowest_scores"])
async def test_what_correct_compares_past_the_keys_kept(engine, control):
    """The benchmark's ``served_past_topk`` on this engine: two prompts of
    128 tokens (five times the 24 keys kept) through the scheduler, two
    rows a prefill dispatch while two short requests decode beside them,
    every generated position held to the reference. The sound reference
    passes (float32 here: at the sums' order); the selection's two
    ``CONTROLS`` — every seen key attended, the 24 keys of LEAST score — are
    refused by the comparison's own limits (the third, four-bit weights, is
    nothing to float32 weights)."""
    c = engine.model_cfg
    got = await asyncio.to_thread(ref.served_past_topk, engine, file_of(c),
                                  ref.CONTROLS.get(control))
    assert got["tokens"] == [128, 128, 64, 64] and got["others_live"]
    assert got["two_row_dispatches"] >= 4
    assert got["ok"] is (control is None), got
    if control is None:
        assert got["max_abs_err"] <= GAP_TOL
    else:
        assert got["max_abs_err"] > 0.25 or got["gap_p50"] > 0.05


REFUSED = {
    "kv_quant": (dict(kv_quant="int8"), "kv_quant 'int8'",
                 "a gathered int8 row needs its scale plane gathered"),
    "prefix_cache": (dict(prefix_cache=True), "prefix_cache",
                     "no rule yet for sharing their index-key side"),
    "spec": (dict(spec_draft_len=3), "spec_draft_len",
             "the verify path has no selection"),
    "mesh": (dict(mesh={"model": 2}), "mesh .*",
             "the index-key side has one head, which no axis divides"),
    "disaggregation": (dict(disaggregation={"enabled": True,
                                            "prefill_slots": 1}),
                       "disaggregation",
                       "not their index-key side"),
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
    assert re.search(f"'keye_vl2' family does not support {label}: ", said)
    assert reason in said, said
