"""Prompt-lookup speculative decoding (engine/speculative.py): output must
be EXACTLY the normal greedy sequence (verification-anchored — wrong drafts
are rejected by construction), with >1 token/step accepted on repetitive
text and the config guardrails enforced."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.engine.engine import GenRequest, InferenceEngine
from llmapigateway_tpu.engine.speculative import draft_from_history
from tests.dense_reference import greedy_tokens
from tests.mesh_parity import CYCLING, serve


def _engine(spec=0, **kw):
    # decode_burst_busy == decode_burst: whether the first decode round
    # sees `busy` (prefill completion races the round under load) must
    # not change the burst SEGMENTATION — different scan depths are
    # different compiled programs whose float rounding can flip a
    # near-tie argmax on random weights, making exact-parity
    # comparisons timing-flaky (1-core repro: two stable greedy
    # continuations of the same prompt).
    kw.setdefault("decode_burst_busy", 8)
    kw.setdefault("kv_page_size", 16)
    kw.setdefault("preset", "tiny-test")
    cfg = LocalEngineConfig(max_batch_size=2,
                            max_seq_len=192, prefill_chunk=32,
                            dtype="float32", decode_burst=8,
                            spec_draft_len=spec, **kw)
    return InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])


async def _gen(eng, prompt_ids, max_tokens):
    req = GenRequest(prompt_ids=list(prompt_ids), max_tokens=max_tokens,
                     temperature=0.0)
    await eng.submit(req)
    async for _ in eng.stream(req):
        pass
    return req


def test_draft_from_history_finds_repeats():
    # History "1 2 3 4 1 2" with current token 2, prev 1 at position 5:
    # the bigram (1, 2) last occurred at j=1 → draft = hist[2:2+k] = 3 4 ...
    hist = jnp.asarray([[1, 2, 3, 4, 1, 2, 0, 0]], jnp.int32)
    draft = draft_from_history(hist, jnp.asarray([2], jnp.int32),
                               jnp.asarray([5], jnp.int32), 3)
    assert draft.tolist() == [[3, 4, 1]]


@pytest.mark.parametrize("spec", [1, 3])
async def test_spec_greedy_parity(spec):
    """Spec engine's tokens must be identical to plain greedy decoding's
    (the dense forward with the same weights, tests/dense_reference.py),
    on a repetitive prompt (high acceptance) AND a non-repetitive one
    (drafts mostly rejected) — both correctness regimes. Verify writes
    beyond a slot's page reservation land on the trash page; the page
    tables thread into the spec program as a traced argument."""
    rng = np.random.default_rng(0)
    repetitive = list(np.tile(rng.integers(2, 500, 6), 8))      # 48 toks
    random_p = list(rng.integers(2, 500, 40))
    spec_eng = _engine(spec=spec)
    try:
        for prompt in (repetitive, random_p):
            got = await _gen(spec_eng, prompt, max_tokens=24)
            assert got.generated == greedy_tokens(spec_eng, prompt, 24), (
                spec, got.generated)
            assert got.finish_reason == "length"
        assert spec_eng.stats()["spec_tokens_per_step"] >= 1.0
    finally:
        await spec_eng.stop()


def _markovify(eng):
    """Zero every layer's residual contributions (attention output and MLP
    down projections), leaving hidden state = embed(token): logits become
    a function of the CURRENT token only, so greedy decode is a fixed map
    on the vocab whose iteration provably enters a cycle. That makes "the
    model's output is repetitive" a structural guarantee instead of an
    accident of random weights — the original form of this test relied on
    a random tiny model greedily continuing its prompt's repetition, which
    is a near-tie argmax accident that flips across boxes/compilers (it
    did: known-failing since PR 7)."""
    eng.params["layers"]["wo"] = jnp.zeros_like(eng.params["layers"]["wo"])
    eng.params["layers"]["wd"] = jnp.zeros_like(eng.params["layers"]["wd"])


async def test_spec_accepts_on_repetitive_text():
    """On a self-repeating greedy loop the acceptance rate must exceed
    1 token/step — the whole point of speculating. The model is Markov-
    ified (see _markovify) so its greedy output is guaranteed to cycle;
    acceptance then starts on the cycle's second lap, once the repetition
    is in the slot's HISTORY (prompt-lookup drafts from past tokens — a
    repetitive prompt alone proves nothing unless the model continues
    it). Both adaptive gates are off: early drafts legitimately reject
    (pre-cycle), and the acceptance gate would otherwise close and not
    re-probe within this horizon (spec_probe_interval=25 rounds ≫ the
    test's ~12) — the gates have their own tests; ACCEPTANCE is the
    subject here."""
    rng = np.random.default_rng(1)
    prompt = list(np.tile(rng.integers(2, 500, 4), 10))
    eng = _engine(spec=3, spec_wall_gate=False,
                  spec_min_tokens_per_step=0.0)
    _markovify(eng)
    try:
        await _gen(eng, prompt, max_tokens=96)
        stats = eng.stats()
        assert stats["spec_draft_len"] == 3
        assert stats["spec_tokens_per_step"] > 1.0, stats
        assert stats["spec_accepted"] > 0, stats
    finally:
        await eng.stop()


async def test_spec_batched_slots_stay_isolated():
    """Two concurrent requests (different prompts) through a spec engine:
    each must match its own solo-run tokens — per-slot histories and
    ragged acceptance must not cross-contaminate."""
    rng = np.random.default_rng(2)
    p1 = list(np.tile(rng.integers(2, 500, 5), 8))
    p2 = list(rng.integers(2, 500, 35))

    async def run_pair(eng):
        r1 = GenRequest(prompt_ids=list(p1), max_tokens=16, temperature=0.0)
        r2 = GenRequest(prompt_ids=list(p2), max_tokens=16, temperature=0.0)
        await eng.submit(r1)
        await eng.submit(r2)

        async def drain(r):
            async for _ in eng.stream(r):
                pass
        await asyncio.gather(drain(r1), drain(r2))
        return r1.generated, r2.generated

    eng = _engine(spec=3)
    try:
        got1, got2 = await run_pair(eng)
        solo1 = (await _gen(_s1 := _engine(spec=3), p1, 16)).generated
        await _s1.stop()
        solo2 = (await _gen(_s2 := _engine(spec=3), p2, 16)).generated
        await _s2.stop()
        assert got1 == solo1
        assert got2 == solo2
    finally:
        await eng.stop()


async def test_spec_engine_serves_sampled_via_normal_path():
    """Mixed mode: a temperature>0 request on a speculative engine is
    served through the normal burst path (speculation verifies argmax
    only), and a concurrent greedy request still completes with the same
    tokens a plain engine produces."""
    rng = np.random.default_rng(3)
    gp = list(rng.integers(2, 500, 30))

    ref_eng = _engine(spec=0)
    try:
        ref = await _gen(ref_eng, gp, max_tokens=12)
    finally:
        await ref_eng.stop()

    eng = _engine(spec=3)
    try:
        sampled = GenRequest(prompt_ids=[5, 6, 7, 8], max_tokens=12,
                             temperature=0.9, top_p=0.9)
        greedy = GenRequest(prompt_ids=list(gp), max_tokens=12,
                            temperature=0.0)
        await eng.submit(sampled)
        await eng.submit(greedy)

        async def drain(r):
            async for _ in eng.stream(r):
                pass
        await asyncio.gather(drain(sampled), drain(greedy))
        assert sampled.finish_reason is not None
        assert len(sampled.generated) >= 1
        assert greedy.generated == ref.generated
        # After the sampled request retires, speculation resumes and the
        # history stayed coherent through the normal-path interlude.
        follow = await _gen(eng, gp, max_tokens=12)
        assert follow.generated == ref.generated
    finally:
        await eng.stop()


def test_spec_burst_lag_one_contract():
    """Full-size spec bursts are lag-one pipelined: call N dispatches
    burst N and returns burst N-1's rows; a flush lands the in-flight
    burst; host lengths advance by exactly the accepted token counts."""
    eng = _engine(spec=3)
    rngp = np.random.default_rng(3)
    base = rngp.integers(2, 500, 8)
    prompt = np.tile(base, 6).astype(np.int32)          # 48 tokens
    for slot in range(eng.B):
        for pos in range(0, len(prompt), eng.prefill_chunk):
            first, eng.cache = eng._exec_prefill(
                slot, pos, prompt[pos:pos + eng.prefill_chunk])
        eng.lengths[slot] = len(prompt)
        eng.active[slot] = True
        eng.last_token[slot] = int(base[0])
        eng.hist[slot, :len(prompt)] = prompt
    np.asarray(first)
    eng._d_dirty = True

    n = eng._spec_scan_len
    rows1 = eng._spec_burst(n)
    assert rows1 == [] and eng._spec_pending is not None
    rows2 = eng._spec_burst(n)                          # flushes burst 1
    assert len(rows2) == n * (eng.spec_k + 1)
    tail = eng._flush_spec_pending()                    # lands burst 2
    assert len(tail) == n * (eng.spec_k + 1)
    assert eng._spec_pending is None
    accepted = sum(int((r >= 0).sum()) for r in rows2 + tail)
    assert int(eng.lengths.sum()) == eng.B * len(prompt) + accepted


async def test_spec_runs_to_cache_end_via_normal_fallback():
    """A greedy generation that fills the cache must cross the spec→normal
    fallback window (S - lengths - inflight < k+1) and still complete —
    regression: the spec path's state upload once left the sampler
    mirrors unbuilt, so this mode switch handed the decode program a
    None sampler (full retrace mid-serving)."""
    eng = _engine(spec=3)                         # S=192
    rng = np.random.default_rng(7)
    prompt = list(np.tile(rng.integers(2, 500, 6), 8))      # 48 tokens
    try:
        req = await _gen(eng, prompt, max_tokens=500)       # clamped to fit
    finally:
        await eng.stop()
    assert req.finish_reason in ("length", "stop")
    if req.finish_reason == "length":
        # Spec engines reserve the last k+1 cache positions (a k+1-wide
        # verify must never write past the extent): S - k - 1 - prompt.
        assert len(req.generated) == 192 - eng.spec_k - 1 - 48


async def test_adaptive_gate_closes_on_low_acceptance():
    """VERDICT r3 item 5: with the acceptance gate on, a batch whose
    measured acceptance can't clear the threshold must fall back to
    NORMAL decode bursts (drafting off) — and the output must still be
    the exact greedy sequence. An impossible threshold (> k+1) makes the
    closure deterministic regardless of the text."""
    rng = np.random.default_rng(11)
    prompt = list(rng.integers(2, 500, 40))
    ref_eng = _engine(spec=0)
    try:
        ref = await _gen(ref_eng, prompt, max_tokens=40)
    finally:
        await ref_eng.stop()
    eng = _engine(spec=3, spec_min_tokens_per_step=5.0,
                  spec_probe_interval=1000)
    try:
        got = await _gen(eng, prompt, max_tokens=40)
        assert got.generated == ref.generated
        # Only the initial optimistic burst(s) speculated; once measured,
        # every step ran through the normal path.
        assert eng._spec_steps_done <= 2 * eng._spec_scan_len, \
            eng._spec_steps_done
        stats = eng.stats()
        assert stats["spec_gate_open"] is False
        assert stats["spec_ema_tokens_per_step"] <= 4.0
    finally:
        await eng.stop()


async def test_adaptive_gate_probes_while_closed():
    """While gated off, a 1-step speculative probe must run every
    `spec_probe_interval` rounds so mid-stream repetitive text can
    re-open the gate."""
    rng = np.random.default_rng(12)
    prompt = list(rng.integers(2, 500, 40))
    eng = _engine(spec=3, spec_min_tokens_per_step=5.0,
                  spec_probe_interval=4)
    try:
        await _gen(eng, prompt, max_tokens=60)
        first_bursts = eng._spec_scan_len  # the initial optimistic burst
        # ≥ one probe fired beyond the initial burst (60 steps at
        # interval 4 → many), each exactly 1 step wide.
        assert eng._spec_steps_done > first_bursts, eng._spec_steps_done
    finally:
        await eng.stop()


async def test_adaptive_gate_stays_open_on_repetitive_text():
    """Default gate (1.2 tok/step): repetitive text keeps acceptance
    high, so drafting stays engaged and still beats 1 token/step."""
    rng = np.random.default_rng(13)
    prompt = list(np.tile(rng.integers(2, 500, 4), 10))
    # Wall gate off: CPU wall times per token aren't the subject here —
    # this test pins the ACCEPTANCE mechanism in isolation.
    eng = _engine(spec=3, spec_wall_gate=False)
    try:
        await _gen(eng, prompt, max_tokens=40)
        stats = eng.stats()
        assert stats["spec_tokens_per_step"] > 1.0, stats
        assert stats["spec_gate_open"] is True
    finally:
        await eng.stop()


def test_wall_clock_gate_closes_net_loss_speculation():
    """The wall-clock gate term (spec_wall_gate): measured spec
    ms/token above the normal path's closes the gate EVEN when
    acceptance is high — the v5e spec_mixed regime, where a repetition
    loop accepts 2.24 tokens/step while each spec step costs ~10x a
    fused decode step (346.9 vs 1475.1 tok/s with the acceptance-only
    gate). Gauges are set directly; the decision must follow them."""
    eng = _engine(spec=3)
    eng.active[:] = True
    # Normal path: 4 ms/step across 2 active slots -> 2 ms/token. The
    # baseline is the fitted step time (per-burst fixed cost removed),
    # not the any-depth stats gauge.
    eng._burst_walls = {8: 32.0}
    # Spec measured at 5 ms/token -> loses; gate reports closed even
    # though acceptance (unmeasured -> optimistic) would hold it open.
    eng._spec_ms_per_tok = 5.0
    assert eng._spec_wall_loses()
    assert eng.stats()["spec_gate_open"] is False
    # Spec measured at 1 ms/token -> wins; gate reopens.
    eng._spec_ms_per_tok = 1.0
    assert not eng._spec_wall_loses()
    assert eng.stats()["spec_gate_open"] is True
    # Knob off restores acceptance-only behavior.
    eng2 = _engine(spec=3, spec_wall_gate=False)
    eng2.active[:] = True
    eng2._burst_walls = {8: 32.0}
    eng2._spec_ms_per_tok = 50.0
    assert not eng2._spec_wall_loses()
    assert eng2.stats()["spec_gate_open"] is True


def test_wall_gate_works_with_acceptance_threshold_disabled():
    """spec_min_tokens_per_step=0 disables only the ACCEPTANCE term:
    the wall-clock term still gates (and still reports in stats) —
    otherwise an operator disabling the threshold silently loses the
    net-loss protection the wall gate exists for."""
    eng = _engine(spec=3, spec_min_tokens_per_step=0.0)
    eng.active[:] = True
    eng._burst_walls = {8: 32.0}       # 4 ms/step -> 2 ms/token
    eng._spec_ms_per_tok = 5.0         # spec loses
    assert eng._spec_wall_loses()
    assert eng.stats()["spec_gate_open"] is False
    eng._spec_ms_per_tok = 1.0         # spec wins
    assert eng.stats()["spec_gate_open"] is True


async def test_baseline_probe_gives_up_when_no_wall_sample_possible():
    """Starvation guard: a workload whose normal bursts can never land
    a wall sample (max_tokens below every compiled rung -> synchronous
    path) must not pin speculation off forever — after a few fruitless
    baseline attempts the wall gate stays inert and drafting resumes."""
    rng = np.random.default_rng(3)
    prompt = list(np.tile(rng.integers(2, 500, 4), 10))
    eng = _engine(spec=3)      # compiled rung {8} (busy pinned to 8)
    try:
        # Many tiny requests: after the prefill token only 2 decode
        # steps remain, so every normal burst is capped below the only
        # compiled rung (8) -> synchronous path -> no steady fused pair
        # ever lands a wall sample.
        # Each request is ~1-2 decode rounds, and the guard trips after
        # 4 fruitless attempts of 2 forced-normal rounds each.
        for _ in range(14):
            await _gen(eng, prompt, max_tokens=3)
        # The guard must have stopped forcing baselines, and drafting
        # must have actually run.
        assert eng._spec_base_fails <= 4
        assert eng._spec_steps_done > 0, \
            "speculation starved by the baseline probe"
    finally:
        await eng.stop()


def test_spec_config_guardrails():
    with pytest.raises(ValueError, match="1, 3, 7"):
        _engine(spec=4)


async def test_spec_engine_recovers_from_injected_fault():
    """A decode fault during speculative serving must error the in-flight
    request and leave the engine serviceable (state re-init covers the
    spec mirrors too)."""
    from llmapigateway_tpu.engine.engine import FaultPlan
    eng = _engine(spec=3)
    try:
        eng.fault_plan = FaultPlan(fail_decode_after=1)
        req = GenRequest(prompt_ids=[3, 1, 4, 1, 5], max_tokens=12,
                         temperature=0.0)
        await eng.submit(req)
        deltas = []
        async for d in eng.stream(req):
            deltas.append(d)
        assert any(d.error for d in deltas)
        eng.fault_plan = None
        # The recovery is a supervised restart (ISSUE 14): a submit while
        # it runs is refused, so wait for it as a router's breaker would.
        for _ in range(1000):
            if eng.supervisor.state == "serving":
                break
            await asyncio.sleep(0.01)
        ok = await _gen(eng, [3, 1, 4, 1, 5], max_tokens=6)
        assert ok.finish_reason is not None and len(ok.generated) >= 1
    finally:
        await eng.stop()


async def test_spec_acceptance_telemetry_and_metrics_bridge():
    """ISSUE 7 satellite (ROADMAP item 3 stub): speculative results are
    counted into stats() as spec_proposed/spec_accepted and bridged onto
    the gateway_engine_spec_* /metrics series (acceptance ratio derived
    at scrape time), under the exposition-grammar validator."""
    rng = np.random.default_rng(2)
    prompt = list(np.tile(rng.integers(2, 500, 6), 8))
    # Gates forced open so drafting definitely runs (CPU wall times would
    # otherwise close the wall gate — acceptance COUNTING is the subject).
    eng = _engine(spec=3, spec_min_tokens_per_step=0.0,
                  spec_wall_gate=False)
    try:
        await _gen(eng, prompt, max_tokens=24)
        s = eng.stats()
        assert s["spec_proposed"] > 0
        assert 0 <= s["spec_accepted"] <= s["spec_proposed"]
        assert s["spec_proposed"] == 3 * eng._spec_steps_done

        # Scrape-time bridge: stats() keys → engine_spec_* gauges.
        from llmapigateway_tpu.obs.metrics import (GatewayMetrics,
                                                   MetricsRegistry)
        from llmapigateway_tpu.server.obs_api import make_stats_collector

        class _Prov:
            engine = eng

        class _Reg:
            @staticmethod
            def instantiated():
                return [("tpu", _Prov())]

        class _Tracer:
            evicted_total = 0

        class _GW:
            metrics = GatewayMetrics(MetricsRegistry())
            registry = _Reg()
            breakers = None
            tracer = _Tracer()

        gw = _GW()
        gw.metrics.registry.register_collector(make_stats_collector(gw))
        from tests.test_metrics import validate_prometheus_text
        families = validate_prometheus_text(gw.metrics.render())

        def val(fam):
            for _, labels, value in families[fam]["samples"]:
                if labels.get("engine") == "tpu":
                    return value
            return None

        assert val("gateway_engine_spec_proposed_total") == \
            s["spec_proposed"]
        assert val("gateway_engine_spec_accepted_total") == \
            s["spec_accepted"]
        ratio = val("gateway_engine_spec_acceptance_ratio")
        assert ratio == pytest.approx(s["spec_accepted"]
                                      / s["spec_proposed"])
    finally:
        await eng.stop()


# -- int8 KV cache (the headline config) --------------------------------------

@pytest.mark.parametrize("ppb", [1, 2, 4])
async def test_spec_int8_greedy_parity_paged(ppb):
    """Speculation over the int8 pool — the headline config — must
    produce EXACTLY the spec-off greedy sequence (the dense forward over
    an int8 cache), across pages_per_block 1/2/4, on a repetitive prompt
    (acceptance exercised) and a random one (drafts mostly rejected — the
    rejection numerics matter too). The verify self-block is
    mixed-precision (models/llama.py): off-diagonal drafted K/V go
    through the SAME quantize→dequantize the insert path applies, so
    verification judges each draft against the numbers plain int8 decode
    would actually read; the diagonal stays full precision like the
    decode self-column. (This combination was a build-time ValueError
    before the fix.)"""
    rng = np.random.default_rng(5)
    repetitive = list(np.tile(rng.integers(2, 500, 6), 8))
    random_p = list(rng.integers(2, 500, 40))
    eng = _engine(spec=3, kv_quant="int8", kv_pages_per_block=ppb)
    try:
        assert eng.kv_ppb == ppb
        for prompt in (repetitive, random_p):
            got = await _gen(eng, prompt, max_tokens=20)
            assert got.generated == greedy_tokens(eng, prompt, 20), (
                ppb, got.generated)
            assert got.finish_reason == "length"
        assert eng._spec_steps_done > 0
    finally:
        await eng.stop()


# -- per-slot adaptive drafting (spec_acceptance_floor) -----------------------

def test_spec_walk_freezes_ema_and_suspends_below_floor():
    """_spec_walk unit contract: a suspended (non-drafting) slot's rows
    carry no acceptance signal — its EMA freezes and its proposal
    counters don't move — while a drafting slot's EMA updates and its
    suspension is re-derived from the floor."""
    eng = _engine(spec=3, spec_acceptance_floor=0.5)
    eng.active[:] = True
    eng.lengths[:] = 10
    eng.last_token[:] = 7
    eng._spec_ema[:] = 2.0
    drafting = np.array([True, False])
    host = np.full((1, 2, 4), -1, np.int32)
    host[0, 0, :] = [5, 6, 7, 8]          # slot 0: all 3 drafts accepted
    host[0, 1, 0] = 5                     # slot 1 (suspended): 1 token/step
    live = np.array([True, True])
    eng._spec_walk(host, live.copy(), live.copy(), drafting=drafting)
    assert eng._spec_ema[1] == 2.0                       # frozen
    assert eng._spec_ema[0] == pytest.approx(3.0)        # 0.5*2 + 0.5*4
    assert eng._spec_slot_proposed.tolist() == [3, 0]
    assert eng._spec_slot_accepted.tolist() == [3, 0]
    assert eng._spec_proposed_total == 3
    assert eng._spec_accepted_total == 3
    # ratio (3-1)/3 = 0.67 >= floor 0.5: slot 0 keeps drafting.
    assert not eng._spec_suspended[0]
    # Now a poor burst: 1 token/step while drafting -> ema falls toward
    # 1, ratio below the floor -> suspended; the drafting mask flips off
    # at the next _spec_draft_ok().
    for _ in range(8):
        host2 = np.full((1, 2, 4), -1, np.int32)
        host2[0, 0, 0] = 9
        host2[0, 1, 0] = 9
        eng._spec_walk(host2, live.copy(), live.copy(),
                       drafting=np.array([True, False]))
    assert eng._spec_suspended[0]
    assert not eng._spec_draft_ok(probe=False)[0]
    assert eng._spec_draft_ok(probe=True).all()          # probe lifts it


async def test_per_slot_floor_suspends_and_output_stays_exact():
    """spec_acceptance_floor end-to-end: random (non-repetitive) text
    can't clear an impossible floor, so the slot suspends after its
    first measured burst; the scheduler then skips spec bursts (every
    decoding slot benched) except the periodic lifted-mask probe — and
    the output is STILL the exact greedy sequence. Suspension is
    visible in stats() and bridged onto /metrics."""
    rng = np.random.default_rng(21)
    prompt = list(rng.integers(2, 500, 40))
    ref_eng = _engine(spec=0)
    try:
        ref = await _gen(ref_eng, prompt, max_tokens=40)
    finally:
        await ref_eng.stop()
    eng = _engine(spec=3, spec_acceptance_floor=1.0,
                  spec_min_tokens_per_step=0.0, spec_wall_gate=False,
                  spec_probe_interval=6)
    try:
        got = await _gen(eng, prompt, max_tokens=40)
        assert got.generated == ref.generated, (
            got.generated, ref.generated)
        s = eng.stats()
        assert s["spec_acceptance_floor"] == 1.0
        assert s["spec_suspended_slots"] == 1, s
        assert s["spec_slot_acceptance"], s
        assert all(v < 1.0 for v in s["spec_slot_acceptance"].values())
        # Suspension engaged early and stuck: far fewer spec steps ran
        # than an always-on engine's (~40 tokens of rejected drafting).
        assert eng._spec_steps_done < 20, eng._spec_steps_done

        # /metrics: suspended-slot count + per-slot ratio gauges render
        # under the exposition-grammar validator.
        from llmapigateway_tpu.obs.metrics import (GatewayMetrics,
                                                   MetricsRegistry)
        from llmapigateway_tpu.server.obs_api import make_stats_collector

        class _Prov:
            engine = eng

        class _Reg:
            @staticmethod
            def instantiated():
                return [("tpu", _Prov())]

        class _Tracer:
            evicted_total = 0

        class _GW:
            metrics = GatewayMetrics(MetricsRegistry())
            registry = _Reg()
            breakers = None
            tracer = _Tracer()

        gw = _GW()
        gw.metrics.registry.register_collector(make_stats_collector(gw))
        from tests.test_metrics import validate_prometheus_text
        families = validate_prometheus_text(gw.metrics.render())
        susp = [v for _, labels, v in
                families["gateway_engine_spec_suspended_slots_total"]["samples"]
                if labels.get("engine") == "tpu"]
        assert susp == [1.0]
        slot_ratios = [
            (labels["slot"], v) for _, labels, v in
            families["gateway_engine_spec_slot_acceptance_ratio"]["samples"]
            if labels.get("engine") == "tpu"]
        assert slot_ratios and all(v < 1.0 for _, v in slot_ratios)
    finally:
        await eng.stop()


async def test_per_slot_floor_releases_new_request_starts_fresh():
    """A suspended slot's bench must not outlive its request: the next
    admission on that slot resets EMA + suspension (new text owes
    nothing to the old regime), so drafting re-engages immediately."""
    rng = np.random.default_rng(22)
    prompt = list(rng.integers(2, 500, 40))
    eng = _engine(spec=3, spec_acceptance_floor=1.0,
                  spec_min_tokens_per_step=0.0, spec_wall_gate=False,
                  spec_probe_interval=1000)
    try:
        await _gen(eng, prompt, max_tokens=24)
        assert eng.stats()["spec_suspended_slots"] == 1
        steps_before = eng._spec_steps_done
        await _gen(eng, prompt, max_tokens=24)
        # Fresh request drafted again (the optimistic NaN prior) — spec
        # steps advanced despite the probe interval being unreachable.
        assert eng._spec_steps_done > steps_before
        assert eng.stats()["spec_suspended_slots"] == 1   # re-benched
    finally:
        await eng.stop()


# -- composition: prefix cache, cancellation chaos ----------------------------

async def test_spec_composes_with_prefix_cache_insert_on_release():
    """Spec over the paged pool + radix prefix cache: spec bursts write
    K/V beyond `lengths` into the cache's undefined zone, and
    insert-on-release must index only the VERIFIED prefix — a warm
    rerun over spec-written pages yields byte-identical tokens with a
    real prefix hit."""
    rng = np.random.default_rng(23)
    prompt = list(np.tile(rng.integers(2, 500, 6), 8))    # 48 tokens
    cfg = LocalEngineConfig(preset="tiny-test", max_batch_size=2,
                            max_seq_len=192, prefill_chunk=16,
                            dtype="float32", decode_burst=8,
                            decode_burst_busy=8, spec_draft_len=3,
                            kv_page_size=16,
                            spec_wall_gate=False,
                            spec_min_tokens_per_step=0.0)
    eng = InferenceEngine(cfg, devices=[jax.devices("cpu")[0]])
    try:
        assert eng._prefix_cache is not None
        cold = await _gen(eng, prompt, max_tokens=20)
        warm = await _gen(eng, prompt, max_tokens=20)
        assert warm.cached_tokens > 0
        assert cold.generated == warm.generated, (
            cold.generated, warm.generated)
        assert eng._spec_steps_done > 0       # spec actually ran
        eng._prefix_cache.check_invariants()
        s = eng.stats()
        assert s["prefix_hits_total"] == 1
    finally:
        await eng.stop()


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-mistral-test"])
async def test_cancel_during_inflight_spec_burst_no_leaks(preset):
    """Chaos: cancel a request while a speculative burst is in flight
    (lag-one). The flush's epoch guard masks the dead slot's rows, the
    slot and all its pages come back — a ring's too, whose margin a spec
    burst widens to k + 1 tokens a step —, the flight lifecycle stays
    balanced (admits == finishes), and the engine keeps serving."""
    rng = np.random.default_rng(24)
    prompt = list(np.tile(rng.integers(2, 500, 4), 10))
    eng = _engine(spec=3, preset=preset, kv_page_size=8,
                  prefix_cache=False, spec_wall_gate=False,
                  spec_min_tokens_per_step=0.0)
    try:
        total_free = eng.allocator.free_pages
        req = GenRequest(prompt_ids=list(prompt), max_tokens=10_000,
                         temperature=0.0)
        await eng.submit(req)
        # A few generated tokens prove decode (and with the gates forced
        # open, speculative bursts) is underway; then cancel mid-stream
        # like a disconnecting client — a spec burst is in flight more
        # often than not at this point (lag-one dispatch). Polling
        # req.generated, not out_queue: the tiny-test detokenizer may
        # hold text back for arbitrary token ids, so the first DELTA can
        # lag the first token by the whole stream.
        for _ in range(1200):
            if len(req.generated) >= 2:
                break
            await asyncio.sleep(0.05)
        assert len(req.generated) >= 2, "decode never started"
        req.cancelled = True
        for _ in range(400):
            if req.finish_reason is not None:
                break
            await asyncio.sleep(0.05)
        assert req.finish_reason == "cancelled"
        for _ in range(400):
            if len(eng._free_slots) == eng.B:
                break
            await asyncio.sleep(0.05)
        assert len(eng._free_slots) == eng.B
        assert eng.allocator.free_pages == total_free    # zero page leak
        fs = eng.flight.stats()
        assert fs["flight_admits"] == fs["flight_finishes"]
        # Still serviceable, still exact: a fresh greedy request matches
        # plain greedy decoding.
        after = await _gen(eng, prompt, max_tokens=12)
        assert after.generated == greedy_tokens(eng, prompt, 12)
        fs = eng.flight.stats()
        assert fs["flight_admits"] == fs["flight_finishes"]
    finally:
        await eng.stop()


async def test_spec_on_a_model_mesh_matches_one_device():
    """Speculation served tensor-parallel: the verify forward runs over
    sharded heads (the deferred verify with the pool whole on each of
    four chips), the history drafts on-device, and the output is the
    one-device engine's — with real acceptance."""
    kw = dict(spec_draft_len=3, spec_min_tokens_per_step=0.0,
              max_tokens=24, kv_page_size=16, decode_burst=8,
              prompts=CYCLING)
    ref, _ = await serve({}, **kw)
    got, eng = await serve({"model": 4}, **kw)
    assert got == ref
    assert eng._spec_tokens_out > eng._spec_steps_done > 0
