"""The Command A+ family ("cohere2_moe": the period scan of models/hybrid.py
over PARALLEL blocks — one LayerNorm, attention and experts side by side,
one add — three windowed rotary layers then a global NoPE layer a period,
the ring group first, shared experts averaged, a tied head) against the
plain reference (benchmark/reference/command_a_plus.py) on seeded random
weights at the tiny preset: logits, not tokens. Every tolerance says where
it comes from."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import command_a_plus as ref
from llmapigateway_tpu.config.schemas import LocalEngineConfig
from llmapigateway_tpu.models import hybrid, mla
from llmapigateway_tpu.models.config import PRESETS, get_preset
from llmapigateway_tpu.models.quant import quantize_array

# The period families' shared scaffolding: seeded params, a paged cache with
# a table of SEQ tokens a slot in EVERY cache group, a provider a group at
# its window, a prefill call.
from test_model_smallthinker import paged, prefill, providers
from tests.hybrid_params import params_of

TINY = get_preset("tiny-cohere2-test")
# Both sides float32 on the same weights: what is left is the order of the
# sums (a paged gather against one softmax over the sequence, a grouped or
# batched product against a loop over experts), ~1e-6 relative on logits
# of size ~4.
F32_TOL = 2e-4
KINDS = ["sliding_attention"] * 3 + ["full_attention"]


def file_of(c, **over) -> dict:
    """What a configuration's file states, for the reference's ``sizes``:
    the published keys, ``layer_types`` an entry a layer."""
    return {"layer_types": KINDS * (c.n_layers // c.layer_period),
            "layer_kinds": {"period": c.layer_period},
            "use_parallel_block": True, "norm_topk_prob": True,
            "expert_selection_fn": "sigmoid", "use_qk_norm": False,
            "shared_expert_combination_strategy": "average",
            "position_embedding_type": "rope_gptj", "rotary_pct": 1,
            "tie_word_embeddings": True, "first_k_dense_replace": 0,
            "sliding_window": c.sliding_window,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "rope_theta": c.rope_theta, "layer_norm_eps": c.layer_norm_eps,
            "num_experts_per_tok": c.experts_per_token,
            "first_expert_held": c.first_expert_held,
            "num_experts": c.experts_held,
            "num_shared_experts": c.n_shared_experts,
            "intermediate_size": c.d_ff_expert,
            "logit_scale": 1, **over}


SIZES = ref.sizes(TINY, file_of(TINY))


@pytest.fixture(scope="module")
def f32_params():
    return params_of(TINY)


_SOUND = []


def sound_logits(params) -> np.ndarray:
    """The reference over ``tokens_of(1, 64)``, computed once."""
    if not _SOUND:
        _SOUND.append(ref.logits(params, SIZES, tokens_of(1, 64)[0], last=64))
    return _SOUND[0]


def tokens_of(n_rows: int, n: int, seed=0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, (n_rows, n)).astype(np.int32)


def test_the_presets_are_the_published_sizes_and_the_ring_comes_first():
    full = PRESETS["command-a-plus"]
    assert (full.family, full.n_layers, full.d_model, full.n_heads,
            full.n_kv_heads, full.head_dim, full.vocab_size) == (
                "cohere2_moe", 32, 4096, 128, 8, 128, 262144)
    assert (full.n_experts, full.experts_per_token, full.d_ff_expert,
            full.n_shared_experts, full.experts_held, full.d_ff) == (
                128, 8, 4096, 4, 128, 4096)
    assert (full.sliding_window, full.max_seq_len, full.rope_theta,
            full.layer_norm_eps, full.rms_eps) == (
                4096, 200000, 5e4, 1e-5, 0.0)
    assert (full.parallel_block, full.norm_kind, full.tie_embeddings,
            full.rope_interleave, full.moe_router) == (
                True, "layernorm", True, True, "sigmoid")
    # The windowed layers come first in a period: group 0 is the RING.
    assert full.cache_groups == ((4096, (0, 1, 2)), (0, (3,)))
    assert full.n_kv_layers == 32 and full.n_lin_layers == 0
    assert [full.rope_at(p) for p in range(4)] == [True, True, True, False]
    assert [full.window_at(p) for p in range(4)] == [4096, 4096, 4096, 0]
    cut = PRESETS["command-a-plus-218b-ep8"]
    assert cut == dataclasses.replace(full, n_layers=8, vocab_size=32768,
                                      n_experts_held=16)
    assert (cut.n_experts, cut.experts_held, cut.first_expert_held) == (
        128, 16, 0)
    assert TINY.cache_groups == ((16, (0, 1, 2)), (0, (3,)))
    with pytest.raises(ValueError, match="unknown norm_kind"):
        dataclasses.replace(TINY, norm_kind="batch")
    # A published scale on the logits other than 1 has no field to land in.
    with pytest.raises(ValueError, match="unscaled head"):
        ref.sizes(TINY, file_of(TINY, logit_scale=0.5))


# -- the whole model against the reference ------------------------------------

def test_full_forward_matches_the_reference(f32_params):
    """64 tokens, four windows of 16: every windowed layer masks, the
    global layers see all 64."""
    cache, table = paged(TINY, 1)
    toks = tokens_of(1, 64)
    got = np.asarray(prefill(TINY, f32_params, cache, table, toks, [0],
                             [0])[0][0], np.float32)
    assert np.abs(got - sound_logits(f32_params)).max() <= F32_TOL


@pytest.mark.parametrize("dtype, quant, kv_quant, tol", [
    ("float32", "", "", F32_TOL),
    # int8 KV adds ~1/127 of each K/V row on all 8 layers.
    ("bfloat16", "int8", "int8", None)], ids=["f32", "int8"])
def test_prefill_in_chunks_then_decode_matches_the_reference(
        f32_params, dtype, quant, kv_quant, tol):
    """Three chunks of 32 in a group of two rows on slots 2 and 0 — the
    second row ends 16 tokens into its last chunk — written into BOTH
    pools (the windowed group's first), then four decode steps through
    both beside an idle slot, 80 and 96 tokens past the window of 16:
    every logit against the reference's full forward over the same
    tokens."""
    c, dt = TINY, jnp.dtype(dtype)
    params = f32_params if dt == jnp.float32 else params_of(c, dt, quant)
    cache, table = paged(c, 3, dt, kv_quant)
    assert len(cache.k) == 2        # a pool a group: [6 layers], [2 layers]
    first = cache.k[0]["q"] if kv_quant else cache.k[0]
    assert first.shape[0] == 6
    toks, true_len, rows = tokens_of(2, 96), [96, 80], [2, 0]
    step = jax.jit(lambda ca, t, s, nv: prefill(c, params, ca, table, t, s,
                                                rows, nv)[::-1])
    last = {}
    for ch in range(3):
        nv = np.clip(np.asarray(true_len) - 32 * ch, 0, 32)
        cache, lg = step(cache, toks[:, 32 * ch:32 * ch + 32],
                         np.full((2,), 32 * ch), nv)
        for r in range(2):
            if nv[r]:           # the call's logits are its LAST real token's
                last[r] = np.asarray(lg[r, 0], np.float32)
    attn = providers(c, table)
    decode = jax.jit(lambda ca, t, ln, a: hybrid.forward(
        params, c, t, ln, ca, active=a, attention_fn=attn)[::-1])
    lengths, active = np.array([80, 0, 96]), np.array([True, False, True])
    seqs = {0: list(toks[1, :80]), 2: list(toks[0, :96])}
    got = {0: [last[1]], 2: [last[0]]}
    nxt = np.array([5, 0, 7])
    for _ in range(4):
        cache, lg = decode(cache, jnp.asarray(nxt[:, None]),
                           jnp.asarray(lengths), jnp.asarray(active))
        for slot in (0, 2):
            seqs[slot].append(int(nxt[slot]))
            got[slot].append(np.asarray(lg[slot, 0], np.float32))
        lengths = lengths + active
        nxt = np.where(active, np.asarray(lg[:, 0]).argmax(-1), 0)
    # One full forward a sequence: causal, so its last five rows are what
    # the prefill's last token and the four decode steps must give.
    errs = np.concatenate([
        np.abs(np.stack(got[slot])
               - ref.logits(params, SIZES, np.asarray(seqs[slot]), last=5))
        for slot in (0, 2)])
    if tol is not None:
        assert errs.max() <= tol
    else:       # the bulk, as in the sibling families' int8 case
        assert np.median(errs.max(-1)) <= 0.08
    # 4 steps x 2 decoding slots x top-4 x 8 layers, all held here.
    total, local, hit = np.asarray(cache.counters)[:3]
    assert total == local == 4 * 2 * 4 * 8
    assert 4 * 8 * 4 <= hit <= 4 * 8 * 8


WRONG = {
    "rotary on the NoPE layer": {"rope_layout": (1, 1, 1, 1)},
    "a window on the global layer": {"window_layout": (1, 1, 1, 1)},
    "no window on a windowed layer": {"window_layout": (0, 1, 1, 0)},
    "no rotary on a rotary layer": {"rope_layout": (0, 1, 1, 0)},
    "half-split rotary pairs": {"rope_interleave": False},
    "RMSNorm for LayerNorm": {"norm_kind": "rms", "rms_eps": 1e-5},
    "softmax for sigmoid": {"moe_router": "softmax"},
    # No field would say these two: the weights make the program compute
    # them (``sd`` doubled is the SUM of the two shared experts, the final
    # gain halved a ``logit_scale`` of 0.5).
    "shared experts summed": lambda p: _with_layers(p, lambda lp: {
        **lp, "mlp": {**lp["mlp"], "sd": lp["mlp"]["sd"] * 2.0}}),
    "a scaled head": lambda p: {**p, "final_norm": p["final_norm"] * 0.5},
}


def _with_layers(params, change):
    return {**params, "layers": {**params["layers"], "attn": tuple(
        change(lp) for lp in params["layers"]["attn"])}}


@pytest.mark.parametrize("what", list(WRONG))
def test_a_wrong_layer_moves_the_logits_past_the_tolerance(f32_params, what):
    """Each departure from the equations, computed by the PROGRAM (on the
    same weights wherever a field says it), against the reference on 64
    tokens. The sound program is inside ``F32_TOL`` (the tests above);
    each of these is past it a hundredfold."""
    wrong = WRONG[what]
    c = TINY if callable(wrong) else dataclasses.replace(TINY, **wrong)
    params = wrong(f32_params) if callable(wrong) else f32_params
    cache, table = paged(c, 1)
    toks = tokens_of(1, 64)
    lg, _ = prefill(c, params, cache, table, toks, [0], [0])
    assert np.abs(np.asarray(lg[0]) - sound_logits(f32_params)).max() \
        > 100 * F32_TOL


@pytest.mark.parametrize("what", [*ref.CONTROLS, *ref.READINGS])
def test_a_control_of_the_reference_is_another_computation(what):
    """``tools/correct_controls.py`` puts each in the reference's place on
    the chip. Here, on the int8 tree (four-bit weights are the int8 values
    less their low bits): each is a change of ``Sizes`` alone and moves the
    reference's own logits — what ``correct`` has to refuse (``CONTROLS``)
    a hundredfold past the tolerance the sound program is held to."""
    q = params_of(TINY, jnp.bfloat16, "int8")
    toks = tokens_of(1, 64)[0]
    change = {**ref.CONTROLS, **ref.READINGS}[what]
    assert not set(ref.CONTROLS) & set(ref.READINGS)
    moved = ref.logits(q, change(SIZES), toks, 64)
    err = np.abs(moved - ref.logits(q, SIZES, toks, 64)).max()
    assert np.isfinite(moved).all()
    assert err > (100 if what in ref.CONTROLS else 1) * F32_TOL


def test_bfloat16_in_float32s_place_misses_the_tolerance():
    """The comparison sees precision: the same float32 weights rounded to
    bfloat16 and served in bfloat16 (no quantisation) against the float32
    reference on the float32 weights miss ``F32_TOL`` a hundredfold."""
    p16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params_of(TINY))
    cache, table = paged(TINY, 1, jnp.bfloat16)
    lg, _ = prefill(TINY, p16, cache, table, tokens_of(1, 64), [0], [0])
    err = np.abs(np.asarray(lg[0], np.float32)
                 - sound_logits(params_of(TINY))).max()
    assert 100 * F32_TOL < err < 0.5


# -- the parallel block's expert branch ---------------------------------------

def _layer_of(params, position=1, period=0):
    return jax.tree.map(lambda a: a[period],
                        params["layers"]["attn"][position])


def test_eight_shares_of_two_experts_add_up_to_the_uncut_reference_layer(
        f32_params):
    """The guide's share test on the parallel block: 16 experts held 2 at
    a time by 8 chips. Every share normalises the same input once, routes
    over all 16 by sigmoid and computes its own two experts' part beside
    the (whole, averaged) shared experts; the eight routed parts, with the
    shared experts and the attention branch counted ONCE, add up to what
    the uncut reference gives for the whole layer, x + A + R + S."""
    lp = _layer_of(f32_params)
    mp = lp["mlp"]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, TINY.d_model))
    flat = lambda a: a.reshape(80, -1)
    want = ref.layer(flat(x), lp, SIZES, windowed=True)
    hf = hybrid.block_norm(x, lp["norm"], TINY)
    h_ref, with_attention = ref._normed_and_attention(
        flat(x), lp["norm"], {k: lp[k] for k in ("wq", "wk", "wv", "wo")},
        SIZES, True)
    np.testing.assert_allclose(flat(hf), h_ref, atol=2e-6)

    def branch(first, held, count=None):
        c = dataclasses.replace(TINY, n_experts_held=held,
                                first_expert_held=first)
        cut = {k: mp[k][first:first + held] for k in ("wg", "wu", "wd")}
        return hybrid.moe_block(x, {**mp, **cut}, c, count=count, normed=hf)
    # A chip that holds none of the model's experts (its first lies past
    # the last): the branch is the shared experts alone.
    shared = hybrid.moe_block(
        x, {**mp, **{k: mp[k][:2] for k in ("wg", "wu", "wd")}},
        dataclasses.replace(TINY, n_experts_held=2,
                            first_expert_held=TINY.n_experts), normed=hf)[0]
    np.testing.assert_allclose(
        flat(shared), ref.shared_experts(h_ref, SIZES, mp["sg"], mp["su"],
                                         mp["sd"]), atol=2e-5)
    parts = []
    for first in range(0, 16, 2):
        out, counted = branch(first, 2, jnp.ones((2,), bool))
        parts.append(flat(out - shared))
        assert float(jnp.abs(parts[-1]).max()) > 0.01   # each share matters
        assert int(counted[0]) == 80 * 4 and 0 < int(counted[1]) < 80 * 4
        assert 1 <= int(counted[2]) <= 2
    np.testing.assert_allclose(with_attention + sum(parts) + flat(shared),
                               want, atol=3e-5)
    # The uncut program: the branch alone, never x + branch.
    whole = branch(0, 16)[0]
    np.testing.assert_allclose(with_attention + flat(whole), want, atol=3e-5)


def test_the_sigmoid_router_normalises_over_the_selected_wherever_they_live(
        f32_params):
    mp = _layer_of(f32_params)["mlp"]
    h = jax.random.normal(jax.random.PRNGKey(7), (50, TINY.d_model))
    idx, w = hybrid.route(h, mp["router"], TINY)
    every = jax.nn.sigmoid(h @ mp["router"])
    picked = jnp.take_along_axis(every, idx, -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    ref_idx, ref_w = ref.routing(h, mp["router"], SIZES)
    assert (np.asarray(idx) == np.asarray(ref_idx)).all()
    np.testing.assert_allclose(w, ref_w, atol=1e-6)
    # A share's weights are the same numbers: normalised over all 4
    # selected, not over those that landed here.
    share = dataclasses.replace(TINY, n_experts_held=2, first_expert_held=6)
    held = hybrid.held_weights(idx, w, share)
    assert held.shape == (50, 2) and float(held.sum(-1).max()) < 1.0


# -- the tied head, the rotary, the norm --------------------------------------

def test_the_tied_head_is_the_embedding():
    """One matrix: no ``lm_head`` in the tree; under quant the head's
    product reads ``lm_head_q8``, the quantisation of the embedding's OWN
    rows (one scale a row); logits are LayerNorm(x) E^T."""
    p32 = params_of(TINY)
    assert "lm_head" not in p32 and "lm_head_q8" not in p32
    q = params_of(TINY, jnp.bfloat16, "int8")
    assert "lm_head" not in q
    # (Compiled, as the tree's copy was: op by op the division rounds a
    # handful of numbers the other way.)
    want = jax.jit(lambda e: quantize_array(e, 1))(q["embed"])
    assert (np.asarray(q["lm_head_q8"]["q"]) == np.asarray(want["q"])).all()
    np.testing.assert_array_equal(q["lm_head_q8"]["s"], want["s"])
    assert q["lm_head_q8"]["s"].shape == (TINY.vocab_size,)
    # The final norm's gain is random signs at D^-1/2: the logits have unit
    # variance and the input token's own row, which the stream carries to
    # the head, does not decide the maximum (under a gain of one sign it
    # would, at every position: |row|^2 D^-1/2 against unit variance).
    assert abs(float(jnp.std(p32["embed"])) - 1) < 0.05
    gain = np.asarray(p32["final_norm"])
    assert (np.abs(gain) == 64 ** -0.5).all() and 16 < (gain > 0).sum() < 48
    one_sign = {**p32, "final_norm": jnp.abs(p32["final_norm"])}
    toks64 = tokens_of(1, 64)[0]
    sound = sound_logits(p32)
    assert 0.7 < sound.std() < 1.5
    assert (sound.argmax(-1) == toks64).mean() < 0.1
    read_back = ref.logits(one_sign, SIZES, toks64, last=64).argmax(-1)
    assert (read_back == toks64).mean() > 0.5
    # Doubling one row of the embedding doubles that id's logit and moves
    # no other (the prompt holds no such id, so the stream is the same).
    cache, table = paged(TINY, 1)
    toks = np.clip(tokens_of(1, 16), 1, None)
    base, _ = prefill(TINY, p32, cache, table, toks, [0], [0])
    moved = {**p32, "embed": p32["embed"].at[0].multiply(2.0)}
    got, _ = prefill(TINY, moved, paged(TINY, 1)[0], table, toks, [0], [0])
    np.testing.assert_allclose(got[0, :, 0], 2 * base[0, :, 0], rtol=1e-5)
    np.testing.assert_allclose(got[0, :, 1:], base[0, :, 1:], atol=1e-6)


def test_the_reference_dequantises_a_block_at_a_time_to_the_same_numbers(
        monkeypatch):
    """So that the float32 reference does not set the process's peak
    memory: the head ``HEAD_ROWS`` rows at a time (one scale a ROW,
    whatever the block's shape) and one shared expert's columns at a time,
    on the int8 tree, against the whole matrices dequantised at once."""
    q = params_of(TINY, jnp.bfloat16, "int8")
    x = jax.random.normal(jax.random.PRNGKey(11), (24, TINY.d_model))
    head = q["lm_head_q8"]
    whole = ref._head.__wrapped__(x, q["final_norm"], head, 8, SIZES)
    table = head["q"].astype(jnp.float32) * head["s"][:, None]
    want = ref.layer_norm(x[-8:], q["final_norm"].astype(jnp.float32),
                          SIZES.eps) @ table.T
    np.testing.assert_allclose(whole, want, atol=1e-5)
    # Blocks as wide as the model: a scale a column would fit the shape.
    monkeypatch.setattr(ref, "HEAD_ROWS", TINY.d_model)
    assert TINY.vocab_size % ref.HEAD_ROWS == 0
    blocked = ref._head.__wrapped__(x, q["final_norm"], head, 8, SIZES)
    np.testing.assert_allclose(blocked, want, atol=1e-5)
    mp = _layer_of(q)["mlp"]
    f = TINY.d_ff_expert
    sg, su, sd = (ref.weight(mp[k], SIZES) for k in ("sg", "su", "sd"))
    own = [slice(i * f, (i + 1) * f) for i in range(2)]
    one_by_one = sum((jax.nn.silu(x @ sg[:, o]) * (x @ su[:, o])) @ sd[o]
                     for o in own) / 2
    np.testing.assert_allclose(
        ref.shared_experts(x, SIZES, mp["sg"], mp["su"], mp["sd"]),
        one_by_one, atol=1e-5)


def test_interleaved_rotary_against_a_hand_case():
    """Pairs (2i, 2i+1) of the whole head: at position 1 with d = 4 and
    theta = 10000 the pairs turn by 1 and by 0.01 radians. The program
    leaves its result [firsts | seconds] (queries and keys alike), the
    reference where each number lay: every dot product is the same."""
    x = jnp.asarray([1.0, 0.0, 0.0, 2.0]).reshape(1, 1, 1, 4)
    ang = jnp.asarray([[[1.0, 0.01]]])
    got = mla.rotate(x, jnp.cos(ang), jnp.sin(ang), True)[0, 0, 0]
    hand = [np.cos(1.0), -2 * np.sin(0.01), np.sin(1.0), 2 * np.cos(0.01)]
    np.testing.assert_allclose(got, hand, atol=1e-6)
    twice = jnp.concatenate([jnp.zeros_like(x[0]), x[0]])      # position 1
    at = ref.rotate(twice, 10000.0)[1, 0]
    np.testing.assert_allclose(at, [hand[0], hand[2], hand[1], hand[3]],
                               atol=1e-6)
    q, k = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 6, 2, 16))
    from llmapigateway_tpu.models.llama import rope_tables
    cos, sin = rope_tables(jnp.arange(6)[None], 16, 10000.0)
    mine = jnp.einsum("bthd,bshd->bhts", mla.rotate(q, cos, sin, True),
                      mla.rotate(k, cos, sin, True))
    theirs = jnp.einsum("thd,shd->hts", ref.rotate(q[0], 10000.0),
                        ref.rotate(k[0], 10000.0))
    np.testing.assert_allclose(mine[0], theirs, atol=1e-5)


def test_layer_norm_removes_the_mean_and_has_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(9), (3, 64)) * 3.0 + 5.0
    w = jnp.linspace(0.5, 1.5, 64)
    got = hybrid.layer_norm(x, w, 1e-5)
    np.testing.assert_allclose(got, ref.layer_norm(x, w, 1e-5), atol=1e-6)
    np.testing.assert_allclose((got / w).mean(-1), 0.0, atol=1e-6)
    np.testing.assert_allclose((got / w).var(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(hybrid.layer_norm(x + 7.0, w, 1e-5), got,
                               atol=1e-5)
    assert hybrid.layer_norm(x.astype(jnp.bfloat16), w, 1e-5).dtype \
        == jnp.bfloat16


# -- what the family does not serve --------------------------------------------

REFUSED = {
    "the prefix cache": (dict(prefix_cache=True), "prefix_cache"),
    "speculation": (dict(spec_draft_len=3), "spec_draft_len"),
    "a mesh axis": (dict(mesh={"model": 2}), "mesh"),
    "disaggregation": (dict(disaggregation={"enabled": True,
                                            "prefill_slots": 1}),
                       "disaggregation"),
    "a checkpoint": (dict(model_path="/nowhere"), "model_path"),
}


@pytest.mark.parametrize("what", list(REFUSED))
def test_the_family_refuses_at_build_with_the_reason(what):
    from llmapigateway_tpu.engine.engine import InferenceEngine
    over, names = REFUSED[what]
    cfg = dict(preset="tiny-cohere2-test", max_batch_size=2, max_seq_len=128,
               prefill_chunk=16, dtype="float32", kv_layout="paged",
               kv_page_size=8, prefix_cache=False, attention="reference")
    devices = jax.devices("cpu")[:2 if "mesh" in over else 1]
    with pytest.raises(ValueError, match=f"'cohere2_moe' family does not "
                                         f"support {names}"):
        InferenceEngine(LocalEngineConfig(**{**cfg, **over}), TINY,
                        devices=devices)


def test_a_parallel_block_is_a_period_of_softmax_layers():
    with pytest.raises(ValueError, match="a parallel block is a period of "
                                         "softmax layers"):
        hybrid.init_params(
            dataclasses.replace(get_preset("tiny-hybrid-test"),
                                parallel_block=True),
            jax.random.PRNGKey(0))
