"""The SmallThinker family (the period scan of models/hybrid.py over
softmax layers only: a global NoPE layer and three windowed rotary layers a
period, two cache groups) against the plain reference
(benchmark/reference/smallthinker.py) on seeded random weights at the tiny
preset: logits, not tokens. Every tolerance says where it comes from."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import smallthinker as ref
from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.config import PRESETS, get_preset
from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn
from tests.hybrid_params import params_of

TINY = get_preset("tiny-smallthinker-test")
# Both sides float32 on the same weights: what is left is the order of the
# sums (a paged gather against one softmax over the sequence, a grouped or
# batched product against a loop over experts), ~1e-6 relative on logits
# of size ~4.
F32_TOL = 2e-4
PAGE, SEQ = 8, 128


def file_of(c) -> dict:
    """What a configuration's file states, for the reference's ``sizes``:
    the layouts as the publication gives them, an entry a layer."""
    periods = c.n_layers // c.layer_period
    return {"num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "sliding_window_layout": list(TINY.window_layout) * periods,
            "rope_layout": list(TINY.rope_layout) * periods,
            "sliding_window_size": TINY.sliding_window,
            "layer_kinds": {"period": c.layer_period},
            "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_eps,
            "moe_num_primary_experts": c.n_experts,
            "moe_num_active_primary_experts": c.experts_per_token,
            "moe_primary_router_apply_softmax": True}


SIZES = ref.sizes(TINY, file_of(TINY))


def paged(c, slots: int, dtype=jnp.float32, kv_quant=""):
    """(cache, page table): ``slots`` slots of SEQ tokens in EVERY cache
    group (no ring: the window is the kernels' mask here; the ring is the
    engine's, tests/test_engine_cache_groups.py), page 0 trash."""
    per = SEQ // PAGE
    table = jnp.arange(1, slots * per + 1, dtype=jnp.int32).reshape(slots, per)
    return hybrid.HybridCache.create(c, slots * per + 1, PAGE, slots, dtype,
                                     kv_quant), table


def providers(c, table):
    """A provider a cache group of ``c``, each at its group's window."""
    fns = tuple(make_paged_attention_fn(table, max_seq=SEQ, impl="reference",
                                        window=w) for w, _ in c.cache_groups)
    return fns if len(fns) > 1 else fns[0]


def prefill(c, params, cache, table, tokens, start, slots, n_valid=None):
    return hybrid.forward(
        params, c, jnp.asarray(tokens), jnp.asarray(start, jnp.int32), cache,
        attention_fn=providers(c, table[jnp.asarray(slots)]),
        slots=jnp.asarray(slots, jnp.int32),
        n_valid=None if n_valid is None else jnp.asarray(n_valid, jnp.int32))


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


def test_the_presets_are_the_published_sizes_and_two_cache_groups():
    full = PRESETS["smallthinker-21b"]
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.head_dim, full.vocab_size) == (52, 2560, 28, 4, 128, 151936)
    assert (full.n_experts, full.experts_per_token, full.d_ff_expert,
            full.n_shared_experts, full.experts_held) == (64, 6, 768, 0, 64)
    assert (full.sliding_window, full.max_seq_len, full.rope_theta,
            full.rms_eps) == (4096, 16384, 1.5e6, 1e-6)
    assert full.cache_groups == ((0, (0,)), (4096, (1, 2, 3)))
    assert full.n_kv_layers == 52 and full.n_lin_layers == 0
    assert [full.rope_at(p) for p in range(4)] == [False, True, True, True]
    assert TINY.cache_groups == ((0, (0,)), (16, (1, 2, 3)))
    # The one-group families through the same properties.
    assert get_preset("tiny-mistral-test").cache_groups == ((16, (0,)),)
    assert get_preset("tiny-hybrid-test").cache_groups == ((0, (0,)),)
    assert get_preset("tiny-test").cache_groups == ((0, (0,)),)
    with pytest.raises(ValueError, match="one entry a position"):
        dataclasses.replace(TINY, window_layout=(0, 1))


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
    pools, then four decode steps through both beside an idle slot: every
    logit against the reference's full forward over the same tokens."""
    c, dt = TINY, jnp.dtype(dtype)
    params = f32_params if dt == jnp.float32 else params_of(c, dt, quant)
    cache, table = paged(c, 3, dt, kv_quant)
    assert len(cache.k) == 2        # a pool a group: [2 layers], [6 layers]
    toks, true_len, rows = tokens_of(2, 96), [96, 80], [2, 0]
    errs = []
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
    else:       # the bulk, as in the full forward's int8 case
        assert np.median(errs.max(-1)) <= 0.08
    # 4 steps x 2 decoding slots x top-3 x 8 layers, all held here; of the
    # 8 experts a layer the 2 rows x top-3 reach 3 to 6.
    total, local, hit = np.asarray(cache.counters)[:3]
    assert total == local == 4 * 2 * 3 * 8
    assert 4 * 8 * 3 <= hit <= 4 * 8 * 6


WRONG = {
    "rotary on the NoPE layer": {"rope_layout": (1, 1, 1, 1)},
    "a window on the global layer": {"window_layout": (1, 1, 1, 1)},
    "no window on a windowed layer": {"window_layout": (0, 0, 1, 1)},
    "no rotary on a rotary layer": {"rope_layout": (0, 0, 1, 1)},
    "SiLU for ReLU": {"moe_act": "silu"},
    "sigmoid for softmax": {"moe_router": "sigmoid"},
    "routing on the normalised input": {"router_reads_block_input": False},
}


@pytest.mark.parametrize("what", list(WRONG))
def test_a_wrong_layer_moves_the_logits_past_the_tolerance(f32_params, what):
    """Each departure from the equations, computed by the PROGRAM on the
    same weights (none changes the weight tree), against the reference on
    64 tokens. The sound program is inside ``F32_TOL`` (the tests above);
    each of these is past it a hundredfold."""
    c = dataclasses.replace(TINY, **WRONG[what])
    cache, table = paged(c, 1)
    toks = tokens_of(1, 64)
    lg, _ = prefill(c, f32_params, cache, table, toks, [0], [0])
    assert np.abs(np.asarray(lg[0]) - sound_logits(f32_params)).max() \
        > 100 * F32_TOL


# -- the expert layer ---------------------------------------------------------

def _mlp_of(params, position=1, period=0):
    return jax.tree.map(lambda a: a[period],
                        params["layers"]["attn"][position]["mlp"])


def test_four_shares_of_two_experts_add_up_to_the_uncut_reference_layer(f32_params):
    """The guide's share test under the softmax router and the ReLU gate: 8
    experts held 2 at a time. Every share routes over all 8 on the block's
    input and computes its own two experts' part; the four parts add up to
    what the uncut reference gives for the whole layer (no shared expert
    to count once)."""
    lp = _mlp_of(f32_params)
    x_in = jax.random.normal(jax.random.PRNGKey(5), (2, 40, TINY.d_model))
    x = x_in + 0.3 * jax.random.normal(jax.random.PRNGKey(6), x_in.shape)
    flat = lambda a: a.reshape(80, -1)
    routed = ref.routing(flat(x_in), lp["router"], SIZES)
    want = ref.expert_mlp(flat(x), lp, SIZES, routed)
    parts = []
    for first in (0, 2, 4, 6):
        c = dataclasses.replace(TINY, n_experts_held=2,
                                first_expert_held=first)
        cut = {k: lp[k][first:first + 2] for k in ("wg", "wu", "wd")}
        out, counted = hybrid.moe_block(x, {**lp, **cut}, c,
                                        count=jnp.ones((2,), bool),
                                        route_on=x_in)
        parts.append(flat(out))
        assert float(jnp.abs(parts[-1]).max()) > 0.01   # each share matters
        assert int(counted[0]) == 80 * 3 and 0 < int(counted[1]) < 80 * 3
        assert 1 <= int(counted[2]) <= 2
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    whole = hybrid.moe_block(x, lp, TINY, route_on=x_in)[0]
    np.testing.assert_allclose(flat(whole), want, atol=2e-5)


def test_the_softmax_router_is_softmax_over_all_renormalised_on_the_top_k(f32_params):
    lp = _mlp_of(f32_params)
    x = jax.random.normal(jax.random.PRNGKey(7), (50, TINY.d_model))
    idx, w = hybrid.route(x, lp["router"], TINY)
    every = jax.nn.softmax(x @ lp["router"], -1)
    picked = jnp.take_along_axis(every, idx, -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True),
                               atol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, atol=1e-6)
    ref_idx, ref_w = ref.routing(x, lp["router"], SIZES)
    assert (np.asarray(idx) == np.asarray(ref_idx)).all()
    np.testing.assert_allclose(w, ref_w, atol=1e-6)


def test_the_grouped_and_the_dense_expert_forms_agree_under_relu(f32_params):
    """More than ``DENSE_MAX_TOKENS`` rows take the grouped product, fewer
    the batched one over every expert: the same layer either way."""
    lp = _mlp_of(f32_params)
    n = hybrid.DENSE_MAX_TOKENS + 24
    x = jax.random.normal(jax.random.PRNGKey(8), (1, n, TINY.d_model))
    big = hybrid.moe_block(x, lp, TINY, route_on=x)[0]
    small = jnp.concatenate(
        [hybrid.moe_block(x[:, i:i + 8], lp, TINY, route_on=x[:, i:i + 8])[0]
         for i in range(0, n, 8)], axis=1)
    np.testing.assert_allclose(big, small, atol=2e-5)
