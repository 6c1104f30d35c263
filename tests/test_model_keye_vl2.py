"""The Keye-VL-2.0 family (the period scan of models/hybrid.py with a period
of ONE softmax layer under a learned indexer, ops/sparse_attention.py, over
a page pool with an index-key side) against the plain reference
(benchmark/reference/keye_vl2.py) on seeded random weights at the tiny
preset, where 24 keys are kept of contexts of 96-160: logits, not tokens.
Every tolerance says where it comes from."""
import dataclasses
import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as ref
from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.config import PRESETS, get_preset
from llmapigateway_tpu.models.llama import apply_rope, rope_tables
from llmapigateway_tpu.ops import sparse_attention as sa
from tests.hybrid_params import params_of

TINY = get_preset("tiny-keye-vl2-test")
# Both sides float32 on the same weights: what is left is the order of the
# sums (pages against one softmax over the sequence, a gathered or masked
# read against a dense one, a grouped or batched expert product against a
# loop over experts), ~1e-6 relative on logits of size ~4. A query that
# kept another key than the reference misses by ~0.1-1.
F32_TOL = 2e-4
PAGE, SEQ = 8, 160


def file_of(c) -> dict:
    """What a configuration's file states, for the reference's ``sizes``."""
    return {"num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.rms_eps,
            "num_experts_per_tok": c.experts_per_token,
            "num_experts": c.experts_held,
            "first_expert_held": c.first_expert_held,
            "mlp_only_layers": [], "decoder_sparse_step": 1,
            "norm_topk_prob": True, "attention_bias": False,
            "tie_word_embeddings": False,
            "rope_scaling": {"mrope_section": [16, 24, 24],
                             "rope_type": "default"},
            "sa_config": {"indexer_head_dim": c.idx_head_dim,
                          "indexer_num_heads": c.idx_heads,
                          "indexer_num_kv_heads": 1, "topk": c.idx_topk}}


SIZES = ref.sizes(TINY, file_of(TINY))


def serve(c, params, tokens, impl: str, chunk: int = 32, prompt: int = 96,
          dtype=jnp.float32, spoil: float = 0.0):
    """Row 0's ``tokens`` [n] through slot 1 of a two-slot cache: the
    prompt in chunks of ``chunk``, then a decode step a token (row b IS
    slot b: slot 0 stays inactive). ``spoil``: what every index key of the
    pool holds before (a stale page's). -> logits [n, V]."""
    per = SEQ // PAGE
    table = jnp.arange(1, 2 * per + 1, dtype=jnp.int32).reshape(2, per)
    cache = hybrid.HybridCache.create(c, 2 * per + 1, PAGE, 2, dtype)
    cache = cache._replace(index=tuple(jnp.full_like(i, spoil)
                                       for i in cache.index))
    one = sa.SparseAttention(table[jnp.asarray([1])], SEQ, c.idx_topk, impl,
                             interpret=True)
    both = sa.SparseAttention(table, SEQ, c.idx_topk, impl, interpret=True)
    prefill = jax.jit(lambda p, t, at, cache: hybrid.forward(
        p, c, t, at, cache, attention_fn=one, slots=jnp.asarray([1])))
    decode = jax.jit(lambda p, t, at, cache, on: hybrid.forward(
        p, c, t, at, cache, active=on, attention_fn=both))
    out = []
    for pos in range(0, prompt, chunk):
        logits, cache = prefill(
            params, jnp.asarray(tokens[None, pos:pos + chunk]),
            jnp.asarray([pos], jnp.int32), cache)
        out.append(np.asarray(logits[0]))
    for i in range(prompt, len(tokens)):
        logits, cache = decode(
            params, jnp.asarray([[0], [tokens[i]]], jnp.int32),
            jnp.asarray([0, i], jnp.int32), cache,
            jnp.asarray([False, True]))
        out.append(np.asarray(logits[1]))
    return np.concatenate(out)


@pytest.fixture(scope="module")
def f32_params():
    return params_of(TINY)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(51).integers(
        0, TINY.vocab_size, 104).astype(np.int32)


@pytest.fixture(scope="module")
def want(f32_params, tokens):
    return ref.logits(f32_params, SIZES, tokens, last=len(tokens))


def test_the_presets_are_the_published_sizes_and_one_group_of_three_sides():
    full = PRESETS["keye-vl2-30b-a3b"]
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.head_dim, full.vocab_size, full.max_seq_len) == (
                48, 2048, 32, 4, 128, 151936, 262144)
    assert (full.n_experts, full.experts_per_token, full.d_ff_expert,
            full.n_shared_experts, full.moe_router) == (128, 8, 768, 0,
                                                        "softmax")
    assert (full.idx_heads, full.idx_head_dim, full.idx_topk) == (16, 64,
                                                                  2048)
    assert full.qk_norm and full.rope_theta == 1e7 and full.is_sparse
    # A random draw gives the head norms 1.73: attention logits of standard
    # deviation 3, peaked enough for a comparison of logits to see WHICH
    # keys were attended (the file's ``assumed``); ones at the tiny preset.
    assert full.qk_norm_draw == 1.73 and TINY.qk_norm_draw == 1.0
    assert full.cache_groups == ((0, (0,)),) and not full.is_mla
    assert full.n_kv_layers == 48 and full.n_lin_layers == 0
    cut = PRESETS["keye-vl2-30b-ep4"]
    assert cut == dataclasses.replace(full, n_layers=12, vocab_size=37984,
                                      n_experts_held=32)
    assert 4 * cut.vocab_size == full.vocab_size
    assert not PRESETS["mistral-7b"].is_sparse
    with pytest.raises(ValueError, match="idx_topk needs idx_heads"):
        dataclasses.replace(full, idx_heads=0)
    cache = jax.eval_shape(lambda: hybrid.HybridCache.create(
        TINY, 9, PAGE, 2, jnp.float32))
    assert [a.shape for a in cache.k] == [(4, 9, 2, PAGE, 16)]
    assert [a.shape for a in cache.index] == [(4, 9, 8, PAGE)]
    assert jax.eval_shape(lambda: hybrid.HybridCache.create(
        get_preset("tiny-mistral4-test"), 9, PAGE, 2)).index == ()


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_prefill_then_decode_is_the_references_forward(f32_params, tokens,
                                                       want, impl):
    """Three chunks of 32 and eight decode steps: from position 24 on every
    query attends a SELECTION (24 of up to 104 keys) — gathered row by row
    in decode, a mask over the page walk in prefill (``pallas``: the
    kernels, interpreted) — over index keys that lay as a stale page's
    would before they were written."""
    got = serve(TINY, f32_params, tokens, impl, spoil=50.0)
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_the_selection_is_live_in_what_is_compared(f32_params, tokens, want):
    """With the selection switched off, or the 24 keys of LEAST score kept,
    the reference reads ~0.5 elsewhere (the benchmark's ``CONTROLS``): the
    comparison above sees WHICH keys were attended."""
    assert set(ref.CONTROLS) == {"int4_weights", "dense_attention",
                                 "lowest_scores"}
    for control in ("dense_attention", "lowest_scores"):
        wrong = ref.logits(f32_params, ref.CONTROLS[control](SIZES), tokens,
                           last=len(tokens))
        assert np.abs(wrong[:24] - want[:24]).max() <= F32_TOL  # all kept
        assert np.abs(wrong[40:] - want[40:]).max() > 0.1, control


@pytest.mark.parametrize("k", [1, 24, 200])
def test_the_selected_set_is_top_ks_ties_and_all(k):
    """Scores with many equal values (a third of them rounded to a tenth,
    some exactly 0, some negative zero) and a causal bound: the plain form
    of the selection (``top_mask``, ``top_positions``' list as a mask) is
    the first ``k`` seen positions of a STABLE descending sort — ties to
    the lower position, the two zeros one value — and every seen key where
    a row sees no more than ``k``; the reference's own form says the same."""
    rng = np.random.default_rng(k)
    scores = rng.normal(size=(2, 40, 160)).astype(np.float32)
    scores[:, :, ::3] = np.round(scores[:, :, ::3], 1)
    scores[:, :, 5::11] = 0.0
    scores[:, :, 7::13] = -0.0
    q_pos = 90 + np.arange(40)
    seen = np.broadcast_to(np.arange(160)[None, :] <= q_pos[:, None],
                           scores.shape)
    got = np.asarray(jax.jit(lambda s, m: sa.top_mask(s, m, k))(scores, seen))
    order = np.argsort(-(scores + 0.0), axis=-1, kind="stable")
    for b, t in np.ndindex(2, 40):
        first = [s for s in order[b, t] if seen[b, t, s]][:k]
        assert np.flatnonzero(got[b, t]).tolist() == sorted(first)
    for b in range(2):
        assert (got[b] == np.asarray(ref.top_positions(
            jnp.asarray(scores[b]), jnp.asarray(seen[b]), k))).all()
    assert (got.sum(-1) == np.minimum(seen.sum(-1), k)).all()
    keys = np.asarray(sa.sortable(jnp.asarray(scores)))
    assert (np.argsort(keys[0, 0], kind="stable")
            == np.argsort(scores[0, 0] + 0.0, kind="stable")).all()


@pytest.mark.parametrize("start", [(0, 40), (100, 7)])
def test_the_chunk_kernel_selects_what_the_plain_form_selects(start):
    """``index_select`` (one kernel over the live pages: scores, bisection,
    ties by position; interpreted here) against ``top_mask`` of
    ``index_scores`` on index keys in shuffled pages. Queries, keys and
    weights are small dyadic numbers, so both forms' float32 sums are exact
    whatever their order and MANY scores tie: the sets are equal, bit for
    bit, below the 24 keys, past them, and at a start that is no multiple
    of the page."""
    rng = np.random.default_rng(sum(start))
    pages, J, W, T = 20, TINY.idx_heads, TINY.idx_head_dim, 32
    table = jnp.asarray(rng.permutation(np.arange(1, 2 * pages + 1)).reshape(
        2, pages).astype(np.int32))
    pool = jnp.asarray(rng.integers(-1, 2, (2, 2 * pages + 1, W, PAGE)),
                       jnp.float32)
    qi = jnp.asarray(rng.integers(-1, 2, (2, T, J, W)), jnp.float32)
    w = jnp.asarray(rng.choice([0.5, -0.5, 0.25, 1.0], (2, T, J)),
                    jnp.float32)
    at = jnp.asarray(start, jnp.int32)
    plain, kernel = (sa.SparseAttention(table, pages * PAGE, TINY.idx_topk,
                                        impl, interpret=True)
                     for impl in ("reference", "pallas"))
    want = np.asarray(plain.select(qi, w, pool, 1, at))
    got = np.asarray(jax.jit(
        lambda qi, w: kernel.select(qi, w, pool, 1, at))(qi, w))
    assert got.dtype == bool and (got == want).all()
    q_pos = np.asarray(start)[:, None] + np.arange(T)
    assert (got.sum(-1) == np.minimum(q_pos + 1, TINY.idx_topk)).all()
    # Ties were there to break: at some query the k-th score has equals.
    scores = np.asarray(plain.scores(qi, w, pool, 1))
    tied = [np.sum(scores[b, t, :q_pos[b, t] + 1]
                   == scores[b, t][got[b, t]].min()) > 1
            for b in range(2) for t in range(T) if q_pos[b, t] >= 24]
    assert any(tied)


def test_a_decode_steps_list_is_the_selected_set():
    """``top_positions`` (one query a row, what the gather reads by) lists
    exactly the reference's set, ties and signed zeros included, and says
    how many of its places are real where a row sees fewer than ``k``."""
    rng = np.random.default_rng(3)
    scores = np.round(rng.normal(size=(3, 160)), 1).astype(np.float32)
    scores[:, 3::7] = -0.0
    scores[:, 5::11] = 0.0
    seen = np.arange(160)[None, :] <= np.asarray([159, 4, 100])[:, None]
    keep = np.asarray(ref.top_positions(jnp.asarray(scores),
                                        jnp.asarray(seen), 24))
    listed, total = map(np.asarray, jax.jit(
        lambda s, m: sa.top_positions(s, m, 24))(scores, seen))
    assert total.tolist() == [24, 5, 24] and listed.shape == (3, 24)
    for b in range(3):
        assert sorted(listed[b, :total[b]].tolist()) == \
            np.flatnonzero(keep[b]).tolist()


# A decode step's selection as ONE kernel (``decode_select``): six slots of
# 20 table pages of 16, 4 index heads of 16, 24 keys kept, layer 1 of two —
# one shape, so the cases share the interpreted kernel's compile.
WORDS_PAGE, WORDS_PAGES, WORDS_K = 16, 20, 24
WORDS_S = WORDS_PAGE * WORDS_PAGES


def _every_fifth_is_its_neighbour(keys, w, live):
    """Every fifth key IS its left neighbour's: equal scores all along, the
    k-th among them (the lower position wins)."""
    keys[:, 5::5] = keys[:, 4:-1:5]


def _zero_scores_of_both_signs(keys, w, live):
    """Half the keys hold nothing, so their scores are zeros and the largest
    there are under weights that are all negative (rows 1 and 3): -0.0
    there, 0.0 in the rows beside them whose weights have both signs."""
    keys[:, ::2] = 0.0
    w[1], w[3] = -0.5, -0.25
    w[0, 0], w[2, 0], w[4, 0] = 1.0, 0.5, 1.0


def _stale_keys_of_large_score(keys, w, live):
    """Every position a slot does not hold yet — the rest of its last page
    and every dead page of its table row — holds an earlier request's keys,
    and they score far above anything live."""
    keys[~live] *= 8.0


WORDS_CASES = {
    # name: (each slot's tokens before the call, what is done to the keys)
    "contexts_under_at_and_over_k": ((0, 5, 23, 24, 100, 303), None),
    "a_pages_last_column_and_its_first": ((15, 16, 31, 32, 159, 160), None),
    "inactive_slots": ((0, 200, 0, 77, 0, 319), None),
    "ties_at_the_threshold": ((4, 23, 24, 99, 250, 319),
                              _every_fifth_is_its_neighbour),
    "signed_zeros": ((40, 141, 100, 201, 300, 3), _zero_scores_of_both_signs),
    "stale_dead_pages": ((0, 17, 23, 48, 150, 290),
                         _stale_keys_of_large_score),
    "unit_normal": ((0, 17, 24, 100, 250, 319), None),
}


@jax.jit
def _both_selections(qi, w, pool, table, start):
    """(the served kernel's words, the plain form's: ``lax.top_k``'s list of
    scores over every table position as words, the plain scores)."""
    kernel, plain = (sa.SparseAttention(table, WORDS_S, WORDS_K, impl,
                                        interpret=True)
                     for impl in ("pallas", "reference"))
    listed = plain.select(qi, w, pool, 1, start)
    return (kernel.select_words(qi, w, pool, 1, start),
            sa.selection_words(*listed, WORDS_PAGES, WORDS_PAGE),
            plain.scores(qi, w, pool, 1)[:, 0])


@pytest.mark.parametrize("case", list(WORDS_CASES))
def test_a_decode_steps_kernel_selects_what_the_list_selects(case):
    """``SparseAttention.select_words`` (``decode_select``: ONE kernel over
    the slots' live index-key pages — scores, the k-th largest by counting,
    the mask words; interpreted here) against ``selection_words(
    *top_positions(index_scores(...)))`` over every table position, on a
    shuffled table of a two-layer pool whose every page holds keys. Small
    dyadic numbers, so both forms' float32 sums are exact whatever their
    order and the words are equal BIT FOR BIT: slots of different contexts
    in one call, under ``k``, at it and past it, a context that ends on a
    page's last column and one on its first, slots that are not active
    (start 0: position 0 alone), ties at the threshold, zeros of both
    signs, stale keys of large score wherever a slot holds nothing. On
    unit-normal bfloat16 inputs the two may differ only where a score lies
    within ``INDEX_SCORE_TOL`` of the slot's k-th: the harness's own rule."""
    starts, spoil = WORDS_CASES[case]
    rng = np.random.default_rng(len(case))
    B, J, W, page, NP, S = (len(starts), 4, 16, WORDS_PAGE, WORDS_PAGES,
                            WORDS_S)
    exact = case != "unit_normal"
    draw = ((lambda *shape: rng.integers(-2, 3, shape).astype(np.float32))
            if exact else (lambda *shape: rng.normal(size=shape)))
    keys, qi = draw(B, S, W), draw(B, 1, J, W)
    w = (rng.choice([0.5, -0.5, 0.25, 1.0], (B, J)) if exact
         else rng.normal(size=(B, J)) / 8).astype(np.float32)
    start = np.asarray(starts)
    live = np.arange(S)[None, :] <= start[:, None]
    if spoil is not None:
        spoil(keys, w, live)
    table = rng.permutation(np.arange(1, B * NP + 1)).reshape(B, NP)
    pool = draw(2, B * NP + 1, W, page)             # layer 0: another's
    pool[1][table] = keys.reshape(B, NP, page, W).transpose(0, 1, 3, 2)
    if case == "inactive_slots":
        table[start == 0] = 0                       # the trash page's row
    got, want, scores = map(np.asarray, _both_selections(
        jnp.asarray(qi, jnp.bfloat16), jnp.asarray(w)[:, None],
        jnp.asarray(pool, jnp.bfloat16), jnp.asarray(table, jnp.int32),
        jnp.asarray(start, jnp.int32)))
    assert got.shape == (B, NP, page) and got.dtype == np.int32
    got, want = got.reshape(B, S), want.reshape(B, S)
    assert (got.sum(-1) == np.minimum(start + 1, WORDS_K)).all()
    assert not got[~live].any()
    kth = np.where(want != 0, scores, np.inf).min(-1, keepdims=True)
    if exact:
        assert (got == want).all(), np.argwhere(got != want)
    else:
        apart = (got != want) & (np.abs(scores - kth) > ref.INDEX_SCORE_TOL)
        assert not apart.any(), np.argwhere(apart)
    assert (got[start == 0, 0] == 1).all()
    # What a case is there for was there.
    tied = ((scores == kth) & live).sum(-1) > 1
    if case == "ties_at_the_threshold":
        pair = np.arange(5, S, 5)           # pair and pair - 1 tie
        assert not (got[:, pair] & ~got[:, pair - 1]).any()
        assert (got[:, pair - 1] & ~got[:, pair] & live[:, pair]).any()
        assert tied[3:].any()
    if case == "signed_zeros":
        # The kernel's own sum starts from head 0's term, not from 0.0: a
        # row of negative weights scores a key that holds nothing -0.0.
        by_head = np.maximum(np.einsum("bjw,bsw->bjs", qi[:, 0], keys), 0.0)
        summed = functools.reduce(np.add, np.moveaxis(
            by_head * w[:, :, None], 1, 0))
        assert (summed == scores).all()
        zeros = (summed == 0.0) & live
        assert np.signbit(summed[[1, 3]][zeros[[1, 3]]]).all()
        assert not np.signbit(summed[[0, 2, 4]][zeros[[0, 2, 4]]]).any()
        assert (kth[[1, 3]] == 0.0).all() and tied[[1, 3]].all()
    if case == "stale_dead_pages":
        assert (np.where(live, -np.inf, scores).max(-1)
                > np.where(live, scores, -np.inf).max(-1)).all()


def _decode_pool(rng, slots: int, table_pages: int, page: int, dtype):
    """(table [slots, table_pages] over shuffled pages, K and V sides of two
    layers of 2 KV heads of 16, q of 8 heads) for the decode kernel's
    cases."""
    pages = slots * table_pages + 1
    table = jnp.asarray(rng.permutation(np.arange(1, pages)).reshape(
        slots, table_pages).astype(np.int32))
    pool_k, pool_v = (jnp.asarray(rng.normal(size=(2, pages, 2, page, 16)),
                                  dtype) for _ in range(2))
    return table, pool_k, pool_v, jnp.asarray(
        rng.normal(size=(slots, 8, 16)), dtype)


def _first_page_last(scores, page):
    """The first page holds the LEAST scores: no key of it is kept."""
    scores[:, :page] -= 100.0
    return scores


def _ties_over_a_boundary(scores, page):
    """Equal scores from four positions before a page's end to four past
    it, the greatest but for ``k - 4`` others: the k-th is among them."""
    scores[:, 6 * page - 4:6 * page + 4] = 50.0
    scores[:, :20] = 60.0
    return scores


DECODE_CASES = {
    # name: (keys a slot holds, k, page, what is done to the scores, dtype)
    "under_k": ((5, 23, 9), 24, 8, None, jnp.float32),
    "exactly_k": ((24, 24), 24, 8, None, jnp.float32),
    "ragged": ((1, 255, 256, 257, 5000), 256, 256, None, jnp.float32),
    "ragged_bfloat16": ((1, 255, 256, 257, 5000), 256, 256, None,
                        jnp.bfloat16),
    "first_page_empty": ((90, 41), 24, 8, _first_page_last, jnp.float32),
    "ties_over_a_boundary": ((90, 33), 24, 8, _ties_over_a_boundary,
                             jnp.float32),
    "inactive_slot": ((70, 1, 12), 24, 8, None, jnp.float32),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_the_decode_kernel_reads_what_the_gathered_form_reads(case):
    """``selected_decode_attention`` (one kernel over a slot's live pages
    under the selection's mask; interpreted here) against
    ``gathered_decode_attention`` over the SAME ``(positions, total)`` of
    ``top_positions``, on shuffled pages of a two-layer pool: a context
    under ``k`` (every key kept), at ``k``, lengths either side of a page's
    end and twenty pages long, a first live page none of whose keys is kept
    (the state must pass it untouched), the k-th score tied across a page
    boundary (the set is ``top_positions``', ties and all), and a slot that
    is not active (one key, a table row of the trash page). float32: the
    two differ by the order of their sums; bfloat16: by the rounding of
    the probabilities before the V product, 2 ** -8 of values of size ~1."""
    lengths, k, page, spoil, dtype = DECODE_CASES[case]
    rng = np.random.default_rng(len(case))
    table_pages = -(-max(lengths) // page) + 1
    table, pool_k, pool_v, q = _decode_pool(rng, len(lengths), table_pages,
                                            page, dtype)
    if case == "inactive_slot":
        table = table.at[1].set(0)
    S = table_pages * page
    scores = rng.normal(size=(len(lengths), S)).astype(np.float32)
    if spoil is not None:
        scores = spoil(scores, page)
    n_keys = jnp.asarray(lengths, jnp.int32)
    seen = jnp.arange(S)[None, :] < n_keys[:, None]
    positions, total = sa.top_positions(jnp.asarray(scores), seen, k)
    assert total.tolist() == [min(n, k) for n in lengths]
    keep = sa.selection_words(positions, total, table_pages, page)
    assert keep.shape == (len(lengths), table_pages, page)
    assert (keep.sum((1, 2)) == total).all()
    assert (np.asarray(keep.reshape(len(lengths), S) != 0)
            == np.asarray(sa.top_mask(jnp.asarray(scores), seen, k))).all()
    # Whatever the unreal places name: position 0 (the benchmark's padding).
    padded = jnp.where(jnp.arange(positions.shape[1])[None, :]
                       < total[:, None], positions, 0)
    assert (sa.selection_words(padded, total, table_pages, page)
            == keep).all()
    if case == "first_page_empty":
        assert int(keep[:, 0].sum()) == 0
    if case == "ties_over_a_boundary":
        # Four of the eight tied keys are kept: the lower positions.
        assert keep[0, 5, -4:].tolist() == [1] * 4
        assert int(keep[0, 6].sum()) == 0
    phys = jnp.take_along_axis(table, positions // page, axis=1)
    want = sa.gathered_decode_attention(q, pool_k, pool_v, 1, phys,
                                        positions % page, total)
    got = jax.jit(lambda q, pk, pv, keep: sa.selected_decode_attention(
        q, pk, pv, table, n_keys, keep, layer=jnp.int32(1),
        interpret=True))(q, pool_k, pool_v, keep)
    assert got.shape == want.shape and got.dtype == want.dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


def test_a_mask_of_every_seen_key_is_plain_causal_attention():
    """With every key a slot holds kept — all ones over a slot of whole
    pages — the decode kernel IS plain attention over the pool: a softmax
    over the slot's context, the keys read through the page table."""
    rng = np.random.default_rng(52)
    lengths, page, table_pages = (64, 37, 1), 8, 9
    table, pool_k, pool_v, q = _decode_pool(rng, 3, table_pages, page,
                                            jnp.float32)
    S = table_pages * page
    n_keys = jnp.asarray(lengths, jnp.int32)
    seen = jnp.arange(S)[None, :] < n_keys[:, None]
    keep = seen.astype(jnp.int32).reshape(3, table_pages, page)
    assert bool(keep[0, :8].all())
    got = sa.selected_decode_attention(q, pool_k, pool_v, table, n_keys,
                                       keep, layer=0, interpret=True)
    dense_k, dense_v = (sa.gather_pages(side[0], table, S)
                        for side in (pool_k, pool_v))
    want = sa.masked_attention_reference(q[:, None], dense_k, dense_v,
                                         seen[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)


def test_the_pages_a_decode_read_walks_are_counted_beside_its_keys():
    """``_count_decode_keys`` under an indexer: a burst of two steps of
    slots at 0, 255, 256 and 5,000 cached tokens (one of a fifth slot not
    active) adds each step's context and itself to the scored keys,
    ``min(that, k)`` to the selected ones and ``ceil(that / page)`` to the
    pages the read walked — ONE layer's, as ``stats()`` hands them out."""
    from llmapigateway_tpu.engine.engine import InferenceEngine
    eng = types.SimpleNamespace(
        lengths=np.asarray([0, 255, 9, 256, 5000]),
        active=np.asarray([True, True, False, True, True]),
        model_cfg=TINY, kv_page=256, _lin_decode_state_updates=0,
        kv_groups=[types.SimpleNamespace(kind="kv", window=0)],
        _dsa_decode_keys={"scored": 0, "selected": 0, "pages_walked": 0})
    InferenceEngine._count_decode_keys(eng, 2)
    seen = [n + i for n in (0, 255, 256, 5000) for i in (1, 2)]
    assert eng._dsa_decode_keys == {
        "scored": sum(seen),
        "selected": sum(min(n, TINY.idx_topk) for n in seen),
        "pages_walked": 1 + 1 + 1 + 2 + 2 + 2 + 20 + 20}


def test_equal_streams_are_the_engines_rotary_and_unequal_ones_are_not():
    """On text the three position streams are equal and the reference's
    multimodal rotary IS the half-split rotary the engine computes; with an
    image's streams (height and width apart from time) it is another."""
    rng = np.random.default_rng(9)
    for d in (TINY.idx_head_dim, 128):
        x = jnp.asarray(rng.normal(size=(12, 3, d)), jnp.float32)
        pos = jnp.arange(5, 17)
        cos, sin = rope_tables(pos[None], d, 1e7)
        mine = apply_rope(x[None], cos, sin)[0]
        text = ref.mrope(x, jnp.broadcast_to(pos, (3, 12)), (16, 24, 24), 1e7)
        # The same angles up to a float32 rounding of the frequency.
        np.testing.assert_allclose(np.asarray(text), np.asarray(mine),
                                   atol=1e-5, rtol=0)
        image = ref.mrope(x, jnp.stack([pos, pos // 4, pos % 4]),
                          (16, 24, 24), 1e7)
        assert float(jnp.abs(image - mine).max()) > 0.1


def test_the_four_shares_are_the_uncut_layer(f32_params):
    """The guide's share test on this family's expert layer (no shared
    expert): what the four chips that share a layer compute for their 4
    held experts each adds up to what the layer that holds all 16 computes;
    and the reference, given one share, computes that share's layer."""
    c = TINY
    lp = jax.tree.map(lambda a: a[0], f32_params["layers"]["attn"]["mlp"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 12, c.d_model)),
                    jnp.float32)
    whole, _ = hybrid.moe_block(x, lp, c)
    parts = []
    for share in range(4):
        held = dataclasses.replace(c, n_experts_held=4,
                                   first_expert_held=4 * share)
        mine = {**lp, **{k: lp[k][4 * share:4 * share + 4]
                         for k in hybrid.EXPERT_KEYS}}
        parts.append(hybrid.moe_block(x, mine, held)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=1e-5, rtol=0)
    assert min(float(jnp.abs(p).max()) for p in parts) > 1e-3
    held = dataclasses.replace(c, n_experts_held=4, first_expert_held=8)
    mine = {k: (v[8:12] if k in hybrid.EXPERT_KEYS else v)
            for k, v in lp.items()}
    routed = {k: mine.pop(k) for k in hybrid.EXPERT_KEYS}
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x[0], mine, ref.sizes(held, file_of(held)),
                            tuple(routed[k][None] for k in ("wg", "wu", "wd")),
                            jnp.int32(0))
    got, _ = hybrid.moe_block(x[:1], {**mine, **routed}, held)
    np.testing.assert_allclose(np.asarray(x[0] + got[0]), np.asarray(want),
                               atol=1e-5, rtol=0)


def test_int8_weights_serve_within_the_benchmarks_bound(tokens):
    """W8A8 as the cell serves it (int8 projections, experts and head; the
    indexer, the router and the pool bfloat16) against the float32
    reference on the dequantised weights: the reference's logit of every
    position's served argmax within the harness's LOGIT_GAP_TOL of its
    maximum."""
    from benchmark.correctness import LOGIT_GAP_TOL
    params = params_of(TINY, jnp.bfloat16, "int8")
    got = serve(TINY, params, tokens, "reference", dtype=jnp.bfloat16)
    want = ref.logits(params, SIZES, tokens, last=len(tokens))
    served = got.argmax(-1)
    gaps = want.max(-1) - want[np.arange(len(tokens)), served]
    assert gaps.max() <= LOGIT_GAP_TOL and np.median(gaps) <= 0.05
