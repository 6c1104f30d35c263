"""The Mistral-Small-4 family (the period scan of models/hybrid.py with a
period of ONE latent-attention layer, models/mla.py, over a latent page
pool, ops/latent_attention.py) against the plain reference
(benchmark/reference/mistral4.py) on seeded random weights at the tiny
preset: logits, not tokens. Every tolerance says where it comes from."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mistral4 as ref
from llmapigateway_tpu.models import hybrid, mla
from llmapigateway_tpu.models.config import (PRESETS, RopeScaling,
                                             get_preset)
from llmapigateway_tpu.models.llama import rope_tables
from llmapigateway_tpu.ops import latent_attention as la
from tests.hybrid_params import params_of

TINY = get_preset("tiny-mistral4-test")
# Both sides float32 on the same weights: what is left is the order of the
# sums (pages against one softmax over the sequence, the absorbed product
# against the expanded one, a grouped or batched expert product against a
# loop over experts), ~1e-6 relative on logits of size ~4.
F32_TOL = 2e-4
PAGE, SEQ = 8, 128


def file_of(c) -> dict:
    """What a configuration's file states, for the reference's ``sizes``."""
    rs = c.rope_scaling
    return {"num_attention_heads": c.n_heads, "kv_lora_rank": c.kv_lora_rank,
            "qk_nope_head_dim": c.qk_nope_head_dim,
            "qk_rope_head_dim": c.qk_rope_head_dim,
            "rms_norm_eps": c.rms_eps, "rope_interleave": c.rope_interleave,
            "num_experts_per_tok": c.experts_per_token,
            "n_routed_experts": c.experts_held,
            "first_expert_held": c.first_expert_held, "n_group": 1,
            "rope_parameters": {
                "rope_type": "yarn", "rope_theta": c.rope_theta,
                "factor": rs.factor,
                "original_max_position_embeddings": rs.original_max_seq,
                "beta_fast": rs.beta_fast, "beta_slow": rs.beta_slow,
                "mscale": rs.mscale, "mscale_all_dim": rs.mscale_all_dim,
                "llama_4_scaling_beta": c.query_scale_beta}}


SIZES = ref.sizes(TINY, file_of(TINY))


def paged(c, slots: int, dtype=jnp.float32):
    """(cache, page table): ``slots`` slots of SEQ tokens, page 0 trash."""
    per = SEQ // PAGE
    table = jnp.arange(1, slots * per + 1, dtype=jnp.int32).reshape(slots, per)
    return hybrid.HybridCache.create(c, slots * per + 1, PAGE, slots,
                                     dtype), table


def tokens_of(n_rows: int, n: int, seed=0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, (n_rows, n)).astype(np.int32)


def serve(c, params, tokens, impl: str, chunk: int = 24, prompt: int = 72,
          dtype=jnp.float32):
    """Row 0's ``tokens`` [n] through slot 1 of a two-slot cache: the
    prompt in chunks of ``chunk``, then a decode step a token (row b IS
    slot b: slot 0 stays inactive). -> logits [n, V] of every position."""
    cache, table = paged(c, 2, dtype)
    one = la.LatentAttention(table[jnp.asarray([1])], SEQ, impl,
                             interpret=True)
    both = la.LatentAttention(table, SEQ, impl, interpret=True)
    prefill = jax.jit(lambda p, t, at, cache: hybrid.forward(
        p, c, t, at, cache, attention_fn=one, slots=jnp.asarray([1])))
    decode = jax.jit(lambda p, t, at, cache, on: hybrid.forward(
        p, c, t, at, cache, active=on, attention_fn=both))
    out = []
    for pos in range(0, prompt, chunk):
        logits, cache = prefill(params, jnp.asarray(tokens[None,
                                                           pos:pos + chunk]),
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


def test_the_presets_are_the_published_sizes_and_one_latent_group():
    full = PRESETS["mistral-small4-119b"]
    assert (full.n_layers, full.d_model, full.n_heads, full.head_dim,
            full.vocab_size, full.max_seq_len) == (
                36, 4096, 32, 128, 131072, 1048576)
    assert (full.q_lora_rank, full.kv_lora_rank, full.qk_nope_head_dim,
            full.qk_rope_head_dim, full.v_head_dim, full.latent_width) == (
                1024, 256, 64, 64, 128, 320)
    assert (full.n_experts, full.experts_per_token, full.d_ff_expert,
            full.n_shared_experts, full.moe_router) == (128, 4, 2048, 1,
                                                        "softmax")
    rs = full.rope_scaling
    assert (rs.rope_type, rs.factor, rs.original_max_seq, rs.beta_fast,
            rs.beta_slow) == ("yarn", 128.0, 8192, 32.0, 1.0)
    assert full.rope_interleave and full.query_scale_beta == 0.1
    assert full.is_mla and full.cache_groups == ((0, (0,)),)
    assert full.n_kv_layers == 36 and full.n_lin_layers == 0
    cut = PRESETS["mistral-small4-119b-ep4"]
    assert cut == dataclasses.replace(full, n_layers=12, vocab_size=32768,
                                      n_experts_held=32)
    assert (cut.n_experts, cut.experts_held) == (128, 32)
    # The family's YaRN softmax scale: m = 0.1 ln(128) + 1, squared.
    assert rs.softmax_mscale ** 0.5 == pytest.approx(1.4852, abs=1e-4)
    assert rs.table_mscale == 1.0
    assert mla.softmax_scale(full) == pytest.approx(
        128 ** -0.5 * 1.4852 ** 2, rel=1e-4)
    with pytest.raises(ValueError, match="supported: llama3, linear, yarn"):
        RopeScaling(rope_type="ntk")


def test_yarn_keeps_fast_pairs_slows_slow_ones_and_blends_between():
    """The program's frequencies are the reference's, and they are YaRN's:
    at the published sizes the pairs that turn 32 times or more inside
    8192 positions keep theta^(-2i/d), those that turn once or less are
    divided by 128, the ramp between is strictly monotone."""
    full = PRESETS["mistral-small4-119b"]
    pos = jnp.asarray([1.0])
    cos, sin = rope_tables(pos, 64, full.rope_theta, full.rope_scaling)
    got = np.arctan2(np.asarray(sin[0]), np.asarray(cos[0]))
    big = ref.sizes(full, {**file_of(full), "rope_parameters": {
        **file_of(full)["rope_parameters"]}})
    want = ref.yarn_frequencies(big)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    plain = 10000.0 ** (-np.arange(32) / 32)
    ratio = want / plain
    assert np.allclose(ratio[:10], 1.0) and np.allclose(ratio[-6:], 1 / 128)
    mid = ratio[(ratio < 0.999) & (ratio > 1.001 / 128)]
    assert len(mid) >= 8 and (np.diff(mid) < 0).all()
    # At the tiny preset too (what the served tests below cross).
    np.testing.assert_allclose(
        np.arctan2(*[np.asarray(t[0]) for t in rope_tables(
            pos, TINY.qk_rope_head_dim, TINY.rope_theta,
            TINY.rope_scaling)][::-1]),
        ref.yarn_frequencies(SIZES), rtol=1e-5)


def test_queries_are_scaled_by_their_position():
    """a_t = 1 + beta ln(1 + floor(t / original)): 1 below the original
    context, then a step a multiple of it."""
    c = TINY
    orig = c.rope_scaling.original_max_seq
    got = np.asarray(mla.query_scale(jnp.arange(4 * orig)[None], c))[0]
    sigma = mla.softmax_scale(c)
    assert np.allclose(got[:orig], sigma)
    for turn in (1, 2, 3):
        assert np.allclose(got[turn * orig:(turn + 1) * orig],
                           sigma * (1 + 0.1 * np.log1p(turn)))


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_served_prefill_then_decode_matches_the_reference(f32_params, impl):
    """96 tokens through the latent pool — three chunks of 24, then 24
    decode steps — against the reference's one full forward. The tiny
    rotary's original context is 32, so two thirds of the positions have
    a_t > 1 and read YaRN-blended frequencies. ``reference`` attends in the
    EXPANDED form over gathered pages, ``pallas`` in the ABSORBED form
    through the kernels (interpreted): both are held to the same
    reference, and so to each other."""
    tokens = tokens_of(1, 96)[0]
    want = ref.logits(f32_params, SIZES, tokens, last=96)
    got = serve(TINY, f32_params, tokens, impl)
    assert np.abs(want).max() > 2.0
    np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)


def test_leaving_out_the_position_scale_or_yarn_is_seen(f32_params):
    """The mechanism the check above holds: a program that skipped a_t, or
    rotated by plain frequencies, is far outside the tolerance past the
    original context and exact inside it."""
    tokens = tokens_of(1, 96)[0]
    want = ref.logits(f32_params, SIZES, tokens, last=96)
    orig = TINY.rope_scaling.original_max_seq
    for wrong in (dataclasses.replace(TINY, query_scale_beta=0.0),
                  dataclasses.replace(TINY, rope_scaling=dataclasses.replace(
                      TINY.rope_scaling, factor=1.0))):
        got = serve(wrong, f32_params, tokens, "reference")
        if wrong.query_scale_beta == 0.0:
            np.testing.assert_allclose(got[:orig], want[:orig],
                                       atol=F32_TOL, rtol=0)
        assert np.abs(got[orig:] - want[orig:]).max() > 100 * F32_TOL


def test_bf16_for_float32_fails_the_float32_tolerance(f32_params):
    """The tolerance is tight enough to tell a coarser arithmetic: the
    same weights served in bfloat16 (pool, activations) miss it by two
    orders of magnitude."""
    tokens = tokens_of(1, 96)[0]
    want = ref.logits(f32_params, SIZES, tokens, last=96)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), f32_params)
    got = serve(TINY, low, tokens, "reference", dtype=jnp.bfloat16)
    assert np.abs(got - want).max() > 100 * F32_TOL


def test_absorbed_and_expanded_agree_on_the_same_inputs():
    """ONE attention in two forms: queries, a written pool and W_kvb in,
    [B, T, H dv] out — the absorbed form through the kernel (interpreted)
    and the expanded form over the gathered pages, float32."""
    c = TINY
    H, dn, dr, dv, r = (c.n_heads, c.qk_nope_head_dim, c.qk_rope_head_dim,
                        c.v_head_dim, c.kv_lora_rank)
    rng = np.random.default_rng(3)
    K, T, NP = 2, 16, 6
    pool = jnp.asarray(rng.normal(size=(2, K * NP + 1, c.latent_width, PAGE)),
                       jnp.float32)
    table = jnp.asarray(rng.permutation(np.arange(1, K * NP + 1)).reshape(
        K, NP).astype(np.int32))
    start = jnp.asarray([5, 24], jnp.int32)
    q_nope = jnp.asarray(rng.normal(size=(K, T, H, dn)) * 0.3, jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(K, T, H, dr)) * 0.3, jnp.float32)
    wkvb = jnp.asarray(rng.normal(size=(r, H, dn + dv)) * r ** -0.5,
                       jnp.float32)
    new = jnp.asarray(rng.normal(size=(K, T, c.latent_width)), jnp.float32)
    fn = la.LatentAttention(table, NP * PAGE, "pallas", interpret=True)
    written = fn.write(pool, new, 1, start)
    absorbed = mla.expand_values(
        fn.attend(mla.absorb_queries(q_nope, q_rope, wkvb, jnp.float32),
                  written, 1, start, r), wkvb, dn)
    expanded = mla.expanded_attention(
        q_nope, q_rope, fn.gather(written, 1), wkvb, start, c)
    assert np.abs(np.asarray(expanded)).max() > 0.1
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5, rtol=0)
    # Layer 0 was not touched, and the oracle's scatter agrees.
    assert (np.asarray(written[0]) == np.asarray(pool[0])).all()
    np.testing.assert_array_equal(
        np.asarray(written), np.asarray(la.latent_insert(
            pool, new, table, start, None, layer=1)))


@pytest.mark.parametrize("T, starts", [(1, [5, 37]), (8, [0, 24]),
                                       (24, [3, 16]), (16, [16, 32])])
@pytest.mark.parametrize("masked", [False, True])
def test_the_in_place_write_is_the_scatter(T, starts, masked):
    """Any start, any length: whole tiles copied, ragged ones patched, an
    inactive row on the trash page — the same bytes off page 0 as the XLA
    scatter, bfloat16."""
    rng = np.random.default_rng(T)
    pool = jnp.asarray(rng.normal(size=(2, 13, 40, 16)), jnp.bfloat16)
    table = jnp.asarray(rng.permutation(np.arange(1, 13)).reshape(
        2, 6).astype(np.int32))
    new = jnp.asarray(rng.normal(size=(2, T, 40)), jnp.bfloat16)
    start = jnp.asarray(starts, jnp.int32)
    active = jnp.asarray([True, False]) if masked else None
    want = la.latent_insert(pool, new, table, start, active, layer=1)
    got = la.latent_insert_in_place(pool, new, table, start, active,
                                    layer=jnp.int32(1), interpret=True)
    np.testing.assert_array_equal(np.asarray(got[:, 1:], np.float32),
                                  np.asarray(want[:, 1:], np.float32))
    if masked:      # slot 1's pages are as they were
        np.testing.assert_array_equal(
            np.asarray(got[1][table[1]], np.float32),
            np.asarray(pool[1][table[1]], np.float32))


def _kernel_case(T, starts, geometry, seed, dtype=jnp.bfloat16):
    """Two rows of ``T`` queries at ``starts`` over 6 pages of 16 keys a
    row: (q, pool, table, start, value width)."""
    rng = np.random.default_rng(seed)
    K, NP, (H, W, wv) = 2, 6, geometry
    pool = jnp.asarray(rng.normal(size=(2, K * NP + 1, W, 16)), dtype)
    table = jnp.asarray(rng.permutation(np.arange(1, K * NP + 1)).reshape(
        K, NP).astype(np.int32))
    q = jnp.asarray(rng.normal(size=(K, T, H, W)) * W ** -0.5, dtype)
    return q, pool, table, jnp.asarray(starts, jnp.int32), wv


# (T, block_t, pages a step, starts, (heads, W, value), (steps, whole
# steps), the draw: a row of few keys can put an output past 2, where ONE
# bfloat16 ulp of the result is over the tolerance)
@pytest.mark.parametrize("T, block_t, ppb, starts, geometry, steps, seed", [
    (1, None, 4, [5, 95], (4, 40, 32), (3, 1), 5),
    (16, 4, 2, [5, 80], (4, 40, 32), (16, 8), 18),
    (16, 16, 4, [5, 80], (4, 40, 32), (3, 1), 20),
    (24, 8, 1, [5, 72], (4, 40, 32), (22, 15), 25),
    # Every step whole: a token on a step's last key.
    (1, None, 2, [31, 63], (4, 40, 32), (3, 3), 3),
    # The last step: the diagonal in its FIRST page, three dead after it.
    (8, 8, 4, [64, 66], (4, 40, 32), (4, 2), 12),
    # Live pages no multiple of a step's: a whole page, the diagonal, two dead.
    (8, 8, 4, [85, 80], (4, 40, 32), (4, 2), 12),
    # A single edge step a program, nothing merged.
    (16, 16, 4, [0, 0], (4, 40, 32), (2, 0), 21),
    # gigachat35-reason's decode row: 64 heads over 576 / 512.
    (1, None, 4, [5, 95], (64, 576, 512), (3, 1), 24),
], ids=["decode", "blocks-of-4", "chunk", "page-a-step", "all-whole",
        "diagonal-first", "ragged-live", "start-0", "decode-576x64"])
def test_the_attention_kernel_is_the_plain_softmax(T, block_t, ppb, starts,
                                                   geometry, steps, seed):
    """Decode (one token a row) and chunks, every row-block and copy
    shape, steps attended whole and page by page (``steps`` says how many
    of each the case walks): bfloat16 pool and queries against float32
    ``jax.numpy`` over the gathered rows; what is left is the
    probabilities' rounding to bfloat16 for the value product (2^-9
    relative on values of size ~1)."""
    q, pool, table, start, wv = _kernel_case(T, starts, geometry, seed)
    assert la.latent_steps_walked(
        starts, T, block_t or la.latent_block_t(T, geometry[0]), 16, 6,
        ppb) == steps
    got = la.latent_paged_attention(
        q, pool, table, start, value_width=wv, layer=jnp.int32(1),
        block_t=block_t, pages_per_step=ppb, interpret=True)
    want = la.latent_attention_reference(
        q, la.gather_latent(pool, table, 96, layer=1), start, wv)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)


def test_a_step_attended_whole_is_the_softmax_of_its_pages(monkeypatch):
    """The same call with its whole steps attended in one update a step,
    in one a page, and page by page under the edge step's branches: one
    softmax, rescaled at other places. In float32 (pool, queries and so
    the probabilities) the three agree to rounding."""
    q, pool, table, start, wv = _kernel_case(8, [85, 64], (4, 40, 32), 7,
                                             jnp.float32)

    def run():
        return np.asarray(la.latent_paged_attention(
            q, pool, table, start, value_width=wv, layer=jnp.int32(1),
            block_t=8, pages_per_step=4, interpret=True))
    assert la.update_span(32, 4, 16, 40, wv, 4) == 4
    merged = run()
    monkeypatch.setattr(la, "update_span", lambda *shapes: 1)
    by_page = run()
    monkeypatch.setattr(la, "step_is_whole", lambda i, first_q, *_: first_q < 0)
    by_branch = run()
    assert np.abs(merged).max() > 0.1
    np.testing.assert_allclose(merged, by_page, atol=2e-6, rtol=0)
    np.testing.assert_allclose(merged, by_branch, atol=2e-6, rtol=0)
    want = la.latent_attention_reference(
        q, la.gather_latent(pool, table, 96, layer=1), start, wv)
    np.testing.assert_allclose(merged, np.asarray(want), atol=1e-5, rtol=0)


@pytest.mark.parametrize("page, ppb", [(8, 1), (8, 4), (16, 2), (256, 4)])
def test_the_hosts_step_count_is_the_kernels_walk(page, ppb):
    """``latent_steps_walked`` in closed form against a loop over the
    kernel's own arithmetic (``n_live``, ``n_steps``, ``step_is_whole``)."""
    table = 24
    for T, bt in ((1, 1), (16, 4), (16, 16), (64, 32), (512, 64)):
        for start in (0, 1, page - 1, page, 3 * page + 5, ppb * page - 1,
                      ppb * page, 2 * ppb * page + page - T % page,
                      max(0, table * page - T), table * page + 40):
            steps = whole = 0
            for first_q in range(start, start + T, bt):
                n_live = min((first_q + bt - 1) // page + 1, table)
                n_steps = (n_live + ppb - 1) // ppb
                steps += n_steps
                whole += sum(bool(la.step_is_whole(i, first_q, n_live, page,
                                                   ppb))
                             for i in range(n_steps))
            assert la.latent_steps_walked([start], T, bt, page, table,
                                          ppb) == (steps, whole)
    assert la.latent_steps_walked([0, 7680], 512, 64, 256, 128) == (
        8 + 64, 0 + 8 * 7)


@pytest.mark.parametrize("rows, width, value, span", [
    (2048, 320, 256, 4),        # mistral-small4-longctx: a chunk
    (2048, 576, 512, 4),        # gigachat35-reason: a chunk
    (32, 320, 256, 4), (64, 576, 512, 4),       # their decode rows
    (4096, 320, 256, 2),        # twice the rows: half the keys
    (8192, 576, 512, 1)])
def test_an_update_spans_what_fits_beside_the_programs_blocks(rows, width,
                                                              value, span):
    assert la.update_span(rows, 4, 256, width, value, 2) == span


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer(
        f32_params):
    """The guide's share test on this family's expert layer: what the four
    chips that share a layer compute for their 4 held experts each, with
    the shared expert (which every chip computes alike) counted once, adds
    up to what the layer that holds all 16 computes."""
    c = TINY
    lp = jax.tree.map(lambda a: a[0], f32_params["layers"]["attn"]["mlp"])
    x = jnp.asarray(np.random.default_rng(5).normal(size=(2, 12, c.d_model)),
                    jnp.float32)
    whole, _ = hybrid.moe_block(x, lp, c)
    shared = hybrid.swiglu_mlp(
        hybrid.rms_norm(x, lp["norm"], c.rms_eps), lp["sg"], lp["su"],
        lp["sd"])
    parts = []
    for share in range(4):
        held = dataclasses.replace(c, n_experts_held=4,
                                   first_expert_held=4 * share)
        mine = {**lp, **{k: lp[k][4 * share:4 * share + 4]
                         for k in hybrid.EXPERT_KEYS}}
        out, _ = hybrid.moe_block(x, mine, held)
        parts.append(out - shared)          # the routed part alone
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(whole), atol=1e-5, rtol=0)
    assert max(float(jnp.abs(p).max()) for p in parts) > 1e-3
    # And the reference, given one share, computes that share's layer.
    held = dataclasses.replace(c, n_experts_held=4, first_expert_held=8)
    sizes = ref.sizes(held, file_of(held))
    mine = {k: (v[8:12] if k in hybrid.EXPERT_KEYS else v)
            for k, v in lp.items()}
    routed = {k: mine.pop(k) for k in hybrid.EXPERT_KEYS}
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x[0], mine, sizes,
                            tuple(routed[k][None] for k in ("wg", "wu", "wd")),
                            jnp.int32(0))
    got, _ = hybrid.moe_block(x[:1], {**mine, **routed}, held)
    np.testing.assert_allclose(np.asarray(x[0] + got[0]), np.asarray(want),
                               atol=1e-5, rtol=0)


def test_int8_weights_serve_within_the_benchmarks_bound():
    """W8A8 as the cell serves it (int8 projections, experts and head;
    bfloat16 latent pool and W_kvb) against the float32 reference on the
    dequantised weights: every served position's argmax stands within the
    harness's LOGIT_GAP_TOL of the reference's maximum."""
    from benchmark.correctness import LOGIT_GAP_TOL
    params = params_of(TINY, jnp.bfloat16, "int8")
    assert isinstance(params["layers"]["attn"]["wqa"], dict)
    assert params["layers"]["attn"]["wkvb"].dtype == jnp.bfloat16
    tokens = tokens_of(1, 96)[0]
    want = ref.logits(params, SIZES, tokens, last=96)
    got = serve(TINY, params, tokens, "pallas", dtype=jnp.bfloat16)
    gaps = want.max(-1) - np.take_along_axis(
        want, got.argmax(-1)[:, None], 1)[:, 0]
    assert gaps.max() <= LOGIT_GAP_TOL


def test_the_cost_functions_count_each_latent_byte_once():
    flops, nbytes = ref.mla_decode_cost([8191, 0], 32, 320, 256)
    assert flops == 2 * 32 * 576 * (8192 + 1)
    assert nbytes == (8192 + 1) * 640 + 2 * 32 * 576 * 2
    flops, nbytes = ref.mla_prefill_cost(512, 512, 32, 320, 256)
    keys = sum(512 + t + 1 for t in range(512))
    assert flops == 2 * 32 * 576 * keys
    assert nbytes == 1024 * 640 + 512 * 32 * 576 * 2
