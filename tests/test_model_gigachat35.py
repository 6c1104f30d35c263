"""The "gigachat3_5" period family at ``tiny-gigachat35-test`` (two leading
layers of a gated-delta mixer and a dense MLP, two periods of a latent layer
and three gated-delta layers with experts, every sub-block normed before
and after) against ``benchmark/reference/gigachat35.py``, which shares no
code with it. Seeded weights, float32, LOGITS not tokens.

Tolerances. Engine and reference are both float32 on the same weights, so
what separates them is the order of the sums (the chunked rule against the
token-by-token recurrence, the absorbed-free reference path's gathered
softmax against a blocked one): logits of size ~1 agree to 1e-5 and are
held to ``LOGIT_TOL`` 2e-4 — a dropped norm, a wrong head grouping, a
missing clamp or bias moves them by 1e-2 and more (the ``CONTROLS`` case
below measures it). The rule's three forms are held to ``FORM_TOL`` 2e-5 on
outputs of size ~1 (float32 round-off of 64-term sums). The expert layer's
own cases (bias, scale, shares, clamp) are tests/test_model_gigachat35_experts.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gigachat35 as ref
from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.config import (PRESETS, ModelConfig,
                                             get_preset)
from llmapigateway_tpu.ops.latent_attention import LatentAttention

from hybrid_params import params_of

TINY = get_preset("tiny-gigachat35-test")
PAGE, SEQ = 8, 128
LOGIT_TOL, FORM_TOL = 2e-4, 2e-5


def file_of(c) -> dict:
    """What a configuration's file states, for the reference's ``sizes``."""
    rs = c.rope_scaling
    return {
        "rope_scaling": {
            "type": "yarn", "factor": rs.factor, "beta_fast": rs.beta_fast,
            "beta_slow": rs.beta_slow, "mscale": rs.mscale,
            "mscale_all_dim": rs.mscale_all_dim,
            "original_max_position_embeddings": rs.original_max_seq},
        "n_group": 1, "layernorm_type": "pre_post",
        "norm_type": "ZeroCenteredGatedNorm", "n_shared_experts": 1,
        "layer_kinds": {"period": c.layer_period,
                        "leading_dense": c.leading_dense},
        "first_k_dense_replace": c.leading_dense,
        "num_attention_heads": c.n_heads, "kv_lora_rank": c.kv_lora_rank,
        "qk_nope_head_dim": c.qk_nope_head_dim,
        "qk_rope_head_dim": c.qk_rope_head_dim, "rope_theta": c.rope_theta,
        "rms_norm_eps": c.rms_eps, "rope_interleave": c.rope_interleave,
        "linear_num_key_heads": c.lin_kheads,
        "linear_num_value_heads": c.lin_heads,
        "linear_key_head_dim": c.lin_head_dim,
        "linear_conv_kernel_dim": c.lin_conv_taps,
        "n_routed_experts": c.experts_held,
        "reduced": {"n_routed_experts": {"published": c.n_experts}},
        "first_expert_held": c.first_expert_held,
        "num_experts_per_tok": c.experts_per_token,
        "routed_scaling_factor": c.routed_scale,
        "swiglu_limit": c.swiglu_limit, "layernorm_gating_weight": 2,
        "linear_sigmoid_gate_scale": 2}


def sizes_of(c):
    return ref.sizes(c, file_of(c))


def paged(c, slots: int):
    """(cache, page table): ``slots`` slots of SEQ tokens, page 0 trash."""
    per = SEQ // PAGE
    table = jnp.arange(1, slots * per + 1, dtype=jnp.int32).reshape(slots, per)
    return hybrid.HybridCache.create(c, slots * per + 1, PAGE, slots,
                                     jnp.float32), table


def call(c, params, cache, table, tokens, start, slots=None, n_valid=None,
         active=None):
    rows = table if slots is None else table[jnp.asarray(slots)]
    fn = LatentAttention(rows, SEQ, "reference")
    more = {} if slots is None else {
        "slots": jnp.asarray(slots, jnp.int32),
        "n_valid": None if n_valid is None else jnp.asarray(n_valid,
                                                            jnp.int32)}
    return hybrid.forward(params, c, jnp.asarray(tokens),
                          jnp.asarray(start, jnp.int32), cache,
                          active=active, attention_fn=fn, **more)


def tokens_of(n_rows: int, n: int, seed=0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, (n_rows, n)).astype(np.int32)


# -- the configuration ---------------------------------------------------------

def test_the_presets_count_a_latent_group_beside_the_state_blocks():
    pub, cut = PRESETS["gigachat35-432b"], PRESETS["gigachat35-432b-ep8"]
    assert (pub.d_model, pub.d_ff, pub.d_ff_expert, pub.n_experts) == (
        7168, 18432, 2048, 256)
    assert (pub.lin_kheads, pub.lin_heads, pub.lin_head_dim,
            pub.lin_conv_width) == (32, 64, 128, 16384)
    assert pub.latent_width == 576 and pub.max_seq_len == 262144
    assert cut == dataclasses.replace(pub, n_layers=7, vocab_size=16032,
                                      n_experts_held=32)
    assert (cut.n_periods, cut.n_kv_layers, cut.n_lin_layers) == (1, 1, 6)
    assert cut.cache_groups == ((0, (0,)),) and cut.softmax_positions == (0,)
    assert (TINY.n_periods, TINY.n_kv_layers, TINY.n_lin_layers) == (2, 2, 8)
    # The older period families count as they did.
    solar = PRESETS["solar-open2-250b-ep8"]
    assert (solar.n_periods, solar.n_kv_layers, solar.n_lin_layers,
            solar.lin_conv_width) == (2, 2, 6, 3 * 64 * 128)
    small4 = PRESETS["mistral-small4-119b-ep4"]
    assert (small4.n_periods, small4.n_kv_layers, small4.n_lin_layers) == (
        12, 12, 0)
    assert PRESETS["mistral-7b"].n_periods == 0
    with pytest.raises(ValueError, match="unknown lin_kind"):
        ModelConfig(lin_kind="rwkv")
    with pytest.raises(ValueError, match="they need lin_heads"):
        ModelConfig(leading_dense=2)
    with pytest.raises(ValueError, match="whole periods of layers behind "
                                         "the leading ones"):
        hybrid.init_params(dataclasses.replace(TINY, n_layers=9),
                           jax.random.PRNGKey(0))


def test_the_tree_and_the_cache_hold_both_kinds_of_storage():
    params = params_of(TINY)
    lead, lin = params["lead"], params["layers"]["lin"]
    assert lead["wq"].shape == (2, 64, 2 * 16) and len(lin) == 3
    assert lead["wv"].shape == lead["wz"].shape == (2, 64, 4 * 16)
    assert lead["conv"].shape == (2, 4, (2 + 2 + 4) * 16)
    assert lead["mlp"]["wg"].shape == (2, 64, 96)
    assert lead["dt_bias"].shape == lead["a_log"].shape == (2, 4)
    attn = params["layers"]["attn"]
    assert attn["wgate"].shape == (2, 64, 4 * 16)
    assert attn["mlp"]["router_bias"].shape == (2, 16)
    for tree in (lead, lead["mlp"], attn, attn["mlp"], lin[0], lin[0]["mlp"]):
        assert "norm" in tree and "post_norm" in tree
    # Gain 1 in front, half of (2 n_layers)^-1/2 behind: 2 sigmoid(w).
    assert float(jnp.max(jnp.abs(attn["norm"]))) == 0.0
    gain = 2.0 * jax.nn.sigmoid(attn["post_norm"])
    np.testing.assert_allclose(gain, (2 * TINY.n_layers) ** -0.5 / 2,
                               rtol=1e-6)
    cache, _ = paged(TINY, 3)
    assert [k.shape for k in cache.k] == [(2, 3 * 16 + 1, 32 + 8, PAGE)]
    assert cache.v == ()
    assert [s.shape for s in cache.state] == 3 * [(2, 3, 4, 16, 16)] + [
        (2, 3, 4, 16, 16)]
    assert [t.shape for t in cache.conv] == 4 * [(2, 3, 3, 128)]
    # Under quant the big matrices are int8, the deciders are not.
    q8 = jax.eval_shape(lambda k: hybrid.init_params(
        TINY, k, jnp.bfloat16, "int8"), jax.random.PRNGKey(0))
    for name in ("wq", "wk", "wv", "wz", "wo"):
        assert q8["lead"][name]["q"].dtype == jnp.int8
    assert q8["lead"]["mlp"]["wg"]["q"].dtype == jnp.int8
    assert q8["layers"]["attn"]["wgate"]["q"].dtype == jnp.int8
    for name in ("wa", "wbeta", "conv", "a_log", "dt_bias"):
        assert not isinstance(q8["lead"][name], dict)
    assert not isinstance(q8["layers"]["attn"]["mlp"]["router"], dict)
    assert q8["layers"]["attn"]["mlp"]["router_bias"].dtype == jnp.float32


# -- prefill in chunks, then decode, against the reference ---------------------

def test_chunked_prefill_then_decode_match_the_references_full_forward():
    """Two rows of 44 and 37 tokens through chunks of 16 (the second row
    padded in its last chunk, the first a bucket longer), then 5 decode
    steps through the latent pool and the state blocks: every position's
    logits against the reference's ONE forward over the whole sequence."""
    c, params = TINY, params_of(TINY)
    cache, table = paged(c, 3)
    cache = cache._replace(                     # garbage a fresh row ignores
        state=tuple(jnp.full_like(s, 7.0) for s in cache.state),
        conv=tuple(jnp.full_like(t, -3.0) for t in cache.conv))
    lens, slots = [44, 37], [2, 0]
    toks = tokens_of(2, 44 + 5, seed=3)
    got = [[], []]
    for pos in range(0, 48, 16):
        n_valid = [max(0, min(16, n - pos)) for n in lens]
        rows = [i for i in range(2) if n_valid[i] > 0]
        chunk = np.zeros((len(rows), 16), np.int32)
        for j, i in enumerate(rows):
            chunk[j, :n_valid[i]] = toks[i, pos:pos + n_valid[i]]
        logits, cache = call(c, params, cache, table, chunk,
                             [pos] * len(rows), [slots[i] for i in rows],
                             [n_valid[i] for i in rows])
        for j, i in enumerate(rows):    # the row's last real position
            got[i].append(np.asarray(logits[j, n_valid[i] - 1]))
    lengths = np.zeros((3,), np.int32)
    lengths[slots] = lens
    active = jnp.asarray([True, False, True])
    for step in range(5):
        tok = np.zeros((3, 1), np.int32)
        for i in range(2):
            tok[slots[i], 0] = toks[i, lens[i] + step]
        idle = jax.tree.map(lambda a: a[:, 1], (cache.state, cache.conv))
        logits, cache = call(c, params, cache, table, tok, lengths,
                             active=active)
        # The idle slot's blocks leave bit-identical.
        for a, b in zip(jax.tree.leaves(idle), jax.tree.leaves(
                jax.tree.map(lambda a: a[:, 1], (cache.state, cache.conv)))):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        for i in range(2):
            got[i].append(np.asarray(logits[slots[i], 0]))
        lengths[slots] += 1
    for i in range(2):
        n = lens[i]
        want = ref.logits(params, sizes_of(c), toks[i, :n + 5], last=n + 5)
        # The chunks' last rows, then the decode steps.
        at = [min(p + 16, n) - 1 for p in range(0, 48, 16) if p < n]
        rows = at + list(range(n, n + 5))
        np.testing.assert_allclose(np.stack(got[i]), want[rows], rtol=0,
                                   atol=LOGIT_TOL)


def test_every_control_moves_the_references_logits():
    """What ``correct`` has to refuse is visible at the tiny size too: each
    ``CONTROLS`` entry moves the reference's own logits by fifty times the
    tolerance the engine is held to and more. A bfloat16 state
    (``READINGS``: what a comparison of logits cannot refuse) moves them
    too, by less than any control."""
    params, seq = params_of(TINY), tokens_of(1, 40, seed=5)[0]
    sound = ref.logits(params, sizes_of(TINY), seq, last=8)
    moved = {name: float(np.max(np.abs(ref.logits(
        params, change(sizes_of(TINY)), seq, last=8) - sound)))
        for name, change in {**ref.CONTROLS, **ref.READINGS}.items()}
    assert set(ref.CONTROLS) == {"no_post_norm", "beta_0_2", "unclamped_mlp"}
    for name in ref.CONTROLS:
        assert moved[name] > 50 * LOGIT_TOL, moved
    assert LOGIT_TOL < moved["bf16_state"] < min(
        moved[name] for name in ref.CONTROLS), moved


def test_the_step_programs_file_their_work_under_the_cells_scopes():
    c, params = TINY, params_of(TINY)
    cache, table = paged(c, 2)

    def text(tokens, **kw):
        return jax.jit(lambda p, cache: call(c, p, cache, table, tokens,
                                             **kw)).lower(
            params, cache).as_text(debug_info=True)
    prefill = text(tokens_of(2, 16), start=[0, 0], slots=[0, 1],
                   n_valid=[16, 16])
    for scope in ("prefill.kda", "kda.prefill_chunk", "mlp.dense", "attn.mla",
                  "prefill.mlp", "moe.experts", "moe.shared"):
        assert scope in prefill, scope
    decode = text(tokens_of(2, 1), start=[16, 16],
                  active=jnp.ones((2,), bool))
    for scope in ("decode.kda", "kda.decode_update", "mlp.dense", "attn.mla",
                  "moe.experts", "moe.shared"):
        assert scope in decode, scope


# -- the delta rule: one decay a head, two value heads a key head --------------

def _rule_inputs(rows=2, t=64, hk=2, hv=4, dk=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))
    spread = lambda a: jnp.repeat(a, hv // hk, axis=2)
    q = spread(unit(jax.random.normal(keys[0], (rows, t, hk, dk)))) * dk ** -.5
    k = spread(unit(jax.random.normal(keys[1], (rows, t, hk, dk))))
    v = jax.random.normal(keys[2], (rows, t, hv, dk))
    beta = jax.nn.sigmoid(jax.random.normal(keys[3], (rows, t, hv)))
    log_a = -jnp.exp(jax.random.uniform(
        keys[4], (rows, t, hv), minval=np.log(1e-4), maxval=np.log(11.0)))
    s0 = jax.random.normal(keys[5], (rows, hv, dk, dk))
    return q, k, v, log_a, beta, s0


def test_the_rules_three_forms_agree_at_one_decay_a_head():
    """``kda_chunked`` (sub-chunks of 64 in blocks of 16), ``kda_recurrent``
    and ``kda_decode_update`` chained — the functions the sibling family
    runs with a decay a channel — take ``log_a`` [.., H, 1] and agree with
    one another and with the reference's ``delta_rule``."""
    q, k, v, log_a, beta, s0 = _rule_inputs(t=64)
    want_o, want_s = jax.vmap(ref.delta_rule)(q, k, v, jnp.exp(log_a), beta,
                                              s0)
    one = log_a[..., None]
    for form in (hybrid.kda_chunked, hybrid.kda_recurrent):
        o, s = jax.jit(form)(q, k, v, one, beta, s0)
        np.testing.assert_allclose(o, want_o, rtol=0, atol=FORM_TOL)
        np.testing.assert_allclose(s, want_s, rtol=0, atol=FORM_TOL)
    s, outs = s0, []
    for i in range(8):
        o, s = hybrid.kda_decode_update(q[:, i], k[:, i], v[:, i], one[:, i],
                                        beta[:, i], s)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o[:, :8], rtol=0,
                               atol=FORM_TOL)


def test_padding_is_inert_and_an_inactive_row_leaves_bit_identical():
    """``linear_block`` of the gated-delta kind: tokens past ``n_valid``
    move neither state nor tail (row 1's 9 real tokens of 16 give what 9
    tokens alone give); a decode row with ``keep`` False leaves with the
    state and the tail it came with, bit for bit."""
    c = TINY
    lp = jax.tree.map(lambda a: a[0], {k: v for k, v in params_of(c)["lead"]
                                       .items() if k != "mlp"})
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, c.d_model))
    s0 = jax.random.normal(jax.random.PRNGKey(3), (2, 4, 16, 16))
    tail = jax.random.normal(jax.random.PRNGKey(4), (2, 3, c.lin_conv_width))
    block = jax.jit(lambda h, s, tail, n: hybrid.linear_block(
        h, lp, c, s, tail, n, None))
    full = block(h, s0, tail, jnp.asarray([16, 9]))
    short = block(h[1:, :9], s0[1:], tail[1:], jnp.asarray([9]))
    np.testing.assert_allclose(full[0][1, :9], short[0][0], atol=FORM_TOL)
    np.testing.assert_allclose(full[1][1], short[1][0], atol=FORM_TOL)
    np.testing.assert_allclose(full[2][1], short[2][0], atol=0)
    # Decode takes the STACKED block and the layer's index in it.
    out, s, new_tail = jax.jit(lambda h, s, tail, keep: hybrid.linear_block(
        h, lp, c, s, tail, None, keep, at=0))(h[:, :1], s0[None], tail,
                                              jnp.asarray([True, False]))
    s = s[0]
    assert np.array_equal(np.asarray(s[1]), np.asarray(s0[1]))
    assert np.array_equal(np.asarray(new_tail[1]), np.asarray(tail[1]))
    assert not np.array_equal(np.asarray(s[0]), np.asarray(s0[0]))
    assert out.shape == (2, 1, c.d_model)
