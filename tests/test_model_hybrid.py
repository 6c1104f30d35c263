"""The hybrid family (models/hybrid.py) against the plain reference
(benchmark/reference/solar_open2.py) on seeded random weights at the tiny
preset: logits, not tokens. Every tolerance says where it comes from. The
expert layer's cases are tests/test_model_hybrid_experts.py (a file of
their own, so that the two run on a worker each)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as ref
from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.config import PRESETS, get_preset
from llmapigateway_tpu.models.quant import is_quantized
from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn
from tests.hybrid_params import params_of

TINY = get_preset("tiny-hybrid-test")
# Both sides float32 on the same weights: what is left is the order of the
# sums (a block-parallel form against a recurrence, a grouped product
# against a loop over experts), ~1e-6 relative on logits of size ~4.
F32_TOL = 2e-4
PAGE, SEQ = 8, 128


def file_of(c) -> dict:
    """What a configuration's file states, for the reference's ``sizes``."""
    return {"num_attention_heads": c.n_heads,
            "num_key_value_heads": c.n_kv_heads, "head_dim": c.head_dim,
            "linear_attn_config": {
                "num_heads": c.lin_heads, "head_dim": c.lin_head_dim,
                "short_conv_kernel_size": c.lin_conv_taps},
            "layer_kinds": {"period": c.layer_period, "paged_attention": [0]},
            "rms_norm_eps": c.rms_eps, "n_routed_experts": c.experts_held,
            "first_expert_held": c.first_expert_held,
            "reduced": {"n_routed_experts": {"published": c.n_experts}},
            "num_experts_per_tok": c.experts_per_token,
            "n_shared_experts": c.n_shared_experts}


def sizes_of(c):
    return ref.sizes(c, file_of(c))


def paged(c, slots: int, dtype=jnp.float32, kv_quant=""):
    """(cache, page table): ``slots`` slots of SEQ tokens, page 0 trash."""
    per = SEQ // PAGE
    table = jnp.arange(1, slots * per + 1, dtype=jnp.int32).reshape(slots, per)
    return hybrid.HybridCache.create(c, slots * per + 1, PAGE, slots, dtype,
                                     kv_quant), table


def prefill(c, params, cache, table, tokens, start, slots, n_valid=None):
    attn = make_paged_attention_fn(table[jnp.asarray(slots)], max_seq=SEQ,
                                   impl="reference")
    return hybrid.forward(
        params, c, jnp.asarray(tokens), jnp.asarray(start, jnp.int32), cache,
        attention_fn=attn, slots=jnp.asarray(slots, jnp.int32),
        n_valid=None if n_valid is None else jnp.asarray(n_valid, jnp.int32))


def tokens_of(n_rows: int, n: int, seed=0) -> np.ndarray:
    return np.random.default_rng(seed).integers(
        0, TINY.vocab_size, (n_rows, n)).astype(np.int32)


# -- the linear layer's two forms against its recurrence ----------------------

def _delta_inputs(t=128, b=2, h=3, dk=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = hybrid._l2norm(jax.random.normal(ks[0], (b, t, h, dk))) * dk ** -0.5
    k = hybrid._l2norm(jax.random.normal(ks[1], (b, t, h, dk)))
    v = jax.random.normal(ks[2], (b, t, h, dk))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h)))
    s0 = jax.random.normal(ks[5], (b, h, dk, dk))
    return q, k, v, beta, s0, ks[4]


@pytest.mark.parametrize("lo, hi", [(1e-4, 1e-2), (1.0, 11.0), (1e-4, 11.0)],
                         ids=["slow", "fast", "mixed"])
def test_chunked_form_is_the_recurrence(lo, hi):
    """Decay exponents from 1e-4 (0.9999 a token) to 11 (e^-11 a token, a
    cumulative product that underflows inside one sub-chunk): the block-
    parallel form forms every decay as a difference of cumulative logs, so
    it stays finite and equals the token-by-token recurrence."""
    q, k, v, beta, s0, key = _delta_inputs()
    log_a = -jnp.exp(jax.random.uniform(key, q.shape, minval=np.log(lo),
                                        maxval=np.log(hi)))
    o1, s1 = hybrid.kda_recurrent(q, k, v, log_a, beta, s0)
    o2, s2 = jax.jit(hybrid.kda_chunked)(q, k, v, log_a, beta, s0)
    assert bool(jnp.isfinite(o2).all() & jnp.isfinite(s2).all())
    # float32 both: 64 tokens summed through a triangular solve against one
    # at a time; values are O(1).
    np.testing.assert_allclose(o2, o1, atol=5e-5, rtol=0)
    np.testing.assert_allclose(s2, s1, atol=5e-5, rtol=0)


def test_a_call_that_is_not_whole_sub_chunks_runs_the_recurrence():
    """24 tokens are one sub-chunk that 16-token blocks do not divide: the
    chunked form hands the call to the recurrence, which is the reference's
    delta rule continued from the same state."""
    q, k, v, beta, s0, key = _delta_inputs(t=24)
    log_a = -jnp.exp(jax.random.uniform(key, q.shape, minval=-4, maxval=1))
    o1, s1 = hybrid.kda_recurrent(q, k, v, log_a, beta, s0)
    o2, s2 = hybrid.kda_chunked(q, k, v, log_a, beta, s0)
    np.testing.assert_array_equal(o2, o1)
    np.testing.assert_array_equal(s2, s1)
    want, s_want = jax.vmap(ref.delta_rule)(q, k, v, jnp.exp(log_a), beta, s0)
    np.testing.assert_allclose(o1, want, atol=5e-6, rtol=0)
    np.testing.assert_allclose(s1, s_want, atol=5e-6, rtol=0)


def test_decode_update_is_one_step_of_the_reference_recurrence():
    q, k, v, beta, _, key = _delta_inputs(t=6, b=1)
    log_a = -jnp.exp(jax.random.uniform(key, q.shape, minval=-4, maxval=1))
    want = ref.delta_rule(q[0], k[0], v[0], jnp.exp(log_a[0]), beta[0])
    s = jnp.zeros((1, 3, 16, 16))
    for t in range(6):
        o, s = hybrid.kda_decode_update(q[:, t], k[:, t], v[:, t],
                                        log_a[:, t], beta[:, t], s)
        np.testing.assert_allclose(o[0], want[t], atol=1e-6, rtol=0)


# -- the whole model against the reference ------------------------------------

@pytest.mark.parametrize("dtype, quant, held", [
    ("float32", "", 16), ("float32", "", 8), ("bfloat16", "", 8),
    ("bfloat16", "int8", 8)], ids=["f32", "f32-held8", "bf16", "int8"])
def test_full_forward_matches_the_reference(dtype, quant, held):
    c = dataclasses.replace(TINY, n_experts_held=held % 16)
    params = params_of(c, jnp.dtype(dtype), quant)
    cache, table = paged(c, 1, jnp.dtype(dtype))
    toks = tokens_of(1, 64)
    got = np.asarray(prefill(c, params, cache, table, toks, [0], [0])[0][0],
                     np.float32)
    want = ref.logits(params, sizes_of(c), toks[0], last=64)
    err = np.abs(got - want)
    if dtype == "float32":
        assert err.max() <= F32_TOL
        return
    # bf16 activations, and int8 W8A8 on top: rounding of ~0.4% (bf16) and
    # ~1% (int8, per matmul) of each branch, which the residual-scaled
    # init keeps from compounding (measured here: 1-2% of the logits' unit
    # scale, median worst logit of a position 0.03). A position where the
    # rounding flips a token's 4th and 5th expert moves by that expert's
    # quarter weight (up to ~0.4 at this width), so the worst position is
    # NOT bounded: the bounds are on the bulk.
    assert np.linalg.norm(got - want) / np.linalg.norm(want) <= 0.05
    assert np.median(err.max(-1)) <= 0.08


@pytest.mark.parametrize("dtype, quant, kv_quant, tol", [
    ("float32", "", "", F32_TOL),
    # int8 KV adds ~1/127 of each K/V row on the 2 softmax layers.
    ("bfloat16", "int8", "int8", None)], ids=["f32", "int8"])
def test_prefill_in_chunks_then_decode_matches_the_reference(
        dtype, quant, kv_quant, tol):
    """Three chunks of 32 in a group of two rows on slots 2 and 0 — the
    second row ends 16 tokens into its last chunk, so its bucket is padded
    — then four decode steps beside an idle slot: every logit against the
    reference's full forward over the same tokens (ONE forward a slot,
    over all it was fed, read at its last five positions: the model is
    causal, and a forward a length is a compile a length)."""
    c = dataclasses.replace(TINY, n_experts_held=8)
    dt = jnp.dtype(dtype)
    params = params_of(c, dt, quant)
    cache, table = paged(c, 3, dt, kv_quant)
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
    attn = make_paged_attention_fn(table, max_seq=SEQ, impl="reference")
    decode = jax.jit(lambda ca, t, ln, a: hybrid.forward(
        params, c, t, ln, ca, active=a, attention_fn=attn)[::-1])
    lengths, active = np.array([80, 0, 96]), np.array([True, False, True])
    seqs = {0: list(toks[1, :80]), 2: list(toks[0, :96])}
    got = {0: [last[1]], 2: [last[0]]}      # a slot's logits, call by call
    nxt = np.array([5, 0, 7])
    for _ in range(4):
        idle = [np.asarray(s[:, 1]) for s in cache.state + cache.conv]
        cache, lg = decode(cache, jnp.asarray(nxt[:, None]),
                           jnp.asarray(lengths), jnp.asarray(active))
        for before, s in zip(idle, cache.state + cache.conv):
            assert (np.asarray(s[:, 1]) == before).all()        # bit-identical
        for slot in (0, 2):
            seqs[slot].append(int(nxt[slot]))
            got[slot].append(np.asarray(lg[slot, 0], np.float32))
        lengths = lengths + active
        nxt = np.where(active, np.asarray(lg[:, 0]).argmax(-1), 0)
    sizes = sizes_of(c)
    errs = np.concatenate([
        np.abs(np.stack(got[slot])
               - ref.logits(params, sizes, np.asarray(seqs[slot]), last=5))
        for slot in (0, 2)])
    if tol is not None:
        assert errs.max() <= tol
    else:       # the bulk, as in the full forward's int8 case
        assert np.median(errs.max(-1)) <= 0.08
    # 4 steps x 2 decoding slots x top-4 x 8 layers; about half land here.
    # Of the 8 held experts a layer, those 2 rows x top-4 reached: 1 to 8
    # a layer a step, summed over 8 layers and 4 steps.
    total, local, hit = np.asarray(cache.counters)[:3]
    assert total == 4 * 2 * 4 * 8 and 0.3 * total < local < 0.7 * total
    assert 4 * 8 <= hit <= min(local, 4 * 8 * 8)


def test_dropping_the_carried_state_moves_the_logits_past_every_tolerance():
    """The parity tests above could not see a state bug if the layer forgot
    in a token. Zero the carried state (not the conv tail, not the KV) at
    the chunk boundary: the next chunk's logits move by far more than the
    loosest bound used here (0.08 on the bulk)."""
    c = dataclasses.replace(TINY, n_experts_held=8)
    params = params_of(c)
    cache, table = paged(c, 1)
    toks = tokens_of(1, 64)
    _, cache = prefill(c, params, cache, table, toks[:, :32], [0], [0])
    good, _ = prefill(c, params, cache, table, toks[:, 32:], [32], [0])
    dropped = cache._replace(state=tuple(jnp.zeros_like(s)
                                         for s in cache.state))
    bad, _ = prefill(c, params, dropped, table, toks[:, 32:], [32], [0])
    want = ref.logits(params, sizes_of(c), toks[0], last=32)
    assert np.abs(np.asarray(good[0]) - want).max() <= F32_TOL
    moved = np.abs(np.asarray(bad[0]) - want).max(-1)
    assert np.median(moved) > 0.3 and moved.min() > 0.1


def test_a_prefill_that_starts_at_zero_ignores_what_the_block_holds():
    c, params = TINY, params_of(TINY)
    cache, table = paged(c, 2)
    dirty = cache._replace(
        state=tuple(jnp.full_like(s, 7.0) for s in cache.state),
        conv=tuple(jnp.full_like(t, -3.0) for t in cache.conv))
    toks = tokens_of(1, 32)
    clean, _ = prefill(c, params, cache, table, toks, [0], [1])
    got, after = prefill(c, params, dirty, table, toks, [0], [1])
    assert (np.asarray(got) == np.asarray(clean)).all()
    assert (np.asarray(after.state[0][:, 0]) == 7.0).all()  # slot 0 untouched


# -- initialisation and presets -----------------------------------------------

def test_the_drawn_decays_remember_and_only_large_matrices_are_int8():
    c = TINY
    p = params_of(c, jnp.bfloat16, "int8")
    lin = p["layers"]["lin"][1]
    for name in ("wq", "wk", "wv", "wo"):
        assert is_quantized(lin[name]) and is_quantized(
            p["layers"]["attn"][name])
    for name in ("wg", "wu", "wd", "sg", "su", "sd"):
        assert is_quantized(lin["mlp"][name])
    assert is_quantized(p["layers"]["attn"]["wgate"])
    assert is_quantized(p["lm_head"]) and not is_quantized(p["embed"])
    for name in ("router", "norm"):
        assert not is_quantized(lin["mlp"][name])
    for name in ("wf_down", "wf_up", "wg_down", "wg_up", "wbeta", "conv",
                 "a_log", "f_bias", "out_norm", "norm"):
        assert not is_quantized(lin[name]), name
    assert lin["mlp"]["wg"]["q"].shape == (2, 16, 64, 32)
    # Median per-channel decay on unit-scale inputs: 0.9-0.999.
    h = jax.random.normal(jax.random.PRNGKey(2), (256, c.d_model))
    lp = jax.tree.map(lambda a: a[0].astype(jnp.float32), {
        k: lin[k] for k in ("wf_down", "wf_up", "f_bias", "a_log")})
    z = (h @ lp["wf_down"]) @ lp["wf_up"] + lp["f_bias"]
    alpha = jnp.exp(-jnp.exp(lp["a_log"])[None, :, None]
                    * jax.nn.softplus(z).reshape(256, c.lin_heads, -1))
    assert 0.9 <= float(jnp.median(alpha)) <= 0.999


def test_the_presets_are_the_published_sizes_and_the_chips_share():
    full, share = PRESETS["solar-open2-250b"], PRESETS["solar-open2-250b-ep8"]
    assert (full.n_layers, full.n_experts, full.vocab_size,
            full.max_seq_len) == (48, 320, 196608, 1048576)
    assert (full.d_model, full.n_heads, full.n_kv_heads, full.head_dim,
            full.d_ff, full.d_ff_expert) == (4096, 64, 8, 128, 10240, 1280)
    assert full.experts_held == 320 and full.n_kv_layers == 12
    assert (share.n_layers, share.experts_held, share.n_experts,
            share.vocab_size) == (8, 40, 320, 24576)
    assert share.n_kv_layers == 2 and share.n_lin_layers == 6
    assert dataclasses.replace(share, n_layers=48, vocab_size=196608,
                               n_experts_held=0) == full
    llama = PRESETS["mistral-7b"]
    assert llama.n_kv_layers == 32 and llama.n_lin_layers == 0


def test_the_shipped_preset_is_the_published_one_under_the_files_cuts():
    """The cut lives twice — the program's preset of the configuration's
    name and the file's ``reduced`` — and ``resolve_preset`` skips the
    reduced keys, so this holds the two together: what the file calls
    published is the published preset's, and the shipped preset is that
    preset with exactly the file's three cuts."""
    import json
    from pathlib import Path
    file = json.loads((Path(ref.__file__).parents[1] / "configs"
                       / "solar-open2-250b-ep8.json").read_text())
    full, share = PRESETS["solar-open2-250b"], PRESETS[file["preset"]]
    cuts = file["reduced"]
    assert {k: v["published"] for k, v in cuts.items()} == {
        "num_hidden_layers": full.n_layers, "vocab_size": full.vocab_size,
        "n_routed_experts": full.n_experts}
    assert cuts["n_routed_experts"]["held_in"] == "n_experts_held"
    assert dataclasses.replace(
        full, n_layers=file["num_hidden_layers"],
        vocab_size=file["vocab_size"],
        n_experts_held=file["n_routed_experts"]) == share


@pytest.mark.parametrize("change", [{"use_rope": True}, {"attn_gate": False}],
                         ids=["rotary", "ungated"])
def test_softmax_layers_the_family_does_not_have_are_refused(change):
    with pytest.raises(ValueError, match="use_rope must be False, "
                                         "attn_gate True"):
        hybrid.init_params(dataclasses.replace(TINY, **change),
                           jax.random.PRNGKey(0))
