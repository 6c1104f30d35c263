"""The cross-decoder family (models/sambay.py: "phi4flash") against the plain
reference (benchmark/reference/phi4_flash.py) on seeded random weights at
the tiny preset — 8 layers: scan, window 16, scan, window, scan -> memory,
full, gate, cross — logits, not tokens. The reference runs ALL layers over
ALL positions and keeps no cache; the program serves from two page pools and
a state block, and its prefill stops every row but a prompt's last half-way
up. Every tolerance says where it comes from."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import phi4_flash as ref
from llmapigateway_tpu.models import forward_fn, init_fn, sambay
from llmapigateway_tpu.models.config import PRESETS, get_preset
from llmapigateway_tpu.models.hybrid import HybridCache
from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn

TINY = get_preset("tiny-phi4flash-test").served()
# Both sides float32 on the same weights: what is left is the order of the
# sums (pages against one softmax over the sequence, a folded head's zero
# half, a chunked scan against one pass), ~1e-6 relative on logits of size
# ~4. A layer that read a wrong key, a stale state or an ungated memory
# misses by 1e-2 and more (the CONTROLS case below).
F32_TOL = 2e-4
PAGE, SEQ, CHUNK = 8, 160, 32


def file_of(c) -> dict:
    """What a configuration's file states, for the reference's ``sizes``."""
    return {"tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "mb_per_layer": 2, "hidden_act": "silu",
            "mamba_expand": c.ssm_expand, "mamba_d_conv": c.lin_conv_taps,
            "mamba_d_state": c.ssm_state, "mamba_dt_rank": c.ssm_dt_rank,
            "num_attention_heads": c.n_heads,
            "num_key_value_heads": 2 * c.n_kv_heads, "hidden_size": c.d_model,
            "num_hidden_layers": c.n_layers,
            "sliding_window": c.sliding_window,
            "layer_norm_eps": c.layer_norm_eps,
            "engine": {"prefill_chunk": CHUNK}}


SIZES = ref.sizes(TINY, file_of(TINY))


def providers(table, impl):
    return tuple(make_paged_attention_fn(table, SEQ, impl=impl, window=w,
                                         interpret=True)
                 for w, _ in TINY.cache_groups)


class Served:
    """Two slots over two pools; requests are served through slot 1 (row b
    IS slot b in decode: slot 0 stays inactive)."""

    def __init__(self, params, impl="reference"):
        per = SEQ // PAGE
        self.table = jnp.arange(1, 2 * per + 1, dtype=jnp.int32).reshape(
            2, per)
        self.cache = HybridCache.create(TINY, 2 * per + 1, PAGE, 2,
                                        jnp.float32)
        self.params = params
        one = providers(self.table[jnp.asarray([1])], impl)
        both = providers(self.table, impl)
        self._prefill = jax.jit(
            lambda p, t, at, cache, n, final: sambay.forward(
                p, TINY, t, at, cache, attention_fn=one,
                slots=jnp.asarray([1]), n_valid=n, final=final))
        self._decode = jax.jit(lambda p, t, at, cache, on: sambay.forward(
            p, TINY, t, at, cache, active=on, attention_fn=both))

    def prompt(self, tokens, pad=0):
        """The prompt in chunks of ``CHUNK`` (each padded by ``pad`` rows
        that are not real) -> the logits of the prompt's last row."""
        for pos in range(0, len(tokens), CHUNK):
            chunk = tokens[pos:pos + CHUNK]
            final = pos + CHUNK >= len(tokens)
            logits, self.cache = self._prefill(
                self.params, jnp.asarray(np.pad(chunk, (0, pad))[None]),
                jnp.asarray([pos], jnp.int32), self.cache,
                jnp.asarray([len(chunk)], jnp.int32), jnp.asarray([final]))
            if not final:       # nothing ran above the full layer's K/V
                assert not np.asarray(logits).any()
        return np.asarray(logits[0])

    def step(self, token, at, on=True):
        logits, self.cache = self._decode(
            self.params, jnp.asarray([[0], [token]], jnp.int32),
            jnp.asarray([0, at], jnp.int32), self.cache,
            jnp.asarray([False, on]))
        return np.asarray(logits[1])

    def serve(self, tokens, prompt=96, pad=0):
        """-> logits [1 + len(tokens) - prompt, V]: the prompt's last row,
        then a decode step a token."""
        out = [self.prompt(tokens[:prompt], pad)]
        out += [self.step(tokens[i], i) for i in range(prompt, len(tokens))]
        return np.concatenate(out)


@pytest.fixture(scope="module")
def f32_params():
    return jax.jit(lambda k: sambay.init_params(TINY, k, jnp.float32))(
        jax.random.PRNGKey(1))


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(54).integers(
        0, TINY.vocab_size, 104).astype(np.int32)


@pytest.fixture(scope="module")
def want(f32_params, tokens):
    """The reference on rows 95 .. 103: the prompt's last row and the eight
    decode steps."""
    return ref.logits(f32_params, SIZES, tokens, last=9)


def test_the_presets_are_the_published_sizes_in_three_runs():
    full = PRESETS["phi4-mini-flash-3.8b"]
    assert (full.n_layers, full.d_model, full.n_heads, full.n_kv_heads,
            full.head_dim, full.d_ff, full.vocab_size, full.max_seq_len) == (
                32, 2560, 40, 20, 64, 10240, 200064, 262144)
    assert (full.sliding_window, full.layer_period, full.layer_norm_eps,
            full.tie_embeddings, full.norm_kind) == (
                512, 2, 1e-5, True, "layernorm_bias")
    assert (full.lin_kind, full.ssm_state, full.ssm_expand, full.ssm_dt_rank,
            full.lin_conv_taps, full.ssm_inner) == ("mamba", 16, 2, 160, 4,
                                                    5120)
    assert (full.n_self_pairs, full.n_cross_pairs) == (8, 7)
    # 9 layers KEEP paged K/V, 16 attend it, 9 keep state.
    assert (full.n_kv_layers, full.n_attn_layers, full.n_lin_layers) == (
        9, 16, 9)
    assert full.cache_groups == ((512, (1,)), (0, (1,)))
    assert full.group_layers == (8, 1)
    served = full.served()
    assert (served.n_heads, served.n_kv_heads, served.head_dim) == (40, 10,
                                                                    128)
    assert served.served() is served and full.head_dim_override == 0
    assert full.group_readers == (8, 8)
    assert full.group_chunk_readers == (8, 0)
    with pytest.raises(ValueError, match="served\\(\\) config"):
        sambay.init_params(full, None)      # the published heads: refused
    assert (TINY.n_layers, TINY.group_layers, TINY.n_lin_layers) == (8, (2, 1),
                                                                     3)
    assert forward_fn(full) is sambay.forward
    assert init_fn(full) is sambay.init_params
    # The families that were there count their layers as they did.
    assert PRESETS["command-a-plus"].group_layers == (24, 8)
    assert PRESETS["gigachat35-432b"].n_lin_layers == 31
    with pytest.raises(ValueError, match="three runs"):
        dataclasses.replace(full, n_layers=30)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_prefill_in_chunks_then_decode_through_the_pools(f32_params, tokens,
                                                         want, impl):
    """Three chunks of 32 (each padded by 5 rows that are not real: they
    move neither state nor tail), then eight decode steps, through the ring
    past its window and the one full-context pool — the gather form and the
    paged kernels (interpreted) — against the reference's ALL layers over
    ALL rows."""
    got = Served(f32_params, impl).serve(tokens, pad=5)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= F32_TOL


def test_a_slot_reused_and_a_row_that_is_not_active(f32_params, tokens, want):
    """Another request first leaves its state, tail and pages in slot 1; a
    fresh request (``lengths`` 0) then starts from ZERO state whatever the
    block holds. A decode step whose row is not ``active`` leaves the state
    block, the conv tail and both pools bit-identical."""
    served = Served(f32_params)
    other = np.random.default_rng(7).integers(0, TINY.vocab_size, 70)
    served.serve(other.astype(np.int32), prompt=64)
    got = served.prompt(tokens[:96])
    assert np.abs(got - want[:1]).max() <= F32_TOL
    before = jax.tree.map(np.asarray, served.cache)
    served.step(int(tokens[96]), 96, on=False)
    after = jax.tree.map(np.asarray, served.cache)
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        # (Page 0 is the trash page: an inactive row's writes land there.)
        assert np.array_equal(a[..., 1:, :, :, :] if a.ndim == 5 else a,
                              b[..., 1:, :, :, :] if b.ndim == 5 else b)
    got = served.step(int(tokens[96]), 96)
    assert np.abs(got - want[1:2]).max() <= F32_TOL


def test_the_chunk_form_of_the_scan_is_the_token_by_token_form():
    """``selective_scan`` (the scan unrolled 16 tokens a trip) over three
    calls of 48 tokens with the state carried, against ``ssm_step`` a token
    in a Python loop, and both against the reference's recurrence: the same
    float32 products in the same order."""
    rng = np.random.default_rng(3)
    B, T, E, N = 2, 144, 32, 8
    x = jnp.asarray(rng.normal(size=(B, T, E)), jnp.float32)
    delta = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                           (B, T, E))), jnp.float32)
    b, c_ = (jnp.asarray(rng.normal(size=(B, T, N)), jnp.float32)
             for _ in range(2))
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, E))
    h = jnp.zeros((B, N, E))
    got = []
    for lo in range(0, T, 48):
        cut = slice(lo, lo + 48)
        y, h = jax.jit(sambay.selective_scan)(x[:, cut], delta[:, cut],
                                              b[:, cut], c_[:, cut], a, h)
        got.append(y)
    got = np.concatenate(got, axis=1)
    h_loop, loop = jnp.zeros((B, N, E)), []
    for t in range(T):
        h_loop, y = sambay.ssm_step(h_loop, a, x[:, t], delta[:, t], b[:, t],
                                    c_[:, t])
        loop.append(y)
    assert np.abs(got - np.stack(loop, 1)).max() <= 1e-5
    assert np.abs(np.asarray(h) - np.asarray(h_loop)).max() <= 1e-5
    plain, h_ref = ref.recurrence(x[0], delta[0], b[0], c_[0], a, SIZES)
    assert np.abs(got[0] - np.asarray(plain)).max() <= 1e-5
    assert np.abs(np.asarray(h[0]) - np.asarray(h_ref)).max() <= 1e-5
    # A padding token (delta 0) moves nothing.
    y0, h0 = sambay.selective_scan(x[:, :8], jnp.zeros((B, 8, E)), b[:, :8],
                                   c_[:, :8], a, h)
    assert np.array_equal(np.asarray(h0), np.asarray(h))


def test_the_half_zero_query_form_is_the_paired_form_written_out():
    """Differential attention on the served kernels' terms — 4 folded query
    heads of 32 with half of each zeros over ONE K/V head of 32, plain
    grouped-query softmax attention at the folded width's scale, then
    ``diff_combine`` — against the published form written out: query heads
    (2i, 2i+1) of 16 against K heads (2j, 2j+1) of 16 at 16^-1/2, both maps
    on [V_2j | V_2j+1], subtracted under lam and normed."""
    rng = np.random.default_rng(5)
    T, H, dh = 24, TINY.n_heads, TINY.head_dim // 2
    q = jnp.asarray(rng.normal(size=(1, T, H, dh)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, T, 2 * TINY.n_kv_heads, dh)),
                        jnp.float32) for _ in range(2))
    lp = {name: jnp.asarray(rng.normal(size=(dh,)) * 0.3, jnp.float32)
          for name in ("lq1", "lk1", "lq2", "lk2")}
    lp["sub_norm"] = jnp.asarray(rng.normal(size=(2 * dh,)), jnp.float32)
    lam_init = sambay.lambda_init(5)
    # Served: fold, attend as GQA at (2 dh)^-1/2, combine.
    qf = sambay.fold_queries(q, TINY)
    kf, vf = (t.reshape(1, T, TINY.n_kv_heads, 2 * dh) for t in (k, v))
    group = H // TINY.n_kv_heads
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, jnp.repeat(kf, group, 2)
                        ) * (2 * dh) ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    attn = jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(vf, group, 2))
    got = sambay.diff_combine(attn.reshape(1, T, -1), lp, TINY, lam_init)
    # Published: by pairs, by hand.
    lam = (np.exp(float(lp["lq1"] @ lp["lk1"]))
           - np.exp(float(lp["lq2"] @ lp["lk2"])) + lam_init)
    out = []
    for i in range(H // 2):
        j = i // 2
        pair_v = jnp.concatenate([v[0, :, 2 * j], v[0, :, 2 * j + 1]], -1)
        maps = [jax.nn.softmax(jnp.where(
            causal, q[0, :, 2 * i + s] @ k[0, :, 2 * j + s].T * dh ** -0.5,
            -jnp.inf), -1) @ pair_v for s in (0, 1)]
        o = maps[0] - lam * maps[1]
        o = o / jnp.sqrt(jnp.mean(o * o, -1, keepdims=True)
                         + TINY.layer_norm_eps) * lp["sub_norm"]
        out.append(o * (1 - lam_init))
    want = jnp.concatenate(out, -1)
    assert np.abs(np.asarray(got[0]) - np.asarray(want)).max() <= 1e-5


@pytest.mark.parametrize("control", sorted(ref.CONTROLS))
def test_every_planted_fault_moves_the_references_own_logits(
        f32_params, tokens, want, control):
    """What `correct` has to refuse: each ``CONTROLS`` entry moves the
    reference's own float32 logits by a hundred times the tolerance the
    served path is held to above (1e-2 and more at the tiny preset) — but
    the two that are nothing to THIS tree and geometry: four-bit weights (a
    float32 tree has no int8 values) and a bfloat16 state, which moves a
    logit by less than the served path's own rounding does at any width and
    is ``scan_parity``'s to refuse (last case)."""
    assert set(ref.CONTROLS) == {
        "int4_weights", "no_window", "cross_lambda_0", "memory_before_gate",
        "bf16_state", "cross_own_chunk"}
    wrong = ref.logits(f32_params, ref.CONTROLS[control](SIZES), tokens,
                       last=9)
    moved = float(np.abs(wrong - want).max())
    if control == "int4_weights":
        assert moved == 0.0
    elif control == "bf16_state":
        assert 0.0 < moved < 100 * F32_TOL
        sound = ref.scan_parity(channels=64, state=8, chunk=32, chunks=3)
        assert sound["ok"] and sound["max_abs_err"] < 1e-5
        rounded = ref.scan_parity(channels=64, state=8, chunk=32, chunks=3,
                                  change=ref.CONTROLS[control])
        assert not rounded["ok"]
        assert rounded["max_abs_err"] > 10 * ref.SCAN_TOL
    else:
        assert moved > 100 * F32_TOL, moved


def test_int8_weights_quantise_the_big_projections_alone():
    """Under ``quant`` the nine big matrices are ``{"q", "s"}`` pairs with a
    scale per output channel, the head an int8 copy of the embedding's rows;
    ``w_x``, ``w_dt``, the conv, the biases and the lambdas stay bfloat16,
    ``a_log`` and ``d_skip`` float32, ``A = -(1..N)`` on every channel."""
    params = jax.jit(lambda k: sambay.init_params(
        TINY, k, jnp.bfloat16, "int8"))(jax.random.PRNGKey(1))
    ssm, attn = params["self"]["ssm"], params["cross"]["attn"]
    for tree, keys in ((ssm, ("w_in", "w_out")), (attn, ("wq", "wo")),
                       (params["cross"]["gmu"], ("g1", "g2")),
                       (params["mid"]["attn"], ("wqkv",)),
                       (ssm["mlp"], ("w1", "w2"))):
        for key in keys:
            assert tree[key]["q"].dtype == jnp.int8
            assert tree[key]["s"].shape == (
                *tree[key]["q"].shape[:-2], tree[key]["q"].shape[-1])
    assert params["lm_head_q8"]["q"].shape == params["embed"].shape
    assert {ssm[k].dtype for k in ("w_x", "w_dt", "conv_w", "conv_b",
                                   "dt_bias")} == {jnp.dtype(jnp.bfloat16)}
    assert ssm["a_log"].dtype == ssm["d_skip"].dtype == jnp.float32
    assert np.allclose(np.exp(np.asarray(ssm["a_log"][0, :, 0])),
                       np.arange(1, TINY.ssm_state + 1))
