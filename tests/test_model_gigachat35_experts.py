"""The "gigachat3_5" family's expert layer and gated MLPs at tiny sizes: a
selection bias and a routed scale in ``route``, two chips' shares adding up
to the uncut layer, the clamp in all three forms of a gated MLP, and the
older families computing what they computed. Plain numpy or
``benchmark/reference/gigachat35.py`` on the other side; float32, so a
tolerance is the order of the sums (1e-4 on outputs of size ~1-10)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gigachat35 as ref
from llmapigateway_tpu.models import hybrid, llama
from llmapigateway_tpu.models.config import get_preset

from hybrid_params import params_of
from test_model_gigachat35 import LOGIT_TOL, TINY, sizes_of


def test_the_bias_moves_the_selection_and_the_weights_stay_the_scores():
    c = TINY
    hf = jax.random.normal(jax.random.PRNGKey(0), (32, c.d_model))
    router = jax.random.normal(jax.random.PRNGKey(1),
                               (c.d_model, c.n_experts)) / 8.0
    bias = jnp.zeros((c.n_experts,)).at[5].set(10.0)    # expert 5 always in
    idx, w = hybrid.route(hf, router, c, bias)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    scores = jax.nn.sigmoid(hf @ router)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(
        w, c.routed_scale * top / top.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-6)
    # No bias: the family's older form, scaled.
    idx0, w0 = hybrid.route(hf, router, c)
    plain = dataclasses.replace(c, routed_scale=1.0)
    idx1, w1 = hybrid.route(hf, router, plain)
    assert np.array_equal(np.asarray(idx0), np.asarray(idx1))
    np.testing.assert_allclose(w0, 2.5 * w1, rtol=1e-6)
    assert not bool(jnp.all(jnp.any(idx0 == 5, axis=-1)))


def test_two_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """ONE tiny layer of 16 experts: the chips that hold experts 0-7 and
    8-15 each compute their part and the shared expert; the two branches,
    the shared expert counted ONCE, are the reference's uncut layer."""
    c = TINY
    mp = jax.tree.map(lambda a: a[0], params_of(c)["layers"]["attn"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, c.d_model))
    parts = []
    for first in (0, 8):
        share = dataclasses.replace(c, n_experts_held=8,
                                    first_expert_held=first)
        held = {**mp, **{k: mp[k][first:first + 8]
                         for k in hybrid.EXPERT_KEYS}}
        parts.append(hybrid.moe_block(x, held, share)[0])
    sizes = sizes_of(c)
    m = ref.norm(x.reshape(-1, c.d_model), mp["norm"], sizes)
    shared = ref.gated_mlp(m, mp["sg"], mp["su"], mp["sd"], sizes)
    stack = {**mp, **{k: mp[k][None] for k in hybrid.EXPERT_KEYS}}
    whole = ref.expert_mlp(m, stack, sizes, 0)
    np.testing.assert_allclose(
        (parts[0] + parts[1]).reshape(-1, c.d_model) - shared, whole,
        rtol=0, atol=LOGIT_TOL)
    # And a share alone is not the layer.
    assert float(jnp.max(jnp.abs(parts[0].reshape(-1, c.d_model)
                                 - whole))) > 0.05


def _plain_gated(x, wg, wu, wd, limit):
    g, u = x @ wg, x @ wu
    if limit:
        g, u = np.minimum(g, limit), np.clip(u, -limit, limit)
    return (g / (1.0 + np.exp(-g)) * u) @ wd


def test_the_clamp_bites_in_all_three_forms_of_a_gated_mlp():
    """Inputs four times unit scale, so that a third of the gate and up
    products lie beyond L = 1: ``swiglu_mlp``, ``experts_dense`` and the
    grouped kernel each against (SiLU(min(g, L)) clip(u, -L, L)) W_d in
    plain numpy, and each differs from its un-clamped self."""
    D, F, held, N, L = 64, 32, 4, 96, 1.0
    keys = jax.random.split(jax.random.PRNGKey(11), 6)
    x = 4.0 * jax.random.normal(keys[0], (N, D))
    lp = {"wg": jax.random.normal(keys[1], (held, D, F)) / 8.0,
          "wu": jax.random.normal(keys[2], (held, D, F)) / 8.0,
          "wd": jax.random.normal(keys[3], (held, F, D)) / 6.0}
    xn, w = np.asarray(x), jax.tree.map(np.asarray, lp)
    beyond = np.mean(np.abs(xn @ w["wu"][0]) > L)
    assert beyond > 0.3
    want = [_plain_gated(xn, w["wg"][e], w["wu"][e], w["wd"][e], L)
            for e in range(held)]
    got = llama.swiglu_mlp(x, lp["wg"][0], lp["wu"][0], lp["wd"][0], limit=L)
    np.testing.assert_allclose(got, want[0], rtol=0, atol=1e-4)
    free = llama.swiglu_mlp(x, lp["wg"][0], lp["wu"][0], lp["wd"][0])
    assert float(jnp.max(jnp.abs(got - free))) > 1.0
    # Every held expert on every token, weighted.
    probs = jax.nn.softmax(jax.random.normal(keys[4], (N, held)))
    dense = hybrid.experts_dense(x, probs, lp, limit=L)
    np.testing.assert_allclose(
        dense, sum(np.asarray(probs)[:, e:e + 1] * want[e]
                   for e in range(held)), rtol=0, atol=1e-4)
    # The grouped kernel (interpreted here) on a top-2 routing.
    idx = jax.random.randint(keys[5], (N, 2), 0, held)
    idx = idx.at[:, 1].set((idx[:, 0] + 1) % held)
    wts = jnp.full((N, 2), 0.5)
    grouped, _ = hybrid.experts_grouped(x, idx, wts, lp, held, tile=32,
                                        limit=L)
    ids = np.asarray(idx)
    plain = sum(0.5 * np.where((ids == e).any(-1)[:, None], want[e], 0.0)
                for e in range(held))
    np.testing.assert_allclose(grouped, plain, rtol=0, atol=1e-4)
    unclamped, _ = hybrid.experts_grouped(x, idx, wts, lp, held, tile=32)
    assert float(jnp.max(jnp.abs(grouped - unclamped))) > 1.0


@pytest.mark.parametrize("preset", ["tiny-hybrid-test", "tiny-mistral4-test",
                                    "tiny-cohere2-test"])
def test_a_family_that_states_no_limit_computes_what_it_computed(preset):
    """Without ``swiglu_limit`` (and without a bias, a scale, a post norm)
    the branches are not traced: a limit no product reaches gives the older
    families' expert layer bit for bit what no limit gives."""
    c = get_preset(preset)
    assert (c.swiglu_limit, c.routed_scale, c.router_bias, c.post_norm,
            c.leading_dense) == (0.0, 1.0, False, False, 0)
    layers = params_of(c)["layers"]["attn"]
    attn = layers[0] if isinstance(layers, tuple) else layers
    mp = jax.tree.map(lambda a: a[0], attn["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 40, c.d_model))
    normed = x.astype(jnp.float32) if c.parallel_block else None
    far = dataclasses.replace(c, swiglu_limit=1e9)
    for T in (40, 8):           # the grouped kernel, the dense form
        a = hybrid.moe_block(x[:, :T], mp, c, normed=(
            None if normed is None else normed[:, :T]))[0]
        b = hybrid.moe_block(x[:, :T], mp, far, normed=(
            None if normed is None else normed[:, :T]))[0]
        assert np.array_equal(np.asarray(a), np.asarray(b))
