"""The hybrid family's expert layer (models/hybrid.py ``moe_block``,
``experts_grouped``) on the tiny preset's own weights: the chips' shares
add up, the grouped product (the kernel of ops/grouped_experts.py,
interpreted) is the dense one under even and uneven routing, and int8 rows
quantised once stand no further from it. The whole model against the
reference is tests/test_model_hybrid.py, whose scaffolding this file
reads; the kernel on drawn stacks at the three expert presets' widths is
tests/test_ops_grouped_experts.py."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import solar_open2 as ref
from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.quant import is_quantized
from test_model_hybrid import TINY, sizes_of
from tests.hybrid_params import params_of


def _mlp_of(params, layer=0):
    return jax.tree.map(lambda a: a[layer], params["layers"]["lin"][0]["mlp"])


def _share(lp, first, held):
    cut = {k: jax.tree.map(lambda a: a[first:first + held], lp[k])
           for k in ("wg", "wu", "wd")}
    return {**lp, **cut}


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The guide's share test: 16 experts held 8 at a time. The routed
    parts of the two shares plus the shared expert counted ONCE equal the
    uncut layer's result, and each share is what the reference computes
    when it is given that share."""
    whole = params_of(TINY)
    lp = _mlp_of(whole)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, TINY.d_model))
    full = hybrid.moe_block(x, lp, TINY)[0]
    no_shared = dataclasses.replace(TINY, n_shared_experts=0)
    shared_only = full - hybrid.moe_block(x, lp, no_shared)[0]
    routed = []
    for first in (0, 8):
        c = dataclasses.replace(TINY, n_experts_held=8,
                                first_expert_held=first)
        part = hybrid.moe_block(x, _share(lp, first, 8), c)[0]
        routed.append(part - shared_only)
        want = ref.expert_mlp(x.reshape(80, -1), _share(lp, first, 8),
                              sizes_of(c))
        np.testing.assert_allclose(part.reshape(80, -1), want, atol=2e-5)
    np.testing.assert_allclose(routed[0] + routed[1] + shared_only, full,
                               atol=2e-5)
    assert float(jnp.abs(routed[0]).max()) > 0.01       # each share matters
    assert float(jnp.abs(routed[1]).max()) > 0.01


def _routed(c, lp, n: int, routing: str):
    """(x [n,D], the layer's weights with the router that routes them).
    "even": the drawn router on drawn rows. "uneven": every token picks
    the four experts 1, 2, 5 and 9 after the first one held (a capacity
    dispatch at factor 2 would drop three quarters of them), so the first
    expert held gets no row and the second every row."""
    if routing == "even":
        return jax.random.normal(jax.random.PRNGKey(9), (n, c.d_model)), lp
    d = jax.random.normal(jax.random.PRNGKey(3), (c.d_model,))
    x = d + 0.05 * jax.random.normal(jax.random.PRNGKey(4), (n, c.d_model))
    picked = (c.first_expert_held + jnp.array([1, 2, 5, 9])) % c.n_experts
    chosen = jnp.zeros((c.n_experts,)).at[picked].set(1.0)
    return x, {**lp, "router": jnp.outer(d, 2.0 * chosen - 1.0)
               / jnp.linalg.norm(d)}


@pytest.mark.parametrize("routing", ["even", "uneven"])
@pytest.mark.parametrize("tile", [16, 128])
@pytest.mark.parametrize("first, held", [(0, 16), (8, 4), (4, 2)],
                         ids=["all", "quarter", "eighth"])
def test_the_grouped_product_is_the_dense_one(first, held, tile, routing):
    """All, a quarter and an eighth of 16 experts held; 200 rows, not a
    multiple of either tile. Under the uneven routing one held expert has
    no row and one has 200 (13 tiles of 16, 2 of 128): no assignment is
    dropped, the grouped product (the kernel of ops/grouped_experts.py,
    interpreted) equals running every held expert on every token, and the
    block (tiles of 128) equals the reference."""
    c = dataclasses.replace(TINY, n_experts_held=held,
                            first_expert_held=first)
    x, lp = _routed(c, _share(_mlp_of(params_of(TINY)), first, held), 200,
                    routing)
    hf = hybrid.rms_norm(x, lp["norm"], c.rms_eps)
    idx, w = hybrid.route(hf, lp["router"], c)
    probs = hybrid.held_weights(idx, w, c)
    counts = np.asarray(jnp.sum(probs > 0, axis=0))
    if routing == "uneven":
        assert counts[0] == 0 and counts[1] == 200 > tile
    grouped, tiles = jax.jit(lambda: hybrid.experts_grouped(
        hf, idx - first, w, lp, held, tile=tile))()
    assert list(np.asarray(tiles)) == [np.sum(-(-counts // tile)),
                                       counts.sum()]
    np.testing.assert_allclose(grouped, hybrid.experts_dense(hf, probs, lp),
                               atol=2e-5)
    got, counted = hybrid.moe_block(x[None], lp, c)      # the grouped path
    np.testing.assert_allclose(got[0], ref.expert_mlp(x, lp, sizes_of(c)),
                               atol=5e-5)
    assert list(np.asarray(counted)) == [
        0, 0, 0, int(np.sum(-(-counts // hybrid.GROUP_TILE))), counts.sum()]


# How far the grouped product may stand from ``experts_dense`` on the same
# int8 tree. float32 rows: the order of the sums (the form before PR 39 read
# 4.5e-8 / 2.2e-8 / 2.2e-8 at all / a quarter / an eighth held; results of
# size 0.45 / 0.31 / 0.24). bfloat16 rows: both forms round each expert's
# result to bfloat16 and differ in WHERE the gate is rounded — the dense
# form and the loops before PR 43 wherever XLA ends a fusion (3.443e-3 /
# 3.080e-3 / 2.226e-3 then), the kernel once, after ``act(gate) * up`` in
# float32 (3.885e-3 / 2.543e-3 / 2.902e-3). Against the dense form on
# float32 rows the kernel stands 3.28e-3 / 2.78e-3 / 3.11e-3 and the
# bfloat16 dense form 4.27e-3 / 2.78e-3 / 2.22e-3: one rounding of a
# result of that size is 2e-3.
INT8_DISTANCE = {(jnp.float32, 16): 1e-7, (jnp.float32, 4): 1e-7,
                 (jnp.float32, 2): 1e-7, (jnp.bfloat16, 16): 3.89e-3,
                 (jnp.bfloat16, 4): 3.09e-3, (jnp.bfloat16, 2): 2.91e-3}


@pytest.mark.parametrize("tile", [16, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("first, held", [(0, 16), (8, 4), (4, 2)],
                         ids=["all", "quarter", "eighth"])
def test_rows_quantised_once_stand_no_further_from_dense(first, held, dtype,
                                                         tile):
    """int8 weights: the grouped product quantises its rows ONCE, outside
    the kernel, and every tile gathers int8 rows and their scales; per-row
    quantisation commutes with a gather, so it stands from the dense form
    no further than the form that quantised in every tile did."""
    c = dataclasses.replace(TINY, n_experts_held=held,
                            first_expert_held=first)
    lp = _share(_mlp_of(params_of(TINY, dtype, "int8")), first, held)
    assert is_quantized(lp["wg"])
    x = jax.random.normal(jax.random.PRNGKey(9), (200, c.d_model)
                          ).astype(dtype)
    idx, w = hybrid.route(x.astype(jnp.float32), lp["router"], c)
    dense = hybrid.experts_dense(x, hybrid.held_weights(idx, w, c), lp)
    grouped, _ = hybrid.experts_grouped(x, idx - first, w, lp, held,
                                        tile=tile)
    assert float(jnp.abs(grouped - dense).max()) <= INT8_DISTANCE[dtype, held]
