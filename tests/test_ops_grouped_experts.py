"""The grouped expert product's kernel (ops/grouped_experts.py, interpret
mode here) against the dense oracle — every held expert on every token —
at the three expert presets' tiny shapes. Its edges (an empty call, plain
matrices, width blocks, slices, the packed rows) and what it costs a
program in set-up are tests/test_ops_grouped_experts_edges.py (a file of
their own, so that the two run on a worker each)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.config import get_preset
from llmapigateway_tpu.models.quant import quantize_array

PRESETS = ("tiny-hybrid-test", "tiny-smallthinker-test", "tiny-mistral4-test")
PERIODS = 2


# The cases' set-up is compiled, a function at a time, and drawn once a
# process: run op by op, every new shape pays a compile an operation (2-4 s
# a case beside the kernel's own 2.6 s, PR 45).

@functools.cache
def stack_of(c, held: int, dtype=jnp.float32, quant: bool = True,
             seed: int = 7) -> dict:
    """``held`` experts' matrices of a preset's widths over two periods,
    every period and expert drawn apart."""
    D, F = c.d_model, c.d_ff_expert or c.d_ff

    def mat(key, din, dout):
        a = (jax.random.normal(key, (PERIODS, held, din, dout), jnp.float32)
             / np.sqrt(din)).astype(dtype)
        return quantize_array(a, 2) if quant else a

    @jax.jit
    def draw(keys):
        return {"wg": mat(keys[0], D, F), "wu": mat(keys[1], D, F),
                "wd": mat(keys[2], F, D)}
    return draw(jax.random.split(jax.random.PRNGKey(seed), 3))


@functools.cache
def routing_of(c, rows: int, first: int, held: int, seed: int = 11):
    """(x [rows, D], each token's top-k of ALL the experts numbered from
    ``first``, their weights, the weight of every held expert a token)."""
    @jax.jit
    def draw(keys):
        x = jax.random.normal(keys[0], (rows, c.d_model), jnp.float32)
        top, idx = jax.lax.top_k(
            jax.random.normal(keys[1], (rows, c.n_experts)),
            c.experts_per_token)
        w = jax.nn.softmax(top, axis=-1)
        local = idx - first
        probs = jnp.sum(jnp.where(
            local[:, :, None] == jnp.arange(held), w[:, :, None], 0.0),
            axis=1)
        return x, local, w, probs
    return draw(jax.random.split(jax.random.PRNGKey(seed + rows), 2))


# The dense oracle — every held expert on every token — as one program.
experts_dense = jax.jit(hybrid.experts_dense, static_argnums=(4,))


@pytest.mark.parametrize("rows", [65, 128, 200, 512])
@pytest.mark.parametrize("share", ["all", "quarter"])
@pytest.mark.parametrize("act", ["silu", "relu"])
@pytest.mark.parametrize("preset", PRESETS)
def test_the_kernel_is_the_dense_product(preset, act, share, rows):
    """int8 matrices, float32 rows, the stack read at a period (the even
    row counts at period 0, the others at 1): every expert held, or a
    quarter of them held from the second quarter on, so that three
    assignments of four land nowhere — the grouped product equals every
    held expert on every token, and the counters are the layout's."""
    c = get_preset(preset)
    held = c.n_experts if share == "all" else c.n_experts // 4
    first = 0 if share == "all" else held
    period = rows % 2
    stack = stack_of(c, held)
    x, idx, w, probs = routing_of(c, rows, first, held)
    if share == "quarter":
        assert float(jnp.mean(probs > 0)) < 0.5      # most land nowhere
    got, tiled = hybrid.experts_grouped(x, idx, w, stack, held,
                                        period=jnp.int32(period), act=act)
    want = experts_dense(x, probs, stack, jnp.int32(period), act)
    np.testing.assert_allclose(got, want, atol=2e-6)
    assert float(jnp.abs(want).max()) > 0.05
    counts = np.asarray(jnp.sum(probs > 0, axis=0))
    assert list(np.asarray(tiled)) == [
        np.sum(-(-counts // hybrid.GROUP_TILE)), counts.sum()]
