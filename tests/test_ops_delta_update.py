"""The delta rule's one-token update as a kernel (ops/delta_update.py),
interpreted at toy widths, against the plain form it stands in for
(``hybrid.delta_step``) and the recurrence (``hybrid.kda_recurrent``): a
decay a channel and one a head through the same body, rows that are not
kept and layers that are not addressed bit for bit as they were, key heads
repeated under the value heads."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.ops import delta_update as du

TOL = 1e-5
DECAYS = pytest.mark.parametrize("one", [False, True],
                                 ids=["a-channel", "a-head"])


@partial(jax.jit, static_argnames=("rows", "t", "hk", "hv", "dk", "dv", "one",
                                   "seed"))
def inputs(rows=3, t=8, hk=4, hv=4, dk=16, dv=16, one=False, seed=0):
    """q, k [rows,t,hv,dk] (``hk`` key heads repeated under ``hv`` value
    heads), v [rows,t,hv,dv], log_a [rows,t,hv,dk or 1] from fast
    (e^-11 a token) to slow (0.9999), beta [rows,t,hv], s0."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))
    spread = lambda a: jnp.repeat(a, hv // hk, axis=2)
    q = spread(unit(jax.random.normal(keys[0], (rows, t, hk, dk)))) * dk ** -.5
    k = spread(unit(jax.random.normal(keys[1], (rows, t, hk, dk))))
    v = jax.random.normal(keys[2], (rows, t, hv, dv))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (rows, t, hv)))
    log_a = -jnp.exp(jax.random.uniform(
        keys[4], (rows, t, hv, 1 if one else dk), minval=np.log(1e-4),
        maxval=np.log(11.0)))
    s0 = jax.random.normal(keys[5], (rows, hv, dk, dv))
    return q, k, v, log_a, beta, s0


def at_token(i, *xs):
    return tuple(x[:, i] for x in xs)


# Each a program, not an op at a time (every op would compile for itself).
update = jax.jit(du.delta_update)
plain_step = jax.jit(hybrid.delta_step)


@DECAYS
@pytest.mark.parametrize("rows, hv, dk, dv", [
    (3, 4, 16, 16), (1, 2, 8, 32), (5, 6, 16, 8)],
    ids=["square", "one-row", "odd"])
def test_one_token_is_the_plain_forms(one, rows, hv, dk, dv):
    q, k, v, log_a, beta, s0 = inputs(rows, 1, hv, hv, dk, dv, one)
    step = at_token(0, q, k, v, log_a, beta)
    o, stack = update(s0[None], 0, *step, jnp.ones((rows,), bool))
    want_o, want_s = plain_step(*step, s0)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=TOL)
    np.testing.assert_allclose(stack[0], want_s, rtol=0, atol=TOL)


@DECAYS
def test_eight_tokens_chained_are_the_recurrence(one):
    """``kda_decode_update`` — the kernel on a one-layer view, as the
    benchmark's own check calls it — a token at a time."""
    q, k, v, log_a, beta, s0 = inputs(one=one)
    want_o, want_s = jax.jit(hybrid.kda_recurrent)(q, k, v, log_a, beta,
                                                    s0)
    s, outs = s0, []
    for i in range(8):
        o, s = hybrid.kda_decode_update(*at_token(i, q, k, v, log_a, beta), s)
        outs.append(o)
    np.testing.assert_allclose(jnp.stack(outs, 1), want_o, rtol=0, atol=TOL)
    np.testing.assert_allclose(s, want_s, rtol=0, atol=TOL)


@DECAYS
def test_a_row_that_is_not_kept_leaves_bit_identical(one):
    q, k, v, log_a, beta, s0 = inputs(rows=4, t=1, one=one)
    keep = jnp.asarray([True, False, True, False])
    step = at_token(0, q, k, v, log_a, beta)
    _, stack = update(s0[None], 0, *step, keep)
    _, want = plain_step(*step, s0)
    got = np.asarray(stack[0])
    assert np.array_equal(got[1], np.asarray(s0[1]))
    assert np.array_equal(got[3], np.asarray(s0[3]))
    np.testing.assert_allclose(got[::2], want[::2], rtol=0, atol=TOL)
    assert not np.array_equal(got[0], np.asarray(s0[0]))


@pytest.mark.parametrize("at", [0, 1, 2])
def test_the_layers_not_addressed_leave_bit_identical(at):
    """A stack of three: the kernel writes layer ``at`` (a traced index,
    as a scan hands it) and no other."""
    q, k, v, log_a, beta, s0 = inputs(t=3)
    stack0 = jnp.stack([s0, s0 * 2.0, s0 - 1.0])
    step = at_token(at, q, k, v, log_a, beta)
    _, stack = update(stack0, jnp.int32(at), *step, jnp.ones((3,), bool))
    _, want = plain_step(*step, stack0[at])
    for layer in range(3):
        if layer == at:
            np.testing.assert_allclose(stack[layer], want, rtol=0, atol=TOL)
        else:
            assert np.array_equal(np.asarray(stack[layer]),
                                  np.asarray(stack0[layer]))


@DECAYS
def test_key_heads_repeated_under_the_value_heads(one):
    """32 key heads under 64 value heads (here 2 under 4): o and s agree
    with the plain form."""
    q, k, v, log_a, beta, s0 = inputs(t=1, hk=2, hv=4, one=one)
    assert np.array_equal(np.asarray(k[:, :, 0]), np.asarray(k[:, :, 1]))
    step = at_token(0, q, k, v, log_a, beta)
    o, s = hybrid.kda_decode_update(*step, s0)
    want_o, want_s = plain_step(*step, s0)
    np.testing.assert_allclose(o, want_o, rtol=0, atol=TOL)
    np.testing.assert_allclose(s, want_s, rtol=0, atol=TOL)


@pytest.mark.parametrize("H, dk, dv, heads, chip", [
    (64, 128, 128, 16, True),       # both linear cells
    (4, 16, 16, 4, False),          # a toy preset: interpreted on a chip too
    (6, 16, 8, 6, False),
    (8, 64, 128, 8, True),
    (12, 128, 128, 12, True),       # not whole sublanes, but all the heads
    (48, 128, 128, 16, True),
], ids=["cells", "toy", "odd", "half", "all-heads", "48"])
def test_a_steps_heads_and_what_the_chips_compiler_takes(H, dk, dv, heads,
                                                         chip):
    assert du.heads_per_step(H, dk, dv) == heads
    assert du.mosaic_can_take(H, dk, dv) is chip
