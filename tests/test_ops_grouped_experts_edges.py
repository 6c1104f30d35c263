"""The grouped expert product's kernel (ops/grouped_experts.py) at its
edges — a call that lands nowhere, matrices that are not quantised, an
expert width in blocks, a call in slices, the packed rows — and what it
costs a program in set-up: one Python trace a row count, one lowered
function a program however many expert layers its period body unrolls.
The kernel against the dense oracle at the presets' shapes, and the drawn
stacks and routings, are tests/test_ops_grouped_experts.py."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.models import hybrid
from llmapigateway_tpu.models.config import get_preset
from llmapigateway_tpu.ops import grouped_experts as ge
from test_ops_grouped_experts import (PRESETS, experts_dense, routing_of,
                                      stack_of)


@pytest.mark.parametrize("quant", [True, False], ids=["int8", "plain"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_an_empty_call_runs_no_tile(dtype, quant):
    """No assignment lands (``counted[0]`` = 0): the kernel's loop runs no
    tile and starts no copy, the result is exact zeros."""
    c = get_preset(PRESETS[0])
    held = 4
    stack = stack_of(c, held, dtype, quant)
    x, _, w, _ = routing_of(c, 96, 0, held)
    idx = jnp.full((96, c.experts_per_token), held + 3, jnp.int32)
    got, tiled = hybrid.experts_grouped(x.astype(dtype), idx, w, stack, held,
                                        period=jnp.int32(1))
    assert list(np.asarray(tiled)) == [0, 0]
    assert got.dtype == jnp.float32 and not np.asarray(got).any()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("blocks", [1, 3])
def test_plain_matrices_and_width_blocks(monkeypatch, dtype, blocks):
    """Matrices that are not quantised (rows packed two to a word in
    bfloat16), and an expert width cut into three blocks of 128 — the
    hidden rows' scale is over the WHOLE width, the down product sums its
    blocks: the same result as one block."""
    c = dataclasses.replace(get_preset(PRESETS[0]), d_model=128,
                            d_ff_expert=384)
    monkeypatch.setattr(ge, "width_blocks", lambda D, F, size, room: blocks)
    for quant in (False, True):
        stack = stack_of(c, 4, dtype, quant)
        x, idx, w, probs = routing_of(c, 200, 0, 4)
        got, _ = hybrid._grouped.__wrapped__(
            x.astype(dtype), idx, w, stack, jnp.int32(1), held=4, tile=32,
            act="silu")
        want = experts_dense(x.astype(dtype), probs, stack,
                                    jnp.int32(1))
        tol = 2e-6 if dtype == jnp.float32 else 0.06
        np.testing.assert_allclose(got, want, atol=tol)


@pytest.mark.parametrize("cap", [64, 128])
def test_a_call_of_more_rows_than_fit_runs_in_slices(monkeypatch, cap):
    """The call's rows and result are resident in the kernel: a call of
    more rows than ``rows_that_fit`` allows runs in slices of that many,
    each with a layout of its own — the same result, the slices' tiles
    and rows summed."""
    c = get_preset(PRESETS[0])
    stack = stack_of(c, 8)
    x, idx, w, probs = routing_of(c, 200, 4, 8)
    whole, counted = hybrid.experts_grouped(x, idx, w, stack, 8,
                                            period=jnp.int32(1))
    monkeypatch.setattr(hybrid, "rows_that_fit", lambda *a: cap)
    sliced, tiled = hybrid.experts_grouped(x, idx, w, stack, 8,
                                           period=jnp.int32(1))
    np.testing.assert_allclose(sliced, whole, atol=1e-6)
    np.testing.assert_allclose(
        whole, experts_dense(x, probs, stack, jnp.int32(1)),
        atol=2e-6)
    assert int(tiled[1]) == int(counted[1]) and tiled[0] >= counted[0]


def test_the_width_is_cut_by_what_fits_twice():
    """``width_blocks`` at the three cells' widths (int8): one block where
    six blocks fit the room, else the fewest whole-lane cuts that do."""
    room = ge.VMEM_LIMIT - ge.FIXED_BYTES
    assert ge.width_blocks(2560, 768, 1, room) == 1      # 6 x 1.9 MB
    assert ge.width_blocks(4096, 1280, 1, room) == 1     # 6 x 5.2 MB
    assert ge.width_blocks(
        4096, 2048, 1, room - ge.resident_bytes(2048, 4096, 1)) == 2
    assert ge.width_blocks(
        4096, 1280, 1, room - ge.resident_bytes(2048, 4096, 1)) == 1
    assert 3000 < ge.rows_that_fit(4096, 2048, 1, 1) < 4096
    assert ge.width_blocks(4096, 2048, 2, room) == 2     # bfloat16: 100 MB
    assert ge.width_blocks(4096, 1280, 1, 16 * 2 ** 20) == 2
    assert ge.width_blocks(4096, 1280, 1, 8 * 2 ** 20) == 5
    assert ge.width_blocks(64, 32, 4, room) == 1         # a toy width
    assert ge.mosaic_can_take(2560, 768, 128)
    assert not ge.mosaic_can_take(64, 32, 16)


@pytest.mark.parametrize("dtype", [jnp.int8, jnp.bfloat16, jnp.float32])
def test_packed_rows_unpack_to_themselves(dtype):
    """``pack_rows`` / ``_unpack_rows``: a row's 32-bit words give back its
    columns in order, negative values and the scale's lanes included."""
    x = (jax.random.normal(jax.random.PRNGKey(2), (9, 256)) * 50).astype(dtype)
    scale = jnp.arange(1.0, 10.0)[:, None] / 7
    words = ge.pack_rows(x, scale)
    assert words.dtype == jnp.int32
    C = 256 * jnp.dtype(dtype).itemsize // 4
    assert words.shape == (9, C + 128)
    back = ge._unpack_rows(words[:, :C], dtype)
    assert back.dtype == dtype and (back == x).all()
    assert (jax.lax.bitcast_convert_type(words[:, C:], jnp.float32)
            == scale).all()


# -- what the kernel costs a program in set-up --------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_one_kernel_a_layer_call_and_rows_rounded_once_outside_it():
    """The traced ``experts_grouped`` at cell 5's shape (1 024 rows of
    2 560, 64 experts of width 768 held of 64, top-6, int8, the stack read
    at a period): ONE Pallas call and no XLA loop around it; outside the
    kernel exactly one rounding to int8, of the rows and their zero row;
    inside it the only rounding is the hidden activation's, a tile at a
    time; the kernel's result is the tokens' float32 result itself, and
    the stacked matrices reach the kernel whole (no slice of a period)."""
    N, D, F, E, k = 1024, 2560, 768, 64, 6
    sds = jax.ShapeDtypeStruct

    def stack(din, dout):
        return {"q": sds((2, E, din, dout), jnp.int8),
                "s": sds((2, E, dout), jnp.float32)}
    lp = {"wg": stack(D, F), "wu": stack(D, F), "wd": stack(F, D)}
    jaxpr = jax.make_jaxpr(
        lambda x, idx, w, lp, period: hybrid.experts_grouped(
            x, idx, w, lp, E, period=period, act="relu"))(
        sds((N, D), jnp.bfloat16), sds((N, k), jnp.int32),
        sds((N, k), jnp.float32), lp, sds((), jnp.int32)).jaxpr
    eqns = list(_eqns(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    inside = list(_eqns(calls[0].params["jaxpr"]))
    outside = [e for e in eqns if not any(e is i for i in inside)]
    assert not [e for e in outside if e.primitive.name == "while"]

    def rounded(some):
        return [e.invars[0].aval.shape for e in some
                if e.primitive.name == "round"]
    assert rounded(outside) == [(N + 1, D)]
    assert rounded(inside) == [(hybrid.GROUP_TILE, F)]
    # Nothing scatters or gathers a row of width D outside the kernel: the
    # layout writes the rows' token ids and weights (two vectors), the
    # kernel brings a tile's rows in by index and adds its results onto
    # the tokens' rows of the float32 result it returns.
    moved = [e for e in outside if "scatter" in e.primitive.name
             or e.primitive.name == "gather"]
    assert not [e for e in moved if D in e.outvars[0].aval.shape]
    assert [e.outvars[0].aval.ndim for e in moved
            if "scatter" in e.primitive.name] == [1, 1]
    assert not [e for e in outside if e.primitive.name == "dynamic_slice"
                and e.invars[0].aval.ndim == 4]
    whole = [v.aval.shape for v in calls[0].invars if v.aval.ndim == 4]
    assert whole.count((2, E, D, F)) == 2 and (2, E, F, D) in whole
    out, = calls[0].outvars
    assert (out.aval.shape, out.aval.dtype) == ((N, D), jnp.float32)


def test_a_program_traces_the_kernel_once_and_lowers_one_function(
        monkeypatch):
    """The set-up guard (PERF.md section 6, PR 43). The tiny hybrid
    preset's prefill forward (a period of FOUR expert layers) lowered at
    two K rungs, 1 and 2 rows of 128 positions, and again at the first:
    the kernel's body is traced once a distinct row count (128, 256) —
    not once a layer, not again for a program of a count already seen —
    and each lowered module holds ONE grouped-product function that its
    four expert layers call."""
    from tests.hybrid_params import params_of
    from tests.test_model_hybrid import TINY, paged
    from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn
    traced = []
    body = ge._kernel
    monkeypatch.setattr(
        ge, "_kernel",
        lambda meta, tile_expert, row_token, *a, **kw: (
            traced.append(row_token.shape[0]),
            body(meta, tile_expert, row_token, *a, **kw))[1])
    hybrid._grouped.clear_cache()
    c = TINY
    params = params_of(c, jnp.bfloat16, "int8")
    texts = []
    for rows in (1, 2, 1):
        cache, table = paged(c, rows, jnp.bfloat16)

        def prefill_step(params, cache, tokens, lengths, table):
            attn = make_paged_attention_fn(table, max_seq=128)
            return hybrid.forward(params, c, tokens, lengths, cache,
                                  attention_fn=attn,
                                  n_valid=jnp.full((rows,), 128))
        texts.append(jax.jit(prefill_step).lower(
            params, cache, jnp.zeros((rows, 128), jnp.int32),
            jnp.zeros((rows,), jnp.int32), table).as_text())
    k, tile = c.experts_per_token, hybrid.GROUP_TILE
    bound = [(-(-n * 128 * k // tile) + c.experts_held) * tile
             for n in (1, 2)]
    assert traced == bound                   # once a row count, in order
    for text in texts:
        assert text.count("func.func private @_grouped(") == 1
        assert text.count("call @_grouped(") == c.layer_period
    hybrid._grouped.clear_cache()
