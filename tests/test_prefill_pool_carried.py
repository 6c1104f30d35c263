"""The page pool as the prefill layer scan's CARRY (PR 34): with a provider
that has ``.prefill_at`` the forwards write a chunk's rows into the stacked
pool in place and attend it at the layer's index. Interpret mode, tiny
presets: (a) ``llama.forward`` / ``hybrid.forward`` give the sliced path's
logits bit for bit and its pool off trash page 0, (b) a mesh, the reference
path and the speculative ``.verify`` keep the sliced pool. Engines on the
carried and on the sliced pool serving the same greedy tokens are
tests/test_engine_pool_carried.py (a file of their own, so that the two
run on a worker each); the kernels themselves,
tests/test_ops_paged_in_place.py and tests/test_ops_paged_chunk_write.py;
the compiled ``prefill_step``, tests/test_aot_tpu_programs.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.models import PRESETS, forward_fn, init_fn
from llmapigateway_tpu.ops import paged_attention as pa


# ---------------------------------------------------------------------------
# (a) the forwards
# ---------------------------------------------------------------------------

def _sliced(provider):
    """The same provider without ``.prefill_at``: the forwards then hand
    the scan the pool's per-layer slices (``paged_insert_kv`` and the
    kernel on a slice), as under a mesh."""
    del provider.prefill_at
    return provider


FORWARDS = {
    # preset -> (rows of a prefill call, chunk, window)
    "tiny-mistral-test": (1, 16, 16),
    "tiny-hybrid-test": (2, 32, 0),
}


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["f32kv", "int8kv"])
@pytest.mark.parametrize("preset", list(FORWARDS))
def test_the_forwards_on_the_carried_pool_are_the_sliced_forwards(
        preset, kv_quant):
    """``llama.forward`` / ``hybrid.forward`` chunk by chunk (a full chunk,
    then a ragged bucket from where it ended) with the in-place provider
    against the same provider stripped of ``.prefill_at``: the logits
    equal bit for bit, the pools equal off trash page 0 — and the
    in-place leg did take the carried path."""
    rows, chunk, window = FORWARDS[preset]
    config = PRESETS[preset]
    params = init_fn(config)(config, jax.random.PRNGKey(0), jnp.float32)
    page, width = 16, 4
    table = jnp.arange(1, 1 + rows * width, dtype=jnp.int32
                       ).reshape(rows, width)
    forward = forward_fn(config)
    extra = {}
    if config.n_lin_layers:
        from llmapigateway_tpu.models.hybrid import HybridCache
        extra = {"slots": jnp.arange(rows, dtype=jnp.int32)}

    def fresh():
        if config.n_lin_layers:
            return HybridCache.create(config, 1 + rows * width, page, rows,
                                      jnp.float32, kv_quant)
        return pa.PagedKVCache.create(config, 1 + rows * width, page,
                                      jnp.float32, kv_quant)

    taken = []

    def provider(in_place: bool):
        fn = pa.make_paged_attention_fn(table, max_seq=page * width,
                                        impl="pallas", interpret=True,
                                        window=window)
        if not in_place:
            return _sliced(fn)
        at = fn.prefill_at
        fn.prefill_at = lambda *a, **kw: taken.append(1) or at(*a, **kw)
        return fn

    rng = np.random.default_rng(3)
    served = {}
    for in_place in (True, False):
        cache, start, outs = fresh(), jnp.zeros((rows,), jnp.int32), []
        for T in (chunk, 8):
            tokens = jnp.asarray(rng.integers(1, 500, (rows, T)), jnp.int32)
            logits, cache = jax.jit(
                lambda c, t, s: forward(params, config, t, s, c,
                                        attention_fn=provider(in_place),
                                        **extra))(cache, tokens, start)
            outs.append(np.asarray(logits))
            start = start + T
        served[in_place] = (outs, cache)
        rng = np.random.default_rng(3)
    assert taken
    for a, b in zip(*(served[k][0] for k in (True, False))):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(*(jax.tree.leaves((served[k][1].k, served[k][1].v))
                      for k in (True, False))):
        np.testing.assert_array_equal(np.asarray(a[:, 1:]),
                                      np.asarray(b[:, 1:]))


# ---------------------------------------------------------------------------
# (b) who keeps the sliced pool
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("who", ["a-mesh", "the-reference-path",
                                 "the-speculative-provider"])
def test_who_keeps_the_sliced_pool(who):
    """A mesh of more than one device and ``attention="reference"`` build
    no ``.prefill_at`` (nor ``.decode_at``); the speculative provider's
    ``.verify`` takes every T > 1 call, so ``llama.forward`` never takes
    its ``.prefill_at``."""
    from llmapigateway_tpu.parallel.mesh import build_mesh
    table = jnp.arange(1, 5, dtype=jnp.int32).reshape(1, 4)
    if who == "the-speculative-provider":
        fn = pa.make_paged_attention_fn(table, max_seq=64, impl="pallas",
                                        interpret=True, spec=True)
        assert hasattr(fn, "verify")

        def refuse(*a, **kw):
            raise AssertionError("the verify path took .prefill_at")
        fn.prefill_at = refuse
        config = PRESETS["tiny-test"]
        params = init_fn(config)(config, jax.random.PRNGKey(0), jnp.float32)
        cache = pa.PagedKVCache.create(config, 5, 16, jnp.float32)
        logits, _ = forward_fn(config)(
            params, config, jnp.ones((1, 4), jnp.int32),
            jnp.zeros((1,), jnp.int32), cache, attention_fn=fn)
        assert logits.shape[:2] == (1, 4)
        return
    mesh = build_mesh({"model": 2}, devices=jax.devices("cpu")[:2]) \
        if who == "a-mesh" else None
    impl = "pallas" if who == "a-mesh" else "reference"
    assert not pa.pool_in_place(impl, mesh)
    fn = pa.make_paged_attention_fn(table, max_seq=64, impl=impl, mesh=mesh)
    assert not hasattr(fn, "prefill_at") and not hasattr(fn, "decode_at")
