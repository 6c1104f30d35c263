"""The sharding rules and the mesh builder (parallel/), without an engine.

The rules are held to a table by leaf name at PUBLISHED widths (shapes
only: ``jax.eval_shape``), for every family that takes a mesh, plain and
quantized; the cache rules to the head counts that do and do not divide
the ``model`` axis; the mesh builder to one message per way of getting a
mesh wrong. The forward tests then run the tiny presets under those rules
against the same weights on one device."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llmapigateway_tpu.models import PRESETS, forward_fn, init_fn, llama
from llmapigateway_tpu.models.quant import quantize_tree
from llmapigateway_tpu.parallel.mesh import MeshSpec, build_mesh
from llmapigateway_tpu.parallel.sharding import (
    batch_sharding, cache_sharding, paged_cache_sharding, param_shardings,
    spec_for_param)
from tests.conftest import cpu_devices

# leaf (without a ".q"/".s" suffix) -> the dim of the WEIGHT that rides
# `model`, counted from the back; None: every chip holds it whole.
COLUMN, ROW, VOCAB = -1, -2, 0
MODEL_DIM = {
    "embed": VOCAB, "lm_head": VOCAB, "lm_head_q8": VOCAB,
    "final_norm": None, "layers.attn_norm": None, "layers.mlp_norm": None,
    "layers.router": None,
    "layers.wq": COLUMN, "layers.wk": COLUMN, "layers.wv": COLUMN,
    "layers.wg": COLUMN, "layers.wu": COLUMN,
    "layers.bq": COLUMN, "layers.bk": COLUMN, "layers.bv": COLUMN,
    "layers.wo": ROW, "layers.wd": ROW,
}
# The dim a matmul contracts over (a scale plane is its weight without it).
CONTRACTED = {"lm_head": 1, "lm_head_q8": 1}          # layers: -2
EXPERT_LEAVES = ("layers.wg", "layers.wu", "layers.wd")
WHOLE_AT_MOST = 4 << 20      # bytes: nothing larger is left on every chip


def _mesh(sizes: dict):
    n = int(np.prod(list(sizes.values())))
    return build_mesh(MeshSpec(sizes=sizes, auto_model=False),
                      cpu_devices()[:n])


def _leaves(tree, prefix=""):
    for key, val in tree.items():
        if isinstance(val, dict):
            yield from _leaves(val, f"{prefix}{key}.")
        else:
            yield f"{prefix}{key}", val


def _abstract_params(preset: str, quant: str):
    c = PRESETS[preset]

    def build(key):
        params = init_fn(c)(c, key, dtype=jnp.bfloat16)
        return quantize_tree(params, c, quant) if quant else params
    return c, jax.eval_shape(build, jax.random.PRNGKey(0))


def _expected(path: str, shape: tuple, mesh, moe: bool) -> tuple:
    base, _, kind = path.rpartition(".")
    if kind not in ("q", "s"):
        base, kind = path, ""
    ndim = len(shape)
    want = [None] * ndim
    dim = MODEL_DIM[base]
    if kind == "s" and dim == ROW:      # the scale has lost the sharded dim
        dim = None                      # (column: still last; vocab: first)
    if dim is not None and mesh.shape["model"] > 1:
        assert shape[dim] % mesh.shape["model"] == 0, (path, shape)
        want[dim % ndim] = "model"
    if moe and base in EXPERT_LEAVES and mesh.shape["expert"] > 1:
        want[1] = "expert"
    return tuple(want)


@pytest.mark.parametrize("quant", ["", "int8", "int4"])
@pytest.mark.parametrize("preset,mesh_sizes", [
    ("llama-3-8b", {"model": 4}), ("mistral-7b", {"model": 4}),
    ("gemma-2b", {"model": 4}), ("mixtral-8x7b", {"model": 4}),
    ("mixtral-8x7b", {"expert": 2, "model": 2}),
    ("qwen2-0.5b", {"model": 2})],
    ids=lambda v: v if isinstance(v, str) else "x".join(
        f"{k}{n}" for k, n in v.items()))
def test_param_rules_by_leaf_name(preset, mesh_sizes, quant):
    """Every leaf of the family's tree, by name: which dim rides `model`
    (and `expert`), that a scale plane follows its weight, and that
    nothing over 4 MiB is left whole on every chip."""
    mesh = _mesh(mesh_sizes)
    c, tree = _abstract_params(preset, quant)
    leaves = dict(_leaves(tree))
    assert any(p.endswith(".q") for p in leaves) == bool(quant)
    for path, leaf in leaves.items():
        spec = spec_for_param(path, leaf.shape, mesh).spec
        got = tuple(spec) + (None,) * (len(leaf.shape) - len(spec))
        assert got == _expected(path, leaf.shape, mesh, c.n_experts > 0), \
            (path, leaf.shape, got)
        if all(ax is None for ax in got):
            size = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            assert size <= WHOLE_AT_MOST, (path, leaf.shape, size)
    for path in leaves:
        if not path.endswith(".q"):
            continue
        base = path[:-2]
        q = list(spec_for_param(path, leaves[path].shape, mesh).spec)
        q += [None] * (len(leaves[path].shape) - len(q))
        del q[CONTRACTED.get(base, -2)]
        s_leaf = leaves[base + ".s"]
        s = list(spec_for_param(base + ".s", s_leaf.shape, mesh).spec)
        s += [None] * (len(s_leaf.shape) - len(s))
        assert s == q, (base, s, q)
    # The same rules, as the tree the engine places its parameters by.
    placed = dict(_leaves(param_shardings(tree, mesh)))
    assert {p: s.spec for p, s in placed.items()} == {
        p: spec_for_param(p, leaf.shape, mesh).spec
        for p, leaf in leaves.items()}


@pytest.mark.parametrize("path,shape,mesh_sizes", [
    ("embed", (32001, 4096), {"model": 4}),            # odd vocabulary
    ("layers.wq", (2, 64, 6), {"model": 4}),           # 6 columns on 4
    ("layers.wo.q", (2, 6, 64), {"model": 4}),
    ("layers.wg", (2, 3, 64, 128), {"expert": 2, "model": 2})])
def test_a_dim_the_axis_does_not_divide_stays_whole(path, shape, mesh_sizes):
    """Every rule degrades to replication of THAT dim; the others keep
    theirs (the last case: 3 experts on 2 chips, columns still split)."""
    mesh = _mesh(mesh_sizes)
    spec = tuple(spec_for_param(path, shape, mesh).spec)
    for dim, ax in enumerate(spec):
        assert ax is None or shape[dim] % mesh.shape[ax] == 0
    if len(shape) == 4:
        assert spec == (None, None, None, "model")
    else:
        assert all(ax is None for ax in spec), spec


@pytest.mark.parametrize("kind,mesh_sizes,kv_heads,batch,want", [
    ("dense", {"model": 4}, 8, 4, (None, None, "model", None, None)),
    # GQA with fewer KV heads than chips: every chip holds every head.
    ("dense", {"model": 4}, 2, 4, (None, None, None, None, None)),
    ("dense", {"model": 4}, 1, 4, (None, None, None, None, None)),   # MQA
    ("dense", {"data": 2, "model": 2}, 2, 4,
     (None, "data", "model", None, None)),
    ("dense", {"data": 2, "model": 2}, 2, 3,
     (None, None, "model", None, None)),             # 3 slots on 2
    # The pool's page dim is global (the table indexes it): never split.
    ("paged", {"model": 4}, 8, 0, (None, None, "model", None, None)),
    ("paged", {"model": 4}, 2, 0, (None, None, None, None, None)),
    ("paged", {"data": 2, "model": 2}, 2, 0,
     (None, None, "model", None, None)),
    ("batch", {"data": 2, "model": 2}, 0, 4, ("data",)),
    ("batch", {"data": 2, "model": 2}, 0, 3, (None,)),
])
def test_cache_and_batch_rules(kind, mesh_sizes, kv_heads, batch, want):
    mesh = _mesh(mesh_sizes)
    if kind == "dense":
        got = cache_sharding(mesh, kv_heads, batch)
    elif kind == "paged":
        got = paged_cache_sharding(mesh, kv_heads)
    else:
        got = batch_sharding(mesh, batch)
    assert tuple(got.spec) == want


@pytest.mark.parametrize("sizes,auto_model,n_devices,want", [
    ({}, True, 1, {"data": 1, "expert": 1, "model": 1}),
    ({}, True, 8, {"data": 1, "expert": 1, "model": 8}),
    ({"data": 2}, True, 8, {"data": 2, "expert": 1, "model": 4}),
    ({"expert": 2, "model": 2}, True, 4,
     {"data": 1, "expert": 2, "model": 2}),
    ({"pipe": 2, "model": 2}, True, 4,
     "unknown mesh axis 'pipe': the axes are data, expert, model"),
    ({"seq": 4}, True, 4,
     "unknown mesh axis 'seq': the axes are data, expert, model"),
    ({"tensor": 4}, True, 4,
     "unknown mesh axis 'tensor': the axes are data, expert, model"),
    ({"model": 3}, True, 8,
     "mesh sizes {'model': 3} (product 3) do not match 8 devices"),
    ({"data": 3}, True, 8,
     "mesh sizes {'data': 3} (product 3) do not match 8 devices"),
    ({"data": 2}, False, 8,
     "mesh sizes {'data': 2} (product 2) do not match 8 devices"),
    ({"model": 0}, True, 4, "mesh axis model must be positive, got 0"),
])
def test_build_mesh(sizes, auto_model, n_devices, want):
    """What a mesh resolves to on the devices it is given — `model` takes
    what the named axes leave, unless told not to — and the message for
    each way of getting one wrong."""
    spec = MeshSpec(sizes=sizes, auto_model=auto_model)
    devices = cpu_devices()[:n_devices]
    if isinstance(want, str):
        with pytest.raises(ValueError, match=re.escape(want)):
            build_mesh(spec, devices)
        return
    mesh = build_mesh(spec, devices)
    assert dict(mesh.shape) == want
    assert mesh.axis_names == ("data", "expert", "model")
    assert mesh.devices.size == n_devices


@pytest.mark.parametrize("preset", ["tiny-test", "tiny-qwen-test",
                                    "tiny-gemma-test", "tiny-mistral-test",
                                    "tiny-moe-test"])
def test_forward_under_the_rules_matches_one_device(preset):
    """A prefill and a decode step with parameters and cache placed by
    the rules on `model` = 4 against the same arrays on one device: the
    llama block, QKV biases with a tied head (qwen2), one KV head on four
    chips with a scaled tied embedding (gemma), a window (mistral) and
    experts whose width is split with no `expert` axis (mixtral)."""
    c = PRESETS[preset]
    mesh = _mesh({"model": 4})
    params = init_fn(c)(c, jax.random.PRNGKey(0), dtype=jnp.float32)
    B, T, S = 2, 12, 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                                c.vocab_size)
    fwd = jax.jit(forward_fn(c), static_argnames=("config",))

    def run(params, cache):
        logits, cache = fwd(params, c, tokens, jnp.zeros((B,), jnp.int32),
                            cache)
        step, cache = fwd(params, c, tokens[:, :1],
                          jnp.full((B,), T, jnp.int32), cache)
        return np.asarray(logits), np.asarray(step), cache
    ref_logits, ref_step, _ = run(params, llama.KVCache.create(
        c, B, S, jnp.float32))
    csh = cache_sharding(mesh, c.n_kv_heads, B)
    assert csh.spec[2] == ("model" if c.n_kv_heads % 4 == 0 else None)
    cache = jax.tree.map(lambda a: jax.device_put(a, csh),
                         llama.KVCache.create(c, B, S, jnp.float32))
    sharded = jax.tree.map(jax.device_put, params,
                           param_shardings(params, mesh))
    assert sharded["layers"]["wq"].sharding.spec[2] == "model"
    logits, step, _ = run(sharded, cache)
    np.testing.assert_allclose(logits, ref_logits, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(step, ref_step, rtol=2e-4, atol=2e-4)
