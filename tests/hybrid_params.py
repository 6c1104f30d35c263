"""The hybrid family's seeded random weights, drawn once a process: the
model files' cases (tests/test_model_hybrid.py, _hybrid_experts,
_smallthinker, _mistral4, _cohere2, tests/test_ops_grouped_experts_edges.py)
read the same few trees, and a draw is a compiled program of its own (7 s
at the tiny presets). The trees are shared, so a test that changes one
builds a new dict (``{**params, ...}``), never assigns into it."""
import functools

import jax
import jax.numpy as jnp

from llmapigateway_tpu.models import hybrid


@functools.cache
def params_of(c, dtype=jnp.float32, quant="", seed=1):
    return jax.jit(lambda k: hybrid.init_params(c, k, dtype, quant))(
        jax.random.PRNGKey(seed))
