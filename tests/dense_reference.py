"""The served path's numerical oracle: greedy decoding by the family's plain
forward (``models.forward_fn``) over a dense ``llama.KVCache`` — the whole
prompt in one call, then one token a step. No engine, no pages, no
scheduler: what an engine serves from its page pool must equal it token
for token."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from llmapigateway_tpu.models import forward_fn, llama


@partial(jax.jit, static_argnums=1)
def _forward(params, config, tokens, lengths, cache):
    return forward_fn(config)(params, config, tokens, lengths, cache)


def greedy_tokens(engine, prompt_ids: list[int], max_tokens: int) -> list[int]:
    """What ``engine`` must generate for ``prompt_ids`` at temperature 0:
    its own weights, cache precision and EOS ids, the dense forward."""
    c = engine.model_cfg
    params = jax.device_put(jax.device_get(engine.params),
                            jax.devices("cpu")[0])
    cache = llama.KVCache.create(c, 1, engine.S, engine.dtype,
                                 kv_quant=engine.kv_quant)
    tokens, n, out = np.asarray([prompt_ids], np.int32), 0, []
    while len(out) < max_tokens:
        logits, cache = _forward(params, c, jnp.asarray(tokens),
                                 jnp.asarray([n], jnp.int32), cache)
        n += tokens.shape[1]
        out.append(int(jnp.argmax(logits[0, -1])))
        if (out[-1] in engine.tokenizer.eos_ids or len(prompt_ids)
                + len(out) + 1 >= engine.S - engine.spec_k):
            break                       # where the engine finishes too
        tokens = np.asarray([[out[-1]]], np.int32)
    return out
