"""Chip probe of the delta rule's one-token update (ops/delta_update.py) at
the two linear cells' geometry — 32 rows x 64 heads of 128 x 128 float32 a
layer: parity with the plain form (``hybrid.delta_step``) at a decay a
channel and at one a head, then the time of one layer's update inside a
scan that carries the stacked block, the kernel (at several sizes of a grid
step's block) beside the plain form with its select, and each one's share
of the chip's memory bandwidth over the state's one read and one write.

    chiprun -- python3 tools/probe_delta_update.py

Results on stdout and in chiprun_out/probe_delta_update.json. Fails without
a TPU.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.roofline import peaks_for                    # noqa: E402
from llmapigateway_tpu.models import hybrid                 # noqa: E402
from llmapigateway_tpu.ops import delta_update as du        # noqa: E402

P, B, H, DK = 2, 32, 64, 128
STEPS = 24


def inputs(one: bool, seed: int = 0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True))
    q = unit(jax.random.normal(ks[0], (B, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (B, H, DK)))
    v = jax.random.normal(ks[2], (B, H, DK))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H)))
    log_a = -jnp.exp(jax.random.uniform(
        ks[4], (B, H, 1 if one else DK), minval=np.log(1e-4),
        maxval=np.log(11.0)))
    return q, k, v, log_a, beta, jax.random.normal(ks[5], (P, B, H, DK, DK))


def burst(update):
    """``STEPS`` updates of layer 1 in one program, the block carried."""
    @jax.jit
    def run(s, q, k, v, log_a, beta, keep):
        def body(s, _):
            o, s = update(s, q, k, v, log_a, beta, keep)
            return s, o[0, 0, 0]
        return jax.lax.scan(body, s, None, length=STEPS)
    return jax.jit(run, donate_argnums=0)


def plain(s, q, k, v, log_a, beta, keep):
    o, new = hybrid.delta_step(q, k, v, log_a, beta, s[1])
    new = jnp.where(keep[:, None, None, None], new, s[1])
    return o, s.at[1].set(new)


def kernel(s, q, k, v, log_a, beta, keep):
    return du.delta_update(s, 1, q, k, v, log_a, beta, keep)


def timed(run, s, *args) -> tuple[float, jax.Array]:
    s, _ = run(s, *args)
    jax.block_until_ready(s)
    t0 = time.perf_counter()
    s, _ = run(s, *args)
    jax.block_until_ready(s)
    return (time.perf_counter() - t0) / STEPS * 1e3, s


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    bw = peaks_for(dev.device_kind)["hbm_bytes_s"]
    moved = 2 * B * H * DK * DK * 4
    out = {"device": dev.device_kind, "bytes_a_layer": moved, "rows": []}
    keep = jnp.ones((B,), bool).at[3].set(False)
    for one in (False, True):
        q, k, v, log_a, beta, s = inputs(one)
        o, new = jax.jit(kernel)(s, q, k, v, log_a, beta, keep)
        o_p, new_p = jax.jit(plain)(s, q, k, v, log_a, beta, keep)
        row = {"decay": "a head" if one else "a channel",
               "o_err": float(jnp.max(jnp.abs(o - o_p))),
               "s_err": float(jnp.max(jnp.abs(new - new_p))),
               "idle_row_same": bool(jnp.array_equal(new[1, 3], s[1, 3])),
               "other_layer_same": bool(jnp.array_equal(new[0], s[0]))}
        for name, fn, step_bytes in (
                ("plain", plain, None), ("kernel", kernel, 2 ** 19),
                ("kernel", kernel, 2 ** 20), ("kernel", kernel, 2 ** 21),
                ("kernel", kernel, 2 ** 22)):
            if step_bytes:
                du.STEP_BYTES = step_bytes
            ms, s = timed(burst(fn), s, q, k, v, log_a, beta, keep)
            row[name + (f"_{step_bytes >> 10}k" if step_bytes else "")] = {
                "ms": round(ms, 4),
                "hbm_share_pct": round(100 * moved / (ms / 1e3) / bw, 1)}
        du.STEP_BYTES = 2 ** 20
        out["rows"].append(row)
        print(json.dumps(row), flush=True)
    path = Path("chiprun_out/probe_delta_update.json")
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
