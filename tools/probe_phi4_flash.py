"""Chip probe of the cross-decoder family's two new costs
(models/sambay.py) at the served geometry of ``phi4-mini-flash-3.8b`` (32
slots of 32,768, page 256; 5,120 channels x 16 state numbers; 40 folded
query heads over 10 K/V heads of 128), each ALONE on the device:

* ``scan``: ONE layer's recurrence over a prefill chunk
  (``sambay.selective_scan``, 512 tokens) at 1, 2 and 4 rows a call and at
  three unrolls of the token loop, beside what the recurrence has to move
  and compute (``reference/phi4_flash.py`` ``ssm_scan_cost``); and ONE
  layer's one-token update of all 32 slots' state (``ssm_step``);
* ``cross``: a decode step's reads of the ONE full-context K/V — the paged
  decode kernel over the global group's pool, once, and EIGHT times in a
  row (what the full layer and the seven cross layers do a step) — at 2k /
  6k / 16k of context a slot, beside the bytes those reads are
  (``cross_decode_cost``) at 819 GB/s.

``chiprun -- python3 tools/probe_phi4_flash.py``; results on stdout and in
chiprun_out/probe_phi4_flash.json. Fails without a TPU."""
from __future__ import annotations

import json
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchmark.reference.phi4_flash import (cross_decode_cost,     # noqa: E402
                                            ssm_scan_cost)
from llmapigateway_tpu.models import sambay                        # noqa: E402
from llmapigateway_tpu.ops.paged_attention import (                # noqa: E402
    paged_decode_attention)
from probe_experts import device_ms, program                       # noqa: E402

SLOTS, S, PAGE, H, KV, DH, E, N, T = 32, 32768, 256, 40, 10, 128, 5120, 16, 512
NP = S // PAGE
READERS = 8
CONTEXTS = (2048, 6144, 16384)
HBM = 819e9


def scan_inputs(rows: int, t: int, key):
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (rows, t, E), jnp.float32)
    delta = jnp.exp(jax.random.uniform(ks[1], (rows, t, E), jnp.float32,
                                       math.log(1e-3), math.log(0.1)))
    b, c = (jax.random.normal(k, (rows, t, N), jnp.float32) for k in ks[2:])
    a = -jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32)[:, None],
                          (N, E))
    return x, delta, b, c, a, jnp.zeros((rows, N, E), jnp.float32)


def probe_scan(out: dict) -> None:
    calls = {}
    for rows in (1, 2, 4):
        for unroll in (1, 16, 64):
            if rows != 2 and unroll != sambay.SCAN_UNROLL:
                continue
            name = f"scan_r{rows}_u{unroll}"
            calls[name] = (program(name, lambda *a, u=unroll:
                                   sambay.selective_scan(*a, unroll=u)),
                           scan_inputs(rows, T, jax.random.PRNGKey(rows)))
    x, delta, b, c, a, h = scan_inputs(SLOTS, 1, jax.random.PRNGKey(9))
    calls["decode_update"] = (
        program("decode_update", lambda h, a, x, d, b, c: sambay.ssm_step(
            h, a, x[:, 0], d[:, 0], b[:, 0], c[:, 0])), (h, a, x, delta, b, c))
    ms, _ = device_ms(calls, n=5)
    ops, moved = ssm_scan_cost(T, 1, E, N)
    out["scan"] = {
        "device_ms": ms, "tokens": T,
        "least_ms_a_row": {"compute_f32_vpu_note": "no published f32 "
                           "vector peak: operations given, not a time",
                           "operations": ops,
                           "memory": round(1e3 * moved / HBM, 4)},
        "state_bytes_a_step_a_layer": 2 * SLOTS * N * E * 4,
        "decode_update_least_ms": round(1e3 * 2 * SLOTS * N * E * 4 / HBM, 4)}


def probe_cross(out: dict) -> None:
    rng = np.random.default_rng(54)
    pages = SLOTS * NP + 1
    table = jnp.asarray(rng.permutation(np.arange(1, pages)).reshape(
        SLOTS, NP).astype(np.int32))
    ks = jax.random.split(jax.random.PRNGKey(54), 5)
    pool_k, pool_v = (jax.random.normal(k, (1, pages, KV, PAGE, DH),
                                        jnp.bfloat16) for k in ks[:2])
    q = jax.random.normal(ks[2], (SLOTS, H, DH), jnp.bfloat16)
    kn, vn = (jax.random.normal(k, (SLOTS, KV, DH), jnp.bfloat16)
              for k in ks[3:])

    def read(times):
        def fn(q, kn, vn, pk, pv, table, stale):
            acc = jnp.zeros((SLOTS, H * DH), jnp.float32)
            for i in range(times):
                # Each read's queries depend on the one before, as a cross
                # layer's depend on the layer below.
                got = paged_decode_attention(
                    (q + acc.reshape(q.shape).astype(q.dtype) * 1e-3), kn, vn,
                    pk, pv, table, stale)
                acc = acc + got.astype(jnp.float32)
            return acc
        return fn
    rows = {}
    for ctx in CONTEXTS:
        stale = jnp.full((SLOTS,), ctx, jnp.int32)
        calls = {f"cross_x{n}_{ctx}": (program(f"cross_x{n}_{ctx}", read(n)),
                                       (q, kn, vn, pool_k, pool_v, table,
                                        stale)) for n in (1, READERS)}
        ms, _ = device_ms(calls, n=5)
        _, nbytes = cross_decode_cost([ctx + 1] * SLOTS, READERS, KV, DH)
        least = 1e3 * nbytes / HBM
        rows[ctx] = {"device_ms": ms, "least_ms_8_reads": round(least, 4),
                     "roofline_pct_8_reads": round(
                         100 * least / ms[f"cross_x{READERS}_{ctx}"], 2)}
    out["cross"] = rows


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("probe_phi4_flash needs a TPU", file=sys.stderr)
        return 1
    out = {"device": dev.device_kind}
    probe_scan(out)
    print(json.dumps({"scan": out["scan"]}), flush=True)
    probe_cross(out)
    print(json.dumps({"cross": out["cross"]}), flush=True)
    path = Path("chiprun_out/probe_phi4_flash.json")
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
