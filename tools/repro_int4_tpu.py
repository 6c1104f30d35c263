"""On-chip repro + fix-variant matrix for the int4 recursive-jit failure.

BENCH_SELF_r5b (2026-07-31, v5e): every int4 rung died with
``RecursionError: Recursively calling jit`` at the FIRST jitted call
taking S4 (jnp.int4) stacked weights as arguments — arg layout
``{2,1,0:T(64,128)(8,1)}``, committed, 5-axis NamedSharding. CPU (and
AOT TPU lowering) cannot reproduce it: the loop is in runtime dispatch
(layout canonicalization of a sub-byte-dtype argument re-enters jit),
not in lowering, so tests/test_tpu_lowering.py stays green while the
chip fails.

This script isolates WHERE the loop starts and which construction
avoids it. Each variant runs in a SUBPROCESS (a recursion error must
not poison sibling variants) with a hard timeout. Variants:

  v0_current      init jit with NamedSharding out_shardings -> S4 leaf,
                  then a second jit consumes it (the engine's exact
                  shape; expected FAIL — the r5b signature)
  v1_no_outsh     init jit WITHOUT out_shardings (compiler default
                  layout + SingleDeviceSharding), second jit consumes
  v2_host_put     host-side numpy int4 (ml_dtypes) + plain device_put
  v3_put_sharded  host-side numpy int4 + device_put(NamedSharding)
  v4_scan_consume lax.scan over the layer dim (the engine's real
                  access pattern) fed by the v1 construction
  v5_format_pin   consume jit with in_shardings=Format pinning the S4
                  arg to the exact layout the producing jit emitted
                  (reads ``x.format`` at runtime — no hardcoding)

Usage (needs the chip):  python tools/repro_int4_tpu.py [--quick]
(JAX_PLATFORMS=cpu in the environment runs the variants on the CPU as a
smoke of the script itself). The parent never imports jax, so each
variant's subprocess — run one at a time — is the only process that
touches the chip.
Writes a one-line JSON verdict per variant + a summary to stdout.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

TIMEOUT_S = int(os.environ.get("REPRO_TIMEOUT_S", "180"))

COMMON = textwrap.dedent("""
    import os, jax, json, sys
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    L, D, F = 4, 512, 1024          # small but tiled like the real leaves
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("data", "expert", "model"))
    sh3 = NamedSharding(mesh, P(None, None, None))

    def quantize(w):                # per-out-channel int4, engine scheme
        amax = jnp.max(jnp.abs(w), axis=1, keepdims=True)
        s = jnp.maximum(amax, 1e-30) / 7.0
        q = jnp.clip(jnp.round(w / s), -7, 7).astype(jnp.int4)
        return q, jnp.squeeze(s, axis=1)

    def consume(x, q, s):           # s8 x s4 dot, engine's mm() shape
        xq = jnp.clip(jnp.round(x), -127, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(xq, q[0], (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        return (acc.astype(jnp.float32) * s[0]).sum()
""")

VARIANTS = {
    "v0_current": """
    qfn = jax.jit(lambda k: quantize(jax.random.normal(k, (L, D, F))),
                  out_shardings=(sh3, NamedSharding(mesh, P(None, None))))
    q, s = qfn(jax.random.PRNGKey(0))
    jax.block_until_ready(q)
    out = jax.jit(consume)(jnp.ones((8, D)), q, s)
    """,
    "v1_no_outsh": """
    qfn = jax.jit(lambda k: quantize(jax.random.normal(k, (L, D, F))))
    q, s = qfn(jax.random.PRNGKey(0))
    jax.block_until_ready(q)
    out = jax.jit(consume)(jnp.ones((8, D)), q, s)
    """,
    "v2_host_put": """
    from ml_dtypes import int4
    rng = np.random.default_rng(0)
    w = rng.standard_normal((L, D, F), dtype=np.float32)
    amax = np.maximum(np.abs(w).max(axis=1, keepdims=True), 1e-30)
    qh = np.clip(np.rint(w / (amax / 7.0)), -7, 7).astype(int4)
    q = jax.device_put(qh)
    s = jax.device_put((amax / 7.0).squeeze(1))
    out = jax.jit(consume)(jnp.ones((8, D)), q, s)
    """,
    "v3_put_sharded": """
    from ml_dtypes import int4
    rng = np.random.default_rng(0)
    w = rng.standard_normal((L, D, F), dtype=np.float32)
    amax = np.maximum(np.abs(w).max(axis=1, keepdims=True), 1e-30)
    qh = np.clip(np.rint(w / (amax / 7.0)), -7, 7).astype(int4)
    q = jax.device_put(qh, sh3)
    s = jax.device_put((amax / 7.0).squeeze(1),
                       NamedSharding(mesh, P(None, None)))
    out = jax.jit(consume)(jnp.ones((8, D)), q, s)
    """,
    "v4_scan_consume": """
    qfn = jax.jit(lambda k: quantize(jax.random.normal(k, (L, D, F))))
    q, s = qfn(jax.random.PRNGKey(0))
    jax.block_until_ready(q)
    def scan_consume(x, q, s):
        def body(h, qs):
            ql, sl = qs
            xq = jnp.clip(jnp.round(h), -127, 127).astype(jnp.int8)
            acc = jax.lax.dot_general(xq, ql, (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.int32)
            y = acc.astype(jnp.float32) * sl
            return y[:, :x.shape[1]], y.sum()
        h, outs = jax.lax.scan(body, x, (q, s))
        return outs.sum()
    out = jax.jit(scan_consume)(jnp.ones((8, D), jnp.float32), q, s)
    """,
    "v5_format_pin": """
    from jax.experimental.layout import Format
    qfn = jax.jit(lambda k: quantize(jax.random.normal(k, (L, D, F))),
                  out_shardings=(sh3, NamedSharding(mesh, P(None, None))))
    q, s = qfn(jax.random.PRNGKey(0))
    jax.block_until_ready(q)
    cfn = jax.jit(consume, in_shardings=(None, q.format, s.format))
    out = cfn(jnp.ones((8, D)), q, s)
    """,
}

EPILOG = """
print(json.dumps({"ok": True, "layout": str(getattr(q, "format", "?")),
                  "out": float(out)}))
"""


def run_variant(name: str) -> dict:
    code = COMMON + textwrap.dedent(VARIANTS[name]) + EPILOG
    try:
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True,
                           timeout=TIMEOUT_S)
    except subprocess.TimeoutExpired:
        return {"variant": name, "ok": False, "error": "TIMEOUT (hang)"}
    if r.returncode == 0 and r.stdout.strip():
        try:
            out = json.loads(r.stdout.strip().splitlines()[-1])
            out["variant"] = name
            return out
        except json.JSONDecodeError:
            pass
    tail = (r.stderr or r.stdout).strip().splitlines()
    return {"variant": name, "ok": False,
            "error": " / ".join(tail[-3:])[:500], "rc": r.returncode}


def main() -> None:
    quick = "--quick" in sys.argv
    names = list(VARIANTS)
    if quick:                        # v0 (the repro) + the leading fixes
        names = ["v0_current", "v1_no_outsh", "v2_host_put"]
    results = []
    for name in names:
        print(f"[repro_int4] running {name}...", flush=True)
        res = run_variant(name)
        results.append(res)
        print(json.dumps(res), flush=True)
    passing = [r["variant"] for r in results if r.get("ok")]
    print(json.dumps({"summary": {"passing": passing,
                                  "failing": [r["variant"] for r in results
                                              if not r.get("ok")]}}))


if __name__ == "__main__":
    main()
