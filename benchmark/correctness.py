"""What decides ``correct``. Three checks, all outside the window:

(a) the compiled paged decode and prefill kernels at the cell's widths
    against the gather+jnp reference on the same inputs;
(b) the served path — prefill, then decoding through the paged cache and
    the kernels, over HTTP — against the plain float32 reference forward
    the configuration's file names (``reference/``; absent:
    ``reference/forward.py``) on the engine's own weights, on a sample
    drawn as the file's ``"correctness"`` block says (``sampling``);
(c) every response well formed: frames parse, the usage frame equals the
    tokens streamed, ``[DONE]`` arrives, a usage row is written.
"""
from __future__ import annotations

import asyncio
import dataclasses
import sqlite3
import time
from pathlib import Path
from types import ModuleType
from typing import Any

import numpy as np

from .metrics import RequestLog

# (a) Kernel against reference on unit-normal inputs, bf16 storage, fp32
# accumulation (the bound chip_smoke.py has held on v5e since PR 21).
KERNEL_TOL = 3e-2

# (b) With random weights the largest logit changes on rounding, so tokens
# are not compared: at every generated position the reference's logit of
# the token the engine SERVED must lie within LOGIT_GAP_TOL of the
# reference's own maximum. Logits here are ~N(0, 1) over 32,000 ids, so
# the maximum sits ~4 above the mean: a token from a wrong computation
# (stale KV, a wrong page, a dropped layer) misses by ~4, while the engine
# legitimately differs from the float32 reference by what W8A8 adds —
# activations re-quantised to int8 at each of 7 matmuls in 32 layers, int8
# KV, bf16 residuals. Measured on v5e (PR 23, 7 runs x 192 positions of
# Mistral-7B): the served token is the reference's argmax at 82-92% of the
# positions and otherwise at most 0.078 below it. The bound is eight bf16
# steps at the magnitude of the top logits (4-6, where bf16 values are
# 0.031 apart) — the bound chip_smoke.py puts on two builds of one model.
# A wrong computation misses it by an order of magnitude; a coarser
# arithmetic than the configuration states has three times the measured
# worst case to stay inside. The median gap is held to LOGIT_GAP_P50_TOL
# (measured 0.0 in every run): a few rounded positions cannot move it, a
# systematic loss of precision would.
#
# A sparse-expert model is held to the SAME bounds against the
# publication's exact routing; nothing of the program's dispatch is given
# to the reference. The program routes a prefill call of more than
# DISPATCH_EXACT_TOKENS tokens by capacity dispatch, which drops what an
# expert is sent beyond its capacity (with random routers 21% of a
# 1024-token prompt's assignments, v5e x4, PR 23: the served tokens then
# sat up to 0.67 below the reference's maximum and `correct` read false),
# and runs decode steps and smaller calls in the exact dense form. So an
# expert model's sample prompts are DISPATCH_EXACT_TOKENS long and are
# served one at a time: what is compared is the regime in which the
# program claims the publication's mathematics. NOT yet measured on a chip
# in that form (no cell with experts ships in PR 23): whether int8 noise
# flipping a token's second expert stays inside 0.25 there is open.
#
# Since PR 28 that regime is what a configuration's file STATES about its
# program (``"correctness": {"exact_up_to_tokens": 64}``), not what the
# harness concludes from ``n_experts``: a program that routes exactly at
# every length says nothing and is sampled at whole chunks like any other.
LOGIT_GAP_TOL = 0.25
LOGIT_GAP_P50_TOL = 0.05
DISPATCH_EXACT_TOKENS = 64      # models/mixtral.py make_mlp_fn threshold
SAMPLE_REQUESTS = 3
SAMPLE_MAX_TOKENS = 64
SAMPLE_INDEX = 1 << 30          # beyond any trace entry's index


@dataclasses.dataclass(frozen=True)
class Sampling:
    """How a configuration's correctness sample is drawn and judged."""
    exact_up_to_tokens: int | None      # None: whole chunks, served together
    gap_tol: float
    gap_p50_tol: float


def sampling(config_name: str, config: dict[str, Any]) -> Sampling:
    """From the ``"correctness"`` block of a configuration's file.
    ``exact_up_to_tokens``: the program computes the publication's
    mathematics only in prefill calls of at most that many tokens (a
    capacity dispatch that drops tokens beyond it), so the sample's prompts
    are that long and are served one at a time; absent, prompts are whole
    prefill chunks served together. ``logit_gap_tol`` and
    ``logit_gap_p50_tol`` (``{"value": x, "why": "..."}``) may state
    TIGHTER bounds than the defaults, for a configuration served in a
    finer arithmetic than W8A8; a looser one is an error, so that no file
    loosens ``correct``."""
    block = config.get("correctness", {})
    unknown = set(block) - {"exact_up_to_tokens", "logit_gap_tol",
                            "logit_gap_p50_tol"}
    if unknown:
        raise ValueError(f"{config_name}: unknown correctness keys {unknown}")
    tols = []
    for key, default in (("logit_gap_tol", LOGIT_GAP_TOL),
                         ("logit_gap_p50_tol", LOGIT_GAP_P50_TOL)):
        stated = block.get(key)
        if stated is None:
            tols.append(default)
            continue
        if not (isinstance(stated, dict) and stated.get("why")
                and isinstance(stated.get("value"), (int, float))):
            raise ValueError(f"{config_name}: {key} is a value with its why")
        if not 0 <= stated["value"] <= default:
            raise ValueError(
                f"{config_name}: {key} {stated['value']} is looser than the "
                f"benchmark's {default}; a file may only tighten it")
        tols.append(float(stated["value"]))
    exact = block.get("exact_up_to_tokens")
    if exact is not None and not (isinstance(exact, int) and exact >= 8):
        raise ValueError(f"{config_name}: exact_up_to_tokens {exact!r}")
    return Sampling(exact, *tols)


def kernel_parity(*, n_heads: int, n_kv_heads: int, head_dim: int, page: int,
                  window: int, kv_quant: str, interpret: bool = False,
                  pages_per_slot: int = 32, t: int = 256
                  ) -> list[dict[str, Any]]:
    """Paged decode and prefill kernels at the given (per-chip) widths,
    the cell's KV type, against the jnp reference. Slots sit below, at and
    past the window, so dead pages and the window floor are in play.
    (After ``chip_smoke.py`` ``kernel_parity``.)"""
    import jax
    import jax.numpy as jnp
    from llmapigateway_tpu.models.llama import quantize_kv
    from llmapigateway_tpu.ops.paged_attention import make_paged_attention_fn

    b, s = 3, page * pages_per_slot
    n_pages = b * pages_per_slot + 1
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.permutation(np.arange(1, n_pages)).reshape(
        b, pages_per_slot).astype(np.int32))
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    pool = [jax.random.normal(k, (n_pages, n_kv_heads, page, head_dim),
                              jnp.bfloat16) for k in keys[:2]]
    far = window or s // 2
    decode_at = jnp.asarray([page // 2, min(far + 3, s - 2), s - page - 1],
                            jnp.int32)
    prefill_at = jnp.asarray([0, min(far, s - t), s - 2 * page], jnp.int32)
    active = jnp.ones((b,), bool)

    def quantized(side):
        q, sc = quantize_kv(side)
        return {"q": q, "s": sc[:, :, None, :]}

    pk, pv = pool if kv_quant != "int8" else [quantized(p) for p in pool]
    fns = {impl: make_paged_attention_fn(
        table, max_seq=s, impl=impl, window=window,
        interpret=interpret if impl == "pallas" else None)
        for impl in ("pallas", "reference")}
    out = []
    for kind, tt, at in (("decode", 1, decode_at), ("prefill", t, prefill_at)):
        q = jax.random.normal(keys[2], (b, tt, n_heads, head_dim),
                              jnp.bfloat16)
        kn = jax.random.normal(keys[3], (b, tt, n_kv_heads, head_dim),
                               jnp.bfloat16)
        vn = jax.random.normal(keys[4], (b, tt, n_kv_heads, head_dim),
                               jnp.bfloat16)
        got = {}
        for impl, fn in fns.items():
            call = fn.decode if kind == "decode" else (
                lambda *a, _fn=fn: _fn(*a)[0])
            got[impl] = np.asarray(jax.jit(call)(
                q, kn, vn, pk, pv, at, active), np.float32)
        err = float(np.max(np.abs(got["pallas"] - got["reference"])))
        out.append({"kernel": f"paged_{kind}", "kv": kv_quant or "bf16",
                    "window": window, "max_abs_err": err,
                    "ok": bool(np.isfinite(got["pallas"]).all()
                               and err <= KERNEL_TOL)})
    return out


async def served_against_reference(gateway, seed: int, prompt_tokens: int,
                                   how: Sampling, reference: ModuleType,
                                   config: dict[str, Any]) -> dict[str, Any]:
    """``SAMPLE_REQUESTS`` seeded prompts served through HTTP (at once, or
    as ``how`` says), then the configuration's ``reference`` over each
    prompt plus what was served: every generated position checked (the
    first comes off the prefill, the rest through the decode kernel and
    the cache)."""
    from .traffic import Entry, prompt_ids
    tok = gateway.tokenizer
    n = prompt_tokens - tok.template_overhead()
    logs = []
    for i in range(SAMPLE_REQUESTS):
        ids = prompt_ids(Entry(SAMPLE_INDEX + i, None, prompt_tokens,
                               SAMPLE_MAX_TOKENS, None),
                         seed, tok.vocab_size, n, None)
        logs.append((RequestLog(index=SAMPLE_INDEX + i, rid=f"ref{i}",
                                prompt_tokens=prompt_tokens,
                                max_tokens=SAMPLE_MAX_TOKENS),
                     tok.text_of(ids)))
    eng = gateway.engine
    c = reference.sizes(eng.model_cfg, config)
    if how.exact_up_to_tokens:
        # One at a time: a prefill call then holds one row of at most
        # that many tokens, which the program computes exactly.
        for log, content in logs:
            await gateway.stream_chat(log, content)
    else:
        await asyncio.gather(*[gateway.stream_chat(log, content)
                               for log, content in logs])
    gaps, agree, positions = [], 0, 0

    def compare() -> None:
        nonlocal agree, positions
        for log, _ in logs:
            gen = gateway.requests[log.rid]
            served = list(gen.generated)
            seq = np.asarray(list(gen.prompt_ids) + served[:-1], np.int32)
            ref = reference.logits(eng.params, c, seq, last=len(served))
            for row, tok_id in zip(ref, served):
                gaps.append(float(row.max() - row[tok_id]))
                agree += int(np.argmax(row) == tok_id)
            positions += len(served)
    t0 = time.monotonic()
    await asyncio.to_thread(compare)
    well_formed = [p for log, _ in logs for p in response_problems(log)]
    return {"positions": positions, "argmax_agree": agree,
            "gap_p50": float(np.median(gaps)), "gap_max": float(max(gaps)),
            "tolerance": how.gap_tol, "tolerance_p50": how.gap_p50_tol,
            "problems": well_formed,
            "reference_s": round(time.monotonic() - t0, 2),
            "ok": bool(max(gaps) <= how.gap_tol
                       and np.median(gaps) <= how.gap_p50_tol
                       and not well_formed),
            "logs": [log for log, _ in logs]}


def response_problems(log: RequestLog) -> list[str]:
    """What is wrong with one finished response, if anything."""
    if log.cancelled or log.t_end is None:
        return []
    p = []
    if log.status != 200:
        p.append(f"{log.rid}: HTTP {log.status}: {log.error}")
        return p
    if log.error:
        p.append(f"{log.rid}: {log.error}")
    if not log.done:
        p.append(f"{log.rid}: no [DONE]")
    if log.usage is None:
        p.append(f"{log.rid}: no usage frame")
    else:
        # An end-of-sequence token is counted by the engine and carries
        # no text.
        want = log.usage.get("completion_tokens", -1) - (
            1 if log.finish_reason == "stop" else 0)
        if want != log.tokens:
            p.append(f"{log.rid}: usage says {want} tokens with text, "
                     f"{log.tokens} were streamed")
        if log.usage.get("prompt_tokens") != log.prompt_tokens:
            p.append(f"{log.rid}: prompt of {log.prompt_tokens} tokens "
                     f"counted as {log.usage.get('prompt_tokens')}")
    if log.finish_reason not in ("stop", "length"):
        p.append(f"{log.rid}: finish_reason {log.finish_reason!r}")
    return p


def usage_rows(db_dir: Path) -> int:
    db = sqlite3.connect(Path(db_dir) / "tokens_usage.db")
    try:
        return db.execute("SELECT COUNT(*) FROM tokens_usage").fetchone()[0]
    finally:
        db.close()
