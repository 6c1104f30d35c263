"""Chip probe of learned sparse attention (ops/sparse_attention.py) at the
served geometry of ``keye-vl2-30b-ep4`` (12 layers, 8 slots of 32,768, page
256, 32 query / 4 KV heads of 128, 16 index heads of 64, 2,048 keys kept):
the time of ONE layer's call of each piece, as a decode step over all 8
slots and as a prefill chunk of ``--rows`` rows of 512 — the index scores,
the plain selection (``lax.top_k``: a decode step's list, and that list as
a chunk's mask) beside the chunk kernel that scores and selects in one, the
masked page walk beside the unmasked one — and a decode step's READ of its
selected keys in three forms at 8k / 20k / 32k of context a slot
(``read``): the gathered rows (``gathered_decode_attention``, the plain
form), the walk of the live pages under the selection's mask
(``selected_decode_attention``; its mask here the plain form's,
``selection_words`` of ``lax.top_k``'s list), and
XLA's gather asked for one slice a token across the KV heads — and a decode
step's SELECTION, from ``qi``, ``w`` and the index pool to the read kernel's
mask words, in two forms at the same three contexts (``select``): the one
kernel over the live pages that is served (``SparseAttention.select_words``)
beside the span it replaced (scores over every table position, ``lax.top_k``'s
list, ``selection_words``).
``chiprun -- python3 tools/probe_sparse_attention.py``; results on stdout
and in chiprun_out/probe_sparse_attention.json. Fails without a TPU."""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from llmapigateway_tpu.ops import sparse_attention as sa       # noqa: E402
from llmapigateway_tpu.ops.paged_attention import (            # noqa: E402
    paged_prefill_attention)
from probe_experts import device_ms, program                   # noqa: E402

L, SLOTS, S, PAGE, H, KV, DH, J, W, K, T = (12, 8, 32768, 256, 32, 4, 128,
                                            16, 64, 2048, 512)
NP = S // PAGE


READ_CONTEXTS = (8192, 20000, 32767)


def slice_gathered_attention(q, pool_k, pool_v, layer, phys, offset, total):
    """:func:`sa.gathered_decode_attention` with ONE slice a token across
    the KV heads: ``[1, 1, KV, 1, Dh]`` of the stacked side at (layer,
    page, 0, offset, 0) — a quarter of the slices, each of 4 rows."""
    def rows(pool):
        one = lambda p, o: jax.lax.dynamic_slice(
            pool, (layer, p, 0, o, 0), (1, 1, KV, 1, DH)).reshape(KV, DH)
        return jax.vmap(jax.vmap(one))(phys, offset)        # [B, k, KV, Dh]
    keys, vals = rows(pool_k), rows(pool_v)
    B, G = q.shape[0], H // KV
    scores = jnp.einsum("bhgd,bshd->bhgs", q.reshape(B, KV, G, DH), keys,
                        preferred_element_type=jnp.float32) * DH ** -0.5
    real = jnp.arange(K)[None, :] < total[:, None]
    probs = jax.nn.softmax(jnp.where(real[:, None, None, :], scores,
                                     sa.NEG_INF), axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", probs.astype(vals.dtype), vals,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H * DH).astype(q.dtype)


def probe_reads(ms, out, table, pools, q, keys):
    """A decode step's read of its selected keys, three forms at three
    contexts; each timed FROM the index scores on (the selection's list or
    mask is part of a form's price) and by its pieces."""
    pool_k, pool_v = pools
    layer = jnp.int32(5)
    q = q[:, 0]
    for ctx in READ_CONTEXTS:
        scores = jax.random.normal(keys[6], (SLOTS, S), jnp.float32)
        n_keys = jnp.full((SLOTS,), ctx + 1, jnp.int32)
        seen = jnp.arange(S)[None, :] < n_keys[:, None]

        def listed(scores):
            positions, total = sa.top_positions(scores, seen, K)
            phys = jnp.take_along_axis(table, positions // PAGE, axis=1)
            return positions, phys, positions % PAGE, total

        def walk(keep, pk, pv):
            return sa.selected_decode_attention(
                q, pk, pv, table, n_keys, keep, layer=layer)

        def words(scores):
            positions, _, _, total = listed(scores)
            return sa.selection_words(positions, total, NP, PAGE)
        forms = {
            "gathered": lambda s, pk, pv: sa.gathered_decode_attention(
                q, pk, pv, layer, *listed(s)[1:]),
            "slice_gathered": lambda s, pk, pv: slice_gathered_attention(
                q, pk, pv, layer, *listed(s)[1:]),
            "walk": lambda s, pk, pv: walk(words(s), pk, pv),
            "list_alone": lambda s, pk, pv: listed(s),
            "mask_alone": lambda s, pk, pv: words(s),
        }
        got = {}
        for name, fn in forms.items():
            fn = jax.jit(fn)
            ms[f"read.{name}@{ctx}"] = timed(fn, scores, pool_k, pool_v,
                                             n=20)
            got[name] = fn(scores, pool_k, pool_v)
        keep = got["mask_alone"]
        ms[f"read.walk_alone@{ctx}"] = timed(jax.jit(walk), keep, pool_k,
                                             pool_v, n=20)
        want = got["gathered"].astype(jnp.float32)
        for name in ("slice_gathered", "walk"):
            out[f"read.{name}_off_by@{ctx}"] = float(jnp.abs(
                got[name].astype(jnp.float32) - want).max())
        print(json.dumps(out), flush=True)


def probe_selects(ms, out, table, pool_i, qi, w):
    """A decode step's selection, from the index queries to the mask words,
    as served (one kernel over the live pages) and as it was (scores over
    the table, the sorted list, the list's words), 8 slots at one context
    and at a mix of them as the cell holds. DEVICE time of a program's
    execution, off the profiler's module line (``select.*``: a call of
    either form through the host costs 0.2 ms of dispatch whatever it
    holds, beside them as ``select_host.*``), the listed form's largest
    ops by category, and where the two sets differ, whether the score there
    lies within 1e-4 of the slot's k-th (two sound selections may)."""
    fn = sa.SparseAttention(table, S, K, "pallas")
    layer = jnp.int32(5)
    forms = {
        "words": lambda qi, w, p, at: fn.select_words(qi, w, p, layer, at),
        "listed": lambda qi, w, p, at: sa.selection_words(
            *fn.select(qi, w, p, layer, at), NP, PAGE),
    }
    forms = {name: program(f"select_{name}", form)
             for name, form in forms.items()}
    mixed = jnp.asarray([0, 8192, 12000, 16000, 20000, 24000, 28000, 0],
                        jnp.int32)
    for ctx in (*READ_CONTEXTS, "mixed"):
        start = mixed if ctx == "mixed" else jnp.full((SLOTS,), ctx,
                                                      jnp.int32)
        got = {}
        for name, form in forms.items():
            ms[f"select_host.{name}@{ctx}"] = timed(form, qi, w, pool_i,
                                                    start, n=20)
            got[name] = form(qi, w, pool_i, start).reshape(SLOTS, S)
        # A trace a context: the context is an ARGUMENT of the two programs,
        # and the module line tells executions apart by program alone.
        device, parts = device_ms({f"select_{name}": (
            form, (qi, w, pool_i, start)) for name, form in forms.items()},
            n=10)
        for name in forms:
            ms[f"select.{name}@{ctx}"] = device[f"select_{name}"]
        out[f"select.listed_by_category@{ctx}"] = parts["select_listed"]
        scores = fn.scores(qi, w, pool_i, layer)[:, 0]
        kth = jnp.where(got["listed"] != 0, scores, jnp.inf).min(
            -1, keepdims=True)
        differ = got["words"] != got["listed"]
        out[f"select.words_differ_at@{ctx}"] = int(differ.sum())
        out[f"select.words_apart_at@{ctx}"] = int(
            (differ & (jnp.abs(scores - kth) > 1e-4)).sum())
        out[f"select.words_kept@{ctx}"] = got["words"].sum(-1).tolist()
        print(json.dumps(out), flush=True)


def timed(fn, *args, n=10):
    for _ in range(2):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / n * 1e3, 4)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=2)
    args = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out: dict = {"device": jax.devices()[0].device_kind, "ms": {}}
    ms = out["ms"]
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    pages = SLOTS * NP + 1
    pool_k = jax.random.normal(keys[0], (L, pages, KV, PAGE, DH), jnp.bfloat16)
    pool_v = jax.random.normal(keys[1], (L, pages, KV, PAGE, DH), jnp.bfloat16)
    pool_i = jax.random.normal(keys[2], (L, pages, W, PAGE), jnp.bfloat16)
    table = jnp.arange(1, pages, dtype=jnp.int32).reshape(SLOTS, NP)
    layer = jnp.int32(5)
    for kind, B, tt, ctx in (("decode", SLOTS, 1, 20000),
                             ("prefill", args.rows, T, 16384)):
        fn = sa.SparseAttention(table[:B], S, K, "pallas")
        q = jax.random.normal(keys[3], (B, tt, H, DH), jnp.bfloat16)
        qi = jax.random.normal(keys[4], (B, tt, J, W), jnp.bfloat16)
        w = jax.random.normal(keys[5], (B, tt, J), jnp.float32) / 32
        start = jnp.full((B,), ctx, jnp.int32)
        scores = jax.jit(lambda qi, w, p: fn.scores(qi, w, p, layer))
        ms[f"{kind}.index_scores"] = timed(scores, qi, w, pool_i)
        got = scores(qi, w, pool_i)
        seen = (jnp.arange(S)[None, None, :]
                <= ctx + jnp.arange(tt)[None, :, None])
        seen = jnp.broadcast_to(seen, got.shape)
        plain = jax.jit(lambda s, m: sa.top_mask(s, m, K))
        ms[f"{kind}.top_mask_plain"] = timed(plain, got, seen)
        keep = plain(got, seen)
        out[f"{kind}.kept"] = int(keep.sum(-1).max())
        if kind == "decode":
            listed = jax.jit(lambda s, m: sa.top_positions(
                s[:, 0], m[:, 0], K))
            ms["decode.top_positions"] = timed(listed, got, seen)
            positions, total = listed(got, seen)
            phys = jnp.take_along_axis(table, positions // PAGE, axis=1)
            offset = positions % PAGE
            gathered = jax.jit(lambda q, pk, pv, ph, off, tot:
                               sa.gathered_decode_attention(
                                   q[:, 0], pk, pv, layer, ph, off, tot))
            ms["decode.gather_and_attend"] = timed(
                gathered, q, pool_k, pool_v, phys, offset, total)
            # All of a layer's decode, as served (the two kernels) and
            # through the list: by the host's clock, then on the device.
            pool = (pool_k, pool_v, pool_i)
            whole = {name: program(
                f"decode_{name}", lambda qi, w, q, pool, select=select:
                fn.attend(q, pool, layer, start,
                          select(qi, w, pool[2], layer, start)))
                for name, select in (("select_and_attend", fn.select_words),
                                     ("listed_and_attend", fn.select))}
            for name, form in whole.items():
                ms[f"decode.{name}"] = timed(form, qi, w, q, pool)
            device, _ = device_ms({f"decode_{name}": (form, (qi, w, q, pool))
                                   for name, form in whole.items()}, n=10)
            for name in whole:
                ms[f"decode_device.{name}"] = device[f"decode_{name}"]
        else:
            kernel = jax.jit(lambda qi, w, p: sa.index_select(
                qi, w, p, table[:B], start, layer=layer, topk=K))
            ms["prefill.index_select_kernel"] = timed(kernel, qi, w, pool_i)
            same = kernel(qi, w, pool_i).astype(bool) == keep
            out["prefill.kernel_differs_at"] = int((~same).sum())
            for name, mask in (("masked", keep), ("unmasked", None)):
                walk = jax.jit(lambda q, pk, pv, keep=mask:
                               paged_prefill_attention(
                                   q, pk, pv, table[:B], start, layer=layer,
                                   keep=keep))
                ms[f"prefill.walk_{name}"] = timed(walk, q, pool_k, pool_v)
        print(json.dumps(out), flush=True)
        if kind == "decode":
            probe_selects(ms, out, table, pool_i, qi, w)
            probe_reads(ms, out, table, (pool_k, pool_v), q, keys)
    path = Path("chiprun_out/probe_sparse_attention.json")
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
