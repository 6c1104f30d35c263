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
(``selected_decode_attention``; its mask as served, ``selection_words``'
product of one-hot rows, beside the two ways that lost: a scatter of the
list, and a threshold on the scores with a running count of the ties), and
XLA's gather asked for one slice a token across the KV heads.
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

L, SLOTS, S, PAGE, H, KV, DH, J, W, K, T = (12, 8, 32768, 256, 32, 4, 128,
                                            16, 64, 2048, 512)
NP = S // PAGE


READ_CONTEXTS = (8192, 20000, 32767)


def scatter_words(positions, total):
    """:func:`sa.selection_words`' mask by XLA's scatter of the list."""
    real = (jnp.arange(K)[None, :] < total[:, None]).astype(jnp.int32)
    return jnp.zeros((SLOTS, S), jnp.int32).at[
        jnp.arange(SLOTS)[:, None], positions].max(real).reshape(
            SLOTS, NP, PAGE)


def threshold_words(scores, seen, k):
    """:func:`sa.top_positions`' set as the decode kernel's mask with no
    scatter: every seen score above the ``k``-th largest and the first of
    those AT it that there is room for, by a running count."""
    masked = jnp.where(seen, jnp.where(scores == 0.0, 0.0, scores), -jnp.inf)
    kth = jax.lax.top_k(masked, k)[0][..., -1:]
    above, at = masked > kth, (masked == kth) & seen
    room = k - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
    keep = above | (at & (jnp.cumsum(at, axis=-1, dtype=jnp.int32) <= room))
    return keep.astype(jnp.int32).reshape(scores.shape[0], NP, PAGE)


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

        def words(scores, how):
            positions, _, _, total = listed(scores)
            return how(positions, total)

        def served(positions, total):
            return sa.selection_words(positions, total, NP, PAGE)
        forms = {
            "gathered": lambda s, pk, pv: sa.gathered_decode_attention(
                q, pk, pv, layer, *listed(s)[1:]),
            "slice_gathered": lambda s, pk, pv: slice_gathered_attention(
                q, pk, pv, layer, *listed(s)[1:]),
            "walk": lambda s, pk, pv: walk(words(s, served), pk, pv),
            "walk_scatter": lambda s, pk, pv: walk(
                words(s, scatter_words), pk, pv),
            "walk_threshold": lambda s, pk, pv: walk(
                threshold_words(s, seen, K), pk, pv),
            "list_alone": lambda s, pk, pv: listed(s),
            "mask_alone": lambda s, pk, pv: words(s, served),
            "mask_scatter_alone": lambda s, pk, pv: words(s, scatter_words),
            "mask_threshold_alone": lambda s, pk, pv: threshold_words(
                s, seen, K),
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
        out[f"read.masks_differ_at@{ctx}"] = [
            int((keep != got[name]).sum())
            for name in ("mask_scatter_alone", "mask_threshold_alone")]
        want = got["gathered"].astype(jnp.float32)
        for name in ("slice_gathered", "walk", "walk_scatter",
                     "walk_threshold"):
            out[f"read.{name}_off_by@{ctx}"] = float(jnp.abs(
                got[name].astype(jnp.float32) - want).max())
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
            whole = jax.jit(lambda qi, w, q, pool: fn.attend(
                q, pool, layer, start, fn.select(qi, w, pool[2], layer,
                                                 start)))
            ms["decode.select_and_attend"] = timed(
                whole, qi, w, q, (pool_k, pool_v, pool_i))
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
            probe_reads(ms, out, table, (pool_k, pool_v), q, keys)
    path = Path("chiprun_out/probe_sparse_attention.json")
    path.parent.mkdir(exist_ok=True)
    path.write_text(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
