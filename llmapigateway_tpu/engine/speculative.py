"""Prompt-lookup speculative decoding: draft-on-device, verify-in-batch.

A decode step at serving batch sizes is HBM-bandwidth-bound — the weights
stream once per step whether the step scores 1 token or 8. Speculative
decoding exploits that: propose ``k`` draft tokens, verify them IN ONE
forward over ``T = k+1`` positions, and accept the longest prefix whose
greedy continuations match. Real text (code, chat with quoting, RAG)
repeats itself, so a cheap draft source — looking the current bigram up in
the slot's OWN token history ("prompt lookup", cf. PAPERS.md n-gram
speculation; no reference counterpart, the reference executes no models —
SURVEY.md §2b) — reaches 2-4 accepted tokens/step with zero extra model.

Correctness is verification-anchored: drafts may be garbage (no match →
whatever bytes the window slice produced) and the output is STILL exactly
the greedy sequence, because a draft token is only accepted when it equals
the model's own argmax given the verified prefix. TPU-first details:

* Drafting is fully on-device and vectorized (no host round trip per
  step): bigram match = two masked equality scans over the [B, S] history
  buffer + an argmax; the draft window is a ``dynamic_slice``.
* The verify forward reuses the model's CHUNK path (T = k+1 triggers the
  same insert-then-attend attention used for prefill chunks — the Pallas
  causal kernel included), so no new kernel is needed. ``k+1`` must be a
  power of two (kernel block divisibility), i.e. ``k ∈ {1, 3, 7}``.
* Rejected positions' KV and history entries land beyond the advanced
  ``lengths`` — the cache's documented undefined zone, overwritten by the
  next step's insert at the new offset. No rollback copies.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def draft_from_history(hist: jax.Array, tokens: jax.Array,
                       lengths: jax.Array, k: int) -> jax.Array:
    """Propose k draft tokens per slot from its token history.

    hist: [B, S] int32 — hist[b, p] is the input token at position p,
    valid for p < lengths[b] (+ the current token at lengths, not yet
    written). tokens: [B] — current input token (position ``lengths``).
    Finds the LAST j with (hist[j-1], hist[j]) equal to (previous token,
    current token) AND the whole continuation window hist[j+1 : j+1+k]
    already in the past (j < lengths - k — without this, a short-period
    repetition loop matches its own most recent occurrence and the window
    reads unwritten history, rejecting every draft). No match → an
    arbitrary window, which verification simply rejects. Returns [B, k]
    int32.
    """
    B, S = hist.shape
    idx = jnp.arange(S)[None, :]
    prev = jnp.take_along_axis(
        hist, jnp.maximum(lengths - 1, 0)[:, None], axis=1)[:, 0]   # [B]
    hist_prev = jnp.pad(hist[:, :-1], ((0, 0), (1, 0)))             # shift
    m = ((hist == tokens[:, None]) & (hist_prev == prev[:, None])
         & (idx >= 1) & (idx < (lengths - k)[:, None]))
    j = jnp.max(jnp.where(m, idx, -1), axis=1)                      # [B]
    start = jnp.clip(j + 1, 0, S - k)

    def window(h, s):
        return jax.lax.dynamic_slice(h, (s,), (k,))
    return jax.vmap(window)(hist, start)


def make_spec_step(model_forward, config, k: int):
    """Build the speculative decode step (greedy only).

    ``model_forward(params, c, tokens[B,T], lengths, cache, active=)``
    is the family forward already configured with the engine's attention
    implementation; T = k+1 routes through its chunk path.

    Returns ``step(params, cache, hist, tokens, lengths, active,
    draft_ok) -> (next_tokens, new_lengths, cache, hist, emitted,
    n_new)`` where ``emitted`` is [B, k+1] int32 with -1 past each slot's
    accepted count (emission-ready: the scheduler already skips negative
    tokens) and ``n_new`` is [B] in [0, k+1] (0 for inactive slots).
    ``draft_ok`` [B] bool is the per-slot adaptive drafting gate: a
    suspended slot's drafts are masked to -1 — never a valid argmax, so
    verification deterministically rejects them all and the slot advances
    exactly 1 token/step, while the batch's drafting slots keep their
    full k-token speculation. (The verify width stays k+1 — suspension
    pays off via the scheduler, which skips spec bursts entirely when
    every slot is suspended, and via the acceptance gate's batch mean,
    which suspended slots no longer drag down.)
    """
    c = config

    def step(params, cache, hist, tokens, lengths, active, draft_ok):
        B = tokens.shape[0]
        S = hist.shape[1]
        draft = draft_from_history(hist, tokens, lengths, k)        # [B, k]
        draft = jnp.where(draft_ok[:, None], draft, -1)
        seq = jnp.concatenate([tokens[:, None], draft], axis=1)     # [B,k+1]
        logits, out = model_forward(params, c, seq, lengths, cache,
                                    active=active)
        # Preserve the caller's cache pytree type through the scan carry
        # (family forwards return llama.KVCache even when the arrays are a
        # PagedKVCache's pools).
        cache = type(cache)(k=out.k, v=out.v)
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)           # [B,k+1]
        # Accept the longest draft prefix that matches the model's own
        # greedy continuation; the token after the last accepted draft is
        # free (it came out of the same forward).
        match = (draft == g[:, :-1]).astype(jnp.int32)              # [B, k]
        acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)           # [B] 0..k
        next_tokens = jnp.take_along_axis(g, acc[:, None], axis=1)[:, 0]
        n_new = jnp.where(active, acc + 1, 0)                       # [B]
        emitted = jnp.where(jnp.arange(k + 1)[None, :] <= acc[:, None],
                            g, -1)
        emitted = jnp.where(active[:, None], emitted, -1)
        # History gains this step's INPUT tokens at [lengths, lengths+k+1)
        # — the accepted prefix is valid, the tail lands beyond the new
        # lengths in the undefined zone. Inactive rows clamp to the tail.
        off = jnp.where(active, lengths, S)

        def write(h, s, o):
            return jax.lax.dynamic_update_slice(h, s, (o,))
        hist = jax.vmap(write)(hist, seq, off)
        new_lengths = lengths + n_new
        return next_tokens, new_lengths, cache, hist, emitted, n_new

    return step


def make_spec_burst(make_forward, config, k: int, n_steps: int):
    """Fused scan over ``n_steps`` speculative steps (ONE dispatch).

    Returns ``burst(params, cache, tables, hist, tokens, lengths, active,
    draft_ok) -> (emitted [n_steps, B, k+1], cache, hist, tokens,
    lengths)``; lengths and the emitted counts are data-dependent, so the
    caller syncs host mirrors from the fetched ``emitted`` (count =
    tokens >= 0 per row). ``draft_ok`` [B] bool (the per-slot adaptive
    drafting gate, see make_spec_step) is burst-invariant: suspension
    decisions happen on the host between bursts. ``make_forward(tables)
    -> model_forward``: the attention closes over the traced page tables,
    which are a positional argument of the burst.
    """
    @partial(jax.jit, donate_argnums=(1,))
    def paged_burst(params, cache, table, hist, tokens, lengths, active,
                    draft_ok):
        step = make_spec_step(make_forward(table), config, k)

        def body(carry, _):
            cache, hist, tokens, lengths = carry
            nt, nl, cache, hist, emitted, _ = step(
                params, cache, hist, tokens, lengths, active, draft_ok)
            return (cache, hist, nt, nl), emitted
        (cache, hist, tokens, lengths), emitted = jax.lax.scan(
            body, (cache, hist, tokens, lengths), None, length=n_steps)
        return emitted, cache, hist, tokens, lengths

    return paged_burst
