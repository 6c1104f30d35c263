"""Prefill/decode disaggregation: slot pools + goodput-first admission
(ISSUE 13, after *DistServe* — goodput-optimized serving via
disaggregated prefill and decoding).

The unified scheduler runs prefill chunks and decode bursts through one
step loop over one slot set, so a long prompt's chunks and a deep decode
scan contend for the same dispatch budget — the interference PR 2's
prefill-aware burst clamp bounds but cannot remove. This module splits
the slot set into two POOLS over the same mesh, params, and paged KV
pool:

* the **prefill pool** owns admissions: a request prefills in a
  prefill-pool slot, and the pool's size caps how much prompt work can
  ever interleave with decoding;
* the **decode pool** owns token generation: at prompt completion the
  request's KV moves to its reserved decode-pool slot via
  ``PageAllocator.transfer`` — a refcount handoff (retain-by-new-owner,
  release-by-old) over the SAME physical pages, so the handoff performs
  zero device copies by construction (the radix prefix cache already
  proves cross-owner page sharing; only the host-side page table row is
  re-uploaded). Decode bursts are compiled ``[B]``-wide and masked by
  the host ``active`` array, so they cover exactly the decode pool's
  residents with no new programs.

In front of both pools sits a goodput-first admission controller
(:class:`DisaggController`): it predicts per-pool TTFT/TPOT attainment
from the engine's fitted step times, the flight ring's decode-burst
occupancy, and queue depth, and when a request's SLO cannot be met it
**sheds** at submit (the PR 3/PR 8 overload path: HTTP 429 with a
numeric ``Retry-After``) or **clamps** (a TTFT-risk admission is flagged
and rides the busy-depth burst interleave until its first token). The
pools export ``gateway_engine_pool_*`` gauges, pool-tagged flight
records, and per-pool SLO attribution so ``gateway_slo_goodput_ratio``
becomes the pooled-vs-unified scoreboard.

Direct-to-decode admissions (no handoff): warm prefix-cache hits whose
unmatched tail fits one prefill chunk (the satellite "prefill skipped"
composition — the matched span never prefills at all), and requests
with sampling penalties (their on-device token-occurrence counts are
built by prefill and must stay on the slot that decodes them; they
already bypass the prefix cache for the same reason).

Everything here runs on the engine's event-loop thread only, like the
scheduler state it was carved from (``# guarded-by: loop``; the runtime
sanitizer instruments both classes).
"""
from __future__ import annotations

import logging
from typing import TYPE_CHECKING, Any

from ..obs.flight import POOL_DECODE, POOL_PREFILL, POOL_UNIFIED

if TYPE_CHECKING:                                    # pragma: no cover
    from .engine import GenRequest, InferenceEngine

logger = logging.getLogger(__name__)

# Flight-ring window the occupancy predictor integrates over (seconds):
# long enough to average burst granularity, short enough that a load
# swing reaches the admission decision within a few requests.
OCCUPANCY_WINDOW_S = 1.0

ADMISSION_POLICIES = ("goodput", "always")


class SlotPool:
    """One scheduler pool's slot ownership: a named, fixed subset of the
    engine's batch slots with its own free list and admission counters.
    The unified scheduler is the degenerate case — ONE pool spanning
    every slot — so the engine's slot bookkeeping is pool-shaped in both
    modes and disaggregation changes the partition, not the code path."""

    def __init__(self, name: str, pool_id: int, slots: range | tuple):
        self.name = name
        self.pool_id = pool_id          # flight-ring POOL_* tag
        self.slots = tuple(slots)
        if not self.slots:
            raise ValueError(f"pool {name!r} needs at least one slot")
        self.free = list(self.slots)    # guarded-by: loop
        self.admits = 0                 # guarded-by: loop
        self.sheds = 0                  # guarded-by: loop

    @property
    def size(self) -> int:
        return len(self.slots)

    def take(self) -> int:
        """Claim a free slot (LIFO — recently-released rows stay warm)."""
        return self.free.pop()

    def reset_free(self) -> None:
        """Crash-recovery hook: every slot back on the free list (the
        engine re-inits device state and drops all requests with it)."""
        self.free = list(self.slots)

    def stats(self) -> dict[str, Any]:
        return {
            "slots": self.size,
            "free_slots": len(self.free),
            "running": self.size - len(self.free),
            "admits": self.admits,
            "sheds": self.sheds,
        }


def build_pools(batch_size: int) -> tuple[SlotPool, ...]:
    """The unified partition: one pool over every slot."""
    return (SlotPool("unified", POOL_UNIFIED, range(batch_size)),)


class DisaggController:
    """The two-pool partition plus the goodput-first admission policy.

    Owns no device state: the controller reads the engine's fitted
    step-time model and flight ring, decides placement/shed/clamp at
    ``submit()``, and counts handoffs — the engine performs the actual
    KV transfer (``InferenceEngine._handoff``) on its loop thread.
    """

    def __init__(self, engine: "InferenceEngine", dcfg) -> None:
        B = engine.B
        if engine.spec_k:
            raise ValueError(
                "disaggregation + spec_draft_len is not supported (v1): "
                "the handoff would have to relocate per-slot draft "
                "history and acceptance state")
        if engine._swa_ring_pages:
            raise ValueError(
                "disaggregation does not compose with the SWA page ring "
                "(v1): ring slots rotate their table mappings in place "
                "and cannot transfer ownership")
        if B < 2:
            raise ValueError("disaggregation needs max_batch_size >= 2 "
                             "(one slot per pool)")
        k = int(dcfg.prefill_slots) or max(1, B // 4)
        if not 1 <= k <= B - 1:
            raise ValueError(
                f"prefill_slots {k} must leave both pools non-empty "
                f"(1..{B - 1} for max_batch_size {B})")
        if dcfg.admission not in ADMISSION_POLICIES:
            raise ValueError(f"unknown admission policy "
                             f"{dcfg.admission!r}; expected one of "
                             f"{ADMISSION_POLICIES}")
        self._engine = engine
        self.policy = dcfg.admission
        self.prefill = SlotPool("prefill", POOL_PREFILL, range(k))
        self.decode = SlotPool("decode", POOL_DECODE, range(k, B))
        self.pools: tuple[SlotPool, ...] = (self.prefill, self.decode)
        # Prefill-dispatch wall EMA (ms per compiled chunk call): the
        # TTFT predictor's per-chunk cost term, fed by the engine after
        # each phase-2 dispatch round. None until the first measurement
        # (the predictor admits optimistically while unmeasured).
        self._chunk_wall_ema_ms: float | None = None    # guarded-by: loop
        self.handoffs = 0                               # guarded-by: loop
        self.handoff_pages = 0                          # guarded-by: loop
        self.clamps = 0                                 # guarded-by: loop
        self.clamp_pending = 0                          # guarded-by: loop
        self.goodput_sheds = 0                          # guarded-by: loop
        logger.info("disaggregated scheduler: prefill pool %d slot(s), "
                    "decode pool %d slot(s), admission=%s",
                    k, B - k, self.policy)

    # -- prediction (loop thread) -------------------------------------------
    def note_prefill_wall(self, ms_per_dispatch: float) -> None:
        self._chunk_wall_ema_ms = (
            ms_per_dispatch if self._chunk_wall_ema_ms is None
            else 0.8 * self._chunk_wall_ema_ms + 0.2 * ms_per_dispatch)

    def note_handoff(self, n_pages: int) -> None:
        self.handoffs += 1
        self.handoff_pages += n_pages

    def clamp_release(self, req: "GenRequest") -> None:
        """A clamped admission reached its first token (or died trying):
        drop its pending count. Idempotent per request."""
        if req.disagg_clamped:
            req.disagg_clamped = False
            self.clamp_pending = max(0, self.clamp_pending - 1)

    def decode_occupancy(self) -> float:
        """Fraction of the last :data:`OCCUPANCY_WINDOW_S` the mesh spent
        inside decode bursts, from the flight ring — the contention term
        that inflates a new prompt's predicted prefill wait (prefill
        dispatches queue behind in-flight decode scans on one mesh)."""
        fl = self._engine.flight
        if fl is None:
            return 0.0
        now = fl.clock()
        busy_ms = fl.steps_overlapping(now - OCCUPANCY_WINDOW_S, now)
        return min(0.95, busy_ms / (OCCUPANCY_WINDOW_S * 1000.0))

    def predict(self, prompt_tokens: int = 0) -> dict[str, Any]:
        """Per-pool attainment forecast for a prompt of
        ``prompt_tokens``: predicted TTFT through the prefill pool
        (queue wait + this prompt's chunk dispatches, inflated by decode
        occupancy) and predicted TPOT through the decode pool (the
        fitted decode step time). ``None`` values mean the model is
        still unmeasured — admission stays optimistic rather than
        shedding on a guess."""
        eng = self._engine
        occ = self.decode_occupancy()
        out: dict[str, Any] = {"decode_occupancy": round(occ, 3)}
        step_ms = eng._ema_step_ms_stats
        if step_ms is None:
            step_ms = eng._step_ms_estimate()
        out["decode_tpot_ms"] = (round(step_ms, 3)
                                 if step_ms is not None else None)
        chunk_ms = self._chunk_wall_ema_ms
        if chunk_ms is None:
            out["prefill_ttft_ms"] = None
            return out
        chunks = -(-max(1, prompt_tokens) // eng.prefill_chunk)
        # Queued work ahead of this request pays its own chunks too;
        # approximate each queued prompt at one chunk plus the measured
        # admission wait EMA (the scheduler half of TTFT).
        queued = eng._queue.qsize() + (1 if eng._head is not None else 0)
        wait_ms = eng._queue_wait_ema_ms or 0.0
        ttft = (wait_ms + (chunks + queued) * chunk_ms) / (1.0 - occ)
        out["prefill_ttft_ms"] = round(ttft, 3)
        return out

    # -- admission (loop thread, called from submit()) ----------------------
    def admit_or_shed(self, req: "GenRequest") -> None:
        """Goodput-first gate: shed (raise, → 429 + numeric Retry-After)
        when the pools' predicted attainment misses the request's SLO and
        no clamp can rescue it; flag a TTFT-risk admission as clamped so
        it rides the busy-depth burst interleave until first token."""
        if self.policy != "goodput":
            return
        if req.slo_ttft_ms is None and req.slo_tpot_ms is None:
            return                      # no target — nothing to attain
        p = self.predict(len(req.prompt_ids))
        ttft_ok = tpot_ok = True
        if req.slo_ttft_ms and p["prefill_ttft_ms"] is not None:
            ttft_ok = p["prefill_ttft_ms"] <= req.slo_ttft_ms
        if req.slo_tpot_ms and p["decode_tpot_ms"] is not None:
            tpot_ok = p["decode_tpot_ms"] <= req.slo_tpot_ms
        if ttft_ok and tpot_ok:
            return
        if not tpot_ok:
            # The decode pool cannot meet the per-token target no matter
            # how shallow prefill runs — admitting would only burn pages
            # on a guaranteed violation (and, if TTFT misses too,
            # neither pool meets the SLO). Shed.
            from .engine import EngineOverloaded
            self.goodput_sheds += 1
            pool = self.decode if ttft_ok else self.prefill
            pool.sheds += 1
            self._engine._shed_n += 1
            fl = self._engine.flight
            if fl is not None:
                from ..obs.flight import SHED
                fl.record(SHED, queued=self._engine._queue.qsize(),
                          free_slots=self._engine._free_slot_count(),
                          val=float(p["decode_tpot_ms"] or 0.0),
                          pool=pool.pool_id,
                          rid=req.request_id or None)
            raise EngineOverloaded(
                f"predicted decode step "
                f"{p['decode_tpot_ms']:.1f} ms misses the request's "
                f"{req.slo_tpot_ms:.1f} ms TPOT target"
                + ("" if ttft_ok else
                   f" (predicted TTFT {p['prefill_ttft_ms']:.0f} ms "
                   f"also misses {req.slo_ttft_ms:.0f} ms)"))
        # TTFT at risk only: admit, but CLAMP — the flag holds the
        # burst-depth policy at the busy (interleave) depth until this
        # request's first token, trading decode dispatch amortization
        # for prefill latency exactly while the risk exists.
        req.disagg_clamped = True
        self.clamps += 1
        self.clamp_pending += 1

    # -- stats ---------------------------------------------------------------
    def stats(self) -> dict[str, Any]:
        """The per-pool block engine.stats() embeds as ``pools`` — the
        obs collector fans it onto ``gateway_engine_pool_*`` gauges."""
        pred = self.predict()
        pf = self.prefill.stats()
        if pred["prefill_ttft_ms"] is not None:
            pf["predicted_ttft_ms"] = pred["prefill_ttft_ms"]
        dc = self.decode.stats()
        if pred["decode_tpot_ms"] is not None:
            dc["predicted_tpot_ms"] = pred["decode_tpot_ms"]
        dc["occupancy_ratio"] = pred["decode_occupancy"]
        return {"prefill": pf, "decode": dc}
