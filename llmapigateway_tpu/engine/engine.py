"""The serving engine: slot-based continuous batching over compiled XLA
programs (chunked prefill + fused decode bursts + optional speculation).

Design (SURVEY.md §2b "Serving scheduler", §7 steps 5-6):

* **Fixed shapes everywhere.** Decode programs are compiled once for the
  full slot batch ``[B]``; inactive slots ride along masked (`active`), so
  admission/retirement never recompiles. Prefill is compiled per power-of-2
  chunk bucket, padded — pad tokens land beyond the true length and are
  masked off by the length-based causal mask, then overwritten by the next
  chunk. The first token is sampled INSIDE the prefill program (one host
  fetch completes the TTFT path).
* **Fused, lag-one-pipelined decode bursts.** A burst of decode steps is
  ONE ``lax.scan`` program (one dispatch, one fetch); burst N+1 dispatches
  before burst N's tokens are fetched, hiding the device→host round trip
  under compute. Two burst depths compile: the deep throughput burst and a
  shallow "busy" burst used while prefill work interleaves. Emission lags
  one burst; slot release/re-admission races are epoch-guarded
  (``_flush_entry``).
* **Deferred-insert decode.** Decode attention reads the STALE cache plus
  a self-column, and every layer's new K/V is written once per step
  outside the layer scan (the provider's ``insert_all``,
  ops/paged_attention.py) — a per-layer functional insert lowers to
  serialized TPU scatters.
* **Greedy fast path + speculation.** When every active slot decodes at
  temperature 0, an argmax-only program runs (no full-vocab sort), and
  with ``spec_draft_len`` set, prompt-lookup speculative bursts verify k
  drafted tokens per weight-streaming pass (engine/speculative.py).
* **Continuous batching.** New requests are admitted into free slots
  between bursts; prefill runs chunk-at-a-time so a long prompt never
  blocks decode for more than one chunk (chunked-prefill interleave).
* **The engine is an async service.** Compiled-program calls are offloaded
  to a worker thread (`asyncio.to_thread`) so the gateway's event loop keeps
  serving; results stream back through per-sequence asyncio queues.
* Per-slot sampling params live in device arrays; sampling is part of the
  decode program (no host round-trip per token beyond the sampled ids).

The KV cache is a page pool (ops/paged_attention.py
``PagedKVCache`` + engine/paged.py allocator): admission reserves pages
for a request's whole lifetime — page exhaustion is backpressure at
admission, never a mid-generation failure — and the radix prefix cache
(engine/prefix_cache.py) reuses resident KV across requests: a prompt
whose prefix is resident maps the matched blocks into its page table and
starts prefill at the match boundary, skipping the matched span's FLOPs
outright (insert-on-release / LRU-by-leaf eviction / refcount pinning).

Two independent int8 precision knobs (models/quant.py): ``quant`` stores
every matmul weight as per-channel int8 (W8A8 on the MXU's native int8
path — decode is weight-bandwidth-bound, so ~2× tok/s) and ``kv_quant``
stores K/V as per-token int8 (halves KV bandwidth and capacity). Both
are plain ``{"q","s"}`` dict leaves in the params/cache pytrees, so
sharding and scanning treat them uniformly.
"""
from __future__ import annotations

import asyncio
import inspect
import logging
import os
import resource
import time
from dataclasses import dataclass, field, replace
from functools import partial
from pathlib import Path
from typing import Any, AsyncIterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..config.schemas import LocalEngineConfig
from ..models import forward_fn, init_fn
from ..models.config import ModelConfig, get_preset
from ..obs.device import part as _part
from ..obs.device import phase as _device_phase
from ..obs.phases import ReqWaits, SchedLedger
from ..parallel.mesh import MeshSpec, build_mesh
from ..parallel.sharding import param_shardings
from .sampling import SamplingParams, sample
from .tokenizer import IncrementalDetokenizer, load_tokenizer

logger = logging.getLogger(__name__)


class EngineOverloaded(Exception):
    """Admission failed (queue full) — maps to a provider error so the
    gateway falls back to the next provider in the chain."""


class EngineUnavailable(Exception):
    """Admission refused because the engine is draining, restarting, or
    failed (ISSUE 14). Maps to a retryable 503 in providers/local.py so
    the breaker opens and the router fails over to remote providers
    while the supervisor recovers the engine."""

    def __init__(self, message: str, retry_after_s: float | None = None):
        super().__init__(message)
        self.retry_after_s = retry_after_s


@dataclass
class FaultPlan:
    """Injectable engine faults (SURVEY.md §5 "failure detection / fault
    injection": the reference tested failures by hand-editing code —
    ``chat.py:143-144`` stubs; here they are first-class hooks). Attach via
    ``engine.fault_plan = FaultPlan(...)``; counters track trigger points.
    """
    fail_prefill_after: int = -1    # raise after N prefill chunks (-1 = off)
    fail_decode_after: int = -1     # raise after N decode bursts (-1 = off)
    slow_decode_s: float = 0.0      # added latency per decode burst
    # Supervision chaos hooks (ISSUE 14). fail_step_after raises at the
    # TOP of scheduler iteration N (before any admission/dispatch) with
    # fail_step_msg — put "RESOURCE_EXHAUSTED" in the message to fake an
    # HBM OOM (classified transient), or set fail_step_fatal to force
    # the fatal (no-restart) classification. fail_handoff_after raises
    # inside the disagg prefill→decode KV handoff. stall_step_after
    # freezes iteration N for stall_s WITHOUT raising — the silent-stall
    # shape only the watchdog can catch.
    fail_step_after: int = -1
    fail_step_fatal: bool = False
    fail_step_msg: str = "injected step fault"
    fail_handoff_after: int = -1
    stall_step_after: int = -1
    stall_s: float = 0.0
    prefill_calls: int = 0
    decode_calls: int = 0
    step_calls: int = 0
    handoff_calls: int = 0

    def on_prefill(self) -> None:
        self.prefill_calls += 1
        if 0 <= self.fail_prefill_after < self.prefill_calls:
            raise RuntimeError("injected prefill fault")

    def on_decode(self) -> None:
        self.decode_calls += 1
        if self.slow_decode_s > 0:
            time.sleep(self.slow_decode_s)
        if 0 <= self.fail_decode_after < self.decode_calls:
            raise RuntimeError("injected decode fault")

    def on_step(self) -> float:
        """Called at the top of every scheduler iteration. Returns the
        stall duration to sleep (0 = none); raises for step faults."""
        self.step_calls += 1
        if 0 <= self.fail_step_after < self.step_calls:
            if self.fail_step_fatal:
                raise ValueError(self.fail_step_msg)
            raise RuntimeError(self.fail_step_msg)
        if 0 <= self.stall_step_after < self.step_calls:
            return self.stall_s
        return 0.0

    def on_handoff(self) -> None:
        self.handoff_calls += 1
        if 0 <= self.fail_handoff_after < self.handoff_calls:
            raise RuntimeError("injected handoff fault")


@dataclass
class GenRequest:
    """One sequence's lifecycle inside the engine."""
    prompt_ids: list[int]
    max_tokens: int
    temperature: float = 0.0
    top_p: float = 1.0
    top_k: int = 0
    presence_penalty: float = 0.0     # OpenAI semantics; engine-native
    frequency_penalty: float = 0.0    # (engine/sampling.py apply_penalties)
    stop: list[str] = field(default_factory=list)
    # Gateway request id (providers/local.py sets it from the active
    # trace) — what the flight recorder's lifecycle records carry, so a
    # scheduler timeline row links back to /v1/api/trace/{id}.
    request_id: str = ""
    # Per-request SLO targets in ms (obs/slo.py; None = no target). The
    # outcome is computed at stream end from the timestamps below and
    # attributed against the flight recorder's step records.
    slo_ttft_ms: float | None = None
    slo_tpot_ms: float | None = None

    # Filled by the engine:
    slot: int = -1
    prefill_pos: int = 0
    # Prefix-cache hit accounting (ISSUE 6): tokens whose prefill was
    # skipped because their KV blocks were resident, the radix nodes
    # pinned for this request's lifetime, and the lookup's wall cost
    # (None = the cache was never consulted — disabled or bypassed).
    cached_tokens: int = 0
    prefix_nodes: list = field(default_factory=list)
    prefix_lookup_ms: float | None = None
    generated: list[int] = field(default_factory=list)
    out_queue: asyncio.Queue = field(default_factory=asyncio.Queue)
    detok: IncrementalDetokenizer | None = None
    text: str = ""
    emitted_upto: int = 0          # index into `text` already sent downstream
    cancelled: bool = False        # client gone — stop generating, free slot
    finish_reason: str | None = None
    t_submit: float = field(default_factory=time.monotonic)
    t_admitted: float | None = None   # slot admission (queued-phase end)
    t_first_token: float | None = None
    t_done: float | None = None
    # What the time in the slot went to (ISSUE 41, obs/phases.py): seven
    # buckets the scheduler's ledger credits from its own clock readings,
    # partitioning [t_admitted, t_first_loop] and [t_first_loop, t_done].
    waits: ReqWaits = field(default_factory=ReqWaits)
    # Flight-recorder cross-links (ISSUE 7): the seq numbers of this
    # request's admit/finish records, surfaced as trace-span attributes.
    flight_admit_seq: int = -1
    flight_done_seq: int = -1
    # Disaggregated serving (ISSUE 13): which pool currently owns the
    # request (obs.flight POOL_* tag; 0 on a unified engine), the decode
    # slot reserved at admission for the prefill→decode handoff (-1 =
    # none; equals `slot` after the handoff or on direct-to-decode
    # admissions), and whether goodput admission flagged this request
    # as TTFT-clamped (burst depth held at the busy/interleave rung
    # until its first token).
    pool: int = 0
    decode_slot: int = -1
    disagg_clamped: bool = False

    @property
    def done(self) -> bool:
        return self.finish_reason is not None

    @property
    def t_first_loop(self) -> float | None:
        """The loop's side of ``t_first_token``: the ledger's reading at
        the close of the prefill wait whose call finished the prompt."""
        return self.waits.t_first_loop


@dataclass
class Delta:
    """One streamed event: text delta and/or terminal state."""
    text: str = ""
    finish_reason: str | None = None
    error: str | None = None


def _kernel_cost_fn(fn, args):
    """AOT ``lower().compile().cost_analysis()`` closure for the kernel
    registry (obs/device.py): capture the call's AVALS now — metadata
    only; holding the real arrays would pin donated buffers — and do the
    lower/compile/analyze later on the registry's resolver thread (an 8B
    lower costs seconds; the persistent compilation cache makes the
    compile itself a lookup)."""
    def aval(x):
        return jax.ShapeDtypeStruct(
            np.shape(x), getattr(x, "dtype", None) or np.asarray(x).dtype,
            sharding=getattr(x, "sharding", None))
    avals = jax.tree.map(aval, args)

    def cost():
        return fn.lower(*avals).compile().cost_analysis()
    return cost


def _start_host_copy(arr) -> None:
    """Kick off an async device→host copy so the transfer overlaps the
    next dispatched burst. Purely an overlap optimization: backends
    without async copies raise assorted exception types here, and the
    later ``np.asarray`` fetch pays the synchronous copy instead —
    correctness is unaffected, so there is nothing useful to log per
    decode step."""
    try:
        arr.copy_to_host_async()
    except Exception:  # graftlint: disable=exception-hygiene — best-effort prefetch, sync fallback is correct
        pass


class InferenceEngine:
    """Owns params, cache, compiled programs, and the batching loop."""

    def __init__(self, engine_cfg: LocalEngineConfig,
                 model_cfg: ModelConfig | None = None,
                 devices: list | None = None):
        self.cfg = engine_cfg
        # Compile monitor FIRST (ISSUE 8): the engine build's own
        # compiles must count under the "startup" phase — installing
        # after init would misattribute nothing-at-all for them and make
        # the recompile telemetry start from a lie.
        from ..obs.device import install_compile_monitor
        install_compile_monitor()
        if model_cfg is None:
            if engine_cfg.preset:
                model_cfg = get_preset(engine_cfg.preset)
            elif engine_cfg.model_path:
                model_cfg = _config_from_checkpoint(engine_cfg.model_path)
            else:
                raise ValueError("local engine needs 'preset' or 'model_path'")
        # The geometry SERVED (differential attention: folded K/V pairs).
        self.model_cfg = model_cfg = model_cfg.served()
        self.dtype = jnp.bfloat16 if engine_cfg.dtype == "bfloat16" else \
            jnp.dtype(engine_cfg.dtype)

        if jax.process_count() > 1:
            raise ValueError(
                f"the engine serves from one process; this one is "
                f"{jax.process_index()} of {jax.process_count()}")
        self.mesh = build_mesh(MeshSpec(sizes=dict(engine_cfg.mesh)), devices)
        self.B = engine_cfg.max_batch_size
        self.S = min(engine_cfg.max_seq_len, model_cfg.max_seq_len)
        self.prefill_chunk = engine_cfg.prefill_chunk
        # Batched-admission K rungs (schemas.LocalEngineConfig
        # .prefill_batch): group sizes the prefill program compiles for,
        # snapped down from the number of same-bucket queued admissions.
        self._prefill_k_rungs = tuple(
            k for k in (8, 4, 2, 1)
            if k <= max(1, min(engine_cfg.prefill_batch, self.B)))
        self.decode_burst = max(1, engine_cfg.decode_burst)
        self.decode_burst_busy = max(1, min(engine_cfg.decode_burst_busy,
                                            self.decode_burst))
        self.ttft_target_ms = max(0.0, engine_cfg.ttft_target_ms)
        # Depths the fused decode scans are compiled for (lazily, on first
        # use). With a TTFT target the 3/4, 1/2 and 1/4 rungs give the
        # adaptive cap real landing spots between deep and busy — the
        # cap snaps DOWN to a compiled depth, so a coarse ladder forfeits
        # throughput (e.g. a 26-step budget truncated to 16 when 24
        # exists ≈ +8% exposure headroom converted to tok/s); each rung
        # costs one lazily-compiled scan program.
        self._burst_depths = {self.decode_burst, self.decode_burst_busy}
        if self.ttft_target_ms > 0:
            for frac in (2, 4):
                self._burst_depths.add(max(1, self.decode_burst // frac))
            self._burst_depths.add(max(1, 3 * self.decode_burst // 4))
        self._burst_depths = tuple(sorted(self._burst_depths))
        # Effective page size, clamped to the cache extent: a page larger
        # than S would waste a whole-page tail per slot (small test/dev
        # engines would otherwise carry 256-token pages for 64-token
        # contexts).
        self.kv_page = max(1, min(engine_cfg.kv_page_size, self.S))
        self._swa_margin = 0            # in-flight burst margin, tokens
        # Int8 weight quantization (models/quant.py): validated here so a
        # bad config fails at engine build (→ provider error → fallback),
        # not mid-load.
        from ..models.quant import QUANT_MODES
        self.quant = engine_cfg.quant
        if self.quant not in QUANT_MODES:
            raise ValueError(f"unknown quant {self.quant!r}; "
                             f"expected one of {QUANT_MODES}")
        # KV-cache quantization (int8 K/V + per-token scales).
        self.kv_quant = engine_cfg.kv_quant
        if self.kv_quant not in ("", "int8"):
            raise ValueError(f"unknown kv_quant {self.kv_quant!r}; "
                             f"expected '' | 'int8'")
        # Prompt-lookup speculative decoding (engine/speculative.py).
        self.spec_k = max(0, engine_cfg.spec_draft_len)
        if self.spec_k:
            if self.spec_k not in (1, 3, 7):
                raise ValueError(
                    f"spec_draft_len must be one of 1, 3, 7 (verify width "
                    f"k+1 must be a power of two), got {self.spec_k}")

        self._refuse_unsupported()

        self.tokenizer = load_tokenizer(
            engine_cfg.tokenizer_path or engine_cfg.model_path or None,
            vocab_size=model_cfg.vocab_size)

        self.fault_plan: FaultPlan | None = None
        self._prev_debug_nans: bool | None = None
        self._enable_debug_nans()
        _enable_compilation_cache(engine_cfg.compilation_cache_dir)

        from ..models.hybrid import N_COUNTERS
        self._moe_totals = [0] * N_COUNTERS     # survive a rebuild of the state
        t0 = time.monotonic()
        self._init_params()
        t1 = time.monotonic()
        self._init_state()
        # What actually serves ("auto" resolved) — exposed in stats() so a
        # run can assert it.
        self.attention_impl = self._resolve_attention_impl()
        # Whether the decode programs and prefill_step leave the page pool
        # where it lies (read by layer, written through aliased operands);
        # static per engine, set by _compile — exposed in stats() too.
        self.kv_pool_in_place = False
        self._compile()
        logger.info("engine build: params %.1fs, state+programs %.1fs "
                    "(programs compile lazily on first call)",
                    t1 - t0, time.monotonic() - t1)

        # Scheduler state is event-loop-thread ONLY (asyncio.Queue and the
        # slot maps are not thread-safe; worker-thread calls touch device
        # programs and host numpy mirrors, never these) — the `guarded-by:
        # loop` marks make graftlint's lock-discipline rule enforce that.
        self._queue: asyncio.Queue[GenRequest] = asyncio.Queue(
            maxsize=max(2 * self.B, 16))                # guarded-by: loop
        self._head: GenRequest | None = None            # guarded-by: loop
        # Slot ownership lives in SlotPool objects (engine/disagg.py,
        # ISSUE 13): ONE pool spanning every slot for the unified
        # scheduler, or a prefill + decode pair sharing this mesh and KV
        # pool in disaggregated mode — where admission reserves a decode
        # slot up front and prompt completion hands the KV over by page
        # refcount transfer (_handoff), never by device copy.
        from .disagg import DisaggController, build_pools
        self._disagg: DisaggController | None = None
        if engine_cfg.disaggregation.enabled:
            self._disagg = DisaggController(
                self, engine_cfg.disaggregation)
            self._pools = self._disagg.pools            # guarded-by: loop
        else:
            self._pools = build_pools(self.B)           # guarded-by: loop
        self._pool_by_slot = {s: p for p in self._pools
                              for s in p.slots}
        self._admit_pool = self._pools[0]     # prefill pool when disagg
        self._decode_pool = self._pools[-1]   # same object when unified
        self._running: dict[int, GenRequest] = {}       # guarded-by: loop
        self._prefilling: dict[int, GenRequest] = {}    # guarded-by: loop
        self._loop_task: asyncio.Task | None = None
        self._stopped = False
        self._work_event = asyncio.Event()
        self._loop = None               # the loop _work_event is bound to
        self._warm_thread = None
        self._prewarm_error: str | None = None
        # Scheduler flight recorder (ISSUE 7): per-step and lifecycle
        # records in a preallocated ring, appended only from the loop
        # thread (its fields are `guarded-by: loop`; the sanitizer
        # instruments the class). None = disabled (flight_ring_size 0).
        from ..obs.flight import FlightRecorder
        self.flight = (FlightRecorder(engine_cfg.flight_ring_size)
                       if engine_cfg.flight_ring_size > 0 else None)
        # The scheduler's time ledger (ISSUE 26, obs/phases.py): the
        # loop's wall partitioned into named phases, and each worker-
        # thread wait into what the worker did with it. Loop-thread only;
        # the worker reaches its call through `_device_phase`.
        self._sched = SchedLedger()
        # What the last compiled prefill dispatch ran (rows, bucket,
        # tokens, lowest/highest start position, KV pages walked, the
        # attention kernel's block, t0, t1): written by the worker inside
        # _exec_prefill, read by the loop after the await for the PREFILL
        # flight record.
        self._last_prefill: tuple | None = None
        # The paged prefill kernel's walk, counted at dispatch (ISSUE
        # 37): over rows and cache groups, the pages a call's row-blocks
        # copy and attend a KV head, and the table entries a grid with a
        # page axis stepped through. Monotone; worker thread.
        self._prefill_pages_walked = 0
        self._prefill_pages_table = 0
        # ... and the block each dispatched bucket's kernel ran at (ISSUE
        # 48): bucket -> (positions a row-block, KV heads a program),
        # worked out at a bucket's first dispatch (_prefill_block).
        self._prefill_blocks: dict[int, tuple[int, int]] = {}
        # A latent layer's keys attended (ISSUE 38): per layer, summed
        # over calls, counted at dispatch — a decode step's over the
        # active slots (each sees its context and itself), a prefill
        # call's over its rows and positions. Monotone; worker thread.
        self._mla_decode_keys = 0
        self._mla_prefill_keys = 0
        # ... and the steps a prefill call's kernel programs walked, with
        # those among them whose pages were all whole (ISSUE 57: attended
        # as one straight line), per layer, counted the same way.
        self._mla_prefill_steps = 0
        self._mla_prefill_steps_whole = 0
        # The state blocks the decode steps rewrote: active rows x linear
        # layers a step (ISSUE 46), counted the same way.
        self._lin_decode_state_updates = 0
        # A cross decoder's (ISSUE 54), counted the same way: the keys its
        # decode steps read of a pool that MORE layers read than keep (the
        # ONE full-context K/V: the full layer and the cross layers), times
        # those layers; and the prompt rows that ended half-way up the
        # stack — every row of a chunk but a prompt's last (``_rows_stop``,
        # which ``_compile`` reads off the family's forward).
        self._cross_decode_keys_read = 0
        self._prefill_rows_stopped = 0
        # Prefill calls that returned without a read (ISSUE 56): no row
        # ended its prompt, so nothing was fetched and the call's device
        # time is waited out in the next wait. Monotone; worker thread.
        self._prefill_calls_unread = 0
        # The keys the decode programs' paged kernel calls attended
        # (ISSUE 44), kept the same way: per layer of a K/V cache group,
        # summed over steps and active slots — a global group's step sees
        # the slot's whole context and itself, a windowed group's what of
        # that lies inside the window. Monotone; worker thread.
        self._attn_decode_keys = {"global": 0, "window": 0}
        # An indexer's decode steps (ISSUE 51), ONE layer's keys, kept the
        # same way: the index keys a step scored (each active slot's
        # context and itself) and the K/V rows it then read (at most
        # ``idx_topk`` a slot) — and the pages the read walked to get them
        # (ISSUE 52: every live page of the slot, ``ceil(context / page)``).
        # Monotone; worker thread.
        self._dsa_decode_keys = {"scored": 0, "selected": 0,
                                 "pages_walked": 0}
        # Device observability plane (ISSUE 8): per-kernel cost registry
        # (worker thread records, lock-guarded internally), the HBM
        # memory ledger, and the process-wide XLA compile monitor. The
        # ledger's watermark feeds submit()'s shed path so admission
        # reacts to device memory pressure, not just slots/pages.
        from ..obs.device import HbmLedger, KernelRegistry
        self.kernels = KernelRegistry()
        self.ledger: HbmLedger = self._build_ledger()
        self._watermark_sheds = 0                       # guarded-by: loop
        # Engine supervision (ISSUE 14): lifecycle state machine +
        # heartbeat/watchdog/backoff bookkeeping. Transitions echo into
        # the flight ring as SUPERVISOR records so an incident reads off
        # the same timeline as the steps it interrupted.
        from ..reliability.supervisor import EngineSupervisor
        sup = engine_cfg.supervisor
        self.supervisor = EngineSupervisor(
            watchdog_ms=sup.watchdog_ms, max_restarts=sup.max_restarts,
            backoff_ms=sup.backoff_ms, backoff_max_ms=sup.backoff_max_ms,
            drain_deadline_ms=sup.drain_deadline_ms,
            on_transition=self._on_lifecycle_transition)
        self._watchdog_task: asyncio.Task | None = None
        self._clean_steps = 0                           # guarded-by: loop

    # What a period family cannot be served with, by the kind of per-slot
    # storage that stands in the way, each feature with that storage's
    # reason. A family with several kinds (a latent pool AND state) is
    # refused the union, each feature with every reason it has.
    _REFUSED_BESIDE = {
        # Whatever the storage: a period family of any kind.
        "any": {
            "model_path": "no checkpoint mapping for this family "
                          "(engine/checkpoint.py)"},
        # A block of recurrent state a slot (models/hybrid.py).
        "state": {
            "prefix_cache": "a cached prefix holds KV pages but not the "
                            "recurrent state at its end; set prefix_cache "
                            "false",
            "spec": "a rejected draft cannot be rolled out of the "
                    "recurrent state",
            "mesh": "the state block and the held experts have no "
                    "sharding rule yet",
            "disagg": "a handoff moves pages between slots, not the "
                      "state block"},
        # ... served by the PERIOD scan (models/hybrid.py), which wires no
        # ring beside its state; a cross decoder's own forward does.
        "period_state": {
            "window": "the page ring is not wired to the pool of the "
                      "softmax layers"},
        # SEVERAL cache groups (window and global layers in one model).
        "groups": {
            "prefix_cache": "the ring re-targets a windowed group's pages, "
                            "and a cached prefix would need the global "
                            "group's pages AND the window's last tokens; "
                            "set prefix_cache false",
            "spec": "the verify path reads one pool at one window",
            "mesh": "the page ring runs on one device, and the experts "
                    "have no sharding rule yet",
            "disagg": "a handoff cannot move a ring slot "
                      "(PageAllocator.transfer)"},
        # A LATENT pool (``latent_width`` numbers a token, models/mla.py).
        "latent": {
            "kv_quant": "the latent pool is bfloat16; an int8 latent needs "
                        "scale planes the latent kernel does not read. Set "
                        "kv_quant ''",
            "prefix_cache": "the radix cache shares K/V pages, and has no "
                            "rule yet for sharing latent pages; set "
                            "prefix_cache false",
            "spec": "the verify path reads a K and a V pool, not a latent "
                    "one",
            "mesh": "the latent pool has one key head, which no axis "
                    "divides, and the held experts have no sharding rule "
                    "yet",
            "disagg": "a handoff of latent pages between pools is not "
                      "wired",
            "page_lanes": "a latent page lies token-minor, and the chip's "
                          "kernels move whole tiles of 128 lanes"},
        # An INDEX-KEY side beside K and V, and attention over the keys an
        # indexer selected (ops/sparse_attention.py).
        "sparse": {
            "kv_quant": "a gathered int8 row needs its scale plane gathered "
                        "with it, which the selected-rows read does not do; "
                        "set kv_quant ''",
            "prefix_cache": "the radix cache shares K/V pages, and has no "
                            "rule yet for sharing their index-key side; set "
                            "prefix_cache false",
            "spec": "the verify path has no selection: it would attend "
                    "every cached key where decode attends the selected",
            "mesh": "the index-key side has one head, which no axis "
                    "divides, and the held experts have no sharding rule "
                    "yet",
            "disagg": "a handoff moves K/V pages between pools, not their "
                      "index-key side",
            "page_lanes": "an index-key page lies token-minor, and the "
                          "chip's kernels move whole tiles of 128 lanes"},
        # ONE layer's K/V read by other layers, under queries that pair
        # (models/sambay.py): a cross decoder. It has "state" and "groups"
        # too, and their reasons beside these.
        "cross": {
            "kv_quant": "two softmax maps are SUBTRACTED, and int8 K/V "
                        "rounds each by more than their difference keeps; "
                        "set kv_quant ''",
            "prefix_cache": "a cached prefix would need the memory layer's "
                            "state at its end and the ring's last window "
                            "beside the full layer's pages; set "
                            "prefix_cache false",
            "spec": "the verify path runs every layer over every draft "
                    "row; this family's upper half runs on one row",
            "mesh": "the folded K/V heads and the scan's channels have no "
                    "sharding rule yet",
            "disagg": "a handoff moves one group's pages, not the ring, "
                      "the state block and the memory"},
    }

    def _refuse_unsupported(self) -> None:
        """Refuse at build, with every reason it has, the first feature
        the configuration asks for that the model's per-slot storage
        cannot be served with — none is silently switched off."""
        cfg, c = self.cfg, self.model_cfg
        kinds = [kind for kind, has in (
            ("state", c.n_lin_layers),
            ("period_state", c.n_lin_layers and not c.cross_decoder),
            ("groups", len(c.cache_groups) > 1),
            ("latent", c.is_mla), ("sparse", c.is_sparse),
            ("cross", c.cross_decoder)) if has]
        if not kinds:
            return
        kinds.append("any")
        asked = {       # in the order they are refused
            "kv_quant": (bool(self.kv_quant), "kv_quant 'int8'"),
            "prefix_cache": (cfg.prefix_cache, "prefix_cache"),
            "spec": (bool(self.spec_k), "spec_draft_len"),
            "window": (bool(c.sliding_window), "a sliding window"),
            "mesh": (self.mesh.size > 1, f"mesh {dict(self.mesh.shape)}"),
            "disagg": (cfg.disaggregation.enabled, "disaggregation"),
            "model_path": (bool(cfg.model_path), "model_path"),
            "page_lanes": (self.kv_page % 128 != 0
                           and jax.default_backend() == "tpu"
                           and self._resolve_attention_impl() == "pallas",
                           f"kv_page_size {self.kv_page}"),
        }
        for feature, (wanted, label) in asked.items():
            whys = [self._REFUSED_BESIDE[kind][feature] for kind in kinds
                    if feature in self._REFUSED_BESIDE[kind]]
            if wanted and whys:
                raise ValueError(
                    f"the {c.family!r} family does not support {label}: "
                    + "; and ".join(whys))

    @property
    def allocator(self):
        """The whole-context cache group's allocator (looked up by its
        window, not by its place: a family whose windowed layers come
        first has the RING as group 0), else the only group's: the one
        wherever a feature needs a single page table (prefix cache,
        disaggregation, speculation — each refused beside several
        groups), and whose free pages the flight records and ``stats()``
        report."""
        return self.kv_groups.whole_context.allocator

    @property
    def _swa_ring_pages(self) -> int:
        """Pages a slot holds in the windowed group's ring (0: no ring)."""
        return max((g.ring_pages for g in self.kv_groups), default=0)

    def _on_lifecycle_transition(self, frm: str, to: str,
                                 reason: str) -> None:
        """Supervisor transition hook: mirror the lifecycle edge into
        the flight ring (kind SUPERVISOR, flag = state entered)."""
        if self.flight is None:
            return
        from ..obs.flight import SUPERVISOR, SUPERVISOR_STATES
        try:
            idx = SUPERVISOR_STATES.index(to)
        except ValueError:
            idx = 0
        self.flight.record(SUPERVISOR, flag=idx, rid=reason or frm)

    # -- initialization ------------------------------------------------------
    def _init_params(self) -> None:
        c = self.model_cfg
        t0 = time.monotonic()
        if self.cfg.model_path:
            from .checkpoint import _np_dtype, load_checkpoint
            from ..parallel.sharding import spec_for_param
            from ..models.quant import (QUANT_TOP_KEYS, _np_quantize,
                                        quantizes, weight_bits)

            def put(path: str, arr: np.ndarray) -> jax.Array:
                # ".q"/".s" quantized sub-leaves get their own rules.
                return jax.device_put(
                    arr, spec_for_param(path, tuple(arr.shape), self.mesh))

            def preprocess(path: str, arr: np.ndarray):
                # quant="int8": quantize each tensor at the checkpoint's
                # SOURCE precision (not a bf16-rounded copy), per layer,
                # before stacking — the host stacks and transfers the int8
                # copy, halving both footprints.
                if self.quant and quantizes(path):
                    return _np_quantize(
                        arr, 1 if path in QUANT_TOP_KEYS else 0,
                        bits=weight_bits(self.quant, path))
                return arr.astype(_np_dtype(self.dtype))
            self.params = load_checkpoint(self.cfg.model_path, c,
                                          dtype=self.dtype, put=put,
                                          preprocess=preprocess)
            if (self.quant and c.tie_embeddings
                    and "lm_head_q8" not in self.params):
                # Tied checkpoints ship no lm_head tensor, so the preprocess
                # hook never saw one to quantize — build the int8 head copy
                # (models/quant.py quantize_tree rationale) from the placed
                # embed on device; out_shardings keep it in lm_head layout.
                from functools import partial
                from ..models.quant import quantize_array
                emb = self.params["embed"]
                out_sh = {
                    "q": spec_for_param("lm_head_q8.q", tuple(emb.shape),
                                        self.mesh),
                    "s": spec_for_param("lm_head_q8.s", (emb.shape[0],),
                                        self.mesh)}
                self.params["lm_head_q8"] = jax.jit(
                    partial(quantize_array, contract_axis=1),
                    out_shardings=out_sh)(emb)
        else:
            # Random init as ONE jitted program with sharded outputs:
            # params materialize directly in their GSPMD layout (no host
            # copy, no host→device transfer), and the whole init lands in
            # the persistent compilation cache — the eager per-op form
            # compiled ~10 one-off programs on every cold start.
            init, key = self._random_init_program()
            self.params = init(key)
            jax.block_until_ready(self.params)
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(self.params))
        logger.info("params ready: %.2fB parameters in %.1fs",
                    n_params / 1e9, time.monotonic() - t0)

    def _random_init_program(self):
        """(jitted ``key -> params`` with sharded outputs, the key to call
        it with). A quantized model is built ONE LAYER AT A TIME inside
        the program (``lax.map`` over per-layer keys, quantizing in the
        body), so the full-precision copy of the stacked layer weights
        never exists whole: built whole-then-quantized, mistral-7b int8
        needs the 7.4 GB result plus 7.5 GB of bf16 temporaries — all of
        a 16 GB chip."""
        c = self.model_cfg
        init = init_fn(c)

        def build(k):
            if c.layer_period:
                # Quantises each matrix where it is drawn: a period of
                # this family is 6 GB in bf16 (models/hybrid.py).
                return init(c, k, dtype=self.dtype, quant=self.quant)
            if not self.quant:
                return init(c, k, dtype=self.dtype)
            from ..models.quant import quantize_tree
            c1 = replace(c, n_layers=1)
            k_top, k_layers = jax.random.split(k)

            def one_layer(kl):
                p = quantize_tree(init(c1, kl, dtype=self.dtype), c1,
                                  self.quant)
                return jax.tree.map(lambda a: a[0], p["layers"])
            top = init(c1, k_top, dtype=self.dtype)
            del top["layers"]
            return {**quantize_tree(top, c, self.quant),
                    "layers": jax.lax.map(
                        one_layer, jax.random.split(k_layers, c.n_layers))}
        key = jax.random.PRNGKey(0)
        shardings = param_shardings(jax.eval_shape(build, key), self.mesh)
        return jax.jit(build, out_shardings=shardings), key

    def _init_state(self) -> None:
        c = self.model_cfg
        self.kv_ppb = 1          # multi-page kernel blocking
        self._prefix_cache = None       # guarded-by: loop
        from ..parallel.sharding import paged_cache_sharding
        from ..ops.paged_attention import PagedKVCache
        from .paged import PageAllocator

        page = self.kv_page
        per_slot = (self.S + page - 1) // page
        # Sliding-window RING reservation (one device), per WINDOWED
        # cache group: the windowed kernels never read below pos −
        # window, so a ring of O(window) physical pages serves ANY
        # context length — ensure_mapped recycles each slot's oldest
        # dead page onto the next logical page (mistral's rolling
        # buffer, at page granularity). Margins: in-flight lag-one
        # bursts may still read one burst below the current floor, and
        # dispatch writes run one burst/chunk ahead. A global group
        # holds the whole context.
        # ONE copy of the margin: _swa_rotate's recycle floor must
        # stay in lockstep with the capacity the ring was sized for,
        # or rotation exhausts mid-stream.
        self._swa_margin = self.decode_burst * (self.spec_k + 1)
        span = max(self.prefill_chunk, self._swa_margin)

        def ring_for(window: int) -> int:
            if not window or self.mesh.size > 1:
                return 0
            ring = -(-(window + self._swa_margin + span) // page) + 2
            if ring >= per_slot:
                return 0
            logger.info(
                "paged SWA ring: %d pages/slot (window %d) instead "
                "of %d — steady-state KV footprint is O(window)",
                ring, window, per_slot)
            return ring
        rings = [ring_for(w) for w, _ in c.cache_groups]
        # Multi-page kernel blocking (kv_pages_per_block): resolve the
        # requested run length against what the pool can actually
        # pack — the allocator's superpage runs are what license the
        # kernels' gather-free index maps, so any geometry the
        # allocator can't pack falls back to per-page blocks instead
        # of serving wrong reads.
        ppb_req = max(1, self.cfg.kv_pages_per_block)
        if ppb_req > 1:
            why = None
            if any(rings):
                why = "SWA page ring (mappings rotate per page)"
            elif per_slot % ppb_req:
                why = (f"pages per slot ({per_slot}) not divisible "
                       f"by {ppb_req}")
            elif (self.cfg.kv_num_pages
                  and self.cfg.kv_num_pages % ppb_req):
                why = (f"kv_num_pages ({self.cfg.kv_num_pages}) not "
                       f"divisible by {ppb_req}")
            if why is None:
                self.kv_ppb = ppb_req
            else:
                logger.warning(
                    "kv_pages_per_block=%d falls back to per-page "
                    "blocks: %s", ppb_req, why)
        # One trash page; a PACKED pool reserves the whole trash
        # superpage instead.
        n_trash = self.kv_ppb
        from .paged import CacheGroup, CacheGroups
        groups = []
        for (window, _), n_layers, readers, chunk_readers, ring in zip(
                c.cache_groups, c.group_layers, c.group_readers,
                c.group_chunk_readers, rings):
            # The most pages one slot ever holds — the ring where it
            # runs, else the whole context — sizes the derived pool:
            # every slot can hold a max-footprint sequence at once
            # either way. (kv_num_pages sizes every group's pool.)
            min_hold = ring or per_slot
            num_pages = self.cfg.kv_num_pages or (
                self.B * min_hold + n_trash)
            if num_pages - n_trash < min_hold:
                raise ValueError(
                    f"kv_num_pages={num_pages} cannot hold one "
                    f"max-footprint sequence ({min_hold} pages of "
                    f"{page})")
            groups.append(CacheGroup(
                n_layers, window, ring,
                PageAllocator(num_pages, page, self.B, self.S,
                              pages_per_block=self.kv_ppb),
                kind="latent" if c.is_mla else "kv",
                token_bytes=self._kv_token_bytes(), readers=readers,
                chunk_readers=chunk_readers))
        self.kv_groups = CacheGroups(groups)
        num_pages = self.allocator.num_pages
        # Radix prefix cache (ISSUE 6): cross-request KV reuse over
        # the pool, block = one superpage run so the multi-page
        # kernels apply to shared pages unchanged. Gated to the
        # geometries where page identity is stable for a sequence's
        # lifetime: non-SWA (ring rotation re-targets pages; windowed
        # attention never re-reads old prefixes anyway).
        if self.cfg.prefix_cache and not c.sliding_window:
            from .prefix_cache import RadixPrefixCache
            self._prefix_cache = RadixPrefixCache(
                self.allocator, block_tokens=self.kv_ppb * page)
        psh = paged_cache_sharding(self.mesh, c.n_kv_heads)
        # Layout owned by PagedKVCache.create (the one copy of the
        # int8 {q,s} scheme); value leaves shard via psh, the rank-4
        # [.., KV, 1, page] scale planes via the same spec with the
        # page axis moved last (head_dim dropped, None for the unit
        # dim). Created by ONE program with sharded outputs: the pool
        # is the largest buffer after the weights, and zeros made on
        # the default device and then placed would exist twice there
        # for a moment — and whole on the first chip of a mesh.
        ssh = NamedSharding(
            self.mesh, P(*psh.spec[:-2], None, psh.spec[-2]))
        side = {"q": psh, "s": ssh} if self.kv_quant else psh
        if c.layer_period:
            # A pool a cache group, of the softmax layers only;
            # beside them a fixed block of recurrent state and a conv
            # tail per slot for every linear layer of a period, and
            # one more stack for the leading layers
            # (models/hybrid.py HybridCache). A prefill that starts at
            # position 0 starts from zero state whatever the block
            # holds, so release, cancel and rebuild do no state work.
            from ..models.hybrid import HybridCache
            rep_sh = NamedSharding(self.mesh, P())
            k_sh = v_sh = (side,) * len(self.kv_groups)
            if c.is_mla:        # ONE latent pool, no V side
                k_sh, v_sh = (rep_sh,), ()
            index_sh = (rep_sh,) * len(self.kv_groups) if c.is_sparse else ()
            create = partial(HybridCache.create, c,
                             tuple(g.allocator.num_pages
                                   for g in self.kv_groups),
                             page, self.B, self.dtype, kv_quant=self.kv_quant)
            # (As many state blocks and conv tails as the family's cache
            # lays out: its own shapes say.)
            n_lin = len(jax.eval_shape(create).state)
            self.cache = jax.jit(
                create, out_shardings=HybridCache(
                    k=k_sh, v=v_sh, counters=rep_sh, index=index_sh,
                    state=(rep_sh,) * n_lin, conv=(rep_sh,) * n_lin))()
        else:
            self.cache = jax.jit(
                partial(PagedKVCache.create, c, num_pages, page,
                        self.dtype, kv_quant=self.kv_quant),
                out_shardings=PagedKVCache(k=side, v=side))()
        self._d_tables: tuple | None = None
        self._table_dirty = True
        # Routed assignments of the decode steps and tiles of the prefill
        # calls (hybrid family): the device keeps wrapping int32 totals in
        # the cache, every burst hands them back beside its tokens, the
        # host sums the deltas.
        self._moe_seen = np.zeros((len(self._moe_totals),), np.int64)
        # Host-authoritative per-slot state, mirrored to device each step.
        self.lengths = np.zeros((self.B,), np.int32)
        self.active = np.zeros((self.B,), bool)
        self.last_token = np.zeros((self.B,), np.int32)
        self.samp_temperature = np.zeros((self.B,), np.float32)
        self.samp_top_p = np.ones((self.B,), np.float32)
        self.samp_top_k = np.zeros((self.B,), np.int32)
        self.samp_presence = np.zeros((self.B,), np.float32)
        self.samp_frequency = np.zeros((self.B,), np.float32)
        # Token-occurrence state for presence/frequency penalties:
        # [B, V] int32, DEVICE-authoritative (prefill resets a slot's row
        # and counts the prompt; the general decode path counts each
        # step's INPUT token — so the count visible when sampling token
        # t+1 covers prompt + generated through t). The greedy fast path
        # passes it through untouched:
        # stale rows are harmless because a row's counts only matter to
        # its OWN request's penalties, and penalty requests are (a)
        # reset at admission and (b) force the general path.
        self._d_counts = jax.device_put(
            np.zeros((self.B, self.model_cfg.vocab_size), np.int32),
            NamedSharding(self.mesh, P()))
        # Typed PRNG key end-to-end (the legacy raw-uint32 path is slated to
        # become an error in future JAX).
        self._rng = jax.random.key(int(time.time() * 1e3) % (2**31))
        # Device-resident mirrors for the chained decode loop; re-uploaded
        # (once) whenever host slot state changes.
        self._d_tokens = None
        self._d_lengths = None
        self._d_active = None
        self._d_samp = None
        self._d_dirty = True
        # Lag-one burst pipelining: the scan path dispatches burst N+1
        # BEFORE fetching burst N's tokens, so the device→host fetch
        # overlaps the next burst's compute instead of serializing with
        # it. The stash holds (device tokens, n_steps, active snapshot,
        # slot epochs) of the in-flight burst; `_slot_epoch` guards against a slot being
        # released + re-admitted between dispatch and flush (the stale
        # burst's token must not clobber the new request's first token).
        self._pending: tuple | None = None
        self._slot_epoch = np.zeros((self.B,), np.int64)
        # Step-time model for the ttft_target_ms burst-depth cap. A
        # burst's wall time is C + d·step (C = per-burst fixed cost:
        # host scheduling plus dispatch and fetch), so the naive wall/d
        # estimate overstates the per-step time at shallow depths; feeding it back into the cap shallowed
        # the bursts further — a death spiral to the minimum compiled
        # depth (observed on v5e: 372 tok/s vs 1468 at a fixed burst 16,
        # same TTFT target). Instead, keep an EMA of burst WALL per
        # depth (any steady same-depth pair — busy stretches at
        # decode_burst_busy feed this too, so it never goes stale under
        # load) and fit step = Δwall/Δdepth across the two largest
        # measured depths: C cancels, the estimate is depth-unbiased,
        # and the control loop is self-correcting in both directions
        # (see _step_ms_estimate). No dedicated refresh bursts needed.
        # Entries age: a depth that stopped running (e.g. the cap
        # settled shallower) holds a wall measured under OLD conditions
        # (shorter contexts); fitting against it would bias the slope —
        # _step_ms_estimate ignores entries not refreshed within the
        # last _BURST_WALL_WINDOW samples (falling back to the newest).
        self._burst_walls: dict[int, float] = {}
        self._burst_wall_stamp: dict[int, int] = {}
        self._burst_wall_n = 0
        # Persistent slope fit + exploration (the staleness window alone
        # is a trap: once the cap settles at one depth, every OTHER
        # depth's wall sample ages out, the estimate degrades to the
        # biased one-depth wall/d (per-burst fixed cost folded back in),
        # the cap shrinks, and the controller never runs a deep burst
        # again — a self-reinforcing spiral observed ON CHIP at 345.7
        # tok/s vs 1475 at fixed burst 16, same target. Two repairs:
        # the last two-depth fitted slope PERSISTS (TTL'd) so a depth
        # aging out doesn't un-learn the fixed cost, and every
        # _EXPLORE_EVERY idle bursts the controller runs a steady PAIR
        # at the next-deeper compiled depth, keeping two fresh depths
        # forever (pairs, because a wall sample only records on a
        # steady same-depth burst pair). Exploration is throughput-free
        # (deeper bursts amortize the fixed cost better); it costs a
        # bounded, rare TTFT exposure one rung deeper.
        self._fit_slope: float | None = None
        self._fit_stamp = 0
        self._idle_burst_i = 0
        self._explore_pending = 0
        self._explore_depth = 0
        self._depth_hist: dict[int, int] = {}
        # Prefill-aware clamp + queue-wait telemetry (stats()): how often
        # busy bursts were clamped below decode_burst_busy, the last
        # depth actually dispatched, and how long admissions waited for a
        # slot — the scheduler-side counters of the roofline story.
        self._busy_clamps = 0
        self._last_burst_depth = 0
        self._queue_wait_n = 0                          # guarded-by: loop
        self._queue_wait_ema_ms: float | None = None    # guarded-by: loop
        self._queue_wait_max_ms = 0.0                   # guarded-by: loop
        # Overload sheds (submit() raised EngineOverloaded on a full
        # admission queue) — the gateway maps these to HTTP 429 with a
        # Retry-After from retry_after_hint_s() (reliability, ISSUE 3).
        self._shed_n = 0
        # Operator-facing gauge for /v1/api/engine-stats: EMA over ANY
        # steady same-depth burst (wall/depth, per-burst overhead
        # included) — the number an operator compares to the bench.
        self._ema_step_ms_stats: float | None = None
        # Speculative decoding state: host token-history mirror (device
        # twin rides the dirty upload) + acceptance counters.
        if self.spec_k:
            self.hist = np.zeros((self.B, self.S), np.int32)
            self._d_hist = None
            self._d_hist_fresh = False
            self._spec_pending = None       # lag-one in-flight spec burst
            self._spec_steps_done = 0
            self._spec_tokens_out = 0
            # Adaptive drafting gate (config.spec_min_tokens_per_step):
            # per-slot EMA of accepted tokens/step (1..k+1); NaN = not yet
            # measured (treated optimistically). Reset on slot release.
            self.spec_min_tps = max(
                0.0, self.cfg.spec_min_tokens_per_step)
            self.spec_probe_interval = max(
                1, self.cfg.spec_probe_interval)
            self._spec_ema = np.full((self.B,), np.nan)
            self._spec_probe_ctr = 0
            # PER-SLOT adaptive drafting (config.spec_acceptance_floor):
            # drafting suspends on a slot whose EMA-derived acceptance
            # ratio ((ema - 1) / k) falls below the floor — its drafts
            # are masked on device (deterministic 1 token/step), its EMA
            # freezes at the suspended value, and the batch-mean gate
            # above excludes it. Suspended slots re-probe together every
            # spec_probe_interval spec rounds. Per-slot proposed/accepted
            # counters feed the
            # /metrics gauges and stats(); lifetime totals survive slot
            # release.
            self.spec_floor = min(1.0, max(
                0.0, self.cfg.spec_acceptance_floor))
            self._spec_suspended = np.zeros((self.B,), bool)
            self._spec_suspend_probe_ctr = 0
            self._spec_slot_proposed = np.zeros((self.B,), np.int64)
            self._spec_slot_accepted = np.zeros((self.B,), np.int64)
            self._spec_proposed_total = 0
            self._spec_accepted_total = 0
            # Wall-clock gate term: EMA of measured ms per emitted token
            # across full spec bursts. Acceptance alone can lie — a
            # random-weight repetition loop accepts 2+ tokens/step while
            # each spec step (host draft + k+1-wide verify + its own
            # dispatch pattern) costs many times a fused decode step
            # (v5e ladder 2026-07-31: spec_mixed 346.9 vs 1475.1 tok/s
            # with the acceptance gate OPEN at ema 2.24). None = not yet
            # measured; _spec_wall_age forces a periodic re-measure so a
            # wall-closed gate isn't pinned shut on stale data.
            self._spec_ms_per_tok: float | None = None
            self._spec_wall_age = 0
            self._spec_wall_gate_on = bool(self.cfg.spec_wall_gate)
            # Baseline probe: spec-open traffic never runs NORMAL decode
            # bursts, so the step-time model the wall gate compares
            # against would never get a sample on an engine that is
            # spec-open from its first request. Every
            # 8*spec_probe_interval spec rounds (or immediately while no
            # baseline exists), run TWO consecutive normal rounds — two,
            # because a steady same-depth PAIR is what lands a wall
            # sample (the first normal burst after a spec burst is a
            # transition and can't be timed).
            self._spec_base_ctr = 0
            self._spec_base_rounds = 0
            # Starvation guard: some workloads can never land a wall
            # sample (every normal burst capped below the smallest
            # compiled rung -> synchronous path -> no steady pair).
            # After this many fruitless baseline attempts, stop forcing
            # normal rounds — the wall gate simply stays inert (no
            # baseline) and the acceptance gate still protects, instead
            # of pinning speculation off forever.
            self._spec_base_fails = 0

    def _resolve_attention_impl(self) -> str:
        """Validate cfg.attention and resolve "auto" (pallas on real TPU;
        interpret-mode Pallas on CPU is correct but slower than fused jnp)."""
        impl = self.cfg.attention
        if impl not in ("auto", "pallas", "reference"):
            raise ValueError(f"unknown attention impl {impl!r}; "
                             f"expected auto | pallas | reference")
        if impl == "auto":
            return "pallas" if jax.default_backend() == "tpu" else "reference"
        return impl

    def _compile(self) -> None:
        """Compile the step programs. The attention_fn is built INSIDE
        each jitted step, closing over the traced page tables — the model
        forward's signature knows nothing of pages."""
        c = self.model_cfg
        family_forward = forward_fn(c)
        # A forward whose upper layers run on a prompt's last row alone
        # asks which rows' chunks END their prompts (``final``).
        self._rows_stop = "final" in inspect.signature(
            family_forward).parameters
        from ..ops.latent_attention import LatentAttention
        from ..ops.sparse_attention import SparseAttention
        from ..ops.paged_attention import (PagedKVCache,
                                           make_paged_attention_fn,
                                           pool_in_place)

        impl = self.attention_impl
        mesh = self.mesh if self.mesh.size > 1 else None
        self.kv_pool_in_place = pool_in_place(impl, mesh)
        logger.info("paged KV cache: %d pages × %d tokens, attention=%s"
                    "%s, kv_pool_in_place=%s", self.allocator.num_pages,
                    self.allocator.page_size, impl,
                    (f", pages_per_block={self.kv_ppb}"
                     if self.kv_ppb > 1 else ""), self.kv_pool_in_place)
        S = self.S

        replicated = NamedSharding(self.mesh, P())

        windows = [w for w, _ in c.cache_groups]

        def call_forward(params, cache, tables, tokens, lengths,
                         active=None, spec=False, **rows):
            # A provider a cache group, over the group's page table and
            # at its window (static in every kernel call it makes); a
            # family of one group is handed the provider itself.
            # `spec` builds the dedicated verify-capable provider:
            # T = k+1 then routes through the deferred paged verify
            # (stale-pool gather + mixed-precision self-block) instead
            # of the chunk path — required for int8 greedy parity and
            # skips the per-layer pool scatters either way.
            if c.is_mla:
                attn = (LatentAttention(tables[0], S, impl),)
            elif c.is_sparse:
                attn = tuple(SparseAttention(table, S, c.idx_topk, impl)
                             for table in tables)
            else:
                attn = tuple(make_paged_attention_fn(
                    table, max_seq=S, impl=impl, mesh=mesh, window=window,
                    pages_per_block=self.kv_ppb, spec=spec)
                    for table, window in zip(tables, windows))
            return family_forward(params, c, tokens, lengths, cache,
                                  active=active,
                                  attention_fn=(attn if len(attn) > 1
                                                else attn[0]), **rows)

        def engine_cache(cache):
            """The forward's cache as the type the engine holds (a family
            with state of its own returns its own type whole)."""
            return cache if c.layer_period else PagedKVCache(
                k=cache.k, v=cache.v)

        # A period family is told which slot each prefill row is and how
        # many of its tokens are real (the rest pad the bucket).
        rows_known = c.layer_period > 0

        @partial(jax.jit, donate_argnums=(1, 2))
        def prefill_step(params, cache: PagedKVCache, counts: jax.Array,
                         tables: tuple[jax.Array, ...],
                         tokens: jax.Array, start_len: jax.Array,
                         slots: jax.Array, last_idx: jax.Array,
                         samp_t: jax.Array, samp_p: jax.Array,
                         samp_k: jax.Array, samp_pp: jax.Array,
                         samp_fp: jax.Array, key: jax.Array,
                         final: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array, PagedKVCache]:
            """One prompt chunk for each of K slots. tokens [K, C],
            start_len/slots/last_idx/samp_* [K]. Returns (first_tokens
            [K, replicated], counts, cache). K=1 is the single-request
            path; K>1 is BATCHED admission: K queued prefills run in one
            program and pay one dispatch (what that saves on an attached
            chip is not measured). The first token is sampled INSIDE this
            program from each row's last REAL position — prefill, row
            fetch and sample-one folded into one dispatch. The pool is
            global, so there is no per-slot cache slice: each slot's
            page-table row does the routing, and the K rows are sliced
            unrolled (NOT a gather: dynamic_slice is the op GSPMD already
            partitions correctly for the K=1 path). ``final`` [K] bool (a
            forward that takes it): which rows' chunks END their prompts —
            a call none of whose rows does runs no upper half at all."""
            K = tokens.shape[0]
            rows_tbl = tuple(jnp.concatenate(
                [jax.lax.dynamic_slice_in_dim(table, slots[k], 1, axis=0)
                 for k in range(K)], axis=0) for table in tables)
            logits, cache = call_forward(
                params, cache, rows_tbl, tokens, start_len,
                **({"slots": slots, "n_valid": last_idx + 1}
                   if rows_known else {}),
                **({"final": final} if self._rows_stop else {}))
            counts, count_rows = _prefill_counts(
                counts, tokens, start_len, slots, last_idx)
            # (A forward that ran its head on a row's last real position
            # alone hands back that ONE position.)
            rows = jax.lax.with_sharding_constraint(
                logits[:, 0, :] if logits.shape[1] == 1
                else jnp.take_along_axis(
                    logits, last_idx[:, None, None], axis=1)[:, 0, :],
                replicated)
            samp = SamplingParams(temperature=samp_t, top_p=samp_p,
                                  top_k=samp_k, presence_penalty=samp_pp,
                                  frequency_penalty=samp_fp)
            with jax.named_scope("sampling"):
                first = jax.lax.with_sharding_constraint(
                    sample(rows, samp, key, counts=count_rows), replicated)
            return first, counts, engine_cache(cache)

        def one_step(params, cache: PagedKVCache, counts: jax.Array,
                     tables: tuple[jax.Array, ...],
                     tokens: jax.Array, lengths: jax.Array,
                     active: jax.Array, samp: SamplingParams,
                     key: jax.Array, *, greedy: bool = False):
            """One decode step — the ONE copy of the forward+sample+advance
            body; every decode program is built from it. Returns
            (next_tokens, new_lengths, counts, cache) so the token/length
            feedback loop stays ON DEVICE across steps — host fetches
            happen asynchronously, steps behind. The page tables route
            the cache rows and are loop-invariant under the burst scan:
            pages are reserved for a request's whole lifetime at
            admission, so no page can change mid-burst. ``greedy=True``
            compiles the argmax-only variant — it skips the full-vocab
            sort the general sampler pays per step; the scheduler picks
            it whenever every active slot has temperature 0 AND zero
            penalties (a penalized argmax differs from plain argmax, so
            penalty requests ride the general path). The general path
            counts each step's INPUT token before sampling, so the
            penalty counts cover prompt + generated through step t when
            sampling t+1 (engine/sampling.py apply_penalties); the
            greedy path passes counts through untouched (aliased
            donation, zero cost)."""
            if not greedy:
                counts = counts.at[jnp.arange(counts.shape[0]),
                                   tokens].add(active.astype(jnp.int32))
            logits, cache = call_forward(params, cache, tables,
                                         tokens[:, None], lengths,
                                         active=active)
            with jax.named_scope("sampling"):
                if greedy:
                    next_tokens = jnp.argmax(
                        logits[:, 0, :], axis=-1).astype(jnp.int32)
                else:
                    next_tokens = sample(logits[:, 0, :], samp, key,
                                         counts=counts)
                next_tokens = jax.lax.with_sharding_constraint(
                    next_tokens, replicated)
            new_lengths = jnp.where(active, lengths + 1, lengths)
            return (next_tokens, new_lengths, counts, engine_cache(cache))

        self._prefill_fn = prefill_step
        self._decode_fns = _decode_programs(one_step, self._burst_depths,
                                            counters=c.layer_period > 0)

        if self.spec_k:
            from .speculative import make_spec_burst, make_spec_step

            def make_fwd(tbl):          # the tables, a tuple over groups
                def fwd(params, c_, tokens, lengths, cache, active=None):
                    return call_forward(params, cache, tbl, tokens,
                                        lengths, active=active, spec=True)
                return fwd

            self._spec_scan_len = max(
                1, self.decode_burst // (self.spec_k + 1))
            self._spec_scan = make_spec_burst(
                make_fwd, c, self.spec_k, self._spec_scan_len)

            @partial(jax.jit, donate_argnums=(1,))
            def spec_step1(params, cache, table, hist, tokens, lengths,
                           active, draft_ok):
                return make_spec_step(make_fwd(table), c, self.spec_k)(
                    params, cache, hist, tokens, lengths, active, draft_ok)
            self._spec_step = spec_step1

    def _warm_decode_variants(self) -> None:
        """AOT lower+compile the greedy AND general decode programs from
        input avals (no device buffers touched), populating the persistent
        compilation cache — the eventual first real call of the not-yet-
        used variant re-traces but hits the disk cache, turning a 30-60 s
        mid-serving stall into a ~1-2 s one. Best-effort: a failure means
        lazy compilation as before, and is kept for stats()."""
        try:
            for greedy in (False, True):
                scans = self._decode_fns[greedy][1]
                for depth in scans or (1,):    # no scan: the step program
                    self.compiled_decode(greedy, depth)
        except Exception as e:
            logger.warning("decode program pre-warm failed", exc_info=True)
            self._prewarm_error = repr(e)

    def _state_avals(self) -> tuple:
        """Avals (shape, dtype, sharding) of what every step program takes
        first — params, cache, penalty counts, page tables — and of the
        PRNG key it takes last. Metadata of the live buffers only. The
        key is left unplaced, as it is at a real call."""
        def aval(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=getattr(x, "sharding", None))
        rep = NamedSharding(self.mesh, P())
        tables = tuple(jax.ShapeDtypeStruct(
            g.allocator.table.shape, jnp.int32, sharding=rep)
            for g in self.kv_groups)
        return ((jax.tree.map(aval, self.params),
                 jax.tree.map(aval, self.cache),
                 aval(self._d_counts), tables),
                jax.ShapeDtypeStruct(self._rng.shape, self._rng.dtype))

    def compiled_decode(self, greedy: bool = True,
                        depth: int | None = None) -> jax.stages.Compiled:
        """The compiled decode-burst program at ``depth`` (default: the
        deep burst; 1 = the single-step program), built from input avals —
        no device buffer is touched, and where the program is already in
        the persistent cache the compile is a lookup. For reading what
        actually serves: ``as_text()`` shows whether the attention kernel
        is in the program (``tpu_custom_call``) and which collectives the
        partitioner put in; ``memory_analysis()`` what it needs."""
        step, scans = self._decode_fns[greedy]
        depth = self.decode_burst if depth is None else depth
        fn = step if depth == 1 else scans[depth]
        rep = NamedSharding(self.mesh, P())

        def vec(dt):
            return jax.ShapeDtypeStruct((self.B,), dt, sharding=rep)
        samp = SamplingParams(temperature=vec(jnp.float32),
                              top_p=vec(jnp.float32), top_k=vec(jnp.int32),
                              presence_penalty=vec(jnp.float32),
                              frequency_penalty=vec(jnp.float32))
        state, key = self._state_avals()
        return fn.lower(*state, vec(jnp.int32), vec(jnp.int32),
                        vec(jnp.bool_), samp, key).compile()

    def compiled_prefill(self, bucket: int, k: int = 1
                         ) -> jax.stages.Compiled:
        """The compiled prefill program for ``k`` same-bucket chunks of
        ``bucket`` tokens — :meth:`compiled_decode`'s twin. The per-row
        inputs are host arrays at a real call (_exec_prefill), so their
        avals carry no placement here either."""
        def row(dt, *shape):
            return jax.ShapeDtypeStruct((k, *shape), dt)
        state, key = self._state_avals()
        return self._prefill_fn.lower(
            *state, row(jnp.int32, bucket), row(jnp.int32), row(jnp.int32),
            row(jnp.int32), row(jnp.float32), row(jnp.float32),
            row(jnp.int32), row(jnp.float32), row(jnp.float32),
            key, *([row(jnp.bool_)] if self._rows_stop else [])).compile()

    def _upload(self, host: np.ndarray) -> jax.Array:
        """Replicated device copy of a host mirror — of a PRIVATE copy of
        it. The runtime may read the host buffer after ``device_put``
        returns (the CPU backend aliases a 64-byte-aligned buffer
        outright), and the mirrors are mutated in place: ``lengths``
        advances right after a burst is dispatched, the page table at
        every admission, release and ring rotation. Handing over the
        mirror itself let a dispatched program see the NEXT state —
        attention reading a burst past the valid length."""
        return jax.device_put(np.array(host),
                              NamedSharding(self.mesh, P()))

    def _device_tables(self) -> tuple[jax.Array, ...]:
        """The device page tables, one a cache group: a group's table is
        uploaded again where it changed (``_table_dirty``: every group's,
        by admission, release or handoff; ``CacheGroup.dirty``: its own,
        by ring rotation too)."""
        old = self._d_tables or (None,) * len(self.kv_groups)
        new = []
        for g, table in zip(self.kv_groups, old):
            if self._table_dirty or g.dirty or table is None:
                table = self._upload(g.allocator.table)
                g.dirty = False
            new.append(table)
        self._d_tables = tuple(new)
        self._table_dirty = False
        return self._d_tables

    def _enable_debug_nans(self) -> None:
        """The numerics sanitizer (SURVEY.md §5): compiled programs raise on
        NaN production instead of streaming garbage tokens. The flag is
        PROCESS-GLOBAL; the previous value is saved here and restored on
        stop() so one engine's config doesn't tax every other program in
        the process forever — and re-applied on start() so a restarted
        engine keeps its sanitizer."""
        if self.cfg.debug_nans and self._prev_debug_nans is None:
            self._prev_debug_nans = bool(jax.config.jax_debug_nans)
            jax.config.update("jax_debug_nans", True)

    # -- public API ----------------------------------------------------------
    async def start(self) -> None:
        if self.supervisor.state == "failed":
            raise EngineUnavailable(
                "engine is failed (restart budget exhausted or fatal "
                "fault); traffic stays on the fallback chain")
        if self._loop_task is None:
            self._stopped = False        # restartable after stop()
            self._enable_debug_nans()
            loop = asyncio.get_running_loop()
            if self._loop is not loop:
                # asyncio.Event binds to the first loop that awaits it; a
                # restarted engine on a NEW loop (sequential asyncio.run
                # phases — the bench does this between rungs) would die
                # with a cross-loop RuntimeError at its first idle
                # `_work_event.wait()`, silently stranding every later
                # submit. Fresh event per serving loop; submit()/stop()
                # set it only after start(), so no waiter is orphaned.
                self._work_event = asyncio.Event()
                self._loop = loop
            self._loop_task = loop.create_task(self._run_loop())
            if (self.supervisor.watchdog_ms > 0
                    and (self._watchdog_task is None
                         or self._watchdog_task.done())):
                self._watchdog_task = loop.create_task(
                    self._watchdog_loop())
        if (self._warm_thread is None and self.cfg.prewarm_sampler_variants
                and jax.default_backend() == "tpu"):
            # Pre-lower+compile BOTH sampler variants into the persistent
            # compilation cache off-thread: without this, the first
            # temperature>0 request after a greedy-only warm-up stalls
            # every in-flight decode for a full XLA compile.
            import threading
            self._warm_thread = threading.Thread(
                target=self._warm_decode_variants, daemon=True)
            self._warm_thread.start()

    async def stop(self) -> None:
        self._stopped = True
        if self._watchdog_task is not None:
            self._watchdog_task.cancel()
            try:
                await self._watchdog_task
            except asyncio.CancelledError:
                pass
            self._watchdog_task = None
        self._work_event.set()
        if self._loop_task is not None:
            await self._loop_task
            self._loop_task = None
        if self._warm_thread is not None:
            # A compile still running in a daemon thread when the
            # interpreter tears down takes the process with it.
            await asyncio.to_thread(self._warm_thread.join)
        if self._prev_debug_nans is not None:
            jax.config.update("jax_debug_nans", self._prev_debug_nans)
            self._prev_debug_nans = None
        # Flush terminal deltas so no consumer awaits a stream forever.
        for req in list(self._running.values()):
            req.out_queue.put_nowait(Delta(error="engine stopped"))
            self._release(req)
        if self._head is not None:
            self._head.out_queue.put_nowait(Delta(error="engine stopped"))
            self._head = None
        while not self._queue.empty():
            req = self._queue.get_nowait()
            req.out_queue.put_nowait(Delta(error="engine stopped"))
        self.supervisor.transition("stopped", "stop() requested")

    async def submit(self, req: GenRequest) -> None:
        """Admit a request; raises EngineOverloaded when the queue is
        full, EngineUnavailable while the supervisor has the engine
        draining/restarting/failed (the router fails over)."""
        if not self.supervisor.is_accepting():
            state = self.supervisor.state
            raise EngineUnavailable(
                f"engine is {state}",
                retry_after_s=(self.supervisor.backoff_s()
                               if state == "restarting" else None))
        max_prompt = self.S - 1 - self.spec_k
        if len(req.prompt_ids) > max_prompt:
            raise EngineOverloaded(
                f"prompt of {len(req.prompt_ids)} tokens exceeds engine "
                f"max_seq_len {self.S}")
        req.max_tokens = max(1, min(req.max_tokens,
                                    self.S - len(req.prompt_ids)))
        # HBM headroom watermark (ISSUE 8): when the runtime allocator
        # reports less free device memory than the configured fraction,
        # shed at admission exactly like a full queue — 429 + Retry-After
        # through the PR 3 path — instead of letting the next compile or
        # fragmentation event OOM mid-stream. Inert where the backend has
        # no allocator stats (CPU) unless a test injects a mem_fn.
        wm = self.cfg.hbm_headroom_watermark
        if wm > 0:
            frac = self.ledger.headroom_fraction()
            if frac is not None and frac < wm:
                self._shed_n += 1
                self._watermark_sheds += 1
                if self.flight is not None:
                    from ..obs.flight import SHED
                    self.flight.record(SHED, queued=self._queue.qsize(),
                                       free_slots=self._free_slot_count(),
                                       val=frac,
                                       rid=req.request_id or None)
                raise EngineOverloaded(
                    f"device memory headroom {frac:.1%} below the "
                    f"{wm:.0%} watermark")
        if self._disagg is not None:
            # Goodput-first admission (ISSUE 13): shed now — 429 with a
            # numeric Retry-After through the same path as a full queue
            # — when neither pool's predicted attainment meets the
            # request's SLO; a TTFT-only risk admits clamped instead.
            self._disagg.admit_or_shed(req)
        req.detok = IncrementalDetokenizer(self.tokenizer)
        try:
            self._queue.put_nowait(req)
        except asyncio.QueueFull:
            self._shed_n += 1
            if self.flight is not None:
                from ..obs.flight import SHED
                self.flight.record(SHED, queued=self._queue.qsize(),
                                   free_slots=self._free_slot_count(),
                                   rid=req.request_id or None)
            raise EngineOverloaded("engine admission queue is full") from None
        await self.start()
        self._work_event.set()
        # Re-stamp the heartbeat at admission: an engine that idled past
        # the watchdog deadline is NOT stalled — the deadline must start
        # from this wake-up, not from the last step before the idle gap.
        self.supervisor.heartbeat(self.flight.seq
                                  if self.flight is not None else 0)

    def _free_slot_count(self) -> int:
        """Free slots across every pool (ONE pool unified, two disagg)."""
        return sum(len(p.free) for p in self._pools)

    @property
    def _free_slots(self) -> list:
        """The admit pool's free list — the WHOLE free list when
        disaggregation is off (one pool), the prefill pool's under
        disaggregation. The pre-pool name, kept because the test surface
        and operator debug consoles reach for it; writes pass through to
        the pool so fault-injection tests can still pin slots."""
        return self._admit_pool.free

    @_free_slots.setter
    def _free_slots(self, slots) -> None:
        self._admit_pool.free = slots

    def retry_after_hint_s(self) -> float:
        """How long a just-shed client should wait before retrying, from the
        fitted step-time / queue-wait telemetry (ISSUE 3): the measured
        admission wait plus one decode step per queued request ahead of it.
        Bounded to [1, 30] s — a Retry-After, not a promise."""
        step_ms = self._ema_step_ms_stats
        if step_ms is None:
            est = self._step_ms_estimate()
            step_ms = est if est is not None else 0.0
        wait_ms = self._queue_wait_ema_ms or 0.0
        est_ms = wait_ms + step_ms * max(1, self._queue.qsize())
        return min(30.0, max(1.0, est_ms / 1000.0))

    async def stream(self, req: GenRequest) -> AsyncIterator[Delta]:
        """Yield deltas for a submitted request until it finishes."""
        while True:
            delta: Delta = await req.out_queue.get()
            yield delta
            if delta.finish_reason is not None or delta.error is not None:
                return

    # -- the batching loop ---------------------------------------------------
    async def _run_loop(self) -> None:
        logger.info("engine loop started (B=%d, S=%d)", self.B, self.S)
        sup = self.supervisor
        sup.transition("serving", "scheduler loop started")
        sup.heartbeat(self.flight.seq if self.flight is not None else 0)
        self._sched.start()
        try:
            await self._serve(sup)
        finally:
            self._sched.stop()
        logger.info("engine loop stopped")

    async def _serve(self, sup) -> None:
        while not self._stopped:
            # Clear BEFORE stepping: a submit() that lands during the await
            # inside _step sets the event and must not be wiped afterwards
            # (missed-wakeup race — the request would strand in the queue).
            self._work_event.clear()
            try:
                progressed = await self._step()
                # Heartbeat AFTER the step returns (piggybacked on the
                # flight seq): a stuck _step leaves the heartbeat stale,
                # which is exactly what the watchdog needs to see.
                sup.heartbeat(self.flight.seq if self.flight is not None
                              else 0)
                if progressed:
                    self._clean_steps += 1
                    if self._clean_steps == 50:
                        # A sustained healthy stretch re-earns the full
                        # restart budget — one crash per day must not
                        # accumulate into "budget exhausted" forever.
                        sup.reset_restarts()
            except asyncio.CancelledError:
                # Watchdog kill path: the canceller owns recovery.
                raise
            except Exception as e:           # engine must never die silently
                logger.exception("engine step failed")
                from ..reliability.supervisor import EngineFailure
                await self._on_step_failure(EngineFailure.classify(e))
                progressed = True
            if not progressed:
                self._sched.switch("parked")
                try:
                    await self._work_event.wait()
                finally:
                    self._sched.switch("other")
                sup.heartbeat(self.flight.seq if self.flight is not None
                              else 0)

    async def _on_step_failure(self, failure) -> None:
        """Supervised recovery from a classified step-loop failure
        (ISSUE 14). In-flight streams get an in-band error delta (the
        PR 3 mid-stream contract — providers/local.py turns it into a
        well-formed SSE error frame and partial usage records
        downstream); queued-but-unstarted admissions stay queued for the
        restarted engine, or are flushed with errors when the engine
        parks in `failed` (the router's fallback chain takes over either
        way, via EngineUnavailable at admission)."""
        sup = self.supervisor
        logger.error("engine failure (%s): %s", failure.kind, failure)
        sup.note_failure(failure)
        self._clean_steps = 0
        # _prefilling is a secondary index into _running (admission adds
        # to both), so flushing _running covers mid-prefill requests.
        for req in list(self._running.values()):
            req.out_queue.put_nowait(
                Delta(error=f"engine failure: {failure}"))
            self._release(req)
        if failure.kind == "fatal" or not sup.can_restart():
            reason = ("fatal failure (restart would loop on it)"
                      if failure.kind == "fatal" else
                      f"restart budget exhausted "
                      f"({sup.max_restarts} attempts)")
            logger.error("engine parked in failed state: %s", reason)
            sup.transition("failed", reason)
            self._stopped = True
            self._fail_queued(f"engine failed: {failure}")
            return
        sup.transition("restarting", f"{failure.kind}: {failure}")
        backoff = sup.backoff_s()
        sup.note_restart()
        if backoff > 0:
            await asyncio.sleep(backoff)
        try:
            self._rebuild_state()
            sup.transition("serving", "supervised restart complete")
        except Exception:
            logger.exception("engine state re-init failed")
            sup.transition("failed", "restart re-init failed")
            self._stopped = True
            self._fail_queued("engine failed: restart re-init failed")

    def _fail_queued(self, msg: str) -> None:
        """Flush queued-but-unstarted admissions with terminal errors —
        only on the no-recovery (failed) paths."""
        if self._head is not None:
            self._head.out_queue.put_nowait(Delta(error=msg))
            self._head = None
        while not self._queue.empty():
            req = self._queue.get_nowait()
            req.out_queue.put_nowait(Delta(error=msg))

    def _rebuild_state(self) -> None:
        """Tear down and rebuild device + scheduler state for a
        supervised restart. Ordering matters: the compile monitor
        re-arms FIRST so the rebuild's own compiles are attributed
        instead of lost (PR 8's install-before-compile bug class, same
        shape as PR 7's `_work_event` rebinding)."""
        from ..obs.device import install_compile_monitor
        install_compile_monitor()
        # donate_argnums may have consumed the cache buffer before the
        # failure: rebuild device state so the engine recovers instead
        # of failing every subsequent step on a deleted array. The radix
        # prefix cache restarts empty — its KV pages died with the pool,
        # so "re-seed" is organic re-warming, not resurrection.
        self._init_state()
        for pool in self._pools:
            pool.reset_free()
        for req in self._running.values():
            self._sched.left(req)       # dropped unreleased: its wall ends
        self._running.clear()
        self._prefilling.clear()
        # The ledger's tracked buffers were donated/freed with the old
        # cache; rebuild it against the new buffers so /metrics doesn't
        # reconcile against ghosts (restart-recovery gap, ISSUE 14).
        self.ledger = self._build_ledger()

    async def _watchdog_loop(self) -> None:
        """Stall detector (ISSUE 14): when the scheduler heartbeat goes
        stale past `watchdog_ms` WHILE work is pending, cancel the
        scheduler task and route the stall through the same supervised
        restart path as a crash. An idle engine parked on its work
        event never trips it."""
        sup = self.supervisor
        from ..reliability.supervisor import EngineFailure
        while not self._stopped:
            # Recomputed each tick (capped at 250 ms) so watchdog_ms can
            # be tuned on a live engine without restarting the task.
            await asyncio.sleep(min(0.25, max(0.005,
                                              sup.watchdog_ms / 4000.0)))
            if self._stopped or sup.state != "serving":
                continue
            busy = bool(self._running or self._prefilling
                        or self._head is not None
                        or not self._queue.empty())
            if not sup.is_stalled(busy):
                continue
            age_ms = sup.heartbeat_age_s() * 1000.0
            logger.error("watchdog: engine stalled (heartbeat %.0f ms "
                         "past the %.0f ms deadline)", age_ms,
                         sup.watchdog_ms)
            task = self._loop_task
            if task is not None and not task.done():
                task.cancel()
                try:
                    await task
                except asyncio.CancelledError:
                    pass
                except Exception:
                    logger.exception("stalled loop died on cancel")
            self._loop_task = None
            await self._on_step_failure(EngineFailure(
                f"scheduler loop stalled: heartbeat {age_ms:.0f} ms past "
                f"the {sup.watchdog_ms:.0f} ms watchdog", kind="stall"))
            if not self._stopped:
                loop = asyncio.get_running_loop()
                self._loop_task = loop.create_task(self._run_loop())

    async def drain(self, *, restart: bool = False,
                    deadline_s: float | None = None) -> dict[str, Any]:
        """Administrative drain (ISSUE 14): stop admissions, let
        in-flight work finish under a bounded deadline, force-cancel
        stragglers past it, then either restart the engine in place
        (config hot-reload / planned maintenance) or stop it (SIGTERM).
        Returns a summary for the admin caller."""
        sup = self.supervisor
        sup.transition("draining", "administrative drain")
        limit = (sup.drain_deadline_ms / 1000.0
                 if deadline_s is None else deadline_s)
        t0 = time.monotonic()
        forced = 0
        while (self._running or self._head is not None
               or not self._queue.empty()):
            if time.monotonic() - t0 > limit:
                # Deadline expired: force-cancel stragglers. The
                # scheduler's cancel path frees slots but emits no
                # terminal delta (its client-gone semantics) — a drain's
                # clients are still connected, so the terminal frame is
                # emitted HERE; queued requests get terminal errors
                # directly (they never started).
                for req in list(self._running.values()):
                    req.cancelled = True
                    req.out_queue.put_nowait(
                        Delta(finish_reason="cancelled"))
                    forced += 1
                if self._head is not None:
                    self._head.cancelled = True
                    self._head.out_queue.put_nowait(
                        Delta(finish_reason="cancelled"))
                    forced += 1
                while not self._queue.empty():
                    req = self._queue.get_nowait()
                    req.out_queue.put_nowait(
                        Delta(error="engine draining"))
                    forced += 1
                self._work_event.set()
                t1 = time.monotonic()
                while self._running and time.monotonic() - t1 < 2.0:
                    await asyncio.sleep(0.01)
                break
            self._work_event.set()
            await asyncio.sleep(0.01)
        summary = {"forced_cancel": forced,
                   "drain_s": round(time.monotonic() - t0, 3)}
        if restart:
            sup.transition("restarting", "planned restart")
            self._stopped = True
            self._work_event.set()
            if self._loop_task is not None:
                await self._loop_task
                self._loop_task = None
            self._rebuild_state()
            self._stopped = False
            sup.transition("serving", "planned restart complete")
            summary["restarted"] = True
        else:
            await self.stop()
            summary["restarted"] = False
        return summary

    async def _step(self) -> bool:
        """One scheduler iteration. Emission always happens here, on the
        event-loop thread (asyncio.Queue is not thread-safe); worker-thread
        calls only touch device programs and host numpy state.

        With the flight recorder on, the iteration leaves ONE step record
        (composition, burst depth, tokens, fitted-vs-measured step time)
        plus lifecycle records for admissions/evictions it performed —
        appended loop-side only, after the worker-thread awaits return."""
        if self.fault_plan is not None:
            stall_s = self.fault_plan.on_step()
            if stall_s > 0:
                # Injected silent stall: the loop stays alive but stops
                # stepping — the failure shape only the watchdog sees.
                await asyncio.sleep(stall_s)
        fl = self.flight
        t_step0 = fl.clock() if fl is not None else 0.0
        clamps0 = self._busy_clamps
        n_chunks = 0                  # compiled prefill dispatches this step
        n_tok = 0                     # tokens emitted downstream this step
        spec_acc_n = 0                # accepted draft tokens landed this step
        # 1. Admit into free slots (_admit).
        with self._sched.span("admit"):
            self._admit(fl)

        # The prefill waits' wall as the ledger read it: one reading for its
        # counter, the requests' credit and the prefill pool's step record.
        pf_wall_ms = 0.0
        # 2. Advance each pending prefill by ONE chunk (chunked-prefill
        #    interleave: a long prompt never blocks decode for more than one
        #    chunk — SURVEY.md §7 hard part (6)). Same-bucket chunks group
        #    into ONE compiled call (batched admission — one dispatch
        #    for the group, see _prefill_chunk_group), the group
        #    size snapped down to a compiled K rung.
        eligible: list[GenRequest] = []
        for slot, req in list(self._prefilling.items()):
            if req.cancelled:
                self._finish(req, "cancelled", emit=False)
                continue
            eligible.append(req)
        batch_k = self._prefill_k_rungs[0]
        if batch_k <= 1 or len(eligible) <= 1:
            for req in eligible:
                if req.cancelled:
                    # Cancelled during an earlier request's await this tick:
                    # don't burn one more prefill chunk on a dead client.
                    self._finish(req, "cancelled", emit=False)
                    continue
                with self._sched.wait("prefill_wait", (req,)):
                    prompt_done = await asyncio.to_thread(
                        self._prefill_one_chunk, req)
                pf_wall_ms += self._sched.wait_ms
                n_chunks += 1
                self._record_prefill(fl)
                if prompt_done:
                    with self._sched.span("emit"):
                        del self._prefilling[req.slot]
                        if self._disagg is not None:
                            self._handoff(req)
                        n_tok += 1
                        self._emit_token(req)  # first token, off prefill
        else:
            groups: dict[int, list[GenRequest]] = {}
            with _device_phase("sched.plan"):
                for req in eligible:
                    pos = req.prefill_pos
                    ch = min(self.prefill_chunk, len(req.prompt_ids) - pos)
                    bucket = min(_bucket(ch, self.prefill_chunk),
                                 self.S - pos)
                    groups.setdefault(bucket, []).append(req)
            for reqs in groups.values():
                pending = reqs
                while pending:
                    # Re-check cancellation per dispatch: a cancel that
                    # landed during a previous group's await must not burn
                    # one more prefill chunk, and dropping it here lets the
                    # survivors re-snap to a smaller compiled K rung.
                    live: list[GenRequest] = []
                    for req in pending:
                        if req.cancelled:
                            self._finish(req, "cancelled", emit=False)
                        else:
                            live.append(req)
                    if not live:
                        break
                    batch = self.prefill_groups(live)[0]
                    pending = live[len(batch):]
                    with self._sched.wait("prefill_wait", batch):
                        dones = await asyncio.to_thread(
                            self._prefill_chunk_group, batch)
                    pf_wall_ms += self._sched.wait_ms
                    n_chunks += 1
                    self._record_prefill(fl)
                    with self._sched.span("emit"):
                        for req, prompt_done in zip(batch, dones):
                            if prompt_done:
                                del self._prefilling[req.slot]
                                if self._disagg is not None:
                                    self._handoff(req)
                                n_tok += 1
                                self._emit_token(req)

        n_tok_prefill = n_tok           # first tokens, sampled off prefill
        if self._disagg is not None and fl is not None and n_chunks:
            # Disaggregated mode emits the PREFILL pool's step record
            # here, with its own wall window, so the per-pool Perfetto
            # lanes (tools/flight_report.py) show where each pool's time
            # actually went; the decode pool's record lands after the
            # burst below. A unified engine keeps its single combined
            # record — snapshot-identical to the pre-pool format.
            from ..obs import flight as _fl
            self._disagg.note_prefill_wall(pf_wall_ms / n_chunks)
            fitted = self._ema_step_ms_stats
            fl.record(
                _fl.STEP, flag=_fl.F_PREFILL, chunks=n_chunks,
                tokens=n_tok_prefill, dur_ms=pf_wall_ms,
                pool=_fl.POOL_PREFILL,
                active=len(self._running),
                free_slots=self._free_slot_count(),
                queued=self._queue.qsize() + (1 if self._head else 0),
                free_pages=self.allocator.free_pages,
                fitted_ms=(fitted if fitted is not None
                           else float("nan")))

        # 3. A decode burst for all slots in decode phase. Burst depth adapts:
        #    stay shallow when new work is waiting (prefill responsiveness →
        #    TTFT), go deep when the batch is just decoding (throughput; deep
        #    bursts hide the device↔host fetch latency).
        decoding = [r for r in self._running.values()
                    if not r.done and r.slot not in self._prefilling]
        if decoding:
            busy, spec_now, spec_probe, burst = self._plan_burst(decoding)
            if spec_now:
                spec_acc0 = self._spec_accepted_total
                with self._sched.wait("decode_wait"):
                    step_tokens = await asyncio.to_thread(
                        self._spec_burst, burst, spec_probe)
                spec_acc_n = self._spec_accepted_total - spec_acc0
            else:
                with self._sched.wait("decode_wait"):
                    step_tokens = await asyncio.to_thread(
                        self._decode_burst, burst)
            # The burst's wall is the wait's, by the ledger's readings.
            dec_wall_ms = self._sched.wait_ms
            with self._sched.span("emit"):
                for tokens in step_tokens:          # in generation order
                    for req in decoding:
                        if req.done:
                            continue
                        tok = int(tokens[req.slot])
                        if tok < 0:
                            # Lag-one pipelining: this token array predates
                            # the slot's current request (masked in
                            # _flush_entry).
                            continue
                        req.generated.append(tok)
                        n_tok += 1
                        self._emit_token(req)
            self._sched.decode_tokens += n_tok - n_tok_prefill
        progressed = bool(decoding) or bool(self._prefilling)
        if not progressed and self._free_slot_count() and (
                self._head is not None or not self._queue.empty()):
            # Slots freed DURING this step (e.g. every prefilling request
            # cancelled mid-chunk) while admissions still wait: phase 1
            # already ran with no free slot, and nothing but submit()
            # sets the work event — without this the loop parks and
            # strands the queue until the next request arrives (latent
            # since the chunked-prefill interleave; the flight recorder's
            # cancellation chaos test caught it).
            progressed = True
        if fl is not None and (n_chunks or decoding):
            with _device_phase("sched.plan"):
                # The step record: what this iteration ran, how long it took,
                # and the scheduler's fitted step time next to the measured
                # one — the per-decision feed the EMAs compress away.
                from ..obs import flight as _fl
                flag = 0
                depth = 0
                if n_chunks:
                    flag |= _fl.F_PREFILL
                if decoding:
                    flag |= _fl.F_DECODE
                    depth = burst
                    if spec_now:
                        flag |= _fl.F_SPEC
                    if busy:
                        flag |= _fl.F_BUSY
                    if self._busy_clamps > clamps0:
                        flag |= _fl.F_CLAMPED
                # The steady-pair EMA gauge, not _step_ms_estimate(): the
                # fit walks every wall sample and would cost more per step
                # than the record itself.
                fitted = self._ema_step_ms_stats
                if self._disagg is not None:
                    # The prefill pool's share of this iteration already went
                    # out after phase 2; this record is the decode pool's
                    # view (dur = burst wall, so steps_overlapping() sums
                    # true decode occupancy). Prefill-only iterations emit
                    # nothing here.
                    if decoding:
                        fl.record(
                            _fl.STEP, flag=flag & ~_fl.F_PREFILL,
                            depth=depth, tokens=n_tok - n_tok_prefill,
                            dur_ms=dec_wall_ms,
                            val=dec_wall_ms,
                            pool=_fl.POOL_DECODE,
                            active=len(self._running),
                            free_slots=self._free_slot_count(),
                            queued=(self._queue.qsize()
                                    + (1 if self._head else 0)),
                            free_pages=self.allocator.free_pages,
                            fitted_ms=(fitted if fitted is not None
                                       else float("nan")))
                else:
                    fl.record(
                        _fl.STEP, flag=flag, depth=depth, tokens=n_tok,
                        chunks=n_chunks,
                        dur_ms=1000.0 * (fl.clock() - t_step0),
                        spec_acc=spec_acc_n,
                        val=dec_wall_ms if decoding else 0.0,
                        active=len(self._running),
                        free_slots=self._free_slot_count(),
                        queued=self._queue.qsize() + (1 if self._head else 0),
                        free_pages=self.allocator.free_pages,
                        fitted_ms=(fitted if fitted is not None
                                   else float("nan")))
        return progressed

    @_device_phase("sched.plan")
    def _plan_burst(self, decoding: list[GenRequest]
                    ) -> tuple[bool, bool, bool, int]:
        """The loop's synchronous prelude to a decode wait: which program
        the burst runs and how deep. Returns ``(busy, spec_now,
        spec_probe, burst)``. Event-loop thread; its wall is the ledger's
        ``other``, and the span ``sched.plan`` says so on the profiler's
        clock — an idle gap under NO program span is then the hand-off
        between the threads."""
        # Prefill-aware (DistServe/Sarathi-style interleave): any
        # admission waiting — queued, parked at the FIFO head for a
        # page reservation, or mid-chunked-prefill — clamps the next
        # burst so prefill work never starves behind a deep scan.
        busy = (self._head is not None or not self._queue.empty()
                or bool(self._prefilling))
        # Speculation verifies against argmax, so it engages only while
        # EVERY active slot is greedy (the common serving case);
        # sampled requests flip the whole batch to the normal burst
        # path for their lifetime — mixed batches stay correct, just
        # unaccelerated.
        spec_now = self.spec_k and self._all_greedy()
        # Adaptive drafting gate: drafting only pays while accepted
        # tokens/step clears the verify forward's overhead
        # (config.spec_min_tokens_per_step). Below it, decode normally
        # and re-probe with a single spec step every
        # spec_probe_interval rounds — so enabling speculation in
        # config is safe for non-repetitive traffic.
        spec_probe = False
        if spec_now and self._spec_wall_gate_on:
            # Baseline probe: the wall gate needs a NORMAL-path step
            # time to compare against, and spec-open traffic never
            # runs normal bursts. Two consecutive normal rounds (a
            # steady same-depth pair is what lands a wall sample),
            # immediately while no baseline exists, then refreshed
            # every 8*spec_probe_interval spec rounds.
            if self._spec_base_rounds > 0:
                self._spec_base_rounds -= 1
                spec_now = False
            else:
                est = self._step_ms_estimate()
                if est is not None:
                    self._spec_base_fails = 0
                self._spec_base_ctr += 1
                # Periodic refresh only while a baseline EXISTS —
                # once the starvation guard trips (workload can't
                # land wall samples), probing again by schedule
                # would pay the same fruitless normal rounds
                # forever.
                if ((est is None and self._spec_base_fails < 4)
                        or (est is not None
                            and self._spec_base_ctr
                            >= 8 * self.spec_probe_interval)):
                    self._spec_base_ctr = 0
                    if est is None:
                        self._spec_base_fails += 1
                    self._spec_base_rounds = 1
                    spec_now = False
        if spec_now and (self.spec_min_tps > 0
                         or self._spec_wall_gate_on):
            # A batch with NO measured slots always drafts — the burst
            # IS the measurement. Unmeasured slots in a mixed batch
            # count optimistically (k+1) so fresh requests can re-open
            # the gate; one low burst closes it again. The wall-clock
            # term applies even with the acceptance threshold
            # disabled (spec_min_tokens_per_step=0): each protects
            # against a different failure mode.
            below = False
            if self.spec_min_tps > 0:
                slots = [r.slot for r in decoding]
                if self.spec_floor > 0:
                    # Per-slot suspension already benches poor slots —
                    # their frozen EMAs must not drag the BATCH mean
                    # below the threshold and close the gate on the
                    # slots that are still profiting. (All-suspended
                    # batches skip the burst below regardless of what
                    # the mean says.)
                    slots = [s for s in slots
                             if not self._spec_suspended[s]] or slots
                ema = self._spec_ema[slots]
                if not np.all(np.isnan(ema)):
                    mean_tps = float(np.mean(np.where(
                        np.isnan(ema), self.spec_k + 1, ema)))
                    below = mean_tps < self.spec_min_tps
            wall_lose = self._spec_wall_loses()
            if below or wall_lose:
                self._spec_probe_ctr += 1
                if self._spec_probe_ctr >= self.spec_probe_interval:
                    self._spec_probe_ctr = 0
                    spec_probe = True            # 1-step re-measure
                    # A probe re-measures ACCEPTANCE only. If the
                    # WALL term is what closed the gate, drop the
                    # wall gauge every few probe cycles so one full
                    # burst can re-time it under current conditions
                    # (bounded tax: one possibly-slow burst per 4
                    # probe intervals). An acceptance-only close
                    # must NOT drop it — no full spec burst would
                    # run to re-measure, silently losing the gauge
                    # (and its stats field) while a stale-free
                    # baseline still protects the reopen path.
                    if wall_lose and not below:
                        self._spec_wall_age += 1
                        if (self._spec_ms_per_tok is not None
                                and self._spec_wall_age >= 4):
                            self._spec_wall_age = 0
                            self._spec_ms_per_tok = None
                else:
                    spec_now = False
        if spec_now and self.spec_floor > 0 and not spec_probe:
            # Per-slot adaptive drafting (spec_acceptance_floor):
            # suspended slots ride along in the k+1-wide verify at a
            # deterministic 1 token/step, so when EVERY decoding slot
            # is suspended the burst is pure overhead — decode
            # normally instead, and every spec_probe_interval such
            # rounds run ONE probe burst with the mask lifted so
            # suspended slots get re-measured (text regimes change;
            # a permanent bench would strand them). A mixed batch
            # keeps bursting (drafting slots still profit) and the
            # same cadence lifts the mask for its benched slots.
            susp = sum(bool(self._spec_suspended[r.slot])
                       for r in decoding)
            if susp:
                self._spec_suspend_probe_ctr += 1
                if (self._spec_suspend_probe_ctr
                        >= self.spec_probe_interval):
                    self._spec_suspend_probe_ctr = 0
                    spec_probe = True        # 1-step, mask lifted
                elif susp == len(decoding):
                    spec_now = False
        # While a spec burst is in flight (lag-one), the host lengths
        # lag dispatch by a data-dependent amount — cap against the
        # worst case (every in-flight step fully accepted).
        inflight = self._spec_inflight_advance() if self.spec_k else 0
        if spec_now:
            # A slot whose dispatch-true length is within k of the
            # cache extent can't fit a k+1-wide verify (possible when
            # lag-one normal bursts ran it ahead of emission): fall
            # back to the 1-wide normal path until emission retires it.
            spec_now = all(
                self.S - (int(self.lengths[r.slot]) + inflight)
                >= self.spec_k + 1
                for r in decoding)
        if spec_now:
            # Speculative steps advance 1..k+1 positions each; cap so a
            # fully-accepted burst fits every slot's cache reserve and
            # token budget.
            kp1 = self.spec_k + 1
            burst = 1 if (busy or spec_probe) else self._spec_scan_len
            for r in decoding:
                ub = int(self.lengths[r.slot]) + inflight
                room = (self.S - ub) // kp1
                dispatched = ub - len(r.prompt_ids) + 1
                left = max(1, r.max_tokens - dispatched)
                burst = min(burst, max(1, room), -(-left // kp1))
            if self._swa_ring_pages:
                self._swa_rotate(decoding, inflight, max(1, burst) * kp1)
            burst = max(1, burst)
        else:
            burst = self._burst_depth(busy)
            # Never burst past any slot's cache capacity or token
            # budget — both computed from DISPATCH-TRUE state
            # (self.lengths advances at dispatch): with lag-one
            # pipelining, len(r.generated) lags a burst behind and
            # would let a whole discarded burst through. `inflight`
            # covers a pending spec burst (mode switch): its
            # data-dependent advance lands on the host mirrors inside
            # _decode_burst, AFTER these caps are computed.
            for r in decoding:
                ub = int(self.lengths[r.slot]) + inflight
                dispatched = ub - len(r.prompt_ids) + 1
                burst = min(burst, self.S - ub,
                            max(1, r.max_tokens - dispatched))
            burst = max(1, burst)
            if self._swa_ring_pages:
                self._swa_rotate(decoding, inflight, burst)
        return busy, bool(spec_now), spec_probe, burst

    @_device_phase("sched.plan")
    def _record_prefill(self, fl) -> None:
        """The PREFILL flight record of the compiled dispatch the await
        just returned from (loop thread; the worker left the facts in
        ``_last_prefill``)."""
        last, self._last_prefill = self._last_prefill, None
        if fl is None or last is None:
            return
        from ..obs import flight as _fl
        rows, bucket, tokens, pos_lo, pos_hi, walked, block, t0, t1 = last
        fl.record(_fl.PREFILL, t=t1, dur_ms=1000.0 * (t1 - t0), depth=rows,
                  val=float(bucket), tokens=tokens, free_pages=pos_lo,
                  spec_acc=pos_hi, chunks=min(walked, 32767),
                  active=block[0], free_slots=block[1],
                  pool=_fl.POOL_PREFILL if self._disagg is not None else 0)

    def _admit(self, fl) -> None:
        """Phase 1 of a scheduler iteration, on the event-loop thread: pop
        the queue into free slots, reserve pages, look prefixes up, evict
        under page pressure, leave the ADMIT records."""
        # Requests whose client is gone are dropped. Paged layout: the
        # FIFO head also needs its full page reservation (engine/paged.py
        # policy) — if pages are short it waits at the head (no
        # starvation: held pages always return via releases).
        while True:
            # Pool capacity gate (ISSUE 13): the unified pool just needs
            # any free slot; a disaggregated COLD admission needs a free
            # prefill slot AND a free decode slot to reserve (so the
            # handoff can never strand a prompt-complete request), while
            # the direct-to-decode path (warm prefix hit / penalties —
            # decided below, after the prefix lookup) needs only the
            # decode slot.
            cold_ok = bool(self._admit_pool.free) and (
                self._disagg is None or bool(self._decode_pool.free))
            if not cold_ok and not (self._disagg is not None
                                    and self._decode_pool.free):
                break
            if self._head is None:
                if self._queue.empty():
                    break
                self._head = self._queue.get_nowait()
            req = self._head
            if req.cancelled:
                req.finish_reason = "cancelled"
                self._head = None
                continue
            total = min(len(req.prompt_ids) + req.max_tokens, self.S)
            # Radix prefix lookup (ISSUE 6): resident prompt blocks map
            # into the new slot's table row instead of allocating +
            # prefilling. Penalty requests bypass the cache — their
            # token-occurrence counts are rebuilt by prefill, which a
            # skipped span would leave incomplete. Matched nodes are
            # pinned here; the pins drop at slot release, or right
            # below if the request parks instead of admitting.
            matched, shared_pages, nodes = 0, [], []
            cache = self._prefix_cache
            if (cache is not None and req.presence_penalty == 0
                    and req.frequency_penalty == 0):
                t_lk = time.monotonic()
                matched, shared_pages, nodes = cache.match(
                    req.prompt_ids)
                req.prefix_lookup_ms = 1000.0 * (time.monotonic()
                                                 - t_lk)
            ok = self.kv_groups.can_admit(
                total, shared_pages=len(shared_pages))
            if not ok and cache is not None:
                # Page pressure: reclaim cold cache entries (LRU
                # leaves; pinned blocks are untouchable) before
                # parking the head — the admission-side half of the
                # overload/Retry-After machinery.
                short = self.kv_groups.fresh_shortfall(
                    total, shared_pages=len(shared_pages))
                evicted = cache.evict(short) if short > 0 else 0
                if evicted > 0:
                    if fl is not None:
                        from ..obs.flight import EVICT
                        fl.record(EVICT, val=float(evicted),
                                  free_pages=self.allocator.free_pages)
                    ok = self.kv_groups.can_admit(
                        total, shared_pages=len(shared_pages))
            if not ok:
                if cache is not None:
                    cache.release_nodes(nodes)
                break
            direct = False
            if self._disagg is not None:
                # Direct-to-decode placement (no handoff): a warm prefix
                # hit whose unmatched tail fits ONE chunk skips the
                # prefill pool entirely (the matched span never prefills
                # at all — the composition the radix cache buys), and a
                # penalty request must build its on-device token counts
                # on the slot that will decode it (it bypasses the
                # prefix cache for the same reason, so matched == 0).
                direct = (req.presence_penalty != 0
                          or req.frequency_penalty != 0
                          or (matched > 0
                              and len(req.prompt_ids) - matched
                              <= self.prefill_chunk))
                if not direct and not cold_ok:
                    # Cold prompt but no prefill slot (or no decode slot
                    # to reserve): park at the FIFO head, exactly like a
                    # page-reservation shortfall.
                    if cache is not None:
                        cache.release_nodes(nodes)
                    break
            if self._disagg is None:
                target_pool = self._admit_pool
                req.slot = target_pool.take()
            elif direct:
                target_pool = self._decode_pool
                req.slot = target_pool.take()
                req.decode_slot = req.slot
            else:
                target_pool = self._admit_pool
                req.slot = target_pool.take()
                req.decode_slot = self._decode_pool.take()  # reservation
            req.pool = target_pool.pool_id
            target_pool.admits += 1
            self._head = None
            # Queue-wait gauge (submit → slot admission): the scheduler
            # half of TTFT — what the prefill-aware burst clamp bounds.
            # t_admitted also closes the trace's engine.queued phase.
            req.t_admitted = time.monotonic()
            self._sched.admitted(req, req.t_admitted)
            wait_ms = 1000.0 * (req.t_admitted - req.t_submit)
            self._queue_wait_n += 1
            self._queue_wait_ema_ms = (
                wait_ms if self._queue_wait_ema_ms is None
                else 0.8 * self._queue_wait_ema_ms + 0.2 * wait_ms)
            self._queue_wait_max_ms = max(self._queue_wait_max_ms, wait_ms)
            if self.spec_k:
                # New text in this slot: acceptance starts unmeasured.
                # (Reset at ADMISSION, not release, so stats keep the last
                # measured rate while the engine drains/idles.) The
                # per-slot suspension lifts with it — the new request's
                # text regime owes nothing to its predecessor's.
                self._spec_ema[req.slot] = np.nan
                self._spec_suspended[req.slot] = False
                self._spec_slot_proposed[req.slot] = 0
                self._spec_slot_accepted[req.slot] = 0
            self.kv_groups.allocate(req.slot, total,
                                    shared_pages=shared_pages)
            if self._prefix_cache is not None:
                self._prefix_cache.record_lookup(matched)
                req.cached_tokens = matched
                req.prefix_nodes = nodes
            if matched and self.spec_k:
                # Prompt-lookup history for the skipped span: the
                # per-chunk maintenance only covers chunks that
                # actually run, and its pos==0 reset never fires on a
                # warm admission.
                self.hist[req.slot, :] = 0
                self.hist[req.slot, :matched] = req.prompt_ids[:matched]
            # Warm admission starts prefill at the match boundary — the
            # matched span's prefill FLOPs are skipped outright (the
            # chunk's attention reads the shared pages through the table,
            # exactly like a later chunk of a cold prefill).
            req.prefill_pos = req.cached_tokens
            self._running[req.slot] = req
            self._prefilling[req.slot] = req
            if fl is not None:
                from ..obs.flight import ADMIT
                req.flight_admit_seq = fl.record(
                    ADMIT, slot=req.slot, val=wait_ms,
                    tokens=req.cached_tokens,
                    queued=self._queue.qsize() + (1 if self._head else 0),
                    free_slots=self._free_slot_count(),
                    free_pages=self.allocator.free_pages,
                    pool=req.pool,
                    rid=req.request_id or None)

    # -- compute (worker thread; no asyncio objects touched) ------------------
    def _prefill_one_chunk(self, req: GenRequest) -> bool:
        """Run one prompt chunk; returns True when the prompt is complete
        (first token sampled and slot armed for decode)."""
        return self._prefill_chunk_group([req])[0]

    def prefill_groups(self, items: list) -> list[list]:
        """Split ``items`` into batched-prefill group sizes, snapping each
        group DOWN to a compiled K rung. The ONE copy of the snapping
        policy: the scheduler's grouper and the bench's fill loop both
        call it, so the bench always warms/times exactly the programs
        serving admission runs."""
        out, i = [], 0
        while i < len(items):
            k = next(r for r in self._prefill_k_rungs if r <= len(items) - i)
            out.append(items[i:i + k])
            i += k
        return out

    @_device_phase("sched.prefill_group")
    def _prefill_chunk_group(self, reqs: list[GenRequest]) -> list[bool]:
        """Advance each request by one prompt chunk in ONE compiled call
        (K=1 is the single-request path): K queued prefills pay one
        dispatch. The scheduler's grouper
        guarantees every request here shares one compile bucket.
        Returns per-request prompt-complete flags."""
        slots, poss, chunks, samps, final = [], [], [], [], []
        with _part("args"):
            for req in reqs:
                slot = req.slot
                ids = req.prompt_ids
                pos = req.prefill_pos
                if pos == 0:
                    self.lengths[slot] = 0
                    self.active[slot] = False
                chunk = np.asarray(ids[pos:pos + self.prefill_chunk],
                                   np.int32)
                if self._swa_ring_pages:
                    # Map the pages this chunk writes by recycling pages
                    # wholly below the chunk's window floor (no in-flight
                    # margin: a prefilling slot has no decode burst of its
                    # own in flight, and cross-slot bursts touch only their
                    # own table rows).
                    self.kv_groups.rotate(slot, pos + len(chunk) - 1, pos)
                if self.fault_plan:
                    self.fault_plan.on_prefill()
                self._spec_hist_chunk(slot, pos, chunk)
                slots.append(slot)
                poss.append(pos)
                chunks.append(chunk)
                samps.append((req.temperature, req.top_p, req.top_k,
                              req.presence_penalty, req.frequency_penalty))
                final.append(pos + len(chunk) >= len(ids))
        with _part("rng"):
            self._rng, key = jax.random.split(self._rng)
        first, self.cache = self._exec_prefill(
            slots, poss, chunks, samp=samps, key=key, final=final)
        first_np: np.ndarray | None = None
        if any(final):
            # A prompt is complete: its first token was sampled inside the
            # prefill program (see prefill_step) — ONE host fetch for the
            # whole group completes the TTFT path.
            with _device_phase("sched.fetch.first"):
                first_np = np.asarray(first)
        else:
            # No row ended its prompt, so nothing reads: the call's device
            # time is waited out in whatever wait comes next.
            self._prefill_calls_unread += 1
        with _part("mirrors"):
            for i, req in enumerate(reqs):
                req.prefill_pos = poss[i] + len(chunks[i])
                if not final[i]:
                    continue
                first_id = int(first_np[i])
                req.generated.append(first_id)
                req.t_first_token = time.monotonic()
                self.lengths[req.slot] = len(req.prompt_ids)
                self.last_token[req.slot] = first_id
                # (Token history for prompt-lookup drafting is maintained
                # per CHUNK above; the first generated token is the input
                # at P, written by the spec step that consumes it.)
                self.active[req.slot] = True
                self.samp_temperature[req.slot] = req.temperature
                self.samp_top_p[req.slot] = req.top_p
                self.samp_top_k[req.slot] = req.top_k
                self.samp_presence[req.slot] = req.presence_penalty
                self.samp_frequency[req.slot] = req.frequency_penalty
                self._d_dirty = True
        return final

    def _exec_prefill(self, slot, pos, chunk,
                      samp=None, key: jax.Array | None = None,
                      final=None):
        """The one compiled-prefill call. A caller that only fills the
        cache (the benchmark's warm-up, a profile) passes no sampling
        state and ignores the sampled token.

        ``slot``/``pos``/``chunk``/``samp`` are scalars-and-one-chunk for
        the K=1 path, or equal-length lists for BATCHED admission (the
        scheduler's grouper). The compile bucket is derived here, from
        chunk lengths and engine config, so scheduler and bench can
        never disagree on it; batches share one bucket (the grouper
        only batches same-bucket chunks). Clamped so pos+bucket never
        exceeds the cache extent S for ANY row: XLA clamps
        dynamic_update_slice starts, so an overrunning padded chunk
        would silently shift and corrupt earlier KV entries. (Paged
        layout: out-of-range pad positions land on the trash page.)
        ``final`` (a list a row, default all): whether the chunk ENDS its
        prompt; only a program whose upper layers run on a prompt's last
        row alone is told.
        Returns (first_tokens [K, replicated device array], cache)."""
        with _part("tables"):
            tables = self._device_tables()
        with _part("args"):
            single = np.isscalar(slot) or isinstance(slot, (int, np.integer))
            slots = [slot] if single else list(slot)
            poss = [pos] if single else list(pos)
            chunks = [chunk] if single else list(chunk)
            samps = ([samp] if single else list(samp)) if samp is not None \
                else [(0.0, 1.0, 0, 0.0, 0.0)] * len(slots)
            K = len(slots)
            bucket = min(_bucket(max(len(ch) for ch in chunks),
                                 self.prefill_chunk),
                         self.S - max(poss))
            padded = np.zeros((K, bucket), np.int32)
            for i, ch in enumerate(chunks):
                padded[i, :len(ch)] = ch
            if key is None:
                key = _DUMMY_KEY()
            args = (self.params, self.cache, self._d_counts, tables, padded,
                    np.asarray(poss, np.int32), np.asarray(slots, np.int32),
                    np.asarray([len(ch) - 1 for ch in chunks], np.int32),
                    np.asarray([s[0] for s in samps], np.float32),
                    np.asarray([s[1] for s in samps], np.float32),
                    np.asarray([s[2] for s in samps], np.int32),
                    np.asarray([s[3] for s in samps], np.float32),
                    np.asarray([s[4] for s in samps], np.float32), key)
            if self._rows_stop:
                ends = np.ones((K,), bool) if final is None else \
                    np.asarray(final, bool)
                args += (ends,)
                self._prefill_rows_stopped += sum(
                    len(ch) - int(end) for ch, end in zip(chunks, ends))
        # Kernel registry (ISSUE 8): one row per (bucket, K) prefill
        # program; the aval capture + cost closure is paid once per
        # variant. The wall is the dispatch wall (on an async backend the
        # device time lands in the group's later fetch; CPU is
        # synchronous) — per-step attribution for decode comes from the
        # flight ring, prefill rows are call/FLOPs accounting.
        kname = f"prefill.b{int(bucket)}.k{K}"
        block = self._prefill_blocks.get(int(bucket))
        if block is None:
            block = self._prefill_blocks[int(bucket)] = \
                self._prefill_block(int(bucket))
        if self.kernels.needs(kname):
            variant = {"bucket": int(bucket), "k": K,
                       "block": "%dx%d" % block}
            with _part("mirrors"):
                self.kernels.register(
                    kname, "prefill", variant=variant,
                    cost_fn=_kernel_cost_fn(self._prefill_fn, args))
        t0 = time.monotonic()
        with _device_phase("prefill"):
            first, self._d_counts, cache = self._prefill_fn(*args)
        t1 = time.monotonic()
        with _part("mirrors"):
            self.kernels.record(kname, wall_ms=1000.0 * (t1 - t0))
            self._last_prefill = (
                K, int(bucket), sum(len(ch) for ch in chunks),
                int(min(poss)), int(max(poss)),
                self._count_prefill_walk(poss, int(bucket), block[0]),
                block, t0, t1)
        return first, cache

    def _prefill_block(self, bucket: int) -> tuple[int, int]:
        """(query positions a row-block, KV heads a program) of the
        prefill attention kernel at this bucket, by the kernel's own rule
        over what its call sees (under a ``model`` mesh the local KV
        heads; the latent kernel folds every head over its one latent)."""
        c = self.model_cfg
        if c.is_mla:
            from ..ops.latent_attention import latent_block_t
            return latent_block_t(bucket, c.n_heads), 1
        from ..ops.paged_attention import prefill_block_shape
        model = self.mesh.shape.get("model", 1)
        kv = c.n_kv_heads // (
            1 if c.n_kv_heads % model or c.n_heads % model else model)
        itemsize = jnp.dtype(self.dtype).itemsize
        return prefill_block_shape(
            bucket, c.n_heads // c.n_kv_heads, kv, self.allocator.page_size,
            c.head_dim, itemsize, 1 if self.kv_quant else itemsize,
            bool(self.kv_quant), self.kv_ppb)

    def _count_prefill_walk(self, poss: list[int], bucket: int,
                            bt: int) -> int:
        """Add one dispatch to the walk's two totals and return its
        walked pages: per row and cache group what
        ``ops.paged_attention.prefill_pages_walked`` counts for row-blocks
        of ``bt`` positions, the kernel's own at this bucket
        (:meth:`_prefill_block`; host integer arithmetic)."""
        from ..ops import paged_attention as pa
        page = self.allocator.page_size
        if self.model_cfg.is_mla:
            # Keys a layer's call attends: row b's query t sees pos + t + 1.
            self._mla_prefill_keys += sum(
                bucket * int(p) + bucket * (bucket + 1) // 2 for p in poss)
            from ..ops.latent_attention import latent_steps_walked
            steps, whole = latent_steps_walked(
                poss, bucket, bt, page,
                self.kv_groups.whole_context.allocator.pages_per_slot)
            self._mla_prefill_steps += steps
            self._mla_prefill_steps_whole += whole
        walked = 0
        for g in self.kv_groups:
            if not g.chunk_readers:
                continue    # no chunk attends there: one row reads it
            w, t = pa.prefill_pages_walked(
                poss, bucket, bt, page, g.window,
                g.allocator.pages_per_slot, self.kv_ppb)
            walked += w
            self._prefill_pages_table += t
        self._prefill_pages_walked += walked
        return walked

    def _kernel_variant(self, **base) -> dict:
        """Registry variant dict for a decode/spec kernel: the caller's
        keys plus the engine's KV identity (quantization, DMA
        blocking) — so the roofline table's worst_kernel() ranking can be
        filtered to e.g. the int8 decode variants (ISSUE 10's kernel-work
        driver) instead of guessing from the engine config."""
        base["kv"] = self.kv_quant or "bf16"
        if self.kv_ppb > 1:
            base["ppb"] = self.kv_ppb
        return base

    def _spec_hist_chunk(self, slot: int, pos: int,
                         chunk: np.ndarray) -> None:
        """Per-chunk token-history maintenance for prompt-lookup drafting
        (a spec upload may happen while another slot is mid-prefill)."""
        if not self.spec_k:
            return
        if pos == 0:
            self.hist[slot, :] = 0
        self.hist[slot, pos:pos + len(chunk)] = chunk

    def _spec_draft_ok(self, probe: bool) -> np.ndarray:
        """The per-slot drafting mask for one spec burst: every slot
        drafts unless per-slot suspension is on (spec_acceptance_floor)
        and the slot is suspended; a PROBE burst re-enables every slot
        for one re-measure."""
        if self.spec_floor <= 0 or probe:
            return np.ones((self.B,), bool)
        return ~self._spec_suspended

    @_device_phase("sched.spec_burst")
    def _spec_burst(self, n_steps: int,
                    probe: bool = False) -> list[np.ndarray]:
        """Run `n_steps` speculative draft+verify steps (engine/
        speculative.py). Full-size bursts run LAG-ONE pipelined like the
        normal path: this call dispatches burst N (device-side hist/token/
        length state chains between bursts) and returns burst N-1's rows,
        hiding the device→host round trip under compute. Host mirrors sync
        EXACTLY at flush time from the fetched emitted-token matrix —
        speculative advances are data-dependent (1..k+1 positions/step),
        so while a burst is in flight the host `lengths` lag dispatch and
        the scheduler caps against `_spec_inflight_advance()`'s upper
        bound. Returns emission-ready [B] token rows with -1 beyond each
        slot's accepted count (the emission loop's negative-token skip
        handles raggedness)."""
        if self.fault_plan:
            self.fault_plan.on_decode()
        # A mixed-mode engine may have a normal burst in flight (the batch
        # just turned all-greedy): land it first so mirrors are exact.
        pre = self._flush_pending()
        if self._d_dirty or not self._d_hist_fresh:
            # Upload needs exact host mirrors — land any in-flight spec
            # burst before reading them.
            pre += self._flush_spec_pending()
            with _part("state"):
                self._upload_slot_state()
                self._d_hist = self._upload(self.hist)
            self._d_dirty = False
            self._d_hist_fresh = True

        with _part("state"):
            d_ok = self._spec_draft_ok(probe)
            d_ok_dev = self._upload(d_ok)
        with _part("tables"):
            tables = self._device_tables()
        if n_steps == self._spec_scan_len:
            t0 = time.monotonic()
            args = (self.params, self.cache, tables, self._d_hist,
                    self._d_tokens, self._d_lengths, self._d_active,
                    d_ok_dev)
            kname = f"spec.s{n_steps}"
            if self.kernels.needs(kname):
                with _part("mirrors"):
                    self.kernels.register(
                        kname, "spec",
                        variant=self._kernel_variant(depth=n_steps),
                        cost_fn=_kernel_cost_fn(self._spec_scan, args))
            with _device_phase("spec.verify"):
                emitted, self.cache, self._d_hist, self._d_tokens, \
                    self._d_lengths = self._spec_scan(*args)
                _start_host_copy(emitted)
            with _part("mirrors"):
                prev, self._spec_pending = self._spec_pending, (
                    emitted, n_steps, self.active.copy(),
                    self._slot_epoch.copy(), d_ok)
            before = self._spec_tokens_out
            out = pre + self._flush_spec_entry(prev)
            with _part("mirrors"):
                steady = prev is not None and prev[1] == n_steps
                self.kernels.record(
                    kname, steps=n_steps,
                    wall_ms=(1000.0 * (time.monotonic() - t0) if steady
                             else None))
                if steady:
                    # Steady state at full spec depth: this call's wall
                    # time covers one same-depth burst (lag-one), and the
                    # flushed burst's emitted count is its token yield —
                    # feed the wall-clock gate gauge (see _spec_wall_loses).
                    toks = self._spec_tokens_out - before
                    if toks > 0:
                        ms = 1000.0 * (time.monotonic() - t0) / toks
                        self._spec_ms_per_tok = (
                            ms if self._spec_ms_per_tok is None else
                            0.7 * self._spec_ms_per_tok + 0.3 * ms)
            return out

        # Partial bursts (cache/budget caps, busy depth 1) stay
        # synchronous: land the in-flight burst, then step one at a time.
        pre += self._flush_spec_pending()
        outs = []
        kname = "spec.step1"
        t0 = time.monotonic()
        with _device_phase("spec.verify"):
            for _ in range(n_steps):
                args = (self.params, self.cache, tables, self._d_hist,
                        self._d_tokens, self._d_lengths, self._d_active,
                        d_ok_dev)
                if self.kernels.needs(kname):
                    self.kernels.register(
                        kname, "spec", variant=self._kernel_variant(depth=1),
                        cost_fn=_kernel_cost_fn(self._spec_step, args))
                self._d_tokens, self._d_lengths, self.cache, self._d_hist, \
                    em, _ = self._spec_step(*args)
                _start_host_copy(em)
                outs.append(em)
            with _device_phase("sched.fetch.sync"):
                host = np.stack([np.asarray(e) for e in outs])
        with _part("mirrors"):
            self.kernels.record(kname, steps=n_steps,
                                wall_ms=1000.0 * (time.monotonic() - t0))
            return pre + self._spec_walk(
                host, self.active, self.active.copy(), drafting=d_ok)

    def _upload_slot_state(self) -> None:
        """Rebuild the device mirrors of the per-slot host state, each
        pinned to the SAME replicated sharding the compiled programs
        produce — a plain jnp.asarray upload carries SingleDeviceSharding
        while the program outputs fed back next burst carry
        NamedSharding(mesh, P()), and that aval mismatch silently
        recompiled the whole burst program on the first post-upload call.
        The speculative chain uploads the sampler mirrors too: a later
        spec→normal mode switch (e.g. the cache-end fallback) must not
        hand _decode_burst a never-built _d_samp — a None there retraces
        the decode program with a different pytree structure."""
        self._d_tokens = self._upload(self.last_token)
        self._d_lengths = self._upload(self.lengths)
        self._d_active = self._upload(self.active)
        self._d_samp = SamplingParams(
            temperature=self._upload(self.samp_temperature),
            top_p=self._upload(self.samp_top_p),
            top_k=self._upload(self.samp_top_k),
            presence_penalty=self._upload(self.samp_presence),
            frequency_penalty=self._upload(self.samp_frequency))

    def _spec_wall_loses(self) -> bool:
        """True when the measured spec wall-clock (ms per emitted token,
        EMA over full spec bursts) exceeds the normal path's (the stats
        step gauge is wall per step; every active slot advances one token
        per step). Acceptance tokens/step alone is not a profit signal:
        it ignores what the spec step itself costs, which wherever the
        k+1-wide verify doesn't amortize can dwarf the accepted-token
        win."""
        if not self._spec_wall_gate_on or self._spec_ms_per_tok is None:
            return False
        # Like-for-like baseline: the fitted per-step time (per-burst
        # fixed cost removed) — an amortized shallow-burst wall/d would
        # inflate the normal-path baseline and hold a net-loss spec open
        # under sustained busy traffic.
        base = self._step_ms_estimate()
        if base is None:
            return False
        n = max(1, int(self.active.sum()))
        return self._spec_ms_per_tok > base / n

    def _step_ms_estimate(self) -> float | None:
        """Per-decode-step ms from the per-depth burst-wall EMAs.

        wall(d) = C + d·step, so with two measured depths the slope
        Δwall/Δdepth is the fixed-cost-free step time (use the two
        LARGEST depths — widest Δ, best signal). With one depth, fall
        back to wall/d — an OVERestimate (C folded in), which errs the
        ttft cap toward shallower bursts (TTFT-safe), and is corrected
        as soon as a second depth is measured. The estimate is clamped
        to (0, min(wall/d)]: the slope can't exceed any amortized wall,
        and noise-negative slopes fall back to the conservative bound.
        Only entries refreshed within the last ``_BURST_WALL_WINDOW``
        samples participate: a depth that stopped running holds a wall
        measured under old conditions (shorter contexts, lighter
        batch), and a fit against it would bias the step time — if all
        are stale, only the most recent entry is used."""
        w = self._burst_walls
        if not w:
            return None
        stamp = self._burst_wall_stamp
        fresh = {d: ms for d, ms in w.items()
                 if self._burst_wall_n - stamp.get(d, self._burst_wall_n)
                 <= self._BURST_WALL_WINDOW}
        if not fresh:
            d = max(w, key=lambda k: stamp.get(k, 0))
            fresh = {d: w[d]}
        w = fresh
        ub = min(ms / d for d, ms in w.items())
        if len(w) >= 2:
            d1, d2 = sorted(w)[-2:]
            step = (w[d2] - w[d1]) / (d2 - d1)
            if step > 0:
                self._fit_slope = min(step, ub)
                self._fit_stamp = self._burst_wall_n
                return self._fit_slope
        # One fresh depth: the fitted slope (if it hasn't expired)
        # still carries the fixed-cost correction — wall/d alone would
        # re-fold C into the estimate and restart the shrink spiral.
        if (self._fit_slope is not None
                and self._burst_wall_n - self._fit_stamp <= self._SLOPE_TTL):
            return min(self._fit_slope, ub)
        return ub

    _BURST_WALL_WINDOW = 512
    _SLOPE_TTL = 4096
    _EXPLORE_EVERY = 32

    def _fixed_cost_ms(self) -> float | None:
        """Estimated per-burst fixed cost C from wall(d) = C + d·step —
        diagnostic only (engine-stats / bench extra): host scheduling
        plus dispatch and fetch."""
        if (self._fit_slope is None or not self._burst_walls
                or self._burst_wall_n - self._fit_stamp > self._SLOPE_TTL):
            return None                 # expired slope = fabricated C
        d = max(self._burst_walls, key=lambda k:
                self._burst_wall_stamp.get(k, 0))
        return max(0.0, self._burst_walls[d] - d * self._fit_slope)

    def _spec_inflight_advance(self) -> int:
        """Upper bound on cache positions an in-flight speculative burst
        may still add per slot (every step fully accepted). The scheduler's
        burst caps add this to the host `lengths` mirror, which lags
        dispatch while a spec burst is pending."""
        if self._spec_pending is None:
            return 0
        return self._spec_pending[1] * (self.spec_k + 1)

    def _flush_spec_pending(self) -> list[np.ndarray]:
        entry, self._spec_pending = self._spec_pending, None
        return self._flush_spec_entry(entry)

    def _flush_spec_entry(self, entry) -> list[np.ndarray]:
        """Fetch an in-flight spec burst's emitted matrix and sync host
        mirrors exactly. The walk starts from the CURRENT host mirrors:
        bursts flush in dispatch order, so at flush time they are exact
        through the previous burst; slots released (or re-admitted) since
        dispatch are excluded by the epoch guard and their rows masked."""
        if entry is None:
            return []
        emitted, _, active_snap, epoch_snap, drafting = entry
        with _device_phase("sched.fetch.spec"):
            host = np.asarray(emitted)                   # [n, B, k+1]
        with _part("mirrors"):
            live = active_snap & (epoch_snap == self._slot_epoch)
            return self._spec_walk(host, active_snap, live,
                                   drafting=drafting)

    def _spec_walk(self, host: np.ndarray, active_snap: np.ndarray,
                   live: np.ndarray,
                   drafting: np.ndarray | None = None) -> list[np.ndarray]:
        """Exact host-mirror walk (lengths / last_token / history): each
        step's valid inputs are [current token] + accepted drafts, i.e.
        [cur] + emitted[:count-1]; the step's last emitted token becomes
        the next input. Returns emission rows (dead slots masked -1).

        ``drafting`` [B] bool is the burst's per-slot drafting mask: a
        suspended slot emitted exactly 1 token/step by construction (its
        drafts were masked to -1), so its rows carry NO acceptance signal
        — the EMA is frozen and proposal counters skip it. The suspension
        mirror itself is re-derived here (ratio = (ema-1)/k against
        spec_acceptance_floor)."""
        kp1 = self.spec_k + 1
        if drafting is None:
            drafting = np.ones((self.B,), bool)
        for slot in np.nonzero(live)[0]:
            pos = int(self.lengths[slot])
            cur = int(self.last_token[slot])
            for i in range(host.shape[0]):
                toks = host[i, slot]
                count = int((toks >= 0).sum())
                if drafting[slot]:
                    # Acceptance EMA feeding the adaptive drafting gates.
                    # Asymmetric: an unmeasured slot decays from the
                    # optimistic k+1 prior — prompt-lookup needs ~10 steps
                    # for a fresh generation to enter its repetitive cycle
                    # (measured on the tiny-test workload), so a slow fall
                    # grants that grace — while a high-acceptance step
                    # rises fast (a=0.5), letting a single 1-step probe
                    # re-open a closed gate the moment text turns
                    # repetitive. Suspended slots contribute no samples:
                    # their 1 token/step is an artifact of the mask, not a
                    # measurement.
                    prev = self._spec_ema[slot]
                    if np.isnan(prev):
                        prev = float(self.spec_k + 1)
                    a = 0.5 if count > prev else 0.2
                    self._spec_ema[slot] = (1 - a) * prev + a * count
                    self._spec_slot_proposed[slot] += self.spec_k
                    self._spec_slot_accepted[slot] += max(0, count - 1)
                    self._spec_proposed_total += self.spec_k
                    self._spec_accepted_total += max(0, count - 1)
                if count == 0:
                    continue
                if pos < self.S:
                    self.hist[slot, pos] = cur
                m = min(count - 1, self.S - (pos + 1))
                if m > 0:
                    self.hist[slot, pos + 1:pos + 1 + m] = toks[:m]
                cur = int(toks[count - 1])
                pos += count
            self.lengths[slot] = pos
            self.last_token[slot] = cur
        if self.spec_floor > 0:
            # Re-derive the per-slot suspension mirror from the freshly
            # updated EMAs. ratio = (ema - 1) / k maps the EMA (1..k+1
            # tokens/step) onto the acceptance fraction [0, 1]; a slot
            # below the floor stops drafting until a probe burst (which
            # runs with the mask lifted) measures it back above. NaN =
            # never measured = keep drafting (the optimistic prior).
            for slot in np.nonzero(live & drafting)[0]:
                ema = self._spec_ema[slot]
                if np.isnan(ema):
                    continue
                ratio = (ema - 1.0) / max(1, self.spec_k)
                self._spec_suspended[slot] = bool(ratio < self.spec_floor)
        if not live.all():
            host = host.copy()
            host[:, ~live] = -1
        self._spec_steps_done += host.shape[0] * int(active_snap.sum())
        self._spec_tokens_out += int((host >= 0).sum())
        return [host[i, :, t] for i in range(host.shape[0])
                for t in range(kp1)]

    def _flush_pending(self) -> list[np.ndarray]:
        """Fetch the in-flight burst's tokens (if any) and sync the host
        ``last_token`` mirror for slots that survived unchanged since its
        dispatch. Returns the per-step host token arrays, in order."""
        entry, self._pending = self._pending, None
        return self._flush_entry(entry)

    def _flush_entry(self, entry) -> list[np.ndarray]:
        if entry is None:
            return []
        toks_dev, n, active_snap, epoch_snap, len_snap, last_snap = entry
        with _device_phase("sched.fetch.burst"):
            host = np.asarray(toks_dev)                  # [n, B (+ counters)]
        with _part("mirrors"):
            if host.shape[1] > self.B:
                seen = host[-1, self.B:].astype(np.int64)
                for i, d in enumerate((seen - self._moe_seen) & 0xFFFFFFFF):
                    self._moe_totals[i] += int(d)
                self._moe_seen = seen
                host = host[:, :self.B]
            live = active_snap & (epoch_snap == self._slot_epoch)
            for slot in np.nonzero(live)[0]:
                self.last_token[slot] = int(host[-1][slot])
                if self.spec_k:
                    # Keep the prompt-lookup history current through the
                    # NORMAL path too (mixed spec/sampled serving): the
                    # burst's inputs were [last@dispatch] + tokens at
                    # positions [L, L+n] (L = dispatch-time length
                    # snapshot).
                    L = int(len_snap[slot])
                    if L < self.S:
                        self.hist[slot, L] = int(last_snap[slot])
                    m = min(n, self.S - (L + 1))
                    if m > 0:
                        self.hist[slot, L + 1:L + 1 + m] = host[:m, slot]
            if not live.all():
                # Slots released (or released+re-admitted) since this
                # burst's dispatch: their tokens belong to a dead request —
                # mask with -1 so the emission loop can't attribute them to
                # the slot's CURRENT request.
                host = host.copy()
                host[:, ~live] = -1
            return [host[i] for i in range(n)]

    def _swa_rotate(self, decoding, inflight: int, advance: int) -> None:
        """Sliding-window ring: before dispatching a burst, map the logical
        pages it will write (dispatch-true lengths + worst-case advance)
        by recycling pages wholly below the window floor minus one burst
        of margin — an undelivered lag-one burst may still read near its
        own, older floor. Runs on the event-loop thread (same as
        admission), before the worker-thread dispatch reads the table."""
        for r in decoding:
            pos = int(self.lengths[r.slot]) + inflight
            self.kv_groups.rotate(r.slot, pos + advance,
                                  pos - self._swa_margin)

    def _all_greedy(self) -> bool:
        """True when every ACTIVE slot is plain-greedy: temperature 0 and
        zero penalties — the condition for the argmax-only decode program
        AND for speculation (its verify is plain argmax)."""
        a = self.active
        return not bool(np.any(self.samp_temperature[a] > 0)
                        or np.any(self.samp_presence[a] != 0)
                        or np.any(self.samp_frequency[a] != 0))

    def _burst_depth(self, busy: bool) -> int:
        """Depth of the next normal decode burst.

        Busy (work queued or prefilling): the shallow depth, so new work
        interleaves within one shallow burst. Idle with ``ttft_target_ms``
        set: an arriving probe cannot preempt the scan already dispatched,
        so its TTFT floor is in-flight depth × step time plus the flush +
        prefill chunk that follow admission — cap the deep depth so the
        exposure spends at most HALF the target, sized by the engine's
        own fitted step time (``_step_ms_estimate``: Δwall/Δdepth, so
        per-burst fixed cost doesn't bias the cap). The cap snaps DOWN
        to a compiled scan depth (``_burst_depths``): an arbitrary
        depth would fall off the fused-scan fast path onto per-step
        dispatch. Until the model has a sample, run the configured
        depth — the first bursts are the measurement.

        Busy bursts are ALSO step-time-aware when a TTFT target is set
        (the prefill-aware clamp, ISSUE 2): at target scale a step costs
        ~23 ms, so even the configured busy depth can spend several
        hundred ms between prefill chunks — each chunk of a queued
        admission then waits out a full busy burst, and a multi-chunk
        prompt accumulates that into the 742.8 ms p50 measured in r5b.
        The clamp caps a busy burst at a QUARTER of the target (the
        interleave runs once per chunk; prefill + flush spend the rest),
        dropping below ``decode_burst_busy`` — to the synchronous
        burst=1 path if nothing compiled fits — while leaving idle-queue
        bursts at the unchanged deep/capped depth."""
        if busy:
            # A busy interleave splits an in-progress exploration pair —
            # its second burst would run against a busy-depth
            # predecessor and record nothing. Cancel rather than spend
            # the deep-burst TTFT exposure for no sample.
            self._explore_pending = 0
            pick = self.decode_burst_busy
            if self.ttft_target_ms > 0:
                est = self._step_ms_estimate()
                if est:
                    cap = 0.25 * self.ttft_target_ms / est
                    if cap < pick:
                        fitting = [d for d in self._burst_depths
                                   if d <= cap]
                        pick = max(fitting) if fitting else 1
                        self._busy_clamps += 1
            self._last_burst_depth = pick
            self._depth_hist[pick] = self._depth_hist.get(pick, 0) + 1
            return pick
        pick = self.decode_burst
        if self.ttft_target_ms > 0:
            est = self._step_ms_estimate()
            if est:
                cap = 0.5 * self.ttft_target_ms / est
                fitting = [d for d in self._burst_depths if d <= cap]
                pick = (min(max(fitting), self.decode_burst) if fitting
                        else self._burst_depths[0])
            # Exploration: a steady PAIR one compiled rung deeper, every
            # _EXPLORE_EVERY idle bursts, keeps a second fresh depth in
            # the wall model so the slope fit never degenerates to the
            # C-biased one-depth form (see _step_ms_estimate).
            if self._explore_pending > 0 and self._explore_depth > pick:
                self._explore_pending -= 1
                pick = self._explore_depth
            else:
                self._explore_pending = 0
                self._idle_burst_i += 1
                if pick < self.decode_burst and \
                        self._idle_burst_i % self._EXPLORE_EVERY == 0:
                    deeper = [d for d in self._burst_depths
                              if pick < d <= self.decode_burst]
                    if deeper:
                        self._explore_depth = deeper[0]
                        self._explore_pending = 1
                        pick = self._explore_depth
        self._last_burst_depth = pick
        self._depth_hist[pick] = self._depth_hist.get(pick, 0) + 1
        return pick

    @_device_phase("sched.decode_burst")
    def _decode_burst(self, n_steps: int) -> list[np.ndarray]:
        """Run `n_steps` chained decode steps; tokens/lengths feed back as
        device arrays (no host round-trip inside the chain) and each step's
        sampled tokens are fetched asynchronously behind the dispatch wave.
        Full-size bursts run LAG-ONE pipelined: this call dispatches burst
        N and returns burst N-1's tokens, so the fetch round trip hides
        under device compute. Returns host token arrays in generation
        order (possibly from the previous burst; possibly two bursts'
        worth when a flush was forced)."""
        if self.fault_plan:
            self.fault_plan.on_decode()
        pre: list[np.ndarray] = []
        if self.spec_k:
            # Mode switch (a sampled request joined): land any in-flight
            # SPECULATIVE burst first — its data-dependent advances must
            # reach the host mirrors before this path reads/advances them.
            pre += self._flush_spec_pending()
        if self._d_dirty:
            # Host slot state changed (admission/release/prefill). The
            # in-flight burst must land first: the upload below reads the
            # host `last_token` mirror, which that burst's tokens update.
            pre += self._flush_pending()
            with _part("state"):
                self._upload_slot_state()
            self._d_dirty = False

        with _part("tables"):
            tables = self._device_tables()
        # Greedy fast path: when every active slot decodes at temperature 0
        # with zero penalties (the common case), run the argmax-only
        # program — the general sampler's full-vocab sort costs
        # measurable per-step time (penalties force the general path:
        # a penalized argmax differs from plain argmax).
        greedy = self._all_greedy()
        step_fn, scans = self._decode_fns[greedy]
        scan_fn = scans.get(n_steps)
        if scan_fn is not None:
            # Full-size burst → the single fused scan program, lag-one
            # pipelined: dispatch burst N, then fetch burst N-1 — its
            # device→host copy was queued at its own dispatch
            # (copy_to_host_async), so the transfer streamed while burst N
            # computes and the asarray below is (near-)immediate. Partial
            # bursts (tail of a request's token budget, or prefill work
            # pending) fall through to the synchronous step loop below.
            t0 = time.monotonic()
            with _part("rng"):
                self._rng, key = jax.random.split(self._rng)
            args = (self.params, self.cache, self._d_counts, tables,
                    self._d_tokens, self._d_lengths, self._d_active,
                    self._d_samp, key)
            kname = (f"decode.d{n_steps}."
                     f"{'greedy' if greedy else 'sampled'}")
            if self.kernels.needs(kname):
                with _part("mirrors"):
                    self.kernels.register(
                        kname, "decode",
                        variant=self._kernel_variant(depth=n_steps,
                                                     greedy=greedy),
                        cost_fn=_kernel_cost_fn(scan_fn, args))
            with _device_phase("decode"):
                toks, self._d_tokens, self._d_lengths, self._d_counts, \
                    self.cache = scan_fn(*args)
                _start_host_copy(toks)
            with _part("mirrors"):
                prev, self._pending = self._pending, (
                    toks, n_steps, self.active.copy(),
                    self._slot_epoch.copy(), self.lengths.copy(),
                    self.last_token.copy())
                # Host length mirror advances at DISPATCH time — the burst-
                # capping logic in _step must see the device-true lengths.
                self._count_decode_keys(n_steps)
                self.lengths[self.active] += n_steps
                if self.spec_k:
                    self._d_hist_fresh = False
            out = pre + self._flush_entry(prev)
            with _part("mirrors"):
                self._record_burst(kname, n_steps, t0, steady=(
                    prev is not None and prev[1] == n_steps))
            return out

        # Synchronous path: flush any in-flight burst first so tokens are
        # returned in generation order.
        pre += self._flush_pending()
        pending: list[jax.Array] = []
        kname = f"decode.step1.{'greedy' if greedy else 'sampled'}"
        t0 = time.monotonic()
        with _device_phase("decode"):
            for _ in range(n_steps):
                self._rng, key = jax.random.split(self._rng)
                args = (self.params, self.cache, self._d_counts, tables,
                        self._d_tokens, self._d_lengths, self._d_active,
                        self._d_samp, key)
                if self.kernels.needs(kname):
                    self.kernels.register(
                        kname, "decode",
                        variant=self._kernel_variant(depth=1, greedy=greedy),
                        cost_fn=_kernel_cost_fn(step_fn, args))
                self._d_tokens, self._d_lengths, self._d_counts, \
                    self.cache = step_fn(*args)
                _start_host_copy(self._d_tokens)
                pending.append(self._d_tokens)
            with _device_phase("sched.fetch.sync"):
                step_tokens = [np.asarray(t) for t in pending]
        with _part("mirrors"):
            # The fetch above synchronizes, so this wall is honest per call.
            self.kernels.record(kname, steps=n_steps,
                                wall_ms=1000.0 * (time.monotonic() - t0))
            # Mirror device-side length advance on the host (+ history for
            # mixed-mode speculative engines).
            for slot in np.nonzero(self.active)[0]:
                if self.spec_k:
                    L = int(self.lengths[slot])
                    if L < self.S:
                        self.hist[slot, L] = int(self.last_token[slot])
                    m = min(n_steps, self.S - (L + 1))
                    for t in range(m):
                        self.hist[slot, L + 1 + t] = \
                            int(step_tokens[t][slot])
                self.last_token[slot] = int(step_tokens[-1][slot])
            self._count_decode_keys(n_steps)
            self.lengths[self.active] += n_steps
            if self.spec_k:
                self._d_hist_fresh = False
        return pre + step_tokens

    def _record_burst(self, kname: str, n_steps: int, t0: float,
                      steady: bool) -> None:
        """One lag-one burst into the kernel registry, with its wall where
        it is honest. A steady same-depth pair: the call's wall covers
        exactly one burst at this depth. Depth transitions (busy<->idle)
        are excluded — the previous burst's wait divided by the new depth
        would feed ~4x-off samples. The wall feeds BOTH the per-depth wall
        model (_step_ms_estimate — the ttft cap's input) and the operator
        stats gauge; transition bursts count calls but contribute no
        time."""
        if not steady:
            self.kernels.record(kname, steps=n_steps)
            return
        wall = 1000.0 * (time.monotonic() - t0)
        prev_w = self._burst_walls.get(n_steps)
        self._burst_walls[n_steps] = (
            wall if prev_w is None else 0.8 * prev_w + 0.2 * wall)
        self._burst_wall_n += 1
        self._burst_wall_stamp[n_steps] = self._burst_wall_n
        ms_any = wall / n_steps
        self._ema_step_ms_stats = (
            ms_any if self._ema_step_ms_stats is None else
            0.8 * self._ema_step_ms_stats + 0.2 * ms_any)
        self.kernels.record(kname, steps=n_steps, wall_ms=wall)

    def _count_decode_keys(self, n_steps: int) -> None:
        """Add a burst of ``n_steps`` to the decode keys of ONE layer of
        each cache group: step ``i`` of an active slot at length ``n``
        sees ``n + i + 1`` keys in a latent or a global group, and what
        of them lies inside the window in a windowed one (under an
        indexer: also the keys it kept and the pages their read walked).
        And to the state blocks rewritten: one a linear layer, active slot
        and step."""
        live = self.lengths[self.active].astype(np.int64)
        self._lin_decode_state_updates += (
            n_steps * len(live) * self.model_cfg.n_lin_layers)
        seen = live[:, None] + np.arange(1, n_steps + 1)    # [slots, steps]
        for g in self.kv_groups:
            if self.model_cfg.is_sparse:
                self._dsa_decode_keys["scored"] += int(seen.sum())
                self._dsa_decode_keys["selected"] += int(np.minimum(
                    seen, self.model_cfg.idx_topk).sum())
                self._dsa_decode_keys["pages_walked"] += int(
                    (-(-seen // self.kv_page)).sum())
            elif g.kind == "latent":
                self._mla_decode_keys += int(seen.sum())
            elif g.window:
                self._attn_decode_keys["window"] += int(
                    np.minimum(seen, g.window).sum())
            else:
                self._attn_decode_keys["global"] += int(seen.sum())
                if g.readers > g.layers:    # a pool other layers read too
                    self._cross_decode_keys_read += \
                        int(seen.sum()) * g.readers

    # -- emission / lifecycle (event-loop thread only) ------------------------
    def _emit_token(self, req: GenRequest) -> None:
        if req.cancelled:
            self._finish(req, "cancelled", emit=False)
            return
        tok = req.generated[-1]
        if tok in self.tokenizer.eos_ids:
            self._finish(req, "stop")
            return
        req.text += req.detok.push(tok)

        # OpenAI `stop` semantics: the stop sequence (and anything after it)
        # is excluded from the output. Because stops can span token/delta
        # boundaries, text that could still be a stop prefix is HELD BACK
        # until resolved — a complete match therefore always starts at or
        # after `emitted_upto`.
        if req.stop:
            idx = -1
            for s in req.stop:
                found = req.text.find(s, req.emitted_upto)
                if found >= 0 and (idx < 0 or found < idx):
                    idx = found
            if idx >= 0:
                req.text = req.text[:idx]
                self._finish(req, "stop", flush_detok=False)
                return

        if len(req.generated) >= req.max_tokens:
            self._finish(req, "length")
            return
        # Exact per-token cache-capacity check (host `lengths` may already be
        # a whole burst ahead of the token being emitted). Speculative
        # engines reserve k tail positions so a k+1-wide verify never
        # writes past the cache extent.
        if (len(req.prompt_ids) + len(req.generated) + 1
                >= self.S - self.spec_k):
            self._finish(req, "length")
            return

        # Emit everything except the longest tail that is a proper prefix of
        # some stop string (held back until it resolves either way).
        hold = 0
        unemitted = len(req.text) - req.emitted_upto
        for s in req.stop:
            for k in range(min(len(s) - 1, unemitted), hold, -1):
                if req.text.endswith(s[:k]):
                    hold = k
                    break
        safe_upto = len(req.text) - hold
        if safe_upto > req.emitted_upto:
            delta = req.text[req.emitted_upto:safe_upto]
            req.emitted_upto = safe_upto
            req.out_queue.put_nowait(Delta(text=delta))

    def _finish(self, req: GenRequest, reason: str, emit: bool = True,
                flush_detok: bool = True) -> None:
        if flush_detok and reason != "cancelled":
            req.text += req.detok.flush()
        req.finish_reason = reason
        req.t_done = time.monotonic()
        if emit:
            delta = req.text[req.emitted_upto:]
            req.emitted_upto = len(req.text)
            req.out_queue.put_nowait(Delta(text=delta, finish_reason=reason))
        self._release(req)

    def _prefix_release(self, req: GenRequest) -> None:
        """Insert-on-release + unpin (ISSUE 6): index the slot's completed
        KV into the radix cache BEFORE the allocator frees the row, then
        drop the pins taken at admission. Only tokens whose cache writes
        have provably landed are indexed: a mid-prefill cancellation
        covers the chunks that ran (`prefill_pos`); a decoding slot
        covers the prompt plus every generated token that has been the
        INPUT of a fetched step — the last emitted token's KV write may
        still be in flight, and with lag-one pipelining positions beyond
        it may hold a dead burst's writes, but both lie in blocks past
        the indexed span."""
        cache = self._prefix_cache
        try:
            if req.slot in self._prefilling:
                n_ok = req.prefill_pos
            else:
                n_ok = len(req.prompt_ids) + max(0, len(req.generated) - 1)
            seq = req.prompt_ids + req.generated
            cache.insert(seq, min(n_ok, self.S, len(seq)),
                         self.allocator.table[req.slot])
        finally:
            cache.release_nodes(req.prefix_nodes)
            req.prefix_nodes = []

    def _release(self, req: GenRequest) -> None:
        if req.slot in self._running:
            self._sched.left(req, req.t_done)
            if self._prefix_cache is not None:
                self._prefix_release(req)
            del self._running[req.slot]
            if self.flight is not None:
                # Every admit record gets a matching finish — the chaos
                # tests assert the pair count balances (a "leaked" flight
                # record is a request the scheduler lost track of).
                from ..obs.flight import FINISH, FINISH_REASONS
                reason = req.finish_reason or "error"
                code = (FINISH_REASONS.index(reason)
                        if reason in FINISH_REASONS else 3)
                req.flight_done_seq = self.flight.record(
                    FINISH, slot=req.slot, flag=code,
                    tokens=len(req.generated),
                    active=len(self._running),
                    free_slots=self._free_slot_count(),
                    pool=req.pool,
                    rid=req.request_id or None)
            self._prefilling.pop(req.slot, None)
            self.active[req.slot] = False
            self.lengths[req.slot] = 0
            self._pool_by_slot[req.slot].free.append(req.slot)
            if req.decode_slot >= 0 and req.decode_slot != req.slot:
                # Cold admission cancelled/shed mid-prefill: its reserved
                # decode slot was never consumed by a handoff — return it
                # or the decode pool leaks a slot per aborted prefill.
                self._decode_pool.free.append(req.decode_slot)
            req.decode_slot = -1
            if self._disagg is not None:
                self._disagg.clamp_release(req)
            self._slot_epoch[req.slot] += 1
            self._d_dirty = True
            self.kv_groups.release(req.slot)

    def _handoff(self, req: GenRequest) -> None:
        """Promote a just-completed prefill into the decode pool
        (ISSUE 13). Zero-copy: the KV pages move by refcount transfer
        inside the allocator (same physical ids, no device memcpy) and
        only the HOST page table + per-slot mirrors change rows — the
        next dirty upload carries both. Runs on the loop thread in the
        gap between the prefill dispatch returning and the next decode
        burst, so no in-flight burst has ever seen ``active`` true for
        either slot: lag-one ``_pending`` snapshots predate the move and
        mask both rows to -1."""
        from ..obs.flight import POOL_DECODE, POOL_PREFILL
        if self.fault_plan is not None:
            self.fault_plan.on_handoff()
        if req.disagg_clamped:
            self._disagg.clamp_release(req)
        if req.pool != POOL_PREFILL:
            return      # admitted direct-to-decode: already home
        p, d = req.slot, req.decode_slot
        pages = self.allocator.transfer(p, d)
        self.lengths[d] = self.lengths[p]
        self.last_token[d] = self.last_token[p]
        self.samp_temperature[d] = self.samp_temperature[p]
        self.samp_top_p[d] = self.samp_top_p[p]
        self.samp_top_k[d] = self.samp_top_k[p]
        self.samp_presence[d] = self.samp_presence[p]
        self.samp_frequency[d] = self.samp_frequency[p]
        # (Penalty count rows are NOT moved: requests with penalties are
        # admitted direct-to-decode so their on-device counts build in
        # place; a penalty-free request's stale counts row is multiplied
        # by zero.)
        self.active[d] = True
        self.active[p] = False
        self.lengths[p] = 0
        self._slot_epoch[p] += 1
        self._d_dirty = True
        self._table_dirty = True
        del self._running[p]
        self._running[d] = req
        req.slot = d
        req.pool = POOL_DECODE
        self._admit_pool.free.append(p)
        self._disagg.note_handoff(len(pages))

    # -- stats ----------------------------------------------------------------
    def _resident_param_bytes(self) -> int:
        """HBM bytes one decode step streams for WEIGHTS: every resident
        leaf read once per step (scales included — they move over the bus
        too; int4 packs two elements per byte). Cached — the tree never
        changes after init."""
        b = getattr(self, "_param_bytes_cache", None)
        if b is None:
            b = 0
            for leaf in jax.tree.leaves(self.params):
                itemsize = (0.5 if leaf.dtype == jnp.int4
                            else leaf.dtype.itemsize)
                b = b + int(np.prod(leaf.shape) * itemsize)
            self._param_bytes_cache = b
        return b

    def _kv_token_bytes(self) -> int:
        """Bytes a token keeps in ONE layer of a cache group's pool: K and
        V of every KV head (int8: with their float32 scales), or a latent
        layer's one row; with an indexer, the token's index key too."""
        c = self.model_cfg
        itemsize = int(np.dtype(self.dtype).itemsize)
        if c.is_mla:
            return c.latent_width * itemsize
        elem, scale = (1, 4) if self.kv_quant else (itemsize, 0)
        return (2 * c.n_kv_heads * (c.head_dim * elem + scale)
                + c.idx_head_dim * itemsize * c.is_sparse)

    def _kv_bytes_per_step(self) -> int:
        """HBM bytes one decode step reads from the KV cache: the live
        (window-clamped) stale prefix of every active slot, K and V, at
        the cache's element width (int8-KV: 1 B + the per-token fp32
        scale amortized over head_dim). The bytes-touched half of the
        roofline model — achieved GB/s = (weights + this) / step time."""
        c = self.model_cfg
        live = self.lengths[self.active].astype(np.int64)
        if c.is_sparse:
            # Every live token's index key, the selected tokens' K and V.
            index = c.idx_head_dim * int(np.dtype(self.dtype).itemsize)
            return c.n_kv_layers * int(
                (live * index + np.minimum(live, c.idx_topk)
                 * (self._kv_token_bytes() - index)).sum())
        # Token reads summed over the layers of every cache group, each
        # clamped to the group's window.
        reads = sum(
            n * int((np.minimum(live, w) if w else live).sum())
            for (w, _), n in zip(c.cache_groups, c.group_readers))
        # np.dtype (in _kv_token_bytes), not jnp: host metadata — stats()
        # runs on the event loop and must not even look like a device sync
        # (graftlint v2 chases this call from the async stats handlers).
        return self._kv_token_bytes() * reads

    def _state_bytes(self) -> int:
        """Bytes of recurrent state and conv tails resident beside the KV
        pool (0 for a family that keeps none); a decode step reads and
        writes all of it."""
        return sum(int(np.prod(a.shape)) * a.dtype.itemsize
                   for a in jax.tree.leaves(
                       (getattr(self.cache, "state", ()),
                        getattr(self.cache, "conv", ()))))

    def _build_ledger(self):
        """Static HBM accounting (ISSUE 8): what the engine INTENDS to
        hold in device memory — parameter bytes at their checkpoint
        dtypes, KV-pool bytes from page geometry × cache dtype (incl.
        int8-KV scale planes), penalty/table auxiliaries, and the spec
        history twin — reconciled at scrape time against the live
        buffers' metadata and, where the backend has an allocator
        (TPU), ``device.memory_stats()``. All byte totals are GLOBAL
        (logical array bytes across the mesh), matching what
        ``tracked_fn`` sums."""
        from ..obs.device import HbmLedger, device_memory_stats
        c = self.model_cfg
        page = self.kv_page
        token_bytes = self._kv_token_bytes()
        kv_pools: dict[str, int] = {}
        for g in self.kv_groups:
            name = ("latent" if g.kind == "latent" else
                    f"window{g.window}" if g.window else "global")
            kv_pools[name] = (g.layers * g.allocator.num_pages * page
                              * token_bytes)
        kv_pool = sum(kv_pools.values())
        page_bytes = (self.kv_groups.whole_context.layers * page
                      * token_bytes)
        aux = self.B * c.vocab_size * 4          # penalty counts [B, V]
        aux += self._state_bytes()               # recurrent state, conv tails
        aux += sum(int(g.allocator.table.size)     # device page tables
                   for g in self.kv_groups) * 4
        spec = self.B * self.S * 4 if self.spec_k else 0  # device hist

        def tracked() -> int:
            # Live buffer bytes: array METADATA only — never a device
            # sync. Params + KV cache + the big auxiliaries; the tiny
            # per-slot mirrors fall inside the reconciliation band.
            total = 0
            for leaf in jax.tree.leaves((self.params, self.cache)):
                itemsize = (0.5 if leaf.dtype == jnp.int4
                            else leaf.dtype.itemsize)
                total += int(np.prod(leaf.shape) * itemsize)
            for extra in (self._d_counts, getattr(self, "_d_hist", None),
                          *(self._d_tables or ())):
                if extra is not None:
                    total += int(np.prod(extra.shape)
                                 * extra.dtype.itemsize)
            return total

        try:
            pidx = jax.process_index()
            local = [d for d in self.mesh.devices.flat
                     if d.process_index == pidx] or None
        except Exception:
            # Best-effort device scoping: fall back to all local devices
            # inside device_memory_stats (the numbers stay correct for
            # single-engine processes, which is every deployment today).
            logger.debug("mesh-local device scoping failed", exc_info=True)
            local = None
        return HbmLedger(
            weights=self._resident_param_bytes(), kv_pool=kv_pool,
            aux=aux, spec=spec, page_bytes=page_bytes, kv_pools=kv_pools,
            tracked_fn=tracked,
            mem_fn=lambda: device_memory_stats(local))

    def kernel_table(self) -> list[dict[str, Any]]:
        """Per-kernel roofline rows (obs/device.py) joined with the
        flight ring's measured step walls — what ``GET /v1/api/roofline``
        serves. Decode/spec rows carry the engine's bytes-touched model
        (same formula as the aggregate ``hbm_bytes_per_step``, so the
        table reconciles with it by construction); prefill rows report
        the XLA static analysis only (prefill is FLOPs-bound)."""
        def bytes_for(kind: str) -> int | None:
            if kind in ("decode", "spec"):
                return (self._resident_param_bytes()
                        + self._kv_bytes_per_step()
                        + 2 * self._state_bytes())
            return None
        return self.kernels.table(
            bytes_per_step_fn=bytes_for, peak_gbps=self.cfg.hbm_peak_gbps,
            flight=(self.flight.snapshot() if self.flight is not None
                    else None))

    def stats(self) -> dict[str, Any]:
        out = {
            "running": len(self._running),
            "queued": self._queue.qsize() + (1 if self._head else 0),
            "free_slots": self._free_slot_count(),
            "batch_size": self.B,
            "max_seq_len": self.S,
            "kv_layout": self.cfg.kv_layout,
            "attention": self.attention_impl,
            "kv_pool_in_place": self.kv_pool_in_place,
        }
        # Supervisor block (ISSUE 14): lifecycle state, restart budget,
        # heartbeat age, recent transitions — the incident story.
        out.update(self.supervisor.stats())
        if self._disagg is not None:
            out["pools"] = self._disagg.stats()
            out["disagg_handoffs"] = self._disagg.handoffs
            out["disagg_handoff_pages"] = self._disagg.handoff_pages
            out["disagg_clamps"] = self._disagg.clamps
            out["disagg_goodput_sheds"] = self._disagg.goodput_sheds
        # Precision config — operators correlating quality/throughput need
        # to see what the engine is actually running.
        if self.quant:
            out["quant"] = self.quant
        if self.kv_quant:
            out["kv_quant"] = self.kv_quant
        out["free_pages"] = self.allocator.free_pages
        out["total_pages"] = (self.allocator.num_pages
                              - self.allocator.pages_per_block)
        out["page_size"] = self.allocator.page_size
        # The cache groups (engine/paged.py): one pool, page table
        # and allocator a group of layers that keep the same KV; the
        # pages the rings re-targeted, monotone.
        out["kv_groups"] = [g.stats() for g in self.kv_groups]
        out["kv_ring_recycled_total"] = sum(
            g.recycled for g in self.kv_groups)
        # The paged prefill kernel's walk (_count_prefill_walk).
        out["prefill_kv_pages_walked_total"] = self._prefill_pages_walked
        out["prefill_kv_pages_table_total"] = self._prefill_pages_table
        out["prefill_kernel_blocks"] = {
            str(b): "%dx%d" % blk for b, blk in sorted(
                self._prefill_blocks.items())}
        # One layer's keys of a global and of a windowed K/V group
        # that the decode programs attended (_count_decode_keys).
        out["attn_decode_keys_global_total"] = \
            self._attn_decode_keys["global"]
        out["attn_decode_keys_window_total"] = \
            self._attn_decode_keys["window"]
        if self.kv_ppb > 1:
            out["pages_per_block"] = self.kv_ppb
        if self._prefix_cache is not None:
            # Radix prefix cache (ISSUE 6): hit/miss/cached-token
            # totals plus residency/pin gauges — the obs collector
            # bridges these onto the engine_prefix_* /metrics series,
            # and the bench's shared-prefix rung asserts skipped
            # prefill from them (not from wall clock).
            out.update(self._prefix_cache.stats())
        if self.model_cfg.n_lin_layers:
            # Recurrent state beside the pool.
            out["state_bytes_resident"] = self._state_bytes()
            out["state_slots"] = self.B
            out["lin_decode_state_updates_total"] = \
                self._lin_decode_state_updates
        if any(g.readers > g.layers for g in self.kv_groups):
            out["cross_decode_keys_read_total"] = \
                self._cross_decode_keys_read
        if self._rows_stop:
            out["prefill_rows_stopped_total"] = self._prefill_rows_stopped
        if self.model_cfg.layer_period and self.model_cfg.n_experts:
            # The expert layer's share: the assignments the decode steps
            # routed, those that landed on an expert held here (their
            # ratio is the share of the experts held when routing is
            # even), and over layers and steps the held experts that at
            # least one active row was assigned to (the experts a step
            # has to stream).
            out["moe_experts_held"] = self.model_cfg.experts_held
            out["moe_assignments_total"] = self._moe_totals[0]
            out["moe_assignments_local_total"] = self._moe_totals[1]
            out["moe_experts_hit_total"] = self._moe_totals[2]
            # The grouped product of the prefill calls: the tiles of
            # GROUP_TILE rows it ran and the rows they held — rows over
            # GROUP_TILE x tiles is how full a tile ran. Seen at the next
            # burst's fetch.
            out["moe_tiles_run_total"] = self._moe_totals[3]
            out["moe_tile_rows_total"] = self._moe_totals[4]
        if self.model_cfg.is_sparse:
            out["dsa_decode_keys_scored_total"] = \
                self._dsa_decode_keys["scored"]
            out["dsa_decode_keys_selected_total"] = \
                self._dsa_decode_keys["selected"]
            out["dsa_decode_pages_walked_total"] = \
                self._dsa_decode_keys["pages_walked"]
        if self.model_cfg.is_mla:
            out["mla_decode_keys_total"] = self._mla_decode_keys
            out["mla_prefill_keys_total"] = self._mla_prefill_keys
            out["mla_prefill_steps_total"] = self._mla_prefill_steps
            out["mla_prefill_steps_whole_total"] = \
                self._mla_prefill_steps_whole
        gauge = (self._ema_step_ms_stats
                 if self._ema_step_ms_stats is not None
                 else self._step_ms_estimate())
        if gauge is not None:
            out["decode_ms_per_step"] = round(gauge, 3)
            active_n = int(self.active.sum())
            if active_n:
                out["decode_tok_s"] = round(1000.0 * active_n / gauge, 1)
        # Roofline counters (ISSUE 2): bytes one decode step must stream
        # (weights + live KV) and the achieved bandwidth that implies at
        # the measured step time — the number the bench ladder and the
        # stats UI both read, so the 0.478→1.0 roofline trajectory is a
        # reading instead of a post-hoc reconstruction.
        hbm_bytes = (self._resident_param_bytes()
                     + self._kv_bytes_per_step() + 2 * self._state_bytes())
        out["hbm_bytes_per_step"] = hbm_bytes
        if gauge:
            out["achieved_gbps"] = round(hbm_bytes / (gauge / 1e3) / 1e9, 1)
            if self.cfg.hbm_peak_gbps > 0:
                out["roofline_fraction"] = round(
                    out["achieved_gbps"] / self.cfg.hbm_peak_gbps, 3)
        # Scheduler-side TTFT counters: where bursts ran, how often the
        # prefill-aware clamp bit, and how long admissions waited.
        if self._last_burst_depth:
            out["burst_depth_last"] = self._last_burst_depth
        out["burst_busy_clamps"] = self._busy_clamps
        if self._queue_wait_n:
            out["queue_wait_ms_ema"] = round(self._queue_wait_ema_ms, 1)
            out["queue_wait_ms_max"] = round(self._queue_wait_max_ms, 1)
            out["queue_waits"] = self._queue_wait_n
        # Overload sheds (queue-full admissions the gateway 429'd).
        out["shed_total"] = self._shed_n
        # Burst-depth controller diagnostics (ttft_target_ms): fitted
        # per-step slope, per-burst fixed cost, and where bursts actually
        # ran — the fields that turn an on-chip TTFT/throughput anomaly
        # from a guess into a reading.
        if self.ttft_target_ms > 0:
            est = self._step_ms_estimate()
            if est is not None:
                out["burst_step_ms_fit"] = round(est, 3)
            c = self._fixed_cost_ms()
            if c is not None:
                out["burst_fixed_cost_ms"] = round(c, 1)
            if self._depth_hist:
                out["burst_depth_hist"] = dict(
                    sorted(self._depth_hist.items()))
            out["burst_walls_ms"] = {
                d: round(ms, 1)
                for d, ms in sorted(self._burst_walls.items())}
        if self.flight is not None:
            # Flight-recorder counters (ISSUE 7): ring position, loss
            # under load, and lifecycle balance — bridged onto /metrics
            # by the obs collector like the prefix/shed counters.
            out.update(self.flight.stats())
        # Device observability plane (ISSUE 8): the HBM ledger (static
        # intent, live buffer bytes, runtime allocator where available),
        # kernel-registry counters, watermark sheds, and the process-wide
        # XLA compile monitor (identical across engines in one process).
        out.update(self.ledger.snapshot(
            prefix_resident_pages=out.get("prefix_resident_pages", 0)))
        out.update(self.kernels.stats())
        out["watermark_sheds"] = self._watermark_sheds
        if self._prewarm_error is not None:
            out["prewarm_error"] = self._prewarm_error
        # The scheduler's time ledger: sched_<phase>_ms_total, ten
        # monotone counters over the loop's wall since it started, their
        # parts, and the threads' own CPU beside the wall (obs/phases.py).
        out.update(self._sched.stats())
        out["prefill_calls_unread_total"] = self._prefill_calls_unread
        # Times the kernel took a core from a thread of this process that
        # was running: beside the CPU counters, what tells a host that
        # slowed down from a machine that ran something else.
        out["proc_invol_ctx_switches_total"] = \
            resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw
        from ..obs.device import compile_monitor
        cm = compile_monitor().stats()
        for key in ("xla_compile_total", "xla_compile_seconds",
                    "xla_compile_by_phase", "xla_trace_total",
                    "xla_trace_ms_total", "xla_trace_by_phase"):
            out[key] = cm[key]
        if self.spec_k:
            out["spec_draft_len"] = self.spec_k
            # Speculative acceptance telemetry (ROADMAP item 3 stub):
            # drafted-vs-accepted token totals, bridged to the
            # gateway_engine_spec_* /metrics series. Counted explicitly
            # per drafting slot in _spec_walk — a suspended slot
            # (spec_acceptance_floor) proposes nothing, so steps*k would
            # overcount the denominator and understate the true rate.
            out["spec_proposed"] = self._spec_proposed_total
            out["spec_accepted"] = self._spec_accepted_total
            if self._spec_steps_done:
                out["spec_tokens_per_step"] = round(
                    self._spec_tokens_out / self._spec_steps_done, 2)
            if self.spec_floor > 0:
                # Per-slot adaptive drafting: the floor, which slots are
                # currently benched, and each measured slot's EMA-derived
                # acceptance ratio ((ema-1)/k — the quantity the floor
                # compares against). Bridged to the per-slot
                # gateway_engine_spec_slot_acceptance_ratio gauge and the
                # gateway_engine_spec_suspended_slots_total count.
                out["spec_acceptance_floor"] = self.spec_floor
                out["spec_suspended_slots"] = int(
                    self._spec_suspended.sum())
                ratios = {}
                for s in range(self.B):
                    ema = self._spec_ema[s]
                    if not np.isnan(ema):
                        ratios[s] = round(
                            (float(ema) - 1.0) / max(1, self.spec_k), 3)
                out["spec_slot_acceptance"] = ratios
            if self.spec_min_tps > 0 or self._spec_wall_gate_on:
                # Live view of the adaptive gate: mean measured acceptance
                # (active slots when serving, else the last measured
                # rates) and whether drafting currently pays. The wall
                # term reports even with the acceptance threshold
                # disabled (spec_min_tokens_per_step=0).
                act = self._spec_ema[self.active]
                basis = act if act.size else self._spec_ema
                known = basis[~np.isnan(basis)]
                accept_ok = True
                if known.size:
                    out["spec_ema_tokens_per_step"] = round(
                        float(known.mean()), 2)
                    if self.spec_min_tps > 0:
                        accept_ok = bool(
                            float(np.mean(np.where(np.isnan(basis),
                                                   self.spec_k + 1, basis)))
                            >= self.spec_min_tps)
                out["spec_gate_open"] = (accept_ok
                                         and not self._spec_wall_loses())
                if self._spec_ms_per_tok is not None:
                    out["spec_ms_per_token"] = round(
                        self._spec_ms_per_tok, 3)
        return out


def _prefill_counts(counts, tokens, start_len, slots, last_idx):
    """Penalty-count maintenance for a prefill chunk group: reset each
    slot's row at prompt start (start_len == 0), add the chunk's REAL
    tokens (bucket pads masked via last_idx), and return (updated
    counts [B, V], the K updated rows [K, V] — the penalty source for
    this program's folded first-token sampling)."""
    K, C = tokens.shape
    pos_ok = (jnp.arange(C)[None, :] <= last_idx[:, None]).astype(jnp.int32)
    rows = []
    for k in range(K):
        row = jax.lax.dynamic_slice_in_dim(counts, slots[k], 1, axis=0)[0]
        row = jnp.where(start_len[k] == 0, jnp.zeros_like(row), row)
        row = row.at[tokens[k]].add(pos_ok[k])
        counts = jax.lax.dynamic_update_slice_in_dim(
            counts, row[None], slots[k], axis=0)
        rows.append(row)
    return counts, jnp.stack(rows)


def _decode_programs(one_step, burst_lens: tuple[int, ...],
                     counters: bool = False):
    """Build the decode programs from one step body: the per-step program,
    and a fused lax.scan per distinct burst length in ``burst_lens`` — ONE
    dispatch + ONE host fetch per burst instead of per step. Two
    lengths are compiled in practice: the deep throughput burst
    and the shallow "busy" burst used while prefill work is interleaving
    (so busy-mode decode stays pipelined instead of dropping to
    synchronous single steps). `one_step(params, cache, counts, tables,
    tokens, lengths, active, samp, key, greedy=) -> (next_tokens,
    new_lengths, counts, cache)`; the penalty-count state rides the
    scan carry beside the cache (donated like it).

    Returns ``{greedy: (step, {n: scan})}`` for greedy in (False, True);
    the scheduler picks per burst (jit compiles lazily, so an engine that
    only ever serves one mode compiles one set)."""
    lens = sorted({n for n in burst_lens if n > 1})

    def build(greedy: bool):
        step = partial(one_step, greedy=greedy)
        decode_step = partial(jax.jit, donate_argnums=(1, 2))(step)

        def make_scan(n_burst: int):
            @partial(jax.jit, donate_argnums=(1, 2))
            def decode_scan(params, cache, counts, tables, tokens, lengths,
                            active, samp, key):

                def body(carry, _):
                    cache, counts, tokens, lengths, key = carry
                    key, sub = jax.random.split(key)
                    nt, nl, counts, cache = step(
                        params, cache, counts, tables, tokens,
                        lengths, active, samp, sub)
                    # ``counters``: the cache carries device-side counters
                    # (models/hybrid.py) and hands them over beside the
                    # step's tokens: they ride the burst's one fetch.
                    row = jnp.concatenate(
                        [nt, cache.counters.astype(nt.dtype)]
                    ) if counters else nt
                    return (cache, counts, nt, nl, key), row
                (cache, counts, tokens, lengths, key), toks = jax.lax.scan(
                    body, (cache, counts, tokens, lengths, key), None,
                    length=n_burst)
                return toks, tokens, lengths, counts, cache
            return decode_scan

        return decode_step, {n: make_scan(n) for n in lens}

    return {greedy: build(greedy) for greedy in (False, True)}


_dummy_key: jax.Array | None = None


def _DUMMY_KEY() -> jax.Array:
    """A fixed typed PRNG key for calls whose sampled output is ignored
    (the benchmark's warm-up, bench prefill) — cached so the input aval is
    identical across calls (no recompiles)."""
    global _dummy_key
    if _dummy_key is None:
        _dummy_key = jax.random.key(0)
    return _dummy_key


# The one compile-cache location the program itself ever chooses: a fixed,
# git-ignored directory at the root of the checkout. Fixed because the
# path is part of what a cache hit depends on — a directory that moves
# between runs never hits.
_CACHE_DIR = Path(__file__).resolve().parents[2] / ".xla_cache"


def _enable_compilation_cache(cfg_dir: str) -> None:
    """Persistent XLA compilation cache: a restarted gateway re-inits its
    engine in seconds instead of re-compiling (provider builds block on
    engine init — routing/router.py).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    this sets no directory at all — the cache can be placed from outside.
    Otherwise the cache goes to ``compilation_cache_dir`` when the
    operator gave one, else to :data:`_CACHE_DIR`. ``"off"`` leaves JAX's
    settings untouched. A directory that cannot be created or written is
    an error at engine build, not a silently cold start."""
    if cfg_dir.strip().lower() == "off":
        return
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = cfg_dir or str(_CACHE_DIR)
    os.makedirs(path, exist_ok=True)
    if not os.access(path, os.W_OK | os.X_OK):
        raise PermissionError(
            f"compilation cache directory {path!r} is not writable")
    jax.config.update("jax_compilation_cache_dir", path)


def _bucket(n: int, cap: int) -> int:
    """Next power of two ≥ n, capped (prefill compile buckets)."""
    b = 8
    while b < n:
        b *= 2
    return min(b, cap)


def _config_from_checkpoint(model_path: str) -> ModelConfig:
    """Derive ModelConfig from an HF checkpoint's config.json."""
    import json
    cfg = json.loads((Path(model_path) / "config.json").read_text())
    mtype = cfg.get("model_type", "llama")
    common = dict(
        rope_scaling=_parse_rope_scaling(cfg.get("rope_scaling")),
        vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
        d_ff=cfg["intermediate_size"],
        rope_theta=cfg.get("rope_theta", 10000.0),
        rms_eps=cfg.get("rms_norm_eps", 1e-5),
        max_seq_len=cfg.get("max_position_embeddings", 4096),
        tie_embeddings=cfg.get("tie_word_embeddings", False),
    )
    if mtype == "mixtral":
        return ModelConfig(family="mixtral",
                           n_experts=cfg.get("num_local_experts", 8),
                           experts_per_token=cfg.get("num_experts_per_tok", 2),
                           **common)
    if mtype == "mistral":
        # Mistral = llama block + sliding-window attention (null in
        # v0.2+ configs → full attention). Explicit head_dim: Nemo-style
        # checkpoints have head_dim * n_heads != hidden_size.
        return ModelConfig(family="llama",
                           sliding_window=cfg.get("sliding_window") or 0,
                           head_dim_override=cfg.get("head_dim", 0) or 0,
                           **common)
    if mtype == "qwen2":
        return ModelConfig(family="qwen2", attn_bias=True, **common)
    if mtype == "phi3":
        # Phi-3 = llama block with FUSED qkv/gate_up checkpoint tensors
        # (split by the loader — checkpoint.py _fused_bounds) + sliding
        # window (mini-4k: 2047). 128k "longrope" variants are refused
        # by _parse_rope_scaling — silently-wrong RoPE is worse.
        return ModelConfig(family="llama",
                           sliding_window=cfg.get("sliding_window") or 0,
                           **common)
    if mtype == "gemma":
        # Gemma always ties embeddings (HF omits the flag in some configs)
        # and carries an explicit head_dim (7B: 16 x 256 != hidden 3072).
        common["tie_embeddings"] = True
        return ModelConfig(family="gemma", act="gelu_tanh", rms_offset=1.0,
                           scale_embed=True,
                           head_dim_override=cfg.get("head_dim", 0),
                           **common)
    return ModelConfig(family="llama", **common)


def _parse_rope_scaling(block: dict | None):
    """HF config.json ``rope_scaling`` → RopeScaling. Unsupported types
    raise — loading a checkpoint with silently-wrong RoPE is worse than
    refusing it. The no-op "default" type and null are both accepted."""
    if not block:
        return None
    from ..models.config import RopeScaling
    rtype = block.get("rope_type", block.get("type", "llama3"))
    if rtype == "default":
        return None
    return RopeScaling(            # RopeScaling validates rtype
        rope_type=rtype,
        factor=float(block.get("factor", 8.0)),
        low_freq_factor=float(block.get("low_freq_factor", 1.0)),
        high_freq_factor=float(block.get("high_freq_factor", 4.0)),
        original_max_seq=int(block.get("original_max_position_embeddings",
                                       8192)),
        **{k: float(block[k]) for k in ("beta_fast", "beta_slow", "mscale",
                                        "mscale_all_dim") if k in block})
