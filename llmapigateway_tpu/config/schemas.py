"""Pydantic schemas for the two json5 config files.

Schema-compatible with the reference's on-disk formats so existing configs
migrate unchanged (``providers.json``: list of single-key dicts name→details,
cf. ``llm_gateway_core/config/loader.py:14-35``; ``models_fallback_rules.json``:
list of rule objects, cf. ``loader.py:37-56``), extended with a ``type`` field
on providers so an in-process TPU engine is "just another provider":

    { "local_tpu": { "type": "local", "engine": { "model_path": ..., ... } } }
"""
from __future__ import annotations

from typing import Any

from pydantic import BaseModel, ConfigDict, Field, field_validator


class ConfigError(Exception):
    """Raised on invalid configuration; callers decide whether to exit."""


class DisaggregationConfig(BaseModel):
    """Prefill/decode disaggregation knobs (engine/disagg.py, ISSUE 13).

    When enabled, the engine's batch slots split into a prefill pool and
    a decode pool over ONE shared paged KV pool; a completed prefill
    hands its KV to the decode pool by allocator refcount transfer (zero
    device copies). Incompatible with speculative decoding and SWA ring
    mode (rejected at engine build).
    """
    model_config = ConfigDict(extra="forbid")

    enabled: bool = False
    # Slots reserved for the prefill pool; 0 = auto (max(1, B // 4)).
    # Must leave at least one decode slot: 0 <= prefill_slots < B.
    prefill_slots: int = Field(default=0, ge=0)
    # "goodput": predict per-pool TTFT/TPOT attainment from fitted step
    # times + flight-ring decode occupancy + queue depth, shed (429 +
    # Retry-After) when the decode pool's predicted TPOT misses the
    # request's SLO, clamp (mark-only) when only TTFT is at risk.
    # "always": admit everything the watermark allows (telemetry still
    # flows; the baseline an A/B of admission compares against).
    admission: str = "goodput"

    @field_validator("admission")
    @classmethod
    def _admission_known(cls, v: str) -> str:
        if v not in ("goodput", "always"):
            raise ValueError(
                f"admission must be 'goodput' or 'always', got {v!r}")
        return v


class SupervisorConfig(BaseModel):
    """Engine supervision knobs (reliability/supervisor.py, ISSUE 14).

    The scheduler loop stamps a heartbeat every step; a watchdog task
    declares the engine stalled when the heartbeat goes stale past
    ``watchdog_ms`` while work is pending, and triggers the same
    supervised restart path as a step-loop crash: bounded exponential
    backoff (``backoff_ms`` doubling per attempt up to
    ``backoff_max_ms``), at most ``max_restarts`` attempts before the
    engine parks in ``failed`` and traffic stays on the router's
    fallback chain. ``drain_deadline_ms`` bounds how long an
    administrative drain waits for in-flight decodes before
    force-cancelling stragglers.
    """
    model_config = ConfigDict(extra="forbid")

    # 0 disables the watchdog task entirely (heartbeats still stamp, so
    # stats()/health report staleness either way).
    watchdog_ms: float = Field(default=0.0, ge=0.0)
    max_restarts: int = Field(default=3, ge=0)
    backoff_ms: float = Field(default=50.0, ge=0.0)
    backoff_max_ms: float = Field(default=5000.0, ge=0.0)
    drain_deadline_ms: float = Field(default=10000.0, gt=0.0)


# The mesh axes the engine shards over, slowest → fastest varying
# (parallel/mesh.py lays devices out in this order).
MESH_AXES = ("data", "expert", "model")


def check_mesh_axes(sizes: dict[str, int]) -> None:
    """Refuse a mesh that names an axis the program does not have: a
    configuration file is input from outside, and an axis dropped in
    silence would serve on fewer chips than the file says."""
    for ax in sizes:
        if ax not in MESH_AXES:
            raise ValueError(
                f"unknown mesh axis {ax!r}: the axes are "
                f"{', '.join(MESH_AXES)}")


class LocalEngineConfig(BaseModel):
    """Engine settings for a ``type: local`` provider entry.

    No reference counterpart — the reference proxies only. These knobs shape
    the JAX serving engine: checkpoint location, mesh layout, batching and
    KV-cache geometry.
    """
    model_config = ConfigDict(extra="forbid")

    model_path: str = ""            # HF checkpoint dir (safetensors); "" → random init
    preset: str | None = None       # named config (e.g. "tinyllama-1.1b") when no checkpoint
    dtype: str = "bfloat16"
    # Mesh geometry: axis name (one of MESH_AXES) -> size. Product must
    # equal device count used.
    mesh: dict[str, int] = Field(default_factory=dict)   # e.g. {"data":1,"model":8}

    @field_validator("mesh")
    @classmethod
    def _known_axes(cls, v: dict[str, int]) -> dict[str, int]:
        check_mesh_axes(v)
        return v

    max_batch_size: int = 8
    max_seq_len: int = 4096
    # The KV cache is a page pool; the key is accepted for old files and
    # has one value (PR 49 removed the contiguous layout from the engine).
    kv_layout: str = "paged"

    @field_validator("kv_layout")
    @classmethod
    def _one_layout(cls, v: str) -> str:
        if v != "paged":
            raise ValueError(
                f"kv_layout {v!r}: the KV cache is a page pool and 'paged' "
                "is the only value; the contiguous layout was removed "
                "(PR 49). Drop the key")
        return v

    # Page size doubles as the paged kernel's DMA block; 256 is the
    # measured optimum on v5e (2026-07-31 ladder: 1647.8 vs 1443.7
    # tok/s at 128, TinyLlama bs=8). Smaller pages trade a little DMA
    # efficiency for finer capacity granularity in the equal-HBM
    # admission math (engine/paged.py).
    kv_page_size: int = 256
    kv_num_pages: int = 0           # 0 → derived from max_batch_size*max_seq_len
    # Multi-page kernel blocking: fetch this many CONTIGUOUS logical pages
    # per paged-kernel grid step (one pages_per_block× larger HBM→VMEM
    # DMA; the kernel grid shrinks by the same factor — the decode
    # roofline lever at target scale, ISSUE 2). >1 switches the page
    # allocator to superpage packing (aligned runs of this many physical
    # pages; up to ppb-1 pages of internal fragmentation per slot) so the
    # kernels' gather-free index maps stay valid. Falls back to 1 with a
    # warning when the pool can't be packed (seq-banded pools, the SWA
    # page ring, or non-divisible page geometry). Numerics are identical
    # for every value (bit-for-bit vs per-page kernels).
    kv_pages_per_block: int = 1
    # Radix prefix cache over the paged pool (ISSUE 6): requests whose
    # prompt prefix is resident (shared system prompts, multi-turn
    # history) map the matched KV blocks straight into their page table
    # and skip the matched span's prefill entirely; completed requests
    # index their pages back on release (insert-on-release). Eviction is
    # LRU-by-leaf under page pressure with in-flight pages refcount-
    # pinned. Reuse granularity is kv_page_size × kv_pages_per_block
    # tokens. Active on single-host, single-band, non-sliding-window
    # paged engines; everywhere else the flag is inert. Hit accounting
    # surfaces as `prompt_tokens_details.cached_tokens` in usage frames
    # and as engine_prefix_cache_* series in /metrics.
    prefix_cache: bool = True
    # Chip HBM peak (GB/s) for the engine's roofline telemetry: with this
    # set, stats()/the /v1/api/roofline endpoint report achieved GB/s as
    # a fraction of peak (v5e: 819). 0 = unknown — absolute achieved_gbps
    # still reports from the bytes-touched model × measured step time.
    hbm_peak_gbps: float = 0.0
    prefill_chunk: int = 512
    # Max queued admissions prefilled in ONE compiled call (the
    # scheduler groups same-bucket chunks and snaps the group size down
    # to a compiled K rung {1,2,4,8}). A K-batch pays one dispatch for
    # K chunks; each (bucket, K) pair costs one lazily-compiled
    # program. 1 disables.
    prefill_batch: int = 8
    decode_burst: int = 8           # chained decode steps per host sync
    # Burst depth while new work is waiting (prefill interleave): deep
    # enough to amortize dispatch latency, shallow enough that admission
    # never waits long. 1 = legacy fully-synchronous busy stepping.
    decode_burst_busy: int = 4
    # TTFT self-tuning (>0 enables): a dispatched decode scan cannot be
    # preempted, so a probe arriving at an IDLE-queue engine waits out
    # the in-flight deep burst before its prefill starts. With a target
    # set, the engine caps the deep depth so that exposure spends at
    # most half the target (the other half covers flush + prefill +
    # first-token sampling), using its own measured steady-state
    # step-time EMA — self-tuning across models/hardware where a fixed
    # decode_burst is only right for one step time. The cap snaps to a
    # compiled scan depth (deep, deep/2, busy) — arbitrary depths would
    # fall off the fused-scan fast path.
    ttft_target_ms: float = 0.0
    max_tokens_default: int = 1024
    # Prompt-lookup speculative decoding: draft N tokens per step from the
    # slot's own token history, verify in one T=N+1 forward (exact greedy
    # output — wrong drafts are rejected by construction). 0 = off.
    # N+1 must be a power of two (kernel blocking): N ∈ {1, 3, 7}.
    # Engages only while every active slot is greedy; while any
    # temperature>0 request is active the whole batch is served through
    # the normal (unaccelerated) decode path. Works with both KV
    # layouts and with kv_quant='int8' (the verify self-block is
    # mixed-precision: off-diagonal drafts go through the same
    # quantize→dequantize plain decode reads, preserving the
    # exact-greedy guarantee).
    spec_draft_len: int = 0
    # Adaptive drafting gate: a speculative step is a T=k+1 verify forward
    # (~1.2-1.3x a T=1 step's device time), so drafting only pays while
    # accepted tokens/step clears that ratio. The engine keeps a per-slot
    # acceptance EMA and falls back to NORMAL decode bursts while the
    # active batch's mean is below this threshold — so spec can stay
    # enabled in config without taxing non-repetitive traffic. While
    # gated off, one 1-step speculative PROBE runs every
    # `spec_probe_interval` decode rounds to re-measure (text often turns
    # repetitive mid-stream: quoting, code, lists). 0 disables the
    # ACCEPTANCE term only — the wall-clock term below still gates
    # unless spec_wall_gate is also off (both off = always draft).
    # New/unmeasured slots count optimistically so fresh requests get a
    # chance to establish their rate.
    spec_min_tokens_per_step: float = 1.2
    spec_probe_interval: int = 25
    # PER-SLOT adaptive drafting: suspend drafting on any slot whose
    # acceptance EMA, expressed as an acceptance RATIO ((ema_tokens/step
    # - 1) / k, i.e. the fraction of proposed drafts accepted), falls
    # below this floor. A suspended slot's drafts are masked on device
    # (deterministic 1 token/step), its EMA freezes, and it stops
    # dragging the batch-mean gate above; when EVERY active slot is
    # suspended the scheduler skips spec bursts entirely (full-width
    # normal decode). Suspended slots re-probe together every
    # `spec_probe_interval` spec rounds: one 1-step burst with all slots
    # drafting re-measures, and a slot whose fresh ratio clears the
    # floor resumes. 0 disables per-slot suspension (batch-level gates
    # above still apply).
    spec_acceptance_floor: float = 0.0
    # Wall-clock gate term: also close the gate while the MEASURED spec
    # ms-per-emitted-token (EMA over full spec bursts) exceeds the normal
    # path's. Acceptance tokens/step alone can hold a net-loss gate open
    # — a degenerate repetition loop accepts 2+ tokens/step while each
    # spec step costs several times a fused decode step (v5e ladder
    # 2026-07-31: 346.9 vs 1475.1 tok/s, acceptance gate open at 2.24).
    # Off = acceptance-only gating (the pre-r5 behavior).
    spec_wall_gate: bool = True
    # Weight quantization: "int8" stores the seven big matmul weights per
    # layer (incl. MoE expert matmuls) + lm_head as symmetric per-channel
    # int8 (activations quantize dynamically inside the step;
    # models/quant.py). Halves the weight bytes each decode step streams
    # from HBM — the decode roofline — at a small accuracy cost (W8A8).
    # "int4" packs the LAYER matmuls to 4-bit (lm_head stays int8):
    # ~45% fewer weight bytes again, at a larger quality cost users opt
    # into per-provider (W4A8; mixed s8×s4 dot_general).
    quant: str = ""                 # "" | "int8" | "int4"
    # KV-cache quantization: "int8" stores K/V as symmetric per-token
    # per-head int8 (+ fp32 scales, ~6% overhead) — halves KV bandwidth
    # AND capacity footprint, the long-context/high-concurrency lever.
    # Works with both KV layouts (a paged int8 pool packs 2x the tokens)
    # and composes with `quant` and with speculation.
    kv_quant: str = ""              # "" | "int8"
    attention: str = "auto"         # "auto" | "pallas" | "reference"
    tokenizer_path: str | None = None
    # Persistent XLA compilation cache: a second engine init skips the
    # trace+compile. JAX_COMPILATION_CACHE_DIR in the environment wins and
    # the engine then sets nothing; otherwise "" → `.xla_cache/` at the
    # root of the checkout, a path → that directory, "off" → the engine
    # leaves JAX's cache settings alone.
    compilation_cache_dir: str = ""
    # Pre-compile BOTH sampler variants (greedy + general) off-thread on
    # start() so the first temperature>0 request doesn't stall mid-serving.
    # Benchmarks disable it (the compile churn competes with latency probes).
    prewarm_sampler_variants: bool = True
    # Numerics sanitizer (SURVEY.md §5 "race detection / sanitizers"): raise
    # on NaN production inside compiled programs (costs performance; debug).
    debug_nans: bool = False
    # Scheduler flight recorder (ISSUE 7): capacity of the preallocated
    # per-step/lifecycle record ring (obs/flight.py), served at
    # GET /v1/api/flight and exported by tools/flight_report.py. Appends
    # are allocation- and lock-free on the step path, so the recorder is
    # on by default; ring-wrap loss is visible as the
    # gateway_engine_flight_ring_evicted_total series. 0 disables.
    # (Same knob pattern as the gateway-level TRACE_RING_SIZE.)
    flight_ring_size: int = 4096
    # HBM headroom watermark (ISSUE 8): shed admissions (HTTP 429 with
    # the engine's Retry-After hint, the PR 3 overload path) while the
    # runtime allocator reports less than this FRACTION of device memory
    # free — admission reacts to memory pressure before the next compile
    # or fragmentation event OOMs mid-stream. 0 disables. Inert on
    # backends without allocator stats (CPU reports none); the HBM
    # ledger's gateway_engine_hbm_* gauges report the same numbers.
    hbm_headroom_watermark: float = Field(default=0.0, ge=0.0, lt=1.0)
    # Prefill/decode disaggregation (ISSUE 13): two pools, one paged KV
    # pool, zero-copy handoff, goodput-first admission. Default off —
    # the unified scheduler is byte-identical to pre-pool behavior.
    disaggregation: DisaggregationConfig = Field(
        default_factory=DisaggregationConfig)
    # Engine supervision (ISSUE 14): crash/stall recovery with bounded
    # backoff, graceful drain. Watchdog defaults off; crash recovery and
    # the lifecycle state machine are always on.
    supervisor: SupervisorConfig = Field(default_factory=SupervisorConfig)


class BreakerSettings(BaseModel):
    """Per-provider circuit-breaker knobs (reliability/breaker.py, ISSUE 3).

    Defaults are deliberately conservative: a provider must fail at least
    half of a 5+-request window inside 30 s before the router stops paying
    its timeouts, and gets a single half-open probe every ``cooldown_s``
    until it recovers. Set ``enabled: false`` to opt a provider out (e.g.
    a single-target chain where skipping the only target helps nobody).
    """
    model_config = ConfigDict(extra="forbid")

    enabled: bool = True
    window_s: float = Field(default=30.0, gt=0)       # sliding failure window
    min_requests: int = Field(default=5, ge=1)        # samples before judging
    failure_threshold: float = Field(default=0.5, gt=0, le=1.0)
    cooldown_s: float = Field(default=15.0, gt=0)     # open → half-open probe


class ProviderDetails(BaseModel):
    """One provider's connection/engine details.

    Reference counterpart: ``ProviderDetails`` (baseUrl, apikey) at
    ``loader.py:14-16``; the reference ignores unknown keys (e.g. the
    "multiple_models" field in its own example) — we accept extras too.
    """
    model_config = ConfigDict(extra="allow")

    type: str = "remote_http"       # "remote_http" | "local"
    baseUrl: str | None = None
    apikey: str | None = None       # env-var name, or the literal key itself
    engine: LocalEngineConfig | None = None
    breaker: BreakerSettings | None = None   # None → BreakerSettings defaults

    @field_validator("type")
    @classmethod
    def _check_type(cls, v: str) -> str:
        if v not in ("remote_http", "local"):
            raise ValueError(f"provider type must be 'remote_http' or 'local', got {v!r}")
        return v

    def validate_semantics(self, name: str) -> None:
        if self.type == "remote_http" and not self.baseUrl:
            raise ValueError(f"provider {name!r}: remote_http requires 'baseUrl'")
        if self.type == "local" and self.engine is None:
            raise ValueError(f"provider {name!r}: local provider requires 'engine' config")


class FallbackModelRule(BaseModel):
    """One target in a gateway model's fallback chain.

    Reference counterpart: ``FallbackModelRule`` at ``loader.py:37-45``.
    """
    model_config = ConfigDict(extra="forbid")

    provider: str
    model: str
    use_provider_order_as_fallback: bool = False
    providers_order: list[str] | None = None
    retry_delay: float = 0.0
    retry_count: int = 0
    custom_body_params: dict[str, Any] | None = None
    custom_headers: dict[str, str] | None = None

    @field_validator("use_provider_order_as_fallback", mode="before")
    @classmethod
    def _coerce_bool(cls, v: Any) -> Any:
        if isinstance(v, str):
            return v.strip().lower() == "true"
        return v


class ModelFallbackConfig(BaseModel):
    """A gateway model: ordered fallback chain + rotation flag.

    Reference counterpart: ``ModelFallbackConfig`` at ``loader.py:47-56``
    (including the '"true"'-string coercion for ``rotate_models``).
    """
    model_config = ConfigDict(extra="forbid")

    gateway_model_name: str
    fallback_models: list[FallbackModelRule]
    rotate_models: bool = False
    # Default end-to-end time budget (ms) for requests to this gateway
    # model when the client sends neither the `x-request-timeout-ms`
    # header nor a `timeout_ms` body field. 0 = fall through to the
    # gateway-wide DEFAULT_REQUEST_TIMEOUT_MS (which itself defaults to
    # unbounded). Exhaustion returns HTTP 504 with per-attempt detail.
    timeout_ms: float = Field(default=0.0, ge=0)
    # Default per-request SLO targets (ms) for this gateway model when
    # the client sends no `x-slo-ttft-ms` / `x-slo-tpot-ms` headers
    # (obs/slo.py; ISSUE 7). Unlike timeout_ms these never fail a
    # request — they only classify it: outcomes land on the
    # gateway_slo_{met,violated}_total /metrics series, the usage DB
    # row, and the final usage frame, with TTFT violations attributed
    # (queued / prefill / decode_contention) from the flight recorder.
    # 0 = no target.
    slo_ttft_ms: float = Field(default=0.0, ge=0)
    slo_tpot_ms: float = Field(default=0.0, ge=0)

    @field_validator("rotate_models", mode="before")
    @classmethod
    def _coerce_bool(cls, v: Any) -> Any:
        if isinstance(v, str):
            return v.strip().lower() == "true"
        return v
