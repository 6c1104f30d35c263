"""Model architecture configs and named presets.

Presets cover the BASELINE.md ladder: tiny-test (CI), TinyLlama-1.1B
(config 1), Llama-3-8B (configs 2-3), Mixtral-8x7B (config 4, MoE),
Llama-3-70B (config 5, multi-host).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, replace


@dataclass(frozen=True)
class RopeScaling:
    """RoPE frequency scaling (HF ``rope_scaling`` block).

    ``llama3`` — Llama-3.1-style per-frequency-band scaling (long
    wavelengths divided by ``factor``, short ones untouched, smooth
    interpolation between ``low_freq_factor``/``high_freq_factor`` bands of
    the ``original_max_seq`` context). ``linear`` — uniform position
    interpolation (every frequency divided by ``factor``). ``yarn`` — by
    parts: a pair that turns more than ``beta_fast`` times inside
    ``original_max_seq`` keeps its frequency, one that turns less than
    ``beta_slow`` times is divided by ``factor``, a linear ramp over the
    pair index between; cos and sin are scaled by ``mscale`` over
    ``mscale_all_dim`` (each ``0.1 m ln(factor) + 1``), and where
    ``mscale_all_dim`` is set the softmax scale by its square
    (:meth:`softmax_mscale`).
    """
    rope_type: str = "llama3"      # "llama3" | "linear" | "yarn"
    factor: float = 8.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_seq: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0

    def __post_init__(self):
        if self.rope_type not in ("llama3", "linear", "yarn"):
            raise ValueError(
                f"unsupported rope_scaling type {self.rope_type!r}; "
                f"supported: llama3, linear, yarn")

    @staticmethod
    def _yarn_mscale(factor: float, m: float) -> float:
        return 0.1 * m * math.log(factor) + 1.0 if factor > 1 and m else 1.0

    @property
    def table_mscale(self) -> float:
        """What YaRN multiplies cos and sin by (1 for the other types)."""
        if self.rope_type != "yarn":
            return 1.0
        return (self._yarn_mscale(self.factor, self.mscale)
                / self._yarn_mscale(self.factor, self.mscale_all_dim))

    @property
    def softmax_mscale(self) -> float:
        """What YaRN multiplies the attention scores by: the square of the
        ``mscale_all_dim`` magnitude (1 where it is not set)."""
        if self.rope_type != "yarn":
            return 1.0
        return self._yarn_mscale(self.factor, self.mscale_all_dim) ** 2


@dataclass(frozen=True)
class ModelConfig:
    # "llama" | "qwen2" | "gemma" | "mixtral", and the six PERIOD families
    # (``layer_period`` > 0, models/hybrid.py): "hybrid" (Solar-Open2),
    # "smallthinker", "mistral4", "cohere2_moe" (Command A+, the parallel
    # block) and "gigachat3_5" (a latent layer then three gated-delta-net
    # layers a period behind ``leading_dense`` layers of a linear mixer and
    # a dense MLP, every sub-block normed before and after) and "keye_vl2"
    # (softmax layers whose learned indexer selects ``idx_topk`` keys a
    # query: an index-key side beside the K/V pages). A period
    # family is served from page pools on one device and refuses, at engine
    # build and with the reason, any mesh axis, the prefix cache,
    # speculation, disaggregation and ``model_path``.
    family: str = "llama"
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 22
    n_heads: int = 32
    n_kv_heads: int = 4
    d_ff: int = 5632
    rope_theta: float = 10000.0
    rope_scaling: RopeScaling | None = None
    rms_eps: float = 1e-5
    max_seq_len: int = 4096
    tie_embeddings: bool = False
    # QKV projection bias (Qwen2-family); the rest of the block is llama.
    attn_bias: bool = False
    # Gemma-family block variations (all config-driven — the llama forward
    # is the single implementation):
    act: str = "silu"              # MLP gate activation: "silu" | "gelu_tanh"
    rms_offset: float = 0.0        # RMSNorm weight offset: x * (offset + w)
    scale_embed: bool = False      # multiply embeddings by sqrt(d_model)
    # Explicit head dim for families where H * Dh != d_model (Gemma-7B:
    # 16 heads x 256 vs d_model 3072). 0 = derive d_model // n_heads.
    head_dim_override: int = 0
    # Sliding-window attention (mistral-family): position i attends keys
    # j with i - j < window (self included) — HF Mistral semantics. 0 =
    # full causal attention. v1 masks only (the linear cache keeps every
    # token; windowed KV eviction is a capacity optimization, not a
    # correctness requirement).
    sliding_window: int = 0
    # MoE (mixtral) fields
    n_experts: int = 0             # 0 → dense
    experts_per_token: int = 2
    # The "hybrid" family (models/hybrid.py): linear-attention layers with
    # one softmax layer per period, and an expert layer that may hold only
    # this chip's share of the experts. The router always scores all
    # ``n_experts``; experts [first_expert_held, first_expert_held +
    # n_experts_held) live here and only their part of the result is
    # computed (0 held = all of them).
    n_experts_held: int = 0
    first_expert_held: int = 0
    d_ff_expert: int = 0           # routed (and shared) expert width
    # Always-on experts of width d_ff_expert; several join the routed sum
    # as the MEAN of their outputs (Command A+'s "average", the one preset
    # with more than one; its ``logit_scale`` is 1 and has no field).
    n_shared_experts: int = 0
    layer_period: int = 0          # layers per period; position 0 is softmax
    #                                (a latent layer where ``is_mla``)
    lin_heads: int = 0             # linear-attention heads ...
    lin_head_dim: int = 0          # ... their key/value size ...
    lin_conv_taps: int = 0         # ... causal depthwise conv before q/k/v
    lin_gate_rank: int = 0         # rank of the decay and output gate pairs
    use_rope: bool = True          # False: no rotary on the softmax layers
    attn_gate: bool = False        # softmax output gated by sigmoid(W x)
    # The layer pattern as data, per position of a period (empty: every
    # softmax layer alike, as ``sliding_window`` and ``use_rope`` say).
    # Without ``lin_heads`` every position of a period is a softmax layer.
    window_layout: tuple[int, ...] = ()   # 1: attends inside sliding_window
    rope_layout: tuple[int, ...] = ()     # 1: rotary on q and k
    # The period families' expert layer: how the router scores ("sigmoid":
    # top-k of the sigmoids, normalised; "softmax": top-k of the logits,
    # softmax over the selected), the experts' gate activation, and whether
    # the router reads the block's input (before attention, un-normalised)
    # instead of the MLP's own normalised input.
    moe_router: str = "sigmoid"
    moe_act: str = "silu"                 # "silu" | "relu"
    router_reads_block_input: bool = False
    # Latent attention (models/mla.py; 0 ranks: none). Queries come through
    # a normed bottleneck of ``q_lora_rank``; keys and values through ONE
    # normed latent of ``kv_lora_rank`` a token, which with one rotary key
    # of ``qk_rope_head_dim`` shared by all heads is all the cache keeps
    # (``latent_width`` numbers a token a layer: ops/latent_attention.py).
    # A head's query and key are ``qk_nope_head_dim`` un-rotated numbers
    # beside the rotary ones; its value has ``v_head_dim``.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Rotary pairs (2i, 2i+1), not (i, i+half): the latent layers' rotary
    # part and, in the period families, the softmax layers' whole head.
    rope_interleave: bool = False
    # Queries scaled by 1 + beta ln(1 + floor(pos / original_max_seq)).
    query_scale_beta: float = 0.0
    # The parallel block of the period families (models/hybrid.py): ONE
    # norm a layer whose result feeds attention, the routed and the shared
    # experts side by side, and ONE residual add, x + A + R + S.
    parallel_block: bool = False
    # The period families' norm: "rms" (``rms_eps``), "layernorm" — mean
    # removed, no bias, ``layer_norm_eps`` — or "rms_2sigmoid": an RMS norm
    # whose gain is ``2 sigmoid(w)`` (1 at w = 0; the tree keeps the raw
    # ``w``). ``post_norm``: every sub-block's branch is normed AGAIN before
    # it joins the stream, x + N(f(N(x))).
    norm_kind: str = "rms"
    layer_norm_eps: float = 1e-5
    post_norm: bool = False
    # Layers in FRONT of the periods (``n_layers`` counts them): each a
    # linear-attention mixer and a dense gated MLP of width ``d_ff``.
    leading_dense: int = 0
    # The linear layer's kind. "kda": as many key heads as value heads, a
    # decay per channel through a rank-``lin_gate_rank`` pair, b = 2 sigmoid,
    # an output gate sigmoid(pair). "gated_delta": ``lin_key_heads`` key
    # heads, each serving ``lin_heads / lin_key_heads`` value heads; ONE
    # decay a value head, exp(-exp(A) softplus(W_a h + dt)); b = sigmoid;
    # the output gate ``2 sigmoid(h W_z)`` at full width.
    lin_kind: str = "kda"
    lin_key_heads: int = 0         # 0: as many as value heads (lin_heads)
    # The period families' router: the selected weights are multiplied by
    # ``routed_scale``; with ``router_bias`` the top-k is taken of score +
    # e (a bias an expert, selection only: the weights stay the scores).
    routed_scale: float = 1.0
    router_bias: bool = False
    # > 0: every gated MLP is (act(min(g, L)) * clip(u, -L, L)) W_d.
    swiglu_limit: float = 0.0
    # A learned indexer on the softmax layers (ops/sparse_attention.py; 0:
    # none): ``idx_heads`` index queries of ``idx_head_dim`` and ONE index
    # key a token score every cached position, and a query attends the
    # ``idx_topk`` positions of largest score only (all of them while there
    # are fewer). The index key is a third side of the group's page pool.
    idx_heads: int = 0
    idx_head_dim: int = 0
    idx_topk: int = 0
    # A per-head RMS norm on q and k before the rotary (one weight each).
    # ``qk_norm_draw``: what a RANDOM draw gives both weights (a checkpoint
    # brings its own): attention logits then have standard deviation
    # ``qk_norm_draw ** 2`` — at 1 a softmax over thousands of keys is all
    # but even, and its output says nothing of WHICH keys it saw.
    qk_norm: bool = False
    qk_norm_draw: float = 1.0   # (differential attention, which has no such
    #                             norm, draws its q and k projections at it)
    # The decoder-hybrid-decoder family (models/sambay.py; "phi4flash"):
    # ``layer_period`` 2 — the even layer of a pair holds the state side,
    # the odd one attends — in THREE runs: ``n_self_pairs`` of [selective
    # scan, attention inside ``sliding_window``], ONE pair [selective scan
    # that also hands its gated output up the stack (the MEMORY), attention
    # over the whole context, whose K/V is the one full-context cache], and
    # ``n_cross_pairs`` of [a gate on the memory, attention whose K/V are
    # that one layer's]. Every MLP is dense. A prompt's rows stop at the
    # full layer's K/V: everything above runs on a prompt's last row only.
    cross_decoder: bool = False
    # ``lin_kind`` "mamba" (the selective scan, Mamba-1): a diagonal state
    # of ``ssm_state`` numbers a channel of ``ssm_expand * d_model``
    # channels, the step, B and C read from the token through a
    # rank-``ssm_dt_rank`` bottleneck; ``lin_conv_taps`` taps before it.
    ssm_state: int = 0
    ssm_expand: int = 0
    ssm_dt_rank: int = 0
    # Its attention is DIFFERENTIAL: query heads (2i, 2i+1) and K/V heads
    # (2j, 2j+1) pair; a pair's two softmax maps read the pair's V side by
    # side and are SUBTRACTED under a learned weight, then normed. SERVED
    # folded (``served()``): a pair of K/V heads is ONE head of twice the
    # width and a query head is half zeros, which IS grouped-query
    # attention on the paged kernels as they are.

    def __post_init__(self):
        for name in ("window_layout", "rope_layout"):
            layout = getattr(self, name)
            if layout and len(layout) != max(1, self.layer_period):
                raise ValueError(f"{name} {layout} is not one entry a "
                                 f"position of a period of "
                                 f"{max(1, self.layer_period)}")
        if self.moe_router not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown moe_router {self.moe_router!r}")
        if self.moe_act not in ("silu", "relu"):
            raise ValueError(f"unknown moe_act {self.moe_act!r}")
        if self.norm_kind not in ("rms", "layernorm", "rms_2sigmoid",
                                  "layernorm_bias"):
            raise ValueError(f"unknown norm_kind {self.norm_kind!r}")
        if self.lin_kind not in ("kda", "gated_delta", "mamba"):
            raise ValueError(f"unknown lin_kind {self.lin_kind!r}")
        if self.idx_topk and not (self.idx_heads and self.idx_head_dim):
            raise ValueError("idx_topk needs idx_heads and idx_head_dim")
        if self.leading_dense and not self.lin_heads:
            raise ValueError("leading_dense layers carry a linear mixer: "
                             "they need lin_heads")
        if self.cross_decoder and (
                self.layer_period != 2 or self.n_layers % 4
                or self.n_layers < 8 or self.lin_kind != "mamba"
                or not (self.ssm_state and self.ssm_expand
                        and self.ssm_dt_rank and self.lin_conv_taps)
                or not self.sliding_window):
            raise ValueError(
                "a cross decoder is pairs of [state, attention] layers in "
                "three runs (n_layers a multiple of 4, 8 or more), its "
                "state layers selective scans with their sizes, its lower "
                "attention layers inside a sliding_window")

    def served(self) -> "ModelConfig":
        """The geometry the engine SERVES (itself for most families). A
        cross decoder's differential attention is served FOLDED: K/V heads
        pair into heads of twice the width (the same bytes a token), the
        query heads keep their number at that width, half of each zeros.
        A preset states the PUBLISHED heads and no ``head_dim_override``;
        the folded config has one, and is its own ``served()``."""
        if not self.cross_decoder or self.head_dim_override:
            return self
        return replace(self, n_kv_heads=self.n_kv_heads // 2,
                       head_dim_override=2 * self.head_dim)

    @property
    def head_dim(self) -> int:
        return self.head_dim_override or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def is_sparse(self) -> bool:
        return self.idx_topk > 0

    @property
    def latent_width(self) -> int:
        """Numbers a latent-attention layer caches a token: the normed
        latent and the one rotated key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    @property
    def softmax_positions(self) -> tuple[int, ...]:
        """The positions of a period that are softmax layers (they keep
        paged KV): position 0 where the others are linear-attention
        layers, else every one."""
        if self.cross_decoder:
            return (1,)
        if self.layer_period and not self.lin_heads:
            return tuple(range(self.layer_period))
        return (0,)

    def window_at(self, position: int) -> int:
        """The window of the softmax layer at ``position`` of a period
        (0: the whole context)."""
        if self.window_layout and not self.window_layout[position]:
            return 0
        return self.sliding_window

    def rope_at(self, position: int) -> bool:
        return bool(self.rope_layout[position] if self.rope_layout
                    else self.use_rope)

    @property
    def cache_groups(self) -> tuple[tuple[int, tuple[int, ...]], ...]:
        """The softmax layers by the KV they must keep: (window, positions
        of a period) for each distinct window, in order of first position.
        A group's layers share one page pool, one page table a slot and
        one allocator (engine/paged.py CacheGroups): a windowed group may
        recycle a slot's pages below the window, a global one (window 0)
        keeps the whole context."""
        if self.cross_decoder:
            # The ring first: the lower pairs' windowed layers, then the ONE
            # full-context layer (which the cross layers read).
            return ((self.sliding_window, (1,)), (0, (1,)))
        groups: dict[int, list[int]] = {}
        for p in self.softmax_positions:
            groups.setdefault(self.window_at(p), []).append(p)
        return tuple((w, tuple(ps)) for w, ps in groups.items())

    @property
    def group_layers(self) -> tuple[int, ...]:
        """Layers whose K/V each cache group's pool holds, in the order of
        ``cache_groups``."""
        if self.cross_decoder:
            return (self.n_self_pairs, 1)
        if not self.layer_period:
            return (self.n_layers,)
        return tuple(self.n_periods * len(ps) for _, ps in self.cache_groups)

    @property
    def group_readers(self) -> tuple[int, ...]:
        """Layers that READ each cache group's pool a decode step: those
        that keep it and, in a cross decoder's full-context group, the
        cross layers too."""
        if self.cross_decoder:
            return (self.n_self_pairs, 1 + self.n_cross_pairs)
        return self.group_layers

    @property
    def group_chunk_readers(self) -> tuple[int, ...]:
        """Layers whose PREFILL CHUNKS attend each cache group's pool: those
        that keep it — but not a cross decoder's full-context layer, which
        writes a chunk's rows and attends from a prompt's last alone."""
        if self.cross_decoder:
            return (self.n_self_pairs, 0)
        return self.group_layers

    @property
    def n_self_pairs(self) -> int:
        """A cross decoder's lower pairs [scan, window attention]."""
        return self.n_layers // 4

    @property
    def n_cross_pairs(self) -> int:
        """A cross decoder's upper pairs [memory gate, cross attention]."""
        return self.n_layers // 4 - 1

    @property
    def ssm_inner(self) -> int:
        """Channels of a selective-scan layer."""
        return self.ssm_expand * self.d_model

    @property
    def n_periods(self) -> int:
        """Whole periods behind the ``leading_dense`` layers (0: the
        family has no periods)."""
        return ((self.n_layers - self.leading_dense) // self.layer_period
                if self.layer_period else 0)

    @property
    def lin_kheads(self) -> int:
        """Key (and query) heads of a linear layer."""
        return self.lin_key_heads or self.lin_heads

    @property
    def lin_conv_width(self) -> int:
        """Channels of a linear layer's convolution: q, k and v side by
        side."""
        return (2 * self.lin_kheads + self.lin_heads) * self.lin_head_dim

    @property
    def n_kv_layers(self) -> int:
        """Layers that keep paged KV (or a latent row): all, or the
        softmax positions of every period."""
        return sum(self.group_layers)

    @property
    def n_attn_layers(self) -> int:
        """Layers that ATTEND paged KV: those that keep it and, in a cross
        decoder, the layers that read another layer's."""
        return sum(self.group_readers)

    @property
    def n_lin_layers(self) -> int:
        """Layers that keep a recurrent state block per slot (the leading
        layers among them; a cross decoder's selective scans)."""
        if self.cross_decoder:
            return self.n_self_pairs + 1
        return self.n_layers - self.n_kv_layers


PRESETS: dict[str, ModelConfig] = {
    # Tiny model for tests: fast to init/compile on CPU devices.
    "tiny-test": ModelConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256),
    # Same CI-scale geometry with a 1k context: the shared-prefix bench
    # rung needs room for a >=512-token common prefix plus tails on CPU.
    "tiny-test-1k": ModelConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=1024),
    "tiny-qwen-test": ModelConfig(
        family="qwen2", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256, tie_embeddings=True,
        attn_bias=True),
    "tiny-gemma-test": ModelConfig(
        family="gemma", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=1, d_ff=128, max_seq_len=256, tie_embeddings=True,
        act="gelu_tanh", rms_offset=1.0, scale_embed=True,
        head_dim_override=16, rms_eps=1e-6),
    "tiny-moe-test": ModelConfig(
        family="mixtral", vocab_size=512, d_model=64, n_layers=2, n_heads=4,
        n_kv_heads=2, d_ff=128, max_seq_len=256, n_experts=4,
        experts_per_token=2),
    # TinyLlama-1.1B (HF: TinyLlama/TinyLlama-1.1B-Chat-v1.0).
    "tinyllama-1.1b": ModelConfig(
        vocab_size=32000, d_model=2048, n_layers=22, n_heads=32, n_kv_heads=4,
        d_ff=5632, rope_theta=10000.0, max_seq_len=2048),
    # Qwen2-0.5B (HF: Qwen/Qwen2-0.5B-Instruct) — llama block + QKV bias,
    # tied embeddings.
    "qwen2-0.5b": ModelConfig(
        family="qwen2", vocab_size=151936, d_model=896, n_layers=24,
        n_heads=14, n_kv_heads=2, d_ff=4864, rope_theta=1000000.0,
        rms_eps=1e-6, max_seq_len=32768, tie_embeddings=True,
        attn_bias=True),
    # ~3B-class llama geometry (TPU-friendly head_dim=128, GQA 24/8):
    # ~3.2B params ≈ 6.4 GB bf16 — the largest preset that comfortably
    # fits one 16 GB v5e chip with a bs=8 KV cache. The bench ladder's mid
    # rung between TinyLlama and 8B (higher arithmetic intensity; shows
    # whether MFU scales with model width).
    "llama-3b-class": ModelConfig(
        vocab_size=32000, d_model=3072, n_layers=28, n_heads=24,
        n_kv_heads=8, d_ff=8192, rope_theta=10000.0, max_seq_len=2048),
    # Mistral-7B-v0.1 (HF: mistralai/Mistral-7B-Instruct-v0.1): llama
    # block + 4096-token sliding-window attention over a 32k context.
    "mistral-7b": ModelConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, rope_theta=10000.0, max_seq_len=32768,
        sliding_window=4096),
    # Phi-3-mini-4k (HF: microsoft/Phi-3-mini-4k-instruct): llama block,
    # MHA, sliding window 2047; the HF checkpoint ships qkv/gate_up
    # FUSED (engine/checkpoint.py splits them at load).
    "phi-3-mini": ModelConfig(
        vocab_size=32064, d_model=3072, n_layers=32, n_heads=32,
        n_kv_heads=32, d_ff=8192, rope_theta=10000.0, max_seq_len=4096,
        sliding_window=2047),
    # Tiny sliding-window model for tests (window << max_seq).
    "tiny-mistral-test": ModelConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, sliding_window=16),
    # Llama-3-8B (HF: meta-llama/Meta-Llama-3-8B-Instruct).
    "llama-3-8b": ModelConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, rope_theta=500000.0, max_seq_len=8192),
    # Llama-3-70B.
    "llama-3-70b": ModelConfig(
        vocab_size=128256, d_model=8192, n_layers=80, n_heads=64,
        n_kv_heads=8, d_ff=28672, rope_theta=500000.0, max_seq_len=8192),
    # Gemma-2B (HF: google/gemma-2b): MQA (1 KV head), head_dim 256,
    # GeGLU MLP, (1+w) RMSNorm, sqrt(D)-scaled tied embeddings.
    "gemma-2b": ModelConfig(
        family="gemma", vocab_size=256000, d_model=2048, n_layers=18,
        n_heads=8, n_kv_heads=1, d_ff=16384, rope_theta=10000.0,
        rms_eps=1e-6, max_seq_len=8192, tie_embeddings=True,
        act="gelu_tanh", rms_offset=1.0, scale_embed=True,
        head_dim_override=256),
    # Gemma-7B (HF: google/gemma-7b): 16 heads x 256 > d_model 3072.
    "gemma-7b": ModelConfig(
        family="gemma", vocab_size=256000, d_model=3072, n_layers=28,
        n_heads=16, n_kv_heads=16, d_ff=24576, rope_theta=10000.0,
        rms_eps=1e-6, max_seq_len=8192, tie_embeddings=True,
        act="gelu_tanh", rms_offset=1.0, scale_embed=True,
        head_dim_override=256),
    # Mixtral-8x7B (HF: mistralai/Mixtral-8x7B-Instruct-v0.1).
    "mixtral-8x7b": ModelConfig(
        family="mixtral", vocab_size=32000, d_model=4096, n_layers=32,
        n_heads=32, n_kv_heads=8, d_ff=14336, rope_theta=1000000.0,
        max_seq_len=32768, n_experts=8, experts_per_token=2),
    # Solar-Open2-250B (HF: upstage/Solar-Open2-250B) at its PUBLISHED sizes:
    # 48 layers in periods of 4 (one gated NoPE GQA layer, three gated
    # delta-rule linear layers), 320 routed experts of width 1280, top-8,
    # one shared. No chip holds it whole: a deployment states the experts,
    # depth and vocabulary rows it holds (benchmark/configs/).
    "solar-open2-250b": ModelConfig(
        family="hybrid", vocab_size=196608, d_model=4096, n_layers=48,
        n_heads=64, n_kv_heads=8, head_dim_override=128, d_ff=10240,
        rope_theta=10000.0, max_seq_len=1048576, n_experts=320,
        experts_per_token=8, d_ff_expert=1280, n_shared_experts=1,
        layer_period=4, lin_heads=64, lin_head_dim=128, lin_conv_taps=4,
        lin_gate_rank=128, use_rope=False, attn_gate=True),
    # The same pattern at CPU-test size: two periods, 16 experts, top-4.
    "tiny-hybrid-test": ModelConfig(
        family="hybrid", vocab_size=512, d_model=64, n_layers=8, n_heads=4,
        n_kv_heads=2, head_dim_override=16, d_ff=128, max_seq_len=256,
        n_experts=16, experts_per_token=4, d_ff_expert=32,
        n_shared_experts=1, layer_period=4, lin_heads=4, lin_head_dim=16,
        lin_conv_taps=4, lin_gate_rank=8, use_rope=False, attn_gate=True),
}
# SmallThinker-21BA3B-Instruct (HF: PowerInfer/SmallThinker-21BA3B-Instruct)
# at its PUBLISHED sizes: 52 layers in periods of 4 — a global layer without
# rotary embedding, then three rotary layers inside a 4096 window — each
# followed by 64 ReLU-gated experts of width 768, top-6 by a softmax router
# that reads the block's input. Two cache groups (ModelConfig.cache_groups).
PRESETS["smallthinker-21b"] = ModelConfig(
    family="smallthinker", vocab_size=151936, d_model=2560, n_layers=52,
    n_heads=28, n_kv_heads=4, head_dim_override=128, d_ff=768,
    rope_theta=1500000.0, rms_eps=1e-6, max_seq_len=16384,
    sliding_window=4096, n_experts=64, experts_per_token=6, d_ff_expert=768,
    layer_period=4, window_layout=(0, 1, 1, 1), rope_layout=(0, 1, 1, 1),
    moe_router="softmax", moe_act="relu", router_reads_block_input=True)
# The same pattern at CPU-test size: two periods, 8 experts, top-3.
PRESETS["tiny-smallthinker-test"] = replace(
    PRESETS["smallthinker-21b"], vocab_size=512, d_model=64, n_layers=8,
    n_heads=4, n_kv_heads=2, head_dim_override=16, d_ff=32,
    rope_theta=10000.0, max_seq_len=256, sliding_window=16, n_experts=8,
    experts_per_token=3, d_ff_expert=32)
# What ONE v5e chip holds of it as one of 8 that share each layer of a
# pipeline stage (benchmark/configs/solar-open2-250b-ep8.json): two whole
# periods, 40 of the 320 experts, an eighth of the vocabulary rows. Every
# width, the router's 320 outputs and its 8 experts per token stay.
PRESETS["solar-open2-250b-ep8"] = replace(
    PRESETS["solar-open2-250b"], n_layers=8, vocab_size=24576,
    n_experts_held=40)
# What ONE v5e chip holds of SmallThinker as the first of three pipeline
# stages (benchmark/configs/smallthinker-21b-pp3.json): five whole periods,
# every layer whole — all 64 experts, every head, the whole vocabulary.
PRESETS["smallthinker-21b-pp3"] = replace(
    PRESETS["smallthinker-21b"], n_layers=20)


# Mistral-Small-4-119B-2603 (HF: mistralai/Mistral-Small-4-119B-2603) at its
# PUBLISHED sizes: 36 layers of latent attention (32 heads of 64 un-rotated +
# 64 rotary query/key numbers and 128 value numbers through a 1024 query and
# a 256 key/value bottleneck; YaRN x128 over 8192, interleaved pairs,
# position-scaled queries) and 128 softmax-routed experts of width 2048,
# top-4, beside one shared expert. One global cache group of the latent kind.
PRESETS["mistral-small4-119b"] = ModelConfig(
    family="mistral4", vocab_size=131072, d_model=4096, n_layers=36,
    n_heads=32, n_kv_heads=32, head_dim_override=128, d_ff=12288,
    rope_theta=10000.0, rms_eps=1e-6, max_seq_len=1048576,
    rope_scaling=RopeScaling(rope_type="yarn", factor=128.0,
                             original_max_seq=8192, beta_fast=32.0,
                             beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    n_experts=128, experts_per_token=4, d_ff_expert=2048, n_shared_experts=1,
    layer_period=1, moe_router="softmax", q_lora_rank=1024, kv_lora_rank=256,
    qk_nope_head_dim=64, qk_rope_head_dim=64, v_head_dim=128,
    rope_interleave=True, query_scale_beta=0.1)
# The same block at CPU-test size: YaRN x8 over 32 positions, so a context
# of a few pages crosses the original length.
PRESETS["tiny-mistral4-test"] = replace(
    PRESETS["mistral-small4-119b"], vocab_size=512, d_model=64, n_layers=4,
    n_heads=4, n_kv_heads=4, head_dim_override=16, d_ff=128, max_seq_len=256,
    rope_scaling=RopeScaling(rope_type="yarn", factor=8.0,
                             original_max_seq=32, beta_fast=4.0,
                             beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    n_experts=16, experts_per_token=2, d_ff_expert=32, q_lora_rank=32,
    kv_lora_rank=32, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16)
# What ONE v5e chip holds of it as one of 4 that share each layer of a
# 3-stage pipeline (benchmark/configs/mistral-small4-119b-ep4.json): 12
# layers, 32 of the 128 experts, a quarter of the vocabulary rows. Every
# width, the router's 128 outputs and its 4 experts per token stay.
PRESETS["mistral-small4-119b-ep4"] = replace(
    PRESETS["mistral-small4-119b"], n_layers=12, vocab_size=32768,
    n_experts_held=32)

# Command A+ (HF: CohereLabs/command-a-plus-05-2026, ``cohere2_moe``) at its
# PUBLISHED sizes: 32 layers in periods of 4 — three layers that rotate q and
# k (interleaved pairs, theta 50000) and attend inside a 4096 window, then a
# global layer without rotary embedding — 128 query heads over 8 KV heads,
# each layer a PARALLEL block: one LayerNorm (no bias, eps 1e-5; the config's
# rms_norm_eps is null, so ``rms_eps`` is 0 and unused) feeds attention, 128
# sigmoid-routed experts of width 4096 (top-8, normalised) and four shared
# experts whose outputs are averaged; one residual add; a tied head. Two
# cache groups, the RING first (ModelConfig.cache_groups).
PRESETS["command-a-plus"] = ModelConfig(
    family="cohere2_moe", vocab_size=262144, d_model=4096, n_layers=32,
    n_heads=128, n_kv_heads=8, head_dim_override=128, d_ff=4096,
    rope_theta=50000.0, rms_eps=0.0, max_seq_len=200000, sliding_window=4096,
    tie_embeddings=True, n_experts=128, experts_per_token=8, d_ff_expert=4096,
    n_shared_experts=4, layer_period=4, window_layout=(1, 1, 1, 0),
    rope_layout=(1, 1, 1, 0), moe_router="sigmoid", rope_interleave=True,
    parallel_block=True, norm_kind="layernorm", layer_norm_eps=1e-5)
# The same block at CPU-test size: two periods, 16 experts top-4, two shared.
PRESETS["tiny-cohere2-test"] = replace(
    PRESETS["command-a-plus"], vocab_size=512, d_model=64, n_layers=8,
    n_heads=8, n_kv_heads=2, head_dim_override=16, d_ff=32,
    rope_theta=10000.0, max_seq_len=256, sliding_window=16, n_experts=16,
    experts_per_token=4, d_ff_expert=32, n_shared_experts=2)
# What ONE v5e chip holds of it as one of 8 that share each layer of a
# 4-stage pipeline (benchmark/configs/command-a-plus-218b-ep8.json): two
# whole periods, 16 of the 128 experts, an eighth of the vocabulary rows.
# Every width, the router's 128 outputs, its 8 experts per token and the
# four shared experts stay.
PRESETS["command-a-plus-218b-ep8"] = replace(
    PRESETS["command-a-plus"], n_layers=8, vocab_size=32768,
    n_experts_held=16)



# GigaChat3.5-432B-A28B (HF: ai-sage/GigaChat3.5-432B-A28B, ``gigachat3_5``)
# at its PUBLISHED sizes: 40 layers — three leading layers of a gated-delta-
# net mixer and a dense SwiGLU MLP of 18,432, then periods of one LATENT
# attention layer (64 heads, 1536 / 512 bottlenecks, 128 + 64 query/key and
# 128 value numbers, YaRN x8 over 32,768, interleaved pairs, its output gated
# by sigmoid(W_g x)) and three gated-delta-net layers (32 key heads serving
# 64 value heads of 128, conv 4, one decay a head), each with 256 sigmoid-
# routed experts of width 2048 (top-8 of score + bias, weights x 2.5) beside
# one shared expert. Every sub-block is normed before and after by an RMS
# norm of gain 2 sigmoid(w); every gated MLP is clamped at 10. One latent
# cache group of ``n_periods`` layers beside ``leading_dense + 3 n_periods``
# state blocks a slot. (The published latent layers are 3, 7, ..., 39:
# behind the three leading layers the pattern IS [latent, linear x 3] from
# layer 3 on, and the 40th layer is a tenth latent layer with no linear
# layers behind it — which the whole-period scan does not build: the
# published preset is a table of sizes, a deployment states whole periods.)
# The two next-token-prediction modules are not served.
PRESETS["gigachat35-432b"] = ModelConfig(
    family="gigachat3_5", vocab_size=128256, d_model=7168, n_layers=40,
    n_heads=64, n_kv_heads=64, head_dim_override=128, d_ff=18432,
    rope_theta=100000.0, rms_eps=1e-6, max_seq_len=262144,
    rope_scaling=RopeScaling(rope_type="yarn", factor=8.0,
                             original_max_seq=32768, beta_fast=32.0,
                             beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    n_experts=256, experts_per_token=8, d_ff_expert=2048, n_shared_experts=1,
    layer_period=4, lin_heads=64, lin_head_dim=128, lin_conv_taps=4,
    lin_kind="gated_delta", lin_key_heads=32, attn_gate=True,
    moe_router="sigmoid", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    rope_interleave=True, norm_kind="rms_2sigmoid", post_norm=True,
    leading_dense=3, routed_scale=2.5, router_bias=True, swiglu_limit=10.0)
# The same pattern at CPU-test size: two leading layers, two periods, 16
# experts top-4, two key heads serving four value heads; YaRN x8 over 32.
PRESETS["tiny-gigachat35-test"] = replace(
    PRESETS["gigachat35-432b"], vocab_size=512, d_model=64, n_layers=10,
    n_heads=4, n_kv_heads=4, head_dim_override=16, d_ff=96, max_seq_len=256,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(rope_type="yarn", factor=8.0,
                             original_max_seq=32, beta_fast=4.0,
                             beta_slow=1.0, mscale=1.0, mscale_all_dim=1.0),
    n_experts=16, experts_per_token=4, d_ff_expert=32, lin_heads=4,
    lin_head_dim=16, lin_key_heads=2, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16, leading_dense=2,
    swiglu_limit=1.0)
# What ONE v5e chip holds of it as one of 8 that share each layer of the
# FIRST of 7 pipeline stages (benchmark/configs/gigachat35-432b-ep8.json):
# the three leading layers and one whole period, 32 of the 256 experts, an
# eighth of the vocabulary rows. Every width, the router's 256 outputs and
# its 8 experts per token stay.
PRESETS["gigachat35-432b-ep8"] = replace(
    PRESETS["gigachat35-432b"], n_layers=7, vocab_size=16032,
    n_experts_held=32)

# Keye-VL-2.0-30B-A3B (HF: Kwai-Keye/Keye-VL-2.0-30B-A3B, ``KeyeVL2``), the
# LANGUAGE model at its PUBLISHED sizes: 48 pre-norm layers of grouped-query
# attention (32 query / 4 KV heads of 128, a per-head RMS norm on q and k,
# half-split rotary at theta 1e7) under a learned indexer — 16 index heads of
# 64 and one index key a token, the 2,048 best-scoring cached positions a
# query are all it attends — each followed by 128 softmax-routed SwiGLU
# experts of width 768, top-8, no shared expert. One global cache group whose
# pool has a third side, the index key. The vision tower is not served.
PRESETS["keye-vl2-30b-a3b"] = ModelConfig(
    family="keye_vl2", vocab_size=151936, d_model=2048, n_layers=48,
    n_heads=32, n_kv_heads=4, head_dim_override=128, d_ff=6144,
    rope_theta=10000000.0, rms_eps=1e-6, max_seq_len=262144, n_experts=128,
    experts_per_token=8, d_ff_expert=768, layer_period=1,
    moe_router="softmax", idx_heads=16, idx_head_dim=64, idx_topk=2048,
    qk_norm=True, qk_norm_draw=1.73)
# The same block at CPU-test size: 16 experts top-4, 4 index heads of 8 that
# keep 24 keys a query, so a context of a few pages is past the selection
# (and a mean over 24 keys is uneven enough at a draw of 1, which W8A8 at a
# width of 64 needs: peaked attention there reads 0.3-0.9 off by rounding).
PRESETS["tiny-keye-vl2-test"] = replace(
    PRESETS["keye-vl2-30b-a3b"], vocab_size=512, d_model=64, n_layers=4,
    n_heads=4, n_kv_heads=2, head_dim_override=16, d_ff=128,
    rope_theta=10000.0, max_seq_len=256, n_experts=16, experts_per_token=4,
    d_ff_expert=32, idx_heads=4, idx_head_dim=8, idx_topk=24,
    qk_norm_draw=1.0)
# What ONE v5e chip holds of it as one of 4 that share each layer of a
# 4-stage pipeline (benchmark/configs/keye-vl2-30b-ep4.json): 12 layers, 32
# of the 128 experts, a quarter of the vocabulary rows. Every width, the
# router's 128 outputs, its 8 experts per token and the indexer whole stay.
PRESETS["keye-vl2-30b-ep4"] = replace(
    PRESETS["keye-vl2-30b-a3b"], n_layers=12, vocab_size=37984,
    n_experts_held=32)

# Phi-4-mini-flash-reasoning (HF: microsoft/Phi-4-mini-flash-reasoning,
# ``phi4flash``; arXiv:2507.06607, the SambaY architecture) at its PUBLISHED
# sizes: 32 layers with LayerNorms (weight and bias) and dense SwiGLU MLPs of
# 10,240, no positional encoding, a tied head. Even layers 0-16 are Mamba-1
# selective scans (5,120 channels x 16 state numbers, conv 4, dt rank 160 —
# the family's defaults, which the config leaves unsaid); odd layers 1-15
# differential attention (40 query / 20 KV heads of 64, paired) inside a
# 512 window; layer 17 the same over the WHOLE context, its K/V the one
# full-context cache; layers 18-30 gate layer 16's memory, layers 19-31
# attend layer 17's K/V with queries of their own. Two cache groups (a ring
# of 8 layers, a global group of ONE) beside 9 state blocks a slot. The whole
# model fits one v5e chip in int8 (benchmark/configs/phi4-mini-flash-3.8b.json).
PRESETS["phi4-mini-flash-3.8b"] = ModelConfig(
    family="phi4flash", vocab_size=200064, d_model=2560, n_layers=32,
    n_heads=40, n_kv_heads=20, d_ff=10240, max_seq_len=262144,
    sliding_window=512, tie_embeddings=True, attn_bias=True, use_rope=False,
    layer_period=2, lin_kind="mamba", lin_conv_taps=4, ssm_state=16,
    ssm_expand=2, ssm_dt_rank=160, norm_kind="layernorm_bias",
    layer_norm_eps=1e-5, cross_decoder=True, qk_norm_draw=1.73)
# The same three runs at CPU-test size: 8 layers — scan, window 16, scan,
# window, scan -> memory, full, gate, cross.
PRESETS["tiny-phi4flash-test"] = replace(
    PRESETS["phi4-mini-flash-3.8b"], vocab_size=512, d_model=64, n_layers=8,
    n_heads=4, n_kv_heads=2, d_ff=128, max_seq_len=256, sliding_window=16,
    ssm_state=8, ssm_dt_rank=4, qk_norm_draw=1.0)


def get_preset(name: str) -> ModelConfig:
    if name not in PRESETS:
        raise KeyError(f"unknown model preset {name!r}; known: {sorted(PRESETS)}")
    return PRESETS[name]
