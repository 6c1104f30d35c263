"""The table of peaks, and what the paged attention kernels have to do.

A roofline share is the least time the chip could take for the work —
the larger of operations over peak FLOP/s and bytes over peak bytes/s —
over the kernel's device time from the trace. The operations and bytes
are those the ALGORITHM needs for the call, computed here from shapes:
tokens really in context (not whole pages), each K/V byte read once.
"""
from __future__ import annotations

import dataclasses

# Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393
# TOP/s int8, 819 GB/s HBM). A device that is not here is an error.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


@dataclasses.dataclass(frozen=True)
class AttnShape:
    """What the attention kernels see of a configuration, on ONE chip."""
    n_layers: int           # layers that call the paged kernels (of a hybrid
    #                         model not all: ``spec.paged_attention_layers``)
    n_heads: int            # query heads on this chip
    n_kv_heads: int         # KV heads on this chip
    head_dim: int
    window: int             # 0 = full causal
    kv_bytes: int           # bytes per K/V element (1 = int8, 2 = bf16)
    kv_scale_bytes: int     # bytes of scale per token per KV head (int8: 4)

    def kv_token_bytes(self) -> int:
        """K and V of one token, one layer, on this chip."""
        return 2 * self.n_kv_heads * (self.head_dim * self.kv_bytes
                                      + self.kv_scale_bytes)

    def visible(self, n_before: int) -> int:
        """Keys a query sees that has ``n_before`` tokens before it
        (itself included; the window counts the query's own position)."""
        n = n_before + 1
        return min(n, self.window) if self.window else n


def paged_decode_cost(ctx_lens: list[int], s: AttnShape) -> tuple[float, float]:
    """(operations, bytes) of ONE decode-kernel call — one layer, one step
    — over slots that hold ``ctx_lens`` tokens before the new one. QK and
    PV are 2 multiply-adds per key, head and head-dim element; every
    visible K/V byte is read once; q in and out in bf16."""
    keys = sum(s.visible(n) for n in ctx_lens)
    flops = 4.0 * s.n_heads * s.head_dim * keys
    io = 2 * len(ctx_lens) * s.n_heads * s.head_dim * 2
    return flops, float(keys * s.kv_token_bytes() + io)


def paged_prefill_cost(pos: int, t: int, s: AttnShape) -> tuple[float, float]:
    """(operations, bytes) of ONE prefill-kernel call for one row — one
    layer, a chunk of ``t`` tokens that starts at position ``pos``. Each
    query sees its own visible keys (causal, windowed); the keys any query
    of the chunk can see are read once."""
    keys = sum(s.visible(pos + i) for i in range(t))
    first_key = max(0, pos + 1 - s.window) if s.window else 0
    span = pos + t - first_key
    flops = 4.0 * s.n_heads * s.head_dim * keys
    io = 2 * t * s.n_heads * s.head_dim * 2
    return flops, float(span * s.kv_token_bytes() + io)


def least_seconds(flops: float, nbytes: float, peaks: dict
                  ) -> tuple[float, str]:
    """The roofline's floor for the work, and which side bounds it."""
    t_c, t_m = flops / peaks["bf16_flops"], nbytes / peaks["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
