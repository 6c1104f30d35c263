"""The kernels' operations and bytes against hand counts at Mistral-7B
widths (32 query heads, 8 KV heads, head 128, window 4096, int8 KV with a
float32 scale per token and head)."""
import pytest

from benchmark.roofline import (PEAKS, AttnShape, least_seconds,
                                paged_decode_cost, paged_prefill_cost,
                                peaks_for)

MISTRAL = AttnShape(n_layers=32, n_heads=32, n_kv_heads=8, head_dim=128,
                    window=4096, kv_bytes=1, kv_scale_bytes=4)


def test_bytes_of_one_token_of_kv():
    # K and V: 8 heads x (128 int8 + one float32 scale) = 8 x 132, twice.
    assert MISTRAL.kv_token_bytes() == 2 * 8 * 132 == 2112


def test_decode_call_by_hand():
    # One slot with 999 tokens before the new one sees 1000 keys.
    flops, nbytes = paged_decode_cost([999], MISTRAL)
    assert flops == 4 * 32 * 128 * 1000 == 16_384_000
    assert nbytes == 1000 * 2112 + 2 * 32 * 128 * 2
    # Past the window a query sees 4096 keys, no more.
    flops, nbytes = paged_decode_cost([6000, 99], MISTRAL)
    assert flops == 4 * 32 * 128 * (4096 + 100)
    assert nbytes == (4096 + 100) * 2112 + 2 * 2 * 32 * 128 * 2
    t, bound = least_seconds(flops, nbytes, PEAKS["TPU v5 lite"])
    assert bound == "memory" and t == pytest.approx(nbytes / 819e9)


def test_prefill_call_by_hand():
    # First chunk of 512: query i sees i + 1 keys -> 512 * 513 / 2.
    flops, nbytes = paged_prefill_cost(0, 512, MISTRAL)
    assert flops == 4 * 32 * 128 * (512 * 513 // 2)
    assert nbytes == 512 * 2112 + 2 * 512 * 32 * 128 * 2
    # A chunk wholly past the window: every query sees 4096 keys; the
    # chunk reads the keys from (6144 + 1 - 4096) to its own end.
    flops, nbytes = paged_prefill_cost(6144, 512, MISTRAL)
    assert flops == 4 * 32 * 128 * 512 * 4096
    assert nbytes == (512 + 4095) * 2112 + 2 * 512 * 32 * 128 * 2
    t, bound = least_seconds(flops, nbytes, PEAKS["TPU v5 lite"])
    assert bound == "compute" and t == pytest.approx(flops / 197e12)


def test_full_attention_has_no_window():
    full = AttnShape(32, 8, 2, 128, 0, 1, 4)         # Mixtral, one of 4 chips
    flops, _ = paged_decode_cost([5000], full)
    assert flops == 4 * 8 * 128 * 5001


def test_an_unknown_device_is_an_error_not_a_default():
    assert peaks_for("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
