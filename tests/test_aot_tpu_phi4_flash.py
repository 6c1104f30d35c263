"""Compile for a described v5e the cross-decoder family's OWN step programs
(``phi4flash``, models/sambay.py) at ``phi4-mini-flash-3.8b``'s served
geometry — all 32 layers at the published widths, int8, 32 slots of 32,768
positions, a ring pool of 8 layers and a global pool of ONE beside nine
float32 state blocks: ``decode_scan`` (sixteen calls of the paged decode
kernel a step, eight of them on the one shared pool, two in-place writes)
and ``prefill_step`` on two rows (the windowed prefill kernel eight times,
the shared pool's write, and the decode kernel for the one row a prompt that
goes up). A file of its own so that a worker can take it beside
tests/test_aot_tpu_programs.py, whose fixtures it borrows. Nothing runs: a
pass here is not a chip run."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from llmapigateway_tpu.ops import paged_attention as pa
from test_aot_tpu_compile import (PAGE, chips,          # noqa: F401
                                  full_effort_uncached)

SLOTS, PER_SLOT, RING = 32, 128, 7
GROUP_PAGES = (SLOTS * RING + 1, SLOTS * PER_SLOT + 1)


def _lower(chips, monkeypatch, program):
    from llmapigateway_tpu.models import PRESETS
    from step_programs import lower_step_program
    monkeypatch.setattr(pa, "_interpret_default", lambda: False)
    return lower_step_program(
        PRESETS["phi4-mini-flash-3.8b"], chips[0], program, quant="int8",
        kv_quant="", dtype=jnp.bfloat16, page=PAGE, slots=SLOTS,
        per_slot=PER_SLOT, depth=8, group_pages=GROUP_PAGES)


def _held(compiled) -> int:
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print("arguments", memory.argument_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes, "held", held)
    return held


@pytest.mark.parametrize("program", ["decode", "prefill-2"])
def test_the_cross_decoders_step_programs_fit_the_chip(chips, monkeypatch,
                                                       program):
    """Both programs at the served fold (40 query over 10 K/V heads of 128,
    G = 4; window 512, the smallest ring yet): the compiler takes the paged
    kernels as they are, every new scope is in the program, the state block
    lies state-number major, and arguments plus temporaries fit 15.75 GB
    with the room a decode burst and a chunk need beside each other."""
    lowered, cache = _lower(chips, monkeypatch, program)
    assert [a.shape for a in cache.k] == [
        (8, GROUP_PAGES[0], 10, PAGE, 128), (1, GROUP_PAGES[1], 10, PAGE, 128)]
    assert [a.shape for a in cache.state] == [(9, SLOTS, 16, 5120)]
    assert [a.shape for a in cache.conv] == [(9, SLOTS, 3, 5120)]
    compiled = lowered.compile()
    text = compiled.as_text()
    for scope in ("ssm.proj", "ssm.scan", "attn.window", "attn.cross", "gmu",
                  "mlp.dense", "kv.paged_insert"):
        assert scope in text, scope
    lines = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert any("attention.paged_decode" in ln and "attn.cross" in ln
               for ln in lines)
    if program == "decode":
        assert any("attention.paged_decode" in ln and "attn.window" in ln
                   for ln in lines)
    else:
        assert any("attention.paged_prefill" in ln and "attn.window" in ln
                   for ln in lines)
        # The chunk's 512 rows never reach the head: no [2, 512, V] logits.
        assert "[2,512,200064]" not in text
    assert 12.0e9 < _held(compiled) < 15.0e9
