"""Compile for a described v5e the ENGINE'S OWN step programs of the
families with recurrent state (PR 46, PR 47) at their cells' served
geometry: ``gigachat35-432b-ep8`` (a latent pool beside state blocks) and
``solar-open2-250b-ep8`` — they fit the chip, and a burst updates the state
where it lies. A file of its own so that a worker can take it beside
tests/test_aot_tpu_programs.py, whose fixtures and reader it borrows (the
prefill program alone compiles for two minutes). Nothing runs: a pass here
is not a chip run."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

from llmapigateway_tpu.ops import paged_attention as pa
from test_aot_tpu_compile import (PAGE, chips,          # noqa: F401
                                  full_effort_uncached)
from test_aot_tpu_programs import _loop_arrays

# What a linear family's step programs are compiled at: the preset, the
# pool's quantisation and a slot's pages of its cell.
STATE_CELLS = {"gigachat35-432b-ep8": ("", 80),
               "solar-open2-250b-ep8": ("int8", 32)}
_compiled_steps: dict = {}


def _state_familys_step_program(chips, monkeypatch, preset: str,
                                program: str):
    """The ENGINE'S OWN ``decode_scan`` (a burst of 8) or ``prefill_step``
    (``prefill-<rows>`` of 512 tokens) of a family with recurrent state,
    at its cell's served geometry (``STATE_CELLS``, 32 slots, int8
    weights), compiled for the described chip; a program is compiled once
    a process. Returns (the compiled program, the cache's shapes)."""
    from llmapigateway_tpu.models import PRESETS, hybrid
    from step_programs import lower_step_program

    if (preset, program) not in _compiled_steps:
        # The kernels are chosen for the CPU backend the process runs on;
        # the program is compiled for the chip. What an earlier test
        # traced interpreted must not be found again.
        monkeypatch.setattr(pa, "_interpret_default", lambda: False)
        jitted = (hybrid._grouped, hybrid._state_update)
        for fn in jitted:
            fn.clear_cache()
        kv_quant, per_slot = STATE_CELLS[preset]
        lowered, cache = lower_step_program(
            PRESETS[preset], chips[0], program, quant="int8",
            kv_quant=kv_quant, dtype=jnp.bfloat16, page=PAGE, slots=32,
            per_slot=per_slot, depth=8)
        _compiled_steps[preset, program] = lowered.compile(), cache
        for fn in jitted:
            fn.clear_cache()
    return _compiled_steps[preset, program]


@pytest.mark.parametrize("program", ["decode", "prefill-4"])
def test_the_latent_and_state_familys_step_programs_fit_the_chip(
        chips, monkeypatch, program):
    """The ENGINE'S OWN step programs at ``gigachat35-432b-ep8``'s served
    geometry — 7 layers at the published widths, int8, 32 slots of 20,480
    positions, a latent pool of one layer beside six float32 state blocks
    a slot — compiled for the described chip: the chip's compiler takes
    every kernel (the latent write and attention at 576 x 64 heads, the
    grouped expert product at 7168 x 2048) and arguments plus temporaries
    fit 16 GB of HBM."""
    compiled, cache = _state_familys_step_program(
        chips, monkeypatch, "gigachat35-432b-ep8", program)
    assert [a.shape for a in cache.k] == [(1, 32 * 80 + 1, 576, PAGE)]
    assert [a.shape[0] for a in cache.state] == [1, 1, 1, 3]
    text = compiled.as_text()
    assert "kv.latent_insert" in text and "attention.latent" in text
    if program != "decode":
        assert "grouped_experts" in text
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.temp_size_in_bytes
            + memory.output_size_in_bytes - memory.alias_size_in_bytes)
    print(program, "arguments", memory.argument_size_in_bytes, "temporaries",
          memory.temp_size_in_bytes, "held", held)
    assert held < 15.75e9


@pytest.mark.parametrize("preset", list(STATE_CELLS))
def test_decode_scan_updates_the_recurrent_state_where_it_lies(
        chips, monkeypatch, preset):
    """The burst program of both linear cells, compiled for the described
    chip: inside its loops the ONLY instructions whose result holds an
    array of the state's shape (float32 [layers, 32, 64, 128, 128]: 67 MB
    a layer) are the delta rule's kernel calls, one a linear layer of a
    scan's body, each with its state operand as its result — no copy, no
    select-and-write, no fresh stack for the burst's carry to copy. The
    temporaries are printed for PERF.md (the parent of PR 47 held a
    second stack there)."""
    import re
    compiled, cache = _state_familys_step_program(
        chips, monkeypatch, preset, "decode")
    stacks = [a.shape[0] for a in cache.state]
    assert {a.shape[1:] for a in cache.state} == {(32, 64, 128, 128)}
    text = compiled.as_text()
    a_layer = 32 * 64 * 128 * 128 * 4
    of_state = [(op, ln) for _, op, ln in _loop_arrays(text, a_layer)
                if re.search(r"f32\[(\d+,)?32,64,128,128\]",
                             ln.split(" " + op + "(")[0])]
    assert of_state, "the state never shows in the loops"
    for op, ln in of_state:
        assert op == "custom-call" and "kda.decode_update" in ln \
            and "output_to_operand_aliasing" in ln, ln[:400]
    # Three positions of a period's body, and the leading layers' body.
    assert len(of_state) == 3 + (len(stacks) > 3), [ln[:200] for _, ln
                                                    in of_state]
    memory = compiled.memory_analysis()
    print(preset, "decode temporaries", memory.temp_size_in_bytes,
          "arguments", memory.argument_size_in_bytes)
    assert memory.temp_size_in_bytes < 2 * a_layer * max(stacks)
