"""The period families' step programs as LOWERED TEXT, pinned by digest.

PR 47 moved the recurrent state of a DECODE step into the scans' carry and
its update into a kernel (``ops/delta_update.py``). That touches
``hybrid.forward``, which every period family runs, and nothing else was
meant to move: the three families without state (their carry gains an empty
tuple) and the two linear families' PREFILL programs (they still scan the
gathered rows in and out, through ``kda_chunked``) must lower to the text
they lowered to at the parent. ``PINNED`` holds the sha256 of the ENGINE'S
OWN ``decode_scan`` and ``prefill_step`` (``_compile_paged``, as
tests/test_aot_tpu_programs.py builds them) at the toy presets, lowered on
the CPU with the kernels interpreted, as commit 5ba9ec1 (the parent of
PR 47) gives them; ``MOVED`` the parent's digests of the two programs
that PR set out to change, which must NOT come back.

PR 48 gave the paged GQA prefill kernel's grid a row-block axis
(``ops/paged_attention.py``): the ``prefill`` programs of the three presets
that hold that call (``tiny-smallthinker-test``, ``tiny-cohere2-test``,
``tiny-hybrid-test``) are recorded anew and their digests at commit aefa4b6
(the parent of PR 48) went to ``MOVED``; every ``decode`` digest and the
``prefill`` digests of the two latent families, which run no paged GQA
prefill, stand unedited: nothing else moved.

PR 57 changed the absorbed latent kernel's body (``ops/latent_attention.py``:
a step of whole pages is ONE softmax update): the three pinned programs of
the two latent families are recorded anew, their digests at commit 59c3c7f
(the parent of PR 57) went to ``MOVED``, and the five programs that never
import the module stand unedited.

A PR that changes a pinned program on purpose records it anew:
``JAX_PLATFORMS=cpu python tests/test_hybrid_programs_pinned.py`` prints
the table of the tree it runs in."""
from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import pytest

PINNED = {
    ("tiny-smallthinker-test", "decode"): "1ef682edbae673474e9f2429",
    ("tiny-smallthinker-test", "prefill"): "c346e1e2aa1d52741cbb0807",
    ("tiny-mistral4-test", "decode"): "104c752fd8fe7c6e161aa1f1",
    ("tiny-mistral4-test", "prefill"): "4a9667b28c6b0c4afea09014",
    ("tiny-cohere2-test", "decode"): "fa375c9237a876ec6e0570bd",
    ("tiny-cohere2-test", "prefill"): "79bc67a8af51d7c71214e630",
    ("tiny-hybrid-test", "prefill"): "564c6e03e7fc0a3cf1002e9c",
    ("tiny-gigachat35-test", "prefill"): "1c1bacf99ffa708ea45459c5",
}
MOVED = {
    ("tiny-hybrid-test", "decode"): "42da75fd2c69ad5db6218ae7",
    ("tiny-gigachat35-test", "decode"): "1268797e7a0047d117fd9451",
    # PR 48: the parent's prefill programs around the paged GQA kernel.
    ("tiny-smallthinker-test", "prefill"): "74d47e27d3de71f868c4c95c",
    ("tiny-cohere2-test", "prefill"): "22d44b265802966e5ba486a8",
    ("tiny-hybrid-test", "prefill"): "a72e4d6d2f50468d59d25459",
    # PR 57: the parent's programs around the absorbed latent kernel.
    ("tiny-mistral4-test", "decode"): "5c81fbba224cd6790ca3cb9a",
    ("tiny-mistral4-test", "prefill"): "9e419a710b190f05078de7a8",
    ("tiny-gigachat35-test", "prefill"): "6bcce1638edad7771fcfb324",
}


def lowered_digest(preset: str, program: str) -> str:
    """sha256 of the StableHLO text of the engine's ``decode_scan`` (a
    burst of 2, greedy) or ``prefill_step`` (2 rows of 16 tokens) at a toy
    preset, float32, no quantisation, 2 slots of 4 pages of 8."""
    from llmapigateway_tpu.models import PRESETS
    from step_programs import lower_step_program

    lowered, _ = lower_step_program(
        PRESETS[preset], jax.devices("cpu")[0],
        "decode" if program == "decode" else "prefill-2", quant="",
        kv_quant="", dtype=jnp.float32, page=8, slots=2, per_slot=4,
        depth=2, chunk=16)
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:24]


@pytest.mark.parametrize("preset, program", list(PINNED),
                         ids=["-".join(k) for k in PINNED])
def test_a_program_no_pr_meant_to_move_lowers_as_pinned(preset, program):
    assert lowered_digest(preset, program) == PINNED[preset, program]


@pytest.mark.parametrize("preset, program", list(MOVED),
                         ids=["-".join(k) for k in MOVED])
def test_a_rebuilt_program_is_not_its_parents(preset, program):
    """The digest sees a change: the two decode programs PR 47 rebuilt,
    the three prefill programs PR 48 did and the three latent programs PR
    57 did differ from their parents'."""
    assert lowered_digest(preset, program) != MOVED[preset, program]


if __name__ == "__main__":
    import sys
    from pathlib import Path
    sys.path[:0] = [str(Path(__file__).resolve().parents[n]) for n in (0, 1)]
    for name, table in (("PINNED", PINNED), ("MOVED", MOVED)):
        print(name, "= {")
        for key in table:
            print(f"    {key!r}: {lowered_digest(*key)!r},")
        print("}")
