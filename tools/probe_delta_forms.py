"""The delta rule's two forms at a cell's widths through a float32 and a
bfloat16 state block: the two readings ``LINEAR_FORM_TOL`` lies between.

    chiprun -- python3 tools/probe_delta_forms.py [configuration]

One JSON line a block dtype: ``kernel_checks``' cases of
``benchmark/reference/gigachat35.py`` (``delta_forms_parity``: the chunked
prefill form over 256 tokens and the one-token update chained over 8, each
against the reference's token-by-token recurrence, at ONE decay a head and
two value heads a key head). A float32 block must pass, a bfloat16 block
must fail both.
"""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax.numpy as jnp                                     # noqa: E402

from benchmark.reference import gigachat35                  # noqa: E402
from llmapigateway_tpu.models.config import PRESETS         # noqa: E402



def main() -> int:
    name = sys.argv[1] if len(sys.argv) > 1 else "gigachat35-432b-ep8"
    config = json.loads((Path(__file__).resolve().parents[1] / "benchmark"
                         / "configs" / f"{name}.json").read_text())
    sizes = gigachat35.sizes(PRESETS[config["preset"]], config)
    for dtype in (jnp.float32, jnp.bfloat16):
        print(json.dumps({
            "block": jnp.dtype(dtype).name,
            "cases": gigachat35.delta_forms_parity(sizes, dtype, False)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
