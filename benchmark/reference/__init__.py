"""The plain reference: each served architecture's forward pass in
straightforward float32 ``jax.numpy`` — no kernels, no cache, no batching.
Independent of the program's model code.

One module per architecture. A configuration's file names its module
(``"reference": "<module>"``; absent: ``forward``), and ``load`` finds
``reference/<module>.py`` in the cell's data directory, else here, so a new
architecture's reference is a new file. What a module gives
(``CONTRACT``):

``sizes(model_cfg, config)``
    From the program's ``ModelConfig`` and the configuration's file (as
    loaded) to the reference's OWN sizes: a hashable value, sizes only.
    Whatever the program's config has no field for is read from the file.

``logits(params, sizes, seq, last)``
    Float32 logits ``[last, V]`` of the LAST ``last`` positions of the
    token ids ``seq`` ``[T]`` under the engine's weight tree ``params``.
    Computed under ``jax.default_matmul_precision("highest")`` (on a TPU a
    float32 matmul otherwise runs in reduced precision), and in blocks —
    a layer, an expert at a time — so that it fits beside the engine.
    Only the LAYOUT of the weight tree is the program's.

``kernel_checks(engine, config, interpret)``, optional
    The architecture's own compiled kernels at the cell's widths against
    plain ``jax.numpy`` on the same inputs, as ``correctness.kernel_parity``
    does for the paged attention kernels: a list of cases, each a dict with
    ``kernel`` (a name), ``max_abs_err`` and ``ok``. They are printed on
    the ``kernel_parity`` line and decide ``correct`` with its cases.
"""
from __future__ import annotations

from pathlib import Path
from types import ModuleType
from typing import Any

CONTRACT = ("sizes", "logits")
DEFAULT = "forward"


def load(config: dict[str, Any], data: Path) -> ModuleType:
    """The reference module the configuration's file names."""
    from ..spec import load_module
    name = config.get("reference", DEFAULT)
    module = load_module(data, "reference", name)
    missing = [f for f in CONTRACT if not callable(getattr(module, f, None))]
    if missing:
        raise TypeError(f"reference/{name}.py lacks {missing} of the "
                        f"contract in benchmark/reference/__init__.py")
    return module
