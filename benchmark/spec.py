"""Finding a cell's files by name. ``BENCHMARK.json`` names cells,
configurations and metrics; everything else about them is a file of its
own under the benchmark's directory, so a later PR adds a cell by adding
files and one entry, and edits nothing that is there:

    benchmark/configs/<config>.json         sizes, source, engine block
    benchmark/traffic/<traffic>.json        parameters of the mix
    benchmark/layer_metrics/<metric>.json   unit, reducer and its arguments
    benchmark/reference/<module>.py         a plain reference forward
    benchmark/reducer_files/<any>.py        reducers, kernel costs

The last two are code, and new files all the same. A configuration's
file names its reference (``"reference"``, absent: ``forward``; the
contract is in ``reference/__init__.py``); every module of
``reducer_files/`` is imported when the cell is loaded and registers its
reducers with ``reducers.reducer``. What else a configuration's file may
state about its architecture (the correctness sample, the cuts, the layer
kinds, further scopes) is read where it is used: ``correctness.sampling``,
``gateway.resolve_preset``, ``paged_attention_layers`` and ``scopes``
below. A file that says nothing gets what every file got before PR 28.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Any

from .traffic import Traffic

REPO_ROOT = Path(__file__).resolve().parents[1]
PACKAGE_DIR = Path(__file__).resolve().parent
_LOADED: dict[Path, ModuleType] = {}   # files of other data directories


@dataclasses.dataclass(frozen=True)
class LayerMetric:
    name: str
    unit: str
    reducer: str
    args: dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict[str, Any]
    data: Path                             # the directory of the cell's files
    traffic: Traffic
    end_to_end: list[dict[str, Any]]       # BENCHMARK.json entries
    per_layer: list[LayerMetric]


def _for_cell(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_module(data: Path, kind: str, name: str) -> ModuleType:
    """``<data>/<kind>/<name>.py``, else the benchmark's own file of that
    name. The benchmark's own is imported as the package's module; a file
    of another data directory under a name of its own, once."""
    own = PACKAGE_DIR / kind / f"{name}.py"
    path = (Path(data) / kind / f"{name}.py").resolve()
    if not path.exists():
        path = own
    if not path.exists():
        raise FileNotFoundError(f"no {kind}/{name}.py under {data} or "
                                f"{PACKAGE_DIR}")
    if path == own:
        return importlib.import_module(f"{__package__}.{kind}.{name}")
    if path not in _LOADED:
        modname = f"_benchmark_data_{len(_LOADED)}.{kind}.{name}"
        found = importlib.util.spec_from_file_location(modname, path)
        module = importlib.util.module_from_spec(found)
        sys.modules[modname] = module       # dataclasses look themselves up
        try:
            found.loader.exec_module(module)
        except BaseException:
            del sys.modules[modname]
            raise
        _LOADED[path] = module
    return _LOADED[path]


def load_reducer_files(data: Path) -> None:
    """Import every module of ``<data>/reducer_files/`` (each registers
    its reducers as it is imported; a directory that is not there holds
    none)."""
    for path in sorted((Path(data) / "reducer_files").glob("*.py")):
        load_module(data, "reducer_files", path.stem)


def paged_attention_layers(config: dict[str, Any], n_layers: int) -> int:
    """How many of the ``n_layers`` served layers call the paged attention
    kernels: ``layer_kinds.paged_attention`` of the configuration's file,
    a count, or the positions inside one ``period`` of the layer pattern
    (after ``leading_dense`` layers, which count as paged). Absent: all."""
    kinds = config.get("layer_kinds", {})
    paged = kinds.get("paged_attention")
    if paged is None:
        return n_layers
    if isinstance(paged, int):
        count = paged
    else:
        lead, period = kinds.get("leading_dense", 0), kinds["period"]
        count = min(lead, n_layers) + sum(
            1 for i in range(max(0, n_layers - lead)) if i % period in paged)
    if not 1 <= count <= n_layers:
        raise ValueError(f"layer_kinds.paged_attention gives {count} paged "
                         f"layers of {n_layers}")
    return count


def scopes(config: dict[str, Any]) -> tuple[str, ...]:
    """The ``jax.named_scope`` names an op is filed under, innermost
    first: the configuration's own (``"scopes"``) before ``xplane.SCOPES``."""
    from .xplane import SCOPES
    own = tuple(config.get("scopes", ()))
    if len(set(own)) != len(own) or set(own) & set(SCOPES):
        raise ValueError(f"scopes {own} repeat one another or xplane.SCOPES")
    return own + SCOPES


def load_cell(name: str, root: Path = REPO_ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    from .reducers import REDUCERS
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    data = root / Path(configs[w["config"]]["file"]).parents[1]
    traffic = Traffic.load(data / "traffic" / f"{w['traffic']}.json")
    load_reducer_files(data)
    per_layer = []
    for m in bench["per_layer"]:
        if _for_cell(m, name):
            raw = json.loads(
                (data / "layer_metrics" / f"{m['name']}.json").read_text())
            if raw["unit"] != m["unit"]:
                raise ValueError(
                    f"{m['name']}: unit {raw['unit']!r} in its file, "
                    f"{m['unit']!r} in BENCHMARK.json")
            if raw["reducer"] not in REDUCERS:
                raise ValueError(
                    f"{m['name']}: no reducer {raw['reducer']!r}; known: "
                    f"{sorted(REDUCERS)}")
            per_layer.append(LayerMetric(
                name=m["name"], unit=raw["unit"], reducer=raw["reducer"],
                args=raw.get("args", {})))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, data=data, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, name)],
        per_layer=per_layer)
