"""Finding a cell's files by name. ``BENCHMARK.json`` names cells,
configurations and metrics; everything else about them is a file of its
own under the benchmark's directory, so a later PR adds a cell by adding
files and one entry, and edits nothing that is there:

    benchmark/configs/<config>.json         sizes, source, engine block
    benchmark/traffic/<traffic>.json        parameters of the mix
    benchmark/layer_metrics/<metric>.json   unit, reducer and its arguments
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Any

from .traffic import Traffic

REPO_ROOT = Path(__file__).resolve().parents[1]


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
    traffic: Traffic
    end_to_end: list[dict[str, Any]]       # BENCHMARK.json entries
    per_layer: list[LayerMetric]


def _for_cell(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = REPO_ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    data = root / Path(configs[w["config"]]["file"]).parents[1]
    traffic = Traffic.load(data / "traffic" / f"{w['traffic']}.json")
    per_layer = []
    for m in bench["per_layer"]:
        if _for_cell(m, name):
            raw = json.loads(
                (data / "layer_metrics" / f"{m['name']}.json").read_text())
            if raw["unit"] != m["unit"]:
                raise ValueError(
                    f"{m['name']}: unit {raw['unit']!r} in its file, "
                    f"{m['unit']!r} in BENCHMARK.json")
            per_layer.append(LayerMetric(
                name=m["name"], unit=raw["unit"], reducer=raw["reducer"],
                args=raw.get("args", {})))
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _for_cell(m, name)],
        per_layer=per_layer)
