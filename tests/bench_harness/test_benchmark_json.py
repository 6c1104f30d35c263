"""``BENCHMARK.json`` against the contract's rules that can be checked
without a chip, and repair A.5's rule: a per-layer metric is reported
only beside the end-to-end metric it moves."""
import json
import re
from pathlib import Path

import pytest

from benchmark.gateway import is_width, resolve_preset
from llmapigateway_tpu.models.config import PRESETS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/bench_harness"]
    assert BENCH["command"] == ["python3", "-m", "benchmark.run"]
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(BENCH["workloads"]) <= 24
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_names_units_and_entries():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in BENCH[group]:
            assert set(e) == keys, e
            assert NAME.match(e["name"]) and len(e["why"]) <= 200
            assert (group, e["name"]) not in seen
            seen.add((group, e["name"]))
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(cells_of(m)) <= set(CELLS)


def test_every_configuration_is_used_and_has_its_file():
    used = {w["config"] for w in BENCH["workloads"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    files = set()
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        assert c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["source"] == c["source"] and c["source"].startswith(
            "https://")
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        # No width is cut (by the rule, not by a substring: the vocabulary
        # is a ``_size`` that counts rows), and what is cut keeps the
        # guide's floors and states its published count and deployment.
        assert not any(is_width(k) for k in c["reduced"])
        assert len(c["reduced"]) <= 16
        assert resolve_preset(c["name"], cfg, dict(PRESETS)) == (
            c["name"] if c["reduced"] else cfg["preset"])
    for w in BENCH["workloads"]:
        assert (REPO / "benchmark/traffic" / f"{w['traffic']}.json").exists()


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_reports_setup_another_metric_and_a_layer_metric(cell):
    e2e = [m["name"] for m in BENCH["end_to_end"] if cell in cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(cell in cells_of(m) for m in BENCH["per_layer"])


def test_a_layer_metric_is_reported_only_beside_the_metric_it_moves():
    e2e = {m["name"]: set(cells_of(m)) for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e, m
        assert set(cells_of(m)) <= e2e[m["moves"]], m
        path = REPO / "benchmark/layer_metrics" / f"{m['name']}.json"
        assert json.loads(path.read_text())["unit"] == m["unit"]
    # One layer, one spelling.
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({x.lower() for x in layers}) == len(layers)


@pytest.mark.parametrize("key, width", [
    ("hidden_size", True), ("intermediate_size", True),
    ("moe_intermediate_size", True), ("head_dim", True),
    ("kda_head_dim", True), ("kv_lora_rank", True), ("v_head_dim", True),
    ("ssm_state_size", True), ("short_conv_kernel_size", True),
    ("sliding_window", True), ("mamba_expand", True), ("expand", True),
    ("num_experts_per_tok", True), ("routed_scaling_factor", True),
    ("vocab_size", False), ("num_hidden_layers", False),
    ("n_routed_experts", False), ("num_local_experts", False),
])
def test_which_keys_are_widths_is_a_rule(key, width):
    """The contract's list: a hidden, intermediate, latent, state or
    projection size, a key that ends in ``_dim`` or ``_rank``, a head
    size, an expansion factor, the experts per token — and not the
    vocabulary, which the parent's test refused for holding ``size``."""
    assert is_width(key) is width
