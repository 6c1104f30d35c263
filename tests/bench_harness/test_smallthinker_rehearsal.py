"""The cell ``smallthinker-21b-mixed``: its files as ``spec.load_cell``
finds them, its published sizes against the catalog's row, its five
per-layer metrics as data over reducers that exist, its one cut through
``resolve_preset`` — and the whole harness rehearsed on a CPU at the
program's ``tiny-smallthinker-test``, the reference's ``served_past_window``
scaled to the tiny window."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.gateway import resolve_preset
from benchmark.reducers import REDUCERS

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

NAME, CONFIG = "smallthinker-21b-mixed", "smallthinker-21b-pp3"
SHIPPED = json.loads(
    (REPO / f"benchmark/configs/{CONFIG}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = {
    "step.prefill_attn_global_share": ("scope_share", "attn.global"),
    "step.prefill_attn_window_share": ("scope_share", "attn.window"),
    "step.prefill_experts_share": ("scope_share", "moe.experts"),
    "step.decode_experts_share": ("scope_share", "moe.experts"),
    "cache.ring_pages_recycled": ("counter_delta", "kv_ring_recycled_total")}
JOINED = {"sched.batch_occupancy", "step.prefill_chunk_ms",
          "device.idle_share", "device.peak_hbm_bytes"}

# The program's ``tiny-smallthinker-test`` (two whole periods of global
# NoPE + 3 x windowed rotary, window 16, 8 experts top-3) cut as the
# shipped file cuts the published model: in depth alone, to whole periods
# (one here: every paged layer runs its kernels interpreted on a CPU).
TINY = {
    "source": "none: CPU rehearsal of smallthinker-21b-pp3",
    "preset": "tiny-smallthinker-test", "reference": SHIPPED["reference"],
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 512,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "sliding_window_size": 16,
    "sliding_window_layout": [0, 1, 1, 1] * 2, "rope_layout": [0, 1, 1, 1] * 2,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "preset_fields": SHIPPED["preset_fields"],
    "reduced": {"num_hidden_layers": {"published": 8}},
    "chips_sharing_a_layer": 1,
    "deployment": "the first of two pipeline stages: one whole period of two",
    "layer_kinds": SHIPPED["layer_kinds"], "scopes": SHIPPED["scopes"],
    "engine": {**TINY_ENGINE, "prefix_cache": False}}
CELL = "tiny-smallthinker-mixed"


def test_the_cells_files_are_found_and_say_what_the_issue_asked():
    from benchmark import reference
    from benchmark.reference import smallthinker
    cell = spec.load_cell(NAME)
    assert (cell.chips, cell.config_name) == (1, CONFIG)
    assert reference.load(cell.config, cell.data) is smallthinker
    assert callable(smallthinker.kernel_checks)
    assert spec.paged_attention_layers(cell.config, 20) == 20
    assert spec.scopes(cell.config)[:3] == ("moe.experts", "attn.global",
                                            "attn.window")
    t = cell.traffic
    assert (t.loop, t.clients, t.stagger_s, t.trace_seed, t.temperature) == (
        "closed", 32, 0.05, 3501, 0.0)
    assert t.clients == 2 * cell.config["engine"]["max_batch_size"]
    from benchmark.traffic import support
    lengths = sorted(support(t.prompt_tokens))
    chunk = cell.config["engine"]["prefill_chunk"]
    assert lengths == [512, 1024, 1536, 2048, 6144, 10240, 14336]
    assert all(n % chunk == 0 for n in lengths)         # one bucket to warm
    window = cell.config["sliding_window_size"]
    assert sum(n > window for n in lengths) == 3
    assert max(lengths) + 160 < cell.config["engine"]["max_seq_len"]
    # `out_tok_s` alone beside `setup_s` (the issue says why), and no
    # metric whose reducer reads ONE window for every paged layer.
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    names = {lm.name for lm in cell.per_layer}
    assert set(NEW_METRICS) | JOINED <= names
    assert not {n for n in names if n.startswith("sched.") and n.endswith(
        "_ms")}
    assert not {"step.decode_ms", "step.decode_mlp_share",
                "kernel.paged_decode_roofline"} & names
    engine = cell.config["engine"]
    assert (engine["max_batch_size"], engine["max_seq_len"],
            engine["kv_page_size"], engine["prefill_chunk"],
            engine["prefix_cache"], engine["mesh"]) == (
                16, 16384, 256, 512, False, {})
    assert engine["prefill_batch"] in (1, 2, 4)
    assert "correctness" not in cell.config     # default bounds, whole chunks


def test_the_files_published_sizes_are_the_catalog_rows():
    if not CATALOG.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert SHIPPED["source"] == row["source_url"]
    assert list(SHIPPED["reduced"]) == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in SHIPPED["reduced"]:
            assert SHIPPED["reduced"][key]["published"] == value
            assert SHIPPED[key] == 20       # five whole periods
        else:
            assert SHIPPED[key] == value, key
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert entry["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_the_new_per_layer_metrics_are_data_over_reducers_that_exist(metric):
    reducer, reads = NEW_METRICS[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["reducer"] == reducer
    assert REDUCERS[reducer].__module__ == "benchmark.reducers"
    assert raw["args"].get("scope", raw["args"].get("counter")) == reads
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [NAME] and entry["moves"] == "out_tok_s"
    assert {m["name"] for m in BENCH["per_layer"][-5:]} == set(NEW_METRICS)
    assert not list((REPO / "benchmark").glob("reducer_files/*.py"))


def test_the_depth_cut_keeps_whole_periods():
    """From the published 52-layer preset on a table of its own: 20
    layers register, 18 (four and a half periods) and 2 (under a period)
    are refused; on the program's table the shipped file names the cut
    the program registers itself (``test_spec_discovery.py`` holds every
    shipped file to ``resolve_preset(name, file) == file["preset"]``, so
    a cut file's preset is the program's entry of the configuration's
    name)."""
    from llmapigateway_tpu.models.config import PRESETS
    table = {"smallthinker-21b": PRESETS["smallthinker-21b"]}
    config = {**SHIPPED, "preset": "smallthinker-21b"}
    assert resolve_preset("cut", config, table) == "cut"
    assert table["cut"] == dataclasses.replace(
        PRESETS["smallthinker-21b"], n_layers=20) == PRESETS[CONFIG]
    assert table["cut"].cache_groups == ((0, (0,)), (4096, (1, 2, 3)))
    for depth in (18, 2):
        with pytest.raises(ValueError, match="not whole periods of 4"):
            resolve_preset("cut", {**config, "num_hidden_layers": depth},
                           dict(table))
    with pytest.raises(ValueError, match="moe_num_primary_experts=32"):
        resolve_preset("cut", {**config, "moe_num_primary_experts": 32},
                       dict(table))
    assert resolve_preset(CONFIG, SHIPPED, dict(PRESETS)) == CONFIG


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("smallthinker")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-smallthinker.json").write_text(
        json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/mixed-b16.json"
                        ).read_text())
    # The shipped cycle's shape at the tiny geometry (chunk 32, ring of 9
    # pages of 8): two of four prompts pass the ring's 72 tokens.
    (root / "benchmark/traffic/tiny-mixed.json").write_text(json.dumps({
        **shape, "clients": 4, "stagger_s": 0.01,
        "prompt_tokens": {"kind": "cycle", "values": [32, 128, 64, 96]},
        "max_tokens": {"kind": "uniform", "min": 4, "max": 8, "snap": 4}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"] if w["name"] == NAME)
    bench["configs"] = [{"name": "tiny-smallthinker", "source": "none",
                         "file": "benchmark/configs/tiny-smallthinker.json",
                         "reduced": ["num_hidden_layers"],
                         "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL,
                           "config": "tiny-smallthinker",
                           "traffic": "tiny-mixed"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:    # the lists the shipped cell was appended to
            m["workloads"] = [CELL] if NAME in m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    # The cut reached the engine; every layer calls the paged kernels.
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"]) == ("tiny-smallthinker", 4, 4, 512)
    assert phases["programs"]["prefill_buckets"] == [32]
    assert phases["programs"]["prefill_groups"] == [1, 2]
    ref = phases["reference"]
    assert ref["ok"] and ref["positions"] == 3 * 64
    assert (ref["tolerance"], ref["tolerance_p50"]) == (0.25, 0.05)
    cases = {c["kernel"]: c for c in phases["kernel_parity"]["cases"]}
    assert list(cases) == [
        "paged_decode", "paged_prefill", "paged_decode_no_window",
        "paged_prefill_no_window", "served_past_window"]
    assert all(c["ok"] for c in cases.values())
    # The harness's own pair at the preset's window, the reference's
    # without one.
    assert cases["paged_decode"]["window"] == 16
    assert cases["paged_prefill_no_window"]["window"] == 0
    # The ring of 9 pages of 8 took a prompt of 96 tokens (three chunks)
    # and 8 decode steps: pages were re-targeted, the slot left both
    # groups, and every served token stood at the reference's maximum.
    past = cases["served_past_window"]
    assert (past["tokens"], past["positions"]) == (96, 9)
    assert past["ring_pages_recycled"] >= 3
    assert past["max_abs_err"] <= 0.25 and past["gap_p50"] <= 0.05
    win = phases["window"]
    assert win["compiles_in_window"] == 0
    assert win["jax_events"] == {"count": 0, "seconds": 0.0, "longest": []}
    # No device plane on a CPU: the share metrics find nothing and are left
    # out; the ring's counter is read. (What a 2 s window on a loaded CPU
    # holds is not asserted: under six workers it may hold no decode step
    # and no rotation; ``served_past_window`` above is what turns the ring.)
    assert not any("_share" in k for k in last["metrics"])
    assert last["metrics"]["cpu_rehearsal.cache.ring_pages_recycled"][
        "value"] >= 0
