"""The cell ``command-a-plus-rag``: its files as ``spec.load_cell`` finds
them, its published sizes against the catalog's row key for key, its six
per-layer metrics as data over reducers and scopes that exist, the lists it
joined, its three cuts through ``resolve_preset`` with the floors that
refuse — and the whole harness rehearsed on a CPU at the program's
``tiny-cohere2-test``, the reference's ``served_past_window`` scaled to the
tiny window. Places in ``BENCHMARK.json`` are pinned from the FRONT only:
neither the tail of ``per_layer`` nor which lists a later cell may join."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.gateway import resolve_preset
from benchmark.reducers import REDUCERS

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

NAME, CONFIG = "command-a-plus-rag", "command-a-plus-218b-ep8"
SHIPPED = json.loads(
    (REPO / f"benchmark/configs/{CONFIG}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = {
    "step.decode_attn_global_share": ("scope_share", "attn.global"),
    "step.decode_attn_window_share": ("scope_share", "attn.window"),
    "step.prefill_shared_share": ("scope_share", "moe.shared"),
    "step.decode_shared_share": ("scope_share", "moe.shared"),
    "attn.decode_keys_global": ("counter_delta",
                                "attn_decode_keys_global_total"),
    "attn.decode_keys_window": ("counter_delta",
                                "attn_decode_keys_window_total")}
JOINED = {"sched.batch_occupancy", "step.prefill_chunk_ms",
          "device.idle_share", "device.peak_hbm_bytes",
          "engine.compiles_in_window", "engine.trace_ms_in_window",
          "step.prefill_attn_global_share", "step.prefill_attn_window_share",
          "step.prefill_experts_share", "step.decode_experts_share",
          "cache.ring_pages_recycled", "kernel.prefill_pages_walked",
          "kernel.prefill_pages_table", "moe.tiles_run", "moe.tile_rows",
          "moe.assignments", "moe.assignments_local", "moe.experts_hit",
          "sched.decode_behind_prefill_pct", "sched.fetch_first_ms",
          "sched.hop_ms", "sched.dispatch_ms", "sched.other_ms"}
CUT = {"num_hidden_layers": 8, "num_experts": 16, "vocab_size": 32768}

# The program's ``tiny-cohere2-test`` (two whole periods of 3 x windowed
# rotary + global NoPE parallel blocks, window 16, 16 experts top-4 beside
# two shared ones) cut as the shipped file cuts the published model: one
# of 2 chips that share each layer (8 of 16 experts, 256 of 512 rows), one
# whole period of its two (every paged layer runs its kernels interpreted
# on a CPU).
TINY = {
    "source": "none: CPU rehearsal of command-a-plus-218b-ep8",
    "preset": "tiny-cohere2-test", "reference": SHIPPED["reference"],
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 8,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "intermediate_size": 32, "max_position_embeddings": 256,
    "rms_norm_eps": None, "layer_norm_eps": 1e-05, "rope_theta": 10000.0,
    "sliding_window": 16, "layer_switch": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_experts": 8, "num_experts_per_tok": 4, "num_shared_experts": 2,
    "first_expert_held": 0, "norm_topk_prob": True,
    "expert_selection_fn": "sigmoid", "use_parallel_block": True,
    "shared_expert_combination_strategy": "average",
    "position_embedding_type": "rope_gptj", "rotary_pct": 1,
    "tie_word_embeddings": True, "first_k_dense_replace": 0,
    "use_qk_norm": False, "logit_scale": 1,
    "preset_fields": SHIPPED["preset_fields"],
    "reduced": {"num_hidden_layers": {"published": 8},
                "num_experts": {"published": 16,
                                "held_in": "n_experts_held"},
                "vocab_size": {"published": 512}},
    "chips_sharing_a_layer": 2,
    "deployment": "one of two chips that share each of four layers, the "
                  "first of two pipeline stages",
    "layer_kinds": SHIPPED["layer_kinds"], "scopes": SHIPPED["scopes"],
    "engine": {**TINY_ENGINE, "prefix_cache": False}}
CELL = "tiny-cohere2-rag"


def test_the_cells_files_are_found_and_say_what_the_issue_asked():
    from benchmark import reference
    from benchmark.reference import command_a_plus
    cell = spec.load_cell(NAME)
    assert (cell.chips, cell.config_name) == (1, CONFIG)
    assert reference.load(cell.config, cell.data) is command_a_plus
    assert callable(command_a_plus.kernel_checks)
    assert spec.paged_attention_layers(cell.config, 8) == 8
    assert spec.scopes(cell.config)[:5] == (
        "block.norm", "moe.experts", "moe.shared", "attn.global",
        "attn.window")
    t = cell.traffic
    assert (t.loop, t.clients, t.stagger_s, t.trace_seed, t.temperature) == (
        "closed", 32, 0.05, 4401, 0.0)
    assert t.clients == 2 * cell.config["engine"]["max_batch_size"]
    from benchmark.traffic import support
    lengths = sorted(support(t.prompt_tokens))
    chunk = cell.config["engine"]["prefill_chunk"]
    assert lengths == [2048, 3072, 4096, 6144, 8192, 10240, 12288, 16384]
    assert sum(lengths) // len(lengths) == 7808
    assert all(n % chunk == 0 for n in lengths)         # one bucket to warm
    assert sum(n > cell.config["sliding_window"] for n in lengths) == 5
    assert max(lengths) + 256 < cell.config["engine"]["max_seq_len"]
    raw = json.loads((REPO / "benchmark/traffic/rag-b16.json").read_text())
    assert raw["prompt_tokens"]["values"] == [
        2048, 12288, 4096, 8192, 3072, 16384, 6144, 10240]
    assert raw["max_tokens"] == {"kind": "uniform", "min": 128, "max": 256,
                                 "snap": 8}
    assert raw["source"]["name"].startswith("none:")
    assert any("AS RECALLED" in a for a in raw["assumed"])
    # `out_tok_s` alone beside `setup_s` (the issue says why), and no
    # metric whose reducer reads ONE window for every paged layer.
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    names = {lm.name for lm in cell.per_layer}
    assert JOINED | set(NEW_METRICS) <= names
    assert not {"step.decode_ms", "step.decode_mlp_share",
                "kernel.paged_decode_roofline"} & names
    engine = cell.config["engine"]
    assert (engine["quant"], engine["kv_quant"], engine["max_batch_size"],
            engine["max_seq_len"], engine["kv_page_size"],
            engine["prefill_chunk"], engine["prefix_cache"],
            engine["mesh"]) == ("int8", "int8", 16, 17408, 256, 512, False,
                                {})
    assert engine["prefill_batch"] in (1, 2, 4)
    assert "correctness" not in cell.config     # default bounds, whole chunks
    # The deployment the cut stands for, stated.
    assert cell.config["chips_sharing_a_layer"] == 8
    assert cell.config["first_expert_held"] == 0
    assert "32 v5e chips" in cell.config["deployment"]
    assert "4 pipeline stages of 8 layers" in cell.config["deployment"]
    assert "8 times a chip's share" in cell.config["deployment"]
    assert len(cell.config["assumed"]) >= 12
    entry = next(w for w in BENCH["workloads"] if w["name"] == NAME)
    assert entry["traffic"] == "rag-b16" and "8x" in entry["why"]


def test_the_files_published_sizes_are_the_catalog_rows():
    if not CATALOG.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "command-a-plus-05-2026")
    assert SHIPPED["source"] == row["source_url"]
    assert sorted(SHIPPED["reduced"]) == sorted(CUT)
    for key, value in row["config"].items():
        if key in CUT:
            assert SHIPPED["reduced"][key]["published"] == value
            assert SHIPPED[key] == CUT[key]
        else:
            assert SHIPPED[key] == value, key
    assert len(SHIPPED["layer_types"]) == 32        # the first 8 apply
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_per_layer_metric_is_data_over_what_exists(metric):
    """The file and the entry, for this cell: the reducer is one of
    ``benchmark.reducers``, the scope one the configuration's file lists,
    the counter one the rehearsal below reads from ``stats()``."""
    reducer, reads = NEW_METRICS[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["reducer"] == reducer
    assert REDUCERS[reducer].__module__ == "benchmark.reducers"
    assert raw["args"].get("scope", raw["args"].get("counter")) == reads
    if reducer == "scope_share":
        assert reads in SHIPPED["scopes"]
        assert raw["args"]["programs"] in (["prefill_step"],
                                           ["decode_scan", "decode_step"])
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert NAME in entry["workloads"] and entry["moves"] == "out_tok_s"
    assert entry["layer"] == ("kernels" if reducer == "counter_delta"
                              else "model block")
    # Entries are only ever appended: these six stand together behind PR
    # 41's last; a later PR's stand behind them.
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("sched.fetch_first_ms") + 1
    assert names[first:first + 6] == list(NEW_METRICS)


def test_the_cell_joined_the_lists_of_the_metrics_it_reports():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in JOINED | {"out_tok_s"}:
            assert NAME in m["workloads"], m["name"]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells.index(NAME) == 6 and len(BENCH["configs"]) >= 5


def test_the_cuts_register_and_the_floors_refuse():
    """From the published 32-layer preset on a table of its own: 8 layers,
    16 of 128 experts and an eighth of the vocabulary register as the
    program's own entry of the configuration's name; a depth off whole
    periods, 4 experts, a sixteenth of the vocabulary and a cut width are
    refused."""
    from llmapigateway_tpu.models.config import PRESETS
    table = {"command-a-plus": PRESETS["command-a-plus"]}
    config = {**SHIPPED, "preset": "command-a-plus"}
    assert resolve_preset("cut", config, table) == "cut"
    assert table["cut"] == dataclasses.replace(
        PRESETS["command-a-plus"], n_layers=8, vocab_size=32768,
        n_experts_held=16) == PRESETS[CONFIG]
    assert (table["cut"].n_experts, table["cut"].experts_held) == (128, 16)
    assert table["cut"].cache_groups == ((4096, (0, 1, 2)), (0, (3,)))
    with pytest.raises(ValueError, match="depth 6 is not whole periods of 4"):
        resolve_preset("cut", {**config, "num_hidden_layers": 6},
                       dict(table))
    with pytest.raises(ValueError, match="4 of 128 experts is not one of"):
        resolve_preset("cut", {**config, "num_experts": 4,
                               "chips_sharing_a_layer": 32}, dict(table))
    with pytest.raises(ValueError, match="16384 of 262144 vocabulary rows"):
        resolve_preset("cut", {**config, "vocab_size": 16384,
                               "num_experts": 8,
                               "chips_sharing_a_layer": 16}, dict(table))
    with pytest.raises(ValueError, match="intermediate_size is a width"):
        resolve_preset("cut", {**config, "intermediate_size": 2048,
                               "reduced": {**SHIPPED["reduced"],
                                           "intermediate_size": {
                                               "published": 4096}}},
                       dict(table))
    # What differs from the preset's without being listed is refused: a
    # width, the norm's epsilon, the block's form.
    with pytest.raises(ValueError, match="head_dim=64 in the file"):
        resolve_preset("cut", {**config, "head_dim": 64}, dict(table))
    with pytest.raises(ValueError, match="use_parallel_block=0 in the file"):
        resolve_preset("cut", {**config, "use_parallel_block": False},
                       dict(table))
    assert resolve_preset(CONFIG, SHIPPED, dict(PRESETS)) == CONFIG
    # The parent of PR 44 has no such preset: the cell fails at once there.
    with pytest.raises(KeyError):
        resolve_preset(CONFIG, SHIPPED, {})


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("command_a_plus")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-cohere2.json").write_text(
        json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/rag-b16.json"
                        ).read_text())
    # The shipped cycle's shape at the tiny geometry (chunk 32, ring of 9
    # pages of 8): two of four prompts pass the ring's 72 tokens.
    (root / "benchmark/traffic/tiny-rag.json").write_text(json.dumps({
        **shape, "clients": 4, "stagger_s": 0.01,
        "prompt_tokens": {"kind": "cycle", "values": [32, 128, 64, 96]},
        "max_tokens": {"kind": "uniform", "min": 4, "max": 8, "snap": 4}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"] if w["name"] == NAME)
    bench["configs"] = [{"name": "tiny-cohere2", "source": "none",
                         "file": "benchmark/configs/tiny-cohere2.json",
                         "reduced": sorted(CUT), "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL, "config": "tiny-cohere2",
                           "traffic": "tiny-rag"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:    # the lists the shipped cell was appended to
            m["workloads"] = [CELL] if NAME in m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 44), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    # The cuts reached the engine; every layer calls the paged kernels.
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"], eng["kv_quant"]) == (
                "tiny-cohere2", 4, 4, 256, "int8")
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
    # groups, every served token stood at the reference's maximum, and the
    # two counters hold exactly the keys of those steps.
    past = cases["served_past_window"]
    assert (past["tokens"], past["positions"]) == (96, 9)
    assert past["ring_pages_recycled"] >= 3
    assert past["max_abs_err"] <= 0.25 and past["gap_p50"] <= 0.05
    assert past["decode_keys"] == {"global": sum(range(97, 105)),
                                   "window": 8 * 16}
    win = phases["window"]
    assert win["compiles_in_window"] == 0
    assert win["jax_events"] == {"count": 0, "seconds": 0.0, "longest": []}
    # No device plane on a CPU: the share metrics find nothing and are left
    # out; the counters are read. (What a 2 s window on a loaded CPU holds
    # is not asserted: it may hold no decode step and no rotation;
    # ``served_past_window`` above is what turns the ring and counts keys.)
    got = last["metrics"]
    assert not any("_share" in k for k in got)
    for name in ("attn.decode_keys_global", "attn.decode_keys_window",
                 "cache.ring_pages_recycled", "moe.assignments"):
        assert got[f"cpu_rehearsal.{name}"]["value"] >= 0
    assert got["cpu_rehearsal.attn.decode_keys_window"]["value"] <= \
        got["cpu_rehearsal.attn.decode_keys_global"]["value"]
