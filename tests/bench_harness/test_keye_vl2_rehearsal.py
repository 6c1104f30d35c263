"""The cell ``keye-vl2-longctx-mixed``: its files as ``spec.load_cell``
finds them, its published sizes against the catalog's row, its seven
per-layer metrics as data over reducers that exist (one of them new: the
decode roofline, whose cost function is checked here by hand), the lists it
joined, its three cuts through ``resolve_preset`` — and the whole harness
rehearsed on a CPU at the program's ``tiny-keye-vl2-test``, where a prompt
of four chunks is already past the indexer's 24 keys."""
import dataclasses
import json
import shutil
import types
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.gateway import resolve_preset
from benchmark.reducers import REDUCERS

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

NAME, CONFIG = "keye-vl2-longctx-mixed", "keye-vl2-30b-ep4"
SHIPPED = json.loads(
    (REPO / f"benchmark/configs/{CONFIG}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
DECODE = ["decode_scan", "decode_step"]
NEW_METRICS = {
    "step.prefill_index_share": ("scope_share", "attn.index"),
    "step.decode_index_share": ("scope_share", "attn.index"),
    "step.prefill_sparse_attn_share": ("scope_share", "attn.sparse"),
    "step.decode_sparse_attn_share": ("scope_share", "attn.sparse"),
    "dsa.decode_keys_scored": ("counter_delta",
                               "dsa_decode_keys_scored_total"),
    "dsa.decode_keys_selected": ("counter_delta",
                                 "dsa_decode_keys_selected_total"),
    "kernel.dsa_decode_roofline": ("dsa_decode_roofline", None)}
JOINED = {"sched.batch_occupancy", "step.prefill_chunk_ms",
          "device.idle_share", "device.peak_hbm_bytes",
          "engine.compiles_in_window", "engine.trace_ms_in_window",
          "sched.decode_behind_prefill_pct", "step.prefill_experts_share",
          "step.decode_experts_share", "moe.tiles_run", "moe.tile_rows",
          "moe.assignments", "moe.assignments_local", "moe.experts_hit",
          "kernel.prefill_pages_walked", "kernel.prefill_pages_table"}

# The program's ``tiny-keye-vl2-test`` (4 layers, 16 experts top-4, 4 index
# heads of 8 that keep 24 keys) cut as the shipped file cuts the published
# model in experts held and vocabulary: one of 2 chips that share each layer
# (8 of 16 experts, 256 of 512 rows); its 4 layers are the floor and stay.
TINY = {
    "source": "none: CPU rehearsal of keye-vl2-30b-ep4",
    "preset": "tiny-keye-vl2-test", "reference": SHIPPED["reference"],
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
    "intermediate_size": 128, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0,
    "rope_scaling": SHIPPED["rope_scaling"],
    "sa_config": {**SHIPPED["sa_config"], "indexer_head_dim": 8,
                  "indexer_num_heads": 4, "topk": 24},
    "attention_bias": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "moe_intermediate_size": 32, "num_experts": 8, "num_local_experts": 16,
    "num_experts_per_tok": 4, "first_expert_held": 0,
    "preset_fields": SHIPPED["preset_fields"],
    "reduced": {"num_experts": {"published": 16,
                                "held_in": "n_experts_held"},
                "vocab_size": {"published": 512}},
    "chips_sharing_a_layer": 2,
    "deployment": "one of two chips that share each of four layers",
    "layer_kinds": SHIPPED["layer_kinds"], "scopes": SHIPPED["scopes"],
    "engine": {**TINY_ENGINE, "kv_quant": "", "prefix_cache": False}}
CELL = "tiny-keye-vl2-longctx-mixed"


def test_the_cells_files_are_found_and_say_what_the_issue_asked():
    from benchmark import reference
    from benchmark.reference import keye_vl2
    cell = spec.load_cell(NAME)
    assert (cell.chips, cell.config_name) == (1, CONFIG)
    assert reference.load(cell.config, cell.data) is keye_vl2
    assert callable(keye_vl2.kernel_checks)
    assert set(keye_vl2.CONTROLS) == {"int4_weights", "dense_attention",
                                      "lowest_scores"}
    assert callable(keye_vl2.controlled_checks)
    assert spec.paged_attention_layers(cell.config, 12) == 12
    assert spec.scopes(cell.config)[:3] == ("attn.index", "attn.sparse",
                                            "moe.experts")
    t = cell.traffic
    assert (t.loop, t.clients, t.stagger_s, t.trace_seed, t.temperature) == (
        "closed", 16, 0.05, 5101, 0.0)
    assert t.clients == 2 * cell.config["engine"]["max_batch_size"]
    from benchmark.traffic import support
    lengths = sorted(support(t.prompt_tokens))
    chunk = cell.config["engine"]["prefill_chunk"]
    topk = cell.config["sa_config"]["topk"]
    assert lengths == [8192, 12288, 16384, 20480, 24576, 28672]
    assert sum(lengths) // len(lengths) == 18432 == 36 * chunk
    assert all(n % chunk == 0 and 4 * topk <= n <= 14 * topk
               for n in lengths)
    assert max(lengths) + 512 < cell.config["engine"]["max_seq_len"]
    raw = json.loads(
        (REPO / "benchmark/traffic/longctx-mixed-b8.json").read_text())
    assert raw["prompt_tokens"]["values"] == [8192, 20480, 12288, 28672,
                                              16384, 24576]
    assert raw["max_tokens"] == {"kind": "uniform", "min": 256, "max": 512,
                                 "snap": 8}
    assert raw["source"]["name"].startswith("none:")
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    names = {lm.name for lm in cell.per_layer}
    assert JOINED | set(NEW_METRICS) <= names
    assert not {"step.decode_ms", "kernel.paged_decode_roofline",
                "mla.decode_keys_read"} & names
    engine = cell.config["engine"]
    assert (engine["quant"], engine["kv_quant"], engine["max_batch_size"],
            engine["max_seq_len"], engine["kv_page_size"],
            engine["prefill_chunk"], engine["prefix_cache"],
            engine["mesh"]) == ("int8", "", 8, 32768, 256, 512, False, {})
    assert engine["prefill_batch"] in (1, 2, 4)
    assert "correctness" not in cell.config     # default bounds, whole chunks
    assert cell.config["chips_sharing_a_layer"] == 4
    assert "16 v5e chips" in cell.config["deployment"]
    assert "4 pipeline stages of 12 layers" in cell.config["deployment"]
    assert len(cell.config["assumed"]) >= 10
    entry = next(w for w in BENCH["workloads"] if w["name"] == NAME)
    assert entry["traffic"] == "longctx-mixed-b8" and "4x" in entry["why"]


def test_the_files_published_sizes_are_the_catalog_rows():
    if not CATALOG.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert SHIPPED["source"] == row["source_url"]
    cut = {"num_hidden_layers": 12, "num_experts": 32, "vocab_size": 37984}
    assert sorted(SHIPPED["reduced"]) == sorted(cut)
    for key, value in row["config"].items():
        if key in cut:
            assert SHIPPED["reduced"][key]["published"] == value
            assert SHIPPED[key] == cut[key]
        else:
            assert SHIPPED[key] == value, key
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(cut)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_per_layer_metric_reads_what_is_there(metric):
    reducer, reads = NEW_METRICS[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["reducer"] == reducer and reducer in REDUCERS
    if reducer == "dsa_decode_roofline":
        assert REDUCERS[reducer].__module__.endswith(
            "reducer_files.dsa_decode_roofline")
        assert raw["args"] == {"programs": DECODE,
                               "scopes": ["attn.index", "attn.sparse"]}
    else:
        assert REDUCERS[reducer].__module__ == "benchmark.reducers"
        assert raw["args"].get("scope", raw["args"].get("counter")) == reads
    if reducer == "scope_share":
        assert reads in SHIPPED["scopes"]
        assert raw["args"]["programs"] == (
            DECODE if ".decode_" in metric else ["prefill_step"])
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [NAME] and entry["moves"] == "out_tok_s"
    assert (entry["unit"] == "%") == (reducer != "counter_delta")


def test_the_new_entries_stand_at_the_end_and_the_cell_joined_its_lists():
    assert [m["name"] for m in BENCH["per_layer"]][-7:] == list(NEW_METRICS)
    assert BENCH["workloads"][-1]["name"] == NAME
    assert BENCH["configs"][-1]["name"] == CONFIG
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in JOINED | {"out_tok_s"}:
            assert m["workloads"][-1] == NAME, m["name"]
        elif m["name"] not in NEW_METRICS:
            assert NAME not in m.get("workloads", []), m["name"]


def test_the_decode_cost_and_the_roofline_over_it():
    """By hand: three steps at contexts 1,000, 2,048 and 30,000 score
    33,048 index keys of 128 B and select 1,000 + 2,048 + 2,048 rows of
    2,048 B; twelve layers of that over 819 GB/s, over 1 ms of device time
    under the two scopes, is what the reducer reads — and nothing where
    the program has no such scope or the file no indexer."""
    from benchmark.reducer_files.dsa_decode_roofline import span_contexts
    from benchmark.reference.keye_vl2 import dsa_decode_cost
    from benchmark.roofline import AttnShape
    scored, selected, nbytes = dsa_decode_cost([1000, 2048, 30000], 2048,
                                               64, 4, 128)
    assert (scored, selected) == (33048, 5096)
    assert nbytes == 33048 * 128 + 5096 * 2048
    log = types.SimpleNamespace(prompt_tokens=999, frames=[
        (0.5, 1), (1.0, 1), (9.0, 1)])      # first token, one inside, one out
    other = types.SimpleNamespace(prompt_tokens=2046, frames=[
        (0.1, 1), (0.2, 1), (1.5, 1), (1.6, 1)])

    class Trace:
        devices = [object()]

        def __init__(self, ns):
            self.ns = ns

        def self_ns(self, program, scope):
            return self.ns.get((program, scope), 0)
    shape = AttnShape(n_layers=12, n_heads=32, n_kv_heads=4, head_dim=128,
                      window=0, kv_bytes=2, kv_scale_bytes=0)
    m = types.SimpleNamespace(
        logs=[log, other], t_trace=(0.9, 2.0), config=SHIPPED, shape=shape,
        peaks={"bf16_flops": 197e12, "hbm_bytes_s": 819e9},
        trace=Trace({("decode_scan", "attn.index"): 600_000,
                     ("decode_scan", "attn.sparse"): 400_000}))
    assert span_contexts(m) == [1000, 2048, 2049]
    args = {"programs": DECODE, "scopes": ["attn.index", "attn.sparse"]}
    want = (5097 * 128 + (1000 + 2048 + 2048) * 2048) * 12 / 819e9
    got = REDUCERS["dsa_decode_roofline"](m, args)
    assert got == pytest.approx(100.0 * want / 1e-3)
    assert 0 < got < 100
    m.trace = Trace({})
    assert REDUCERS["dsa_decode_roofline"](m, args) is None
    m.trace, m.config = Trace({("decode_scan", "attn.index"): 1}), {}
    assert REDUCERS["dsa_decode_roofline"](m, args) is None
    m.trace = None
    assert REDUCERS["dsa_decode_roofline"](m, args) is None


def test_the_cuts_register_and_the_floors_refuse():
    from llmapigateway_tpu.models.config import PRESETS
    table = {"keye-vl2-30b-a3b": PRESETS["keye-vl2-30b-a3b"]}
    config = {**SHIPPED, "preset": "keye-vl2-30b-a3b"}
    assert resolve_preset("cut", config, table) == "cut"
    assert table["cut"] == dataclasses.replace(
        PRESETS["keye-vl2-30b-a3b"], n_layers=12, vocab_size=37984,
        n_experts_held=32) == PRESETS[CONFIG]
    assert (table["cut"].n_experts, table["cut"].experts_held) == (128, 32)
    assert table["cut"].cache_groups == ((0, (0,)),)
    assert (table["cut"].idx_heads, table["cut"].idx_head_dim,
            table["cut"].idx_topk) == (16, 64, 2048)
    with pytest.raises(ValueError, match="depth 3 is not whole periods"):
        resolve_preset("cut", {**config, "num_hidden_layers": 3},
                       dict(table))
    with pytest.raises(ValueError, match="4 of 128 experts is not one of"):
        resolve_preset("cut", {**config, "num_experts": 4,
                               "chips_sharing_a_layer": 32}, dict(table))
    with pytest.raises(ValueError, match="head_dim is a width"):
        resolve_preset("cut", {**config, "head_dim": 64, "reduced": {
            **SHIPPED["reduced"], "head_dim": {"published": 128}}},
            dict(table))
    assert resolve_preset(CONFIG, SHIPPED, dict(PRESETS)) == CONFIG


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("keye_vl2")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-keye-vl2.json").write_text(
        json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/longctx-mixed-b8.json"
                        ).read_text())
    # The shipped cycle's shape at the tiny geometry (chunk 32, 24 keys
    # kept): every prompt is past the selection.
    (root / "benchmark/traffic/tiny-longctx-mixed.json").write_text(
        json.dumps({
            **shape, "clients": 4, "stagger_s": 0.01,
            "prompt_tokens": {"kind": "cycle",
                              "values": [64, 160, 96, 128]},
            "max_tokens": {"kind": "uniform", "min": 8, "max": 16,
                           "snap": 8}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"] if w["name"] == NAME)
    bench["configs"] = [{"name": "tiny-keye-vl2", "source": "none",
                         "file": "benchmark/configs/tiny-keye-vl2.json",
                         "reduced": ["num_experts", "vocab_size"],
                         "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL,
                           "config": "tiny-keye-vl2",
                           "traffic": "tiny-longctx-mixed"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if NAME in m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 51), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"], eng["kv_quant"]) == (
                "tiny-keye-vl2", 4, 4, 256, "")
    assert phases["programs"]["prefill_buckets"] == [32]
    ref = phases["reference"]
    assert ref["ok"] and ref["positions"] == 3 * 64
    assert (ref["tolerance"], ref["tolerance_p50"]) == (0.25, 0.05)
    cases = phases["kernel_parity"]["cases"]
    names = [c["kernel"] for c in cases]
    assert names == ["paged_decode", "paged_prefill",
                     *["dsa_select_decode", "dsa_select_prefill"] * 3,
                     "dsa_attend_decode", "dsa_attend_prefill",
                     "served_past_topk"]
    assert all(c["ok"] for c in cases), cases
    # Below the 24 keys every seen position is selected; past them exactly
    # 24 — the provider's list and the chunk kernel's mask against the
    # reference's own selection of plain scores: no key apart from it but
    # at the k-th score's rounding, no tie broken to the higher position.
    picked = [(c["kernel"][11:], c["context"], c["selected"]) for c in cases
              if c["kernel"].startswith("dsa_select_")]
    assert picked == [("decode", 12, 13), ("prefill", 12, 24),
                      ("decode", 48, 24), ("prefill", 48, 24),
                      ("decode", 120, 24), ("prefill", 120, 24)]
    assert all(c["apart"] == 0 and c["ties_broken_upward"] == 0
               for c in cases if c["kernel"].startswith("dsa_select_"))
    # Two prompts of 4.5 times the keys kept (128 tokens in whole chunks),
    # two rows a dispatch while two short requests decode beside them:
    # through the scheduler, every page back after.
    past = cases[-1]
    assert past["tokens"] == [128, 128, 64, 64]
    assert past["positions"] == 2 * 16 + 2 * 48 and past["others_live"]
    assert past["two_row_dispatches"] >= 128 // 32
    least = sum(n + i for n, m in zip(past["tokens"], (16, 16, 48, 48))
                for i in range(1, m))
    assert least <= past["keys_scored"] <= least + 16 * (128 + 16 + 16)
    assert past["keys_selected"] < 0.4 * past["keys_scored"]
    assert past["max_abs_err"] <= 0.25 and past["gap_p50"] <= 0.05
    win = phases["window"]
    assert win["compiles_in_window"] == 0 and win["drained"] is True
    # No device plane on a CPU: the shares and the roofline find nothing
    # and are left out; the two counters are read, and their ratio is the
    # read the selection saved.
    assert not any("_share" in k or "roofline" in k for k in last["metrics"])
    scored = last["metrics"]["cpu_rehearsal.dsa.decode_keys_scored"]["value"]
    kept = last["metrics"]["cpu_rehearsal.dsa.decode_keys_selected"]["value"]
    assert 0 < kept < scored
    # ... and the page walk's two totals, which the cell joined: a masked
    # walk still touches every live page of the table.
    walked, table = (
        last["metrics"][f"cpu_rehearsal.kernel.prefill_pages_{k}"]["value"]
        for k in ("walked", "table"))
    assert 0 < walked < table
