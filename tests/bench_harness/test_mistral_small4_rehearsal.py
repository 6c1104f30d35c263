"""The cell ``mistral-small4-longctx``: its files as ``spec.load_cell``
finds them, its published sizes against the catalog's row, the reducers and
scopes its three queued per-layer metrics would read (``PERF.md`` section 7
row 3e holds their entries and files: an entry appended to ``per_layer``
fails ``test_smallthinker_rehearsal.py:125``, one inserted is refused by
the driver), its three cuts through
``resolve_preset`` with the floors that refuse — and the whole harness
rehearsed on a CPU at the program's ``tiny-mistral4-test``, the reference's
``served_past_8192`` scaled to the tiny rotary's original context."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.gateway import resolve_preset
from benchmark.reducers import REDUCERS

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

NAME, CONFIG = "mistral-small4-longctx", "mistral-small4-119b-ep4"
SHIPPED = json.loads(
    (REPO / f"benchmark/configs/{CONFIG}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
QUEUED_METRICS = {
    "step.prefill_mla_share": ("scope_share", "attn.mla"),
    "step.decode_mla_share": ("scope_share", "attn.mla"),
    "mla.decode_keys_read": ("counter_delta", "mla_decode_keys_total")}
JOINED = {"sched.batch_occupancy", "step.prefill_chunk_ms",
          "device.idle_share", "device.peak_hbm_bytes"}

# The program's ``tiny-mistral4-test`` (4 latent layers, 16 experts top-2
# beside a shared one, YaRN x8 over 32 positions) cut as the shipped file
# cuts the published model in experts held and vocabulary: one of 2 chips
# that share each layer (8 of 16 experts, 256 of 512 rows); its 4 layers
# are the floor and stay.
TINY = {
    "source": "none: CPU rehearsal of mistral-small4-119b-ep4",
    "preset": "tiny-mistral4-test", "reference": SHIPPED["reference"],
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 256,
    "intermediate_size": 128, "max_position_embeddings": 256,
    "rms_norm_eps": 1e-06, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_interleave": True, "n_group": 1, "topk_group": 1,
    "rope_parameters": {
        "rope_type": "yarn", "type": "yarn", "rope_theta": 10000,
        "factor": 8, "original_max_position_embeddings": 32,
        "beta_fast": 4, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "llama_4_scaling_beta": 0.1},
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "first_expert_held": 0, "preset_fields": SHIPPED["preset_fields"],
    "reduced": {"n_routed_experts": {"published": 16,
                                     "held_in": "n_experts_held"},
                "vocab_size": {"published": 512}},
    "chips_sharing_a_layer": 2,
    "deployment": "one of two chips that share each of four layers",
    "layer_kinds": SHIPPED["layer_kinds"], "scopes": SHIPPED["scopes"],
    "engine": {**TINY_ENGINE, "kv_quant": "", "prefix_cache": False}}
CELL = "tiny-mistral4-longctx"


def test_the_cells_files_are_found_and_say_what_the_issue_asked():
    from benchmark import reference
    from benchmark.reference import mistral4
    cell = spec.load_cell(NAME)
    assert (cell.chips, cell.config_name) == (1, CONFIG)
    assert reference.load(cell.config, cell.data) is mistral4
    assert callable(mistral4.kernel_checks)
    assert callable(mistral4.mla_decode_cost)
    assert callable(mistral4.mla_prefill_cost)
    assert spec.paged_attention_layers(cell.config, 12) == 12
    assert spec.scopes(cell.config)[:3] == ("attn.mla", "moe.experts",
                                            "moe.shared")
    t = cell.traffic
    assert (t.loop, t.clients, t.stagger_s, t.trace_seed, t.temperature) == (
        "closed", 16, 0.05, 3801, 0.0)
    assert t.clients == 2 * cell.config["engine"]["max_batch_size"]
    from benchmark.traffic import support
    lengths = sorted(support(t.prompt_tokens))
    chunk = cell.config["engine"]["prefill_chunk"]
    assert lengths == [8192, 12288, 16384, 20480, 24576, 28672]
    assert sum(lengths) // len(lengths) == 18432 == 36 * chunk
    assert all(n % chunk == 0 for n in lengths)         # one bucket to warm
    assert max(lengths) + 128 < cell.config["engine"]["max_seq_len"]
    raw = json.loads((REPO / "benchmark/traffic/longctx-b8.json").read_text())
    assert raw["prompt_tokens"]["values"] == [8192, 20480, 12288, 28672,
                                              16384, 24576]
    assert raw["max_tokens"] == {"kind": "uniform", "min": 64, "max": 128,
                                 "snap": 8}
    assert raw["source"]["name"].startswith("none:")
    # `out_tok_s` alone beside `setup_s` (the issue says why).
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    names = {lm.name for lm in cell.per_layer}
    assert JOINED <= names
    assert not {"step.decode_ms", "step.decode_mlp_share",
                "kernel.paged_decode_roofline"} & names
    engine = cell.config["engine"]
    assert (engine["quant"], engine["kv_quant"], engine["max_batch_size"],
            engine["max_seq_len"], engine["kv_page_size"],
            engine["prefill_chunk"], engine["prefix_cache"],
            engine["mesh"]) == ("int8", "", 8, 32768, 256, 512, False, {})
    assert engine["prefill_batch"] in (1, 2, 4)
    assert "correctness" not in cell.config     # default bounds, whole chunks
    # The deployment the cut stands for, stated.
    assert cell.config["chips_sharing_a_layer"] == 4
    assert "12 v5e chips" in cell.config["deployment"]
    assert "3 pipeline stages of 12 layers" in cell.config["deployment"]
    assert len(cell.config["assumed"]) >= 10
    entry = next(w for w in BENCH["workloads"] if w["name"] == NAME)
    assert entry["traffic"] == "longctx-b8" and "4x" in entry["why"]


def test_the_files_published_sizes_are_the_catalog_rows():
    if not CATALOG.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Mistral-Small-4-119B-2603")
    assert SHIPPED["source"] == row["source_url"]
    cut = {"num_hidden_layers": 12, "n_routed_experts": 32,
           "vocab_size": 32768}
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


@pytest.mark.parametrize("metric", sorted(QUEUED_METRICS))
def test_what_a_queued_per_layer_metric_would_read_is_there(metric):
    """Whether or not the entry is there yet (the module's docstring says
    why it is not): the reducer is one of ``benchmark.reducers``, the scope
    is one the configuration's file lists; the counter is read by the
    rehearsal below (``served_past_8192`` takes its ``keys_attended`` from
    it)."""
    reducer, reads = QUEUED_METRICS[metric]
    assert REDUCERS[reducer].__module__ == "benchmark.reducers"
    if reducer == "scope_share":
        assert reads in SHIPPED["scopes"]


def test_the_cell_joined_the_lists_of_the_metrics_it_reports():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in JOINED | {"out_tok_s"}:
            assert NAME in m["workloads"], m["name"]


def test_the_cuts_register_and_the_floors_refuse():
    """From the published 36-layer preset on a table of its own: 12 layers,
    32 of 128 experts and a quarter of the vocabulary register as the
    program's own entry of the configuration's name; depth 3, 4 experts, a
    sixteenth of the vocabulary and a cut width are refused."""
    from llmapigateway_tpu.models.config import PRESETS
    table = {"mistral-small4-119b": PRESETS["mistral-small4-119b"]}
    config = {**SHIPPED, "preset": "mistral-small4-119b"}
    assert resolve_preset("cut", config, table) == "cut"
    assert table["cut"] == dataclasses.replace(
        PRESETS["mistral-small4-119b"], n_layers=12, vocab_size=32768,
        n_experts_held=32) == PRESETS[CONFIG]
    assert (table["cut"].n_experts, table["cut"].experts_held) == (128, 32)
    assert table["cut"].cache_groups == ((0, (0,)),)
    with pytest.raises(ValueError, match="depth 3 is not whole periods"):
        resolve_preset("cut", {**config, "num_hidden_layers": 3},
                       dict(table))
    with pytest.raises(ValueError, match="4 of 128 experts is not one of"):
        resolve_preset("cut", {**config, "n_routed_experts": 4,
                               "chips_sharing_a_layer": 32}, dict(table))
    with pytest.raises(ValueError, match="8192 of 131072 vocabulary rows"):
        resolve_preset("cut", {**config, "vocab_size": 8192,
                               "n_routed_experts": 8,
                               "chips_sharing_a_layer": 16}, dict(table))
    with pytest.raises(ValueError, match="kv_lora_rank is a width"):
        resolve_preset("cut", {**config, "kv_lora_rank": 128, "reduced": {
            **SHIPPED["reduced"], "kv_lora_rank": {"published": 256}}},
            dict(table))
    # A width that differs from the preset's without being listed: refused.
    with pytest.raises(ValueError, match="qk_rope_head_dim=32 in the file"):
        resolve_preset("cut", {**config, "qk_rope_head_dim": 32},
                       dict(table))
    assert resolve_preset(CONFIG, SHIPPED, dict(PRESETS)) == CONFIG


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("mistral_small4")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-mistral4.json").write_text(
        json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/longctx-b8.json"
                        ).read_text())
    # The shipped cycle's shape at the tiny geometry (chunk 32, original
    # context 32): three of four prompts pass the original context.
    (root / "benchmark/traffic/tiny-longctx.json").write_text(json.dumps({
        **shape, "clients": 4, "stagger_s": 0.01,
        "prompt_tokens": {"kind": "cycle", "values": [32, 128, 64, 96]},
        "max_tokens": {"kind": "uniform", "min": 4, "max": 8, "snap": 4}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"] if w["name"] == NAME)
    bench["configs"] = [{"name": "tiny-mistral4", "source": "none",
                         "file": "benchmark/configs/tiny-mistral4.json",
                         "reduced": ["n_routed_experts", "vocab_size"],
                         "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL,
                           "config": "tiny-mistral4",
                           "traffic": "tiny-longctx"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:    # the lists the shipped cell was appended to
            m["workloads"] = [CELL] if NAME in m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 38), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    # The cuts reached the engine; every layer keeps a latent cache.
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"], eng["kv_quant"]) == (
                "tiny-mistral4", 4, 4, 256, "")
    assert phases["programs"]["prefill_buckets"] == [32]
    ref = phases["reference"]
    assert ref["ok"] and ref["positions"] == 3 * 64
    assert (ref["tolerance"], ref["tolerance_p50"]) == (0.25, 0.05)
    cases = {c["kernel"]: c for c in phases["kernel_parity"]["cases"]}
    assert list(cases) == ["paged_decode", "paged_prefill", "latent_decode",
                           "latent_prefill", "served_past_8192"]
    assert all(c["ok"] for c in cases.values())
    # A prompt of 64 tokens (the original context of 32 and a chunk of 32)
    # and 8 decode steps: every key counted once a layer, the slot left its
    # group, and every served token stood at the reference's maximum.
    past = cases["served_past_8192"]
    assert (past["tokens"], past["positions"]) == (64, 9)
    assert past["keys_attended"] == 72 * 73 // 2
    assert past["max_abs_err"] <= 0.25 and past["gap_p50"] <= 0.05
    win = phases["window"]
    assert win["compiles_in_window"] == 0
    assert win["jax_events"] == {"count": 0, "seconds": 0.0, "longest": []}
    # No device plane on a CPU: the share metrics find nothing and are left
    # out.
    assert not any("_share" in k for k in last["metrics"])
