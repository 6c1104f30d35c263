"""The cell ``gigachat35-reason``: its files as ``spec.load_cell`` finds
them, its published sizes against the catalog's row, its four per-layer
metrics as data over reducers that exist, the lists it joined, its three
cuts through ``resolve_preset`` with the floors that refuse (depth counts
whole periods BEHIND the leading layers) — and the whole harness rehearsed
on a CPU at the program's ``tiny-gigachat35-test``: a latent pool AND
recurrent state a slot, the reference's kernel checks, the new counter in
the result line. ``per_layer`` places are pinned from the FRONT only, so a
later cell or metric may join behind."""
import dataclasses
import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.gateway import resolve_preset
from benchmark.reducers import REDUCERS

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

NAME, CONFIG = "gigachat35-reason", "gigachat35-432b-ep8"
SHIPPED = json.loads(
    (REPO / f"benchmark/configs/{CONFIG}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
NEW_METRICS = {
    "step.prefill_dense_share": ("scope_share", "mlp.dense"),
    "step.decode_dense_share": ("scope_share", "mlp.dense"),
    "step.decode_kda_proj_share": ("scope_share", "decode.kda"),
    "lin.decode_state_updates": ("counter_delta",
                                 "lin_decode_state_updates_total")}
# The lists the cell stands in beside the cells that share its code.
JOINED = {"sched.batch_occupancy", "step.prefill_chunk_ms",
          "device.idle_share", "device.peak_hbm_bytes",
          "engine.compiles_in_window", "engine.trace_ms_in_window",
          "sched.decode_behind_prefill_pct", "step.prefill_experts_share",
          "step.decode_experts_share", "moe.tiles_run", "moe.tile_rows",
          "moe.assignments", "moe.assignments_local", "moe.experts_hit",
          "step.prefill_kda_chunk_share", "step.decode_kda_update_share"}
# NOT joined, though the cell's latent layer fills them: the three metrics
# whose list ``test_mistral_small4_rehearsal.py`` pins to its own cell alone
# (a file the benchmark has, which only a ``benchmark`` PR may edit).
PINNED_ELSEWHERE = {"step.prefill_mla_share", "step.decode_mla_share",
                    "mla.decode_keys_read"}
CUT = {"num_hidden_layers": 7, "n_routed_experts": 32, "vocab_size": 16032}

# The program's ``tiny-gigachat35-test`` (two leading layers, two periods
# of a latent and three gated-delta layers, 16 experts top-4 beside a
# shared one) cut as the shipped file cuts the published model in experts
# held and vocabulary: one of 2 chips that share each layer (8 of 16
# experts, 256 of 512 rows); its depth stays. Served in float32 without
# quantisation: at 64 numbers a row a rounded stream flips a token's fourth
# expert of sixteen every few positions, and behind weights of 2.5 and a
# post norm a flip is a third of a logit (bfloat16 alone reads gap_max
# 0.12-0.48 over seeds against the limit of 0.25; float32 reads 0.0). The
# rounding at the published widths is the chip run's to judge.
TINY = {
    **{k: SHIPPED[k] for k in (
        "reference", "preset_fields", "scopes", "norm_type", "layernorm_type",
        "layernorm_gating_weight", "linear_sigmoid_gate_scale", "n_group",
        "topk_group", "n_shared_experts", "rope_interleave",
        "routed_scaling_factor", "gated_attention", "norm_topk_prob")},
    "source": "none: CPU rehearsal of gigachat35-432b-ep8",
    "preset": "tiny-gigachat35-test",
    "hidden_size": 64, "num_hidden_layers": 10, "num_attention_heads": 4,
    "num_key_value_heads": 4, "vocab_size": 256, "intermediate_size": 96,
    "max_position_embeddings": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_scaling": {"type": "yarn", "factor": 8, "beta_fast": 4,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32},
    "moe_intermediate_size": 32, "n_routed_experts": 8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 2, "swiglu_limit": 1.0,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "first_expert_held": 0,
    "reduced": {"n_routed_experts": {"published": 16,
                                     "held_in": "n_experts_held"},
                "vocab_size": {"published": 512}},
    "chips_sharing_a_layer": 2,
    "deployment": "one of two chips that share each of ten layers",
    "layer_kinds": {"period": 4, "leading_dense": 2, "paged_attention": 2},
    "engine": {**TINY_ENGINE, "quant": "", "dtype": "float32", "kv_quant": "",
               "prefix_cache": False}}
CELL = "tiny-gigachat35-reason"


def test_the_cells_files_are_found_and_say_what_the_issue_asked():
    from benchmark import reference
    from benchmark.reference import gigachat35
    from benchmark.traffic import support
    cell = spec.load_cell(NAME)
    assert (cell.chips, cell.config_name) == (1, CONFIG)
    assert reference.load(cell.config, cell.data) is gigachat35
    assert callable(gigachat35.kernel_checks)
    assert set(gigachat35.CONTROLS) == {"no_post_norm", "beta_0_2",
                                        "unclamped_mlp"}
    assert set(gigachat35.READINGS) == {"bf16_state"}
    # ONE latent layer of the seven calls the cache's kernels.
    assert spec.paged_attention_layers(cell.config, 7) == 1
    assert set(spec.scopes(cell.config)[:8]) == {
        "kda.prefill_chunk", "kda.decode_update", "attn.mla", "mlp.dense",
        "moe.experts", "moe.shared", "prefill.kda", "decode.kda"}
    t = cell.traffic
    assert (t.loop, t.clients, t.stagger_s, t.trace_seed, t.temperature) == (
        "closed", 64, 0.05, 4601, 0.0)
    assert t.clients == 2 * cell.config["engine"]["max_batch_size"]
    raw = json.loads((REPO / "benchmark/traffic/reason-b32.json").read_text())
    cycle = raw["prompt_tokens"]["values"]
    chunk = cell.config["engine"]["prefill_chunk"]
    assert cycle == [512, 1024, 2048, 1024, 16384, 512, 1024, 2048]
    assert sum(cycle) // len(cycle) == 3072 == 6 * chunk
    assert sorted(support(t.prompt_tokens)) == [512, 1024, 2048, 16384]
    assert all(n % chunk == 0 for n in cycle)       # one bucket to warm
    assert raw["max_tokens"] == {"kind": "uniform", "min": 1024, "max": 2048,
                                 "snap": 8}
    assert max(cycle) + 2048 < cell.config["engine"]["max_seq_len"]
    assert raw["source"]["name"].startswith("none:")
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    names = {lm.name for lm in cell.per_layer}
    assert JOINED | set(NEW_METRICS) <= names
    assert not PINNED_ELSEWHERE & names
    engine = cell.config["engine"]
    assert (engine["quant"], engine["kv_quant"], engine["max_seq_len"],
            engine["kv_page_size"], engine["prefill_chunk"],
            engine["prefix_cache"], engine["mesh"]) == (
                "int8", "", 20480, 256, 512, False, {})
    assert (engine["max_batch_size"], engine["prefill_batch"]) in (
        (32, 4), (32, 2), (32, 1), (16, 4), (16, 2), (16, 1))
    assert "correctness" not in cell.config     # default bounds, whole chunks
    assert cell.config["chips_sharing_a_layer"] == 8
    assert "56 v5e chips" in cell.config["deployment"]
    assert "7 pipeline stages" in cell.config["deployment"]
    assert any("next-token-prediction" in a and "NOT served" in a
               for a in cell.config["assumed"])
    assert sum("NOT TAKEN" in a for a in cell.config["assumed"]) >= 6
    entry = next(w for w in BENCH["workloads"] if w["name"] == NAME)
    assert entry["traffic"] == "reason-b32" and entry["chips"] == 1


def test_the_files_published_sizes_are_the_catalog_rows():
    if not CATALOG.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "GigaChat3.5-432B-A28B")
    assert SHIPPED["source"] == row["source_url"]
    assert sorted(SHIPPED["reduced"]) == sorted(CUT)
    for key, value in row["config"].items():
        if key in CUT:
            assert SHIPPED["reduced"][key]["published"] == value
            assert SHIPPED[key] == CUT[key]
        else:
            assert SHIPPED[key] == value, key
    # Every published width, by its number.
    assert [SHIPPED[k] for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_conv_kernel_dim",
        "num_experts_per_tok")] == [7168, 18432, 2048, 1536, 512, 128, 64,
                                    128, 32, 64, 128, 4, 8]
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"]
    assert sorted(entry["reduced"]) == sorted(CUT)
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_per_layer_metric_is_data_over_a_reducer_that_exists(metric):
    reducer, reads = NEW_METRICS[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["reducer"] == reducer
    assert REDUCERS[reducer].__module__ == "benchmark.reducers"
    assert raw["args"].get("scope", raw["args"].get("counter")) == reads
    if reducer == "scope_share":
        assert reads in SHIPPED["scopes"]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"][0] == NAME and entry["moves"] == "out_tok_s"


def test_the_cell_joined_the_lists_and_nothing_in_front_moved():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in JOINED | {"out_tok_s"}:
            assert NAME in m["workloads"], m["name"]
    # Pinned from the front: what stood at PR 45 stands where it stood,
    # the four new metrics directly behind it.
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[0] == "client.ttft_p90_ms"
    assert names[53] == "attn.decode_keys_window"
    assert names[54:58] == ["step.prefill_dense_share",
                            "step.decode_dense_share",
                            "step.decode_kda_proj_share",
                            "lin.decode_state_updates"]
    assert [c["name"] for c in BENCH["configs"]][5] == CONFIG
    assert [w["name"] for w in BENCH["workloads"]][7] == NAME


def test_the_cuts_register_and_the_floors_refuse():
    """From the published 40-layer preset on a table of its own: 7 layers
    (three leading and one whole period), 32 of 256 experts and an eighth
    of the vocabulary register as the program's own entry; a depth that is
    no whole period behind the leading layers, 16 experts as one of 16
    chips' share with a sixteenth of the vocabulary, and a cut width are
    refused."""
    from llmapigateway_tpu.models.config import PRESETS
    table = {"gigachat35-432b": PRESETS["gigachat35-432b"]}
    config = {**SHIPPED, "preset": "gigachat35-432b"}
    assert resolve_preset("cut", config, table) == "cut"
    assert table["cut"] == dataclasses.replace(
        PRESETS["gigachat35-432b"], n_layers=7, vocab_size=16032,
        n_experts_held=32) == PRESETS[CONFIG]
    cut = table["cut"]
    assert (cut.n_experts, cut.experts_held, cut.n_periods) == (256, 32, 1)
    assert (cut.n_kv_layers, cut.n_lin_layers) == (1, 6)
    assert cut.cache_groups == ((0, (0,)),) and cut.latent_width == 576
    assert cut.max_seq_len == 262144
    for depth in (6, 8, 9):
        with pytest.raises(ValueError, match=f"depth {depth} is not whole "
                                             f"periods of 4"):
            resolve_preset("cut", {**config, "num_hidden_layers": depth},
                           dict(table))
    with pytest.raises(ValueError, match="8016 of 128256 vocabulary rows"):
        resolve_preset("cut", {**config, "vocab_size": 8016,
                               "n_routed_experts": 16,
                               "chips_sharing_a_layer": 16}, dict(table))
    with pytest.raises(ValueError, match="kv_lora_rank is a width"):
        resolve_preset("cut", {**config, "kv_lora_rank": 256, "reduced": {
            **SHIPPED["reduced"], "kv_lora_rank": {"published": 512}}},
            dict(table))
    with pytest.raises(ValueError, match="linear_num_key_heads=64 in the "
                                         "file"):
        resolve_preset("cut", {**config, "linear_num_key_heads": 64},
                       dict(table))
    assert resolve_preset(CONFIG, SHIPPED, dict(PRESETS)) == CONFIG


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("gigachat35")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-gigachat35.json").write_text(
        json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/reason-b32.json"
                        ).read_text())
    # The shipped cycle's shape at the tiny geometry (chunk 32): short
    # prompts, one document in four, answers longer than most prompts.
    (root / "benchmark/traffic/tiny-reason.json").write_text(json.dumps({
        **shape, "clients": 4, "stagger_s": 0.01,
        "prompt_tokens": {"kind": "cycle", "values": [32, 64, 160, 32]},
        "max_tokens": {"kind": "uniform", "min": 24, "max": 48, "snap": 8}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"] if w["name"] == NAME)
    bench["configs"] = [{"name": "tiny-gigachat35", "source": "none",
                         "file": "benchmark/configs/tiny-gigachat35.json",
                         "reduced": ["n_routed_experts", "vocab_size"],
                         "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL,
                           "config": "tiny-gigachat35",
                           "traffic": "tiny-reason"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:    # the lists the shipped cell was appended to
            m["workloads"] = [CELL] if NAME in m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 46), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    # The cuts reached the engine; two of ten layers keep a latent cache.
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"], eng["quant"], eng["kv_quant"]) == (
                "tiny-gigachat35", 10, 2, 256, "", "")
    assert phases["programs"]["prefill_buckets"] == [32]
    ref = phases["reference"]
    assert ref["ok"] and ref["positions"] == 3 * 64
    assert (ref["tolerance"], ref["tolerance_p50"]) == (0.25, 0.05)
    cases = {c["kernel"]: c for c in phases["kernel_parity"]["cases"]}
    assert list(cases) == ["paged_decode", "paged_prefill", "latent_decode",
                           "latent_prefill", "delta_prefill_chunked",
                           "delta_decode_update"]
    assert all(c["ok"] for c in cases.values())
    win = phases["window"]
    assert win["compiles_in_window"] == 0
    assert win["jax_events"] == {"count": 0, "seconds": 0.0, "longest": []}
    # No device plane on a CPU: the share metrics (three of the four new
    # ones among them) find nothing and are left out; the counters are read.
    metrics = last["metrics"]
    assert not any("_share" in k for k in metrics)
    updates = metrics["cpu_rehearsal.lin.decode_state_updates"]["value"]
    assert updates > 0 and updates % 8 == 0     # 8 linear layers a row-step
    assert metrics["cpu_rehearsal.moe.assignments"]["value"] > 0
