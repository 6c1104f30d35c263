"""The cell ``phi4-mini-flash-reason``: its files as ``spec.load_cell``
finds them, its published sizes against the catalog's row (nothing cut), its
eight per-layer metrics as data over reducers that exist (one of them new:
the cross reads' roofline, whose cost function is checked here by hand), the
lists it joined — pinned from the FRONT, so that a later cell's entries move
nothing here — and the whole harness rehearsed on a CPU at the program's
``tiny-phi4flash-test``, where a prompt of three chunks is already past the
ring. The counters are read over the lead-in too (the reference's own
request in set-up): a 2 s CPU window under six workers may hold no
dispatch."""
import json
import shutil
import types
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.gateway import resolve_preset
from benchmark.reducers import REDUCERS

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

NAME, CONFIG = "phi4-mini-flash-reason", "phi4-mini-flash-3.8b"
SHIPPED = json.loads(
    (REPO / f"benchmark/configs/{CONFIG}.json").read_text())
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
DECODE = ["decode_scan", "decode_step"]
NEW_METRICS = {
    "step.prefill_ssm_scan_share": ("scope_share", "ssm.scan"),
    "step.decode_ssm_update_share": ("scope_share", "ssm.scan"),
    "step.decode_cross_attn_share": ("scope_share", "attn.cross"),
    "step.prefill_cross_attn_share": ("scope_share", "attn.cross"),
    "xattn.decode_keys_read": ("counter_delta",
                               "cross_decode_keys_read_total"),
    "prefill.rows_stopped": ("counter_delta", "prefill_rows_stopped_total"),
    "kernel.cross_decode_roofline": ("cross_decode_roofline", "attn.cross")}
JOINED = {"sched.batch_occupancy", "step.prefill_chunk_ms",
          "device.idle_share", "device.peak_hbm_bytes",
          "engine.compiles_in_window", "engine.trace_ms_in_window",
          "sched.decode_behind_prefill_pct", "kernel.prefill_pages_walked",
          "kernel.prefill_pages_table", "step.prefill_attn_window_share",
          "step.decode_attn_window_share", "cache.ring_pages_recycled",
          "attn.decode_keys_global", "attn.decode_keys_window",
          "step.prefill_dense_share", "step.decode_dense_share",
          "lin.decode_state_updates"}

# The program's ``tiny-phi4flash-test`` whole (8 layers: scan, window 16,
# scan, window, scan -> memory, full, gate, cross), as the shipped file
# serves the published model whole.
TINY = {
    "source": "none: CPU rehearsal of phi4-mini-flash-3.8b",
    "preset": "tiny-phi4flash-test", "reference": SHIPPED["reference"],
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 128,
    "layer_norm_eps": 1e-05, "max_position_embeddings": 256,
    "mb_per_layer": 2, "num_attention_heads": 4, "num_hidden_layers": 8,
    "num_key_value_heads": 2, "sliding_window": 16,
    "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    "vocab_size": 512, "reduced": {}, "mamba_d_state": 8, "mamba_d_conv": 4,
    "mamba_expand": 2, "mamba_dt_rank": 4,
    "preset_fields": SHIPPED["preset_fields"],
    "layer_kinds": SHIPPED["layer_kinds"], "scopes": SHIPPED["scopes"],
    # One row a prefill dispatch: half the prefill programs to compile
    # (interpreted kernels: 3 s each here).
    "engine": {**TINY_ENGINE, "kv_quant": "", "prefix_cache": False,
               "prefill_batch": 1}}
CELL = "tiny-phi4-mini-flash-reason"


def test_the_cells_files_are_found_and_say_what_the_issue_asked():
    from benchmark import reference
    from benchmark.reference import phi4_flash
    cell = spec.load_cell(NAME)
    assert (cell.chips, cell.config_name) == (1, CONFIG)
    assert reference.load(cell.config, cell.data) is phi4_flash
    assert callable(phi4_flash.kernel_checks)
    assert callable(phi4_flash.controlled_checks)
    assert set(phi4_flash.CONTROLS) == {
        "int4_weights", "no_window", "cross_lambda_0", "memory_before_gate",
        "bf16_state", "cross_own_chunk"}
    # 16 of the 32 layers call the paged kernels: 9 keep K/V, 7 read it.
    assert spec.paged_attention_layers(cell.config, 32) == 16
    assert spec.scopes(cell.config)[:6] == (
        "ssm.scan", "ssm.proj", "attn.window", "attn.cross", "gmu",
        "mlp.dense")
    t = cell.traffic
    assert (t.loop, t.clients, t.stagger_s, t.trace_seed, t.temperature) == (
        "closed", 64, 0.05, 5401, 0.0)
    assert t.clients == 2 * cell.config["engine"]["max_batch_size"]
    raw = json.loads(
        (REPO / "benchmark/traffic/reason-doc-b32.json").read_text())
    lengths = raw["prompt_tokens"]["values"]
    chunk = cell.config["engine"]["prefill_chunk"]
    assert lengths == [1024, 2048, 8192, 1024, 24576, 2048, 4096, 1024]
    assert sum(lengths) / len(lengths) == 5504 == 10.75 * chunk
    assert all(n % chunk == 0 and n > SHIPPED["sliding_window"]
               for n in lengths)
    assert 0.55 < max(lengths) / sum(lengths) < 0.57
    assert max(lengths) + 1024 < cell.config["engine"]["max_seq_len"]
    assert raw["max_tokens"] == {"kind": "uniform", "min": 512, "max": 1024,
                                 "snap": 8}
    assert raw["source"]["name"].startswith("none:")
    assert {m["name"] for m in cell.end_to_end} == {"out_tok_s", "setup_s"}
    names = {lm.name for lm in cell.per_layer}
    assert JOINED | set(NEW_METRICS) == names
    engine = cell.config["engine"]
    assert (engine["quant"], engine["kv_quant"], engine["max_batch_size"],
            engine["max_seq_len"], engine["kv_page_size"],
            engine["prefill_chunk"], engine["prefix_cache"],
            engine["mesh"]) == ("int8", "", 32, 32768, 256, 512, False, {})
    assert engine["prefill_batch"] in (1, 2, 4)
    assert "correctness" not in cell.config     # default bounds, whole chunks
    assert "chips_sharing_a_layer" not in cell.config   # nothing is shared
    assert "WHOLE" in cell.config["deployment"]
    assert len(cell.config["assumed"]) >= 15
    assert cell.config["assumed"][0].startswith("every line below")
    entry = next(w for w in BENCH["workloads"] if w["name"] == NAME)
    assert entry["traffic"] == "reason-doc-b32" and entry["chips"] == 1


def test_the_files_published_sizes_are_the_catalog_rows_and_nothing_is_cut():
    from llmapigateway_tpu.models.config import PRESETS
    assert resolve_preset(CONFIG, SHIPPED, dict(PRESETS)) == CONFIG
    entry = next(c for c in BENCH["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == [] and SHIPPED["reduced"] == {}
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # The preset keeps the PUBLISHED heads; the fold is the engine's.
    preset = PRESETS[CONFIG]
    assert (preset.n_heads, preset.n_kv_heads, preset.head_dim) == (40, 20, 64)
    with pytest.raises(ValueError, match="num_key_value_heads=10 in the file"):
        resolve_preset(CONFIG, {**SHIPPED, "num_key_value_heads": 10},
                       dict(PRESETS))
    if not CATALOG.exists():
        pytest.skip("no catalog beside the model-configs guide here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r["name"] == "Phi-4-mini-flash-reasoning")
    assert SHIPPED["source"] == entry["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert SHIPPED[key] == value, key


@pytest.mark.parametrize("metric", sorted(NEW_METRICS))
def test_a_new_per_layer_metric_reads_what_is_there(metric):
    reducer, reads = NEW_METRICS[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["reducer"] == reducer and reducer in REDUCERS
    if reducer == "cross_decode_roofline":
        assert REDUCERS[reducer].__module__.endswith(
            "reducer_files.cross_decode_roofline")
        assert raw["args"] == {"programs": DECODE, "scope": reads}
    else:
        assert REDUCERS[reducer].__module__ == "benchmark.reducers"
        assert raw["args"].get("scope", raw["args"].get("counter")) == reads
    if reducer == "scope_share":
        assert reads in SHIPPED["scopes"]
        assert raw["args"]["programs"] == (
            DECODE if ".decode_" in metric else ["prefill_step"])
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"][0] == NAME and entry["moves"] == "out_tok_s"
    assert (entry["unit"] == "%") == (reducer != "counter_delta")


def test_the_cell_joined_the_lists_and_nothing_in_front_moved():
    """Pinned from the FRONT: what stood at PR 53 stands where it stood,
    this PR's seven metrics, configuration and cell directly behind it. A
    later cell appends behind them and moves nothing here."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[0] == "client.ttft_p90_ms"
    assert names[64] == "kernel.dsa_decode_roofline"
    assert names[65:72] == list(NEW_METRICS)
    assert [c["name"] for c in BENCH["configs"]][7] == CONFIG
    assert [w["name"] for w in BENCH["workloads"]][9] == NAME
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        if m["name"] in JOINED | {"out_tok_s"}:
            assert NAME in m["workloads"], m["name"]
        elif m["name"] not in NEW_METRICS:
            assert NAME not in m.get("workloads", []), m["name"]


def test_the_cross_reads_cost_and_the_roofline_over_it():
    """By hand: three steps at contexts 1,000, 2,048 and 30,000 read 33,048
    keys of 5,120 B EIGHT times over (the full layer and seven cross
    layers); that over 819 GB/s, over 10 ms of device time under attn.cross
    in the decode programs and the burst the trace files under no program
    (6 + 3 + 1 ms; ``prefill_step``'s 5 ms on neither side), is what the
    reducer reads — and nothing where the program has no such scope or the
    file is no cross decoder's. A chunk's scan: 8 operations a token,
    channel and state number."""
    from benchmark.reducer_files.cross_decode_roofline import span_contexts
    from benchmark.reference.phi4_flash import (cross_decode_cost,
                                                ssm_scan_cost)
    from benchmark.roofline import AttnShape
    keys, nbytes = cross_decode_cost([1000, 2048, 30000], 8, 10, 128)
    assert keys == 8 * 33048 and nbytes == 8 * 33048 * 5120
    ops, moved = ssm_scan_cost(512, 9, 5120, 16)
    assert ops == 8 * 512 * 9 * 5120 * 16
    assert moved == 4 * 512 * 9 * (3 * 5120 + 32)
    log = types.SimpleNamespace(prompt_tokens=999, frames=[
        (0.5, 1), (1.0, 1), (9.0, 1)])      # first token, one inside, one out
    other = types.SimpleNamespace(prompt_tokens=2046, frames=[
        (0.1, 1), (0.2, 1), (1.5, 1), (1.6, 1)])

    class Trace:
        devices = [object()]

        def __init__(self, ns):
            self.ns = {"decode_scan": 0.6 * ns, "decode_step": 0.3 * ns,
                       "_unknown": 0.1 * ns, "prefill_step": 0.5 * ns}

        def self_ns(self, program=None, scope=None):
            return self.ns.get(program, 0) if scope == "attn.cross" else 0
    shape = AttnShape(n_layers=16, n_heads=40, n_kv_heads=10, head_dim=128,
                      window=512, kv_bytes=2, kv_scale_bytes=0)
    m = types.SimpleNamespace(
        logs=[log, other], t_trace=(0.9, 2.0), trace=Trace(10_000_000),
        shape=shape, config=SHIPPED, peaks={"bf16_flops": 197e12,
                                            "hbm_bytes_s": 819e9})
    assert span_contexts(m) == [1000, 2048, 2049]
    args = {"programs": DECODE, "scope": "attn.cross"}
    want = 100.0 * (8 * (1000 + 2048 + 2049) * 5120 / 819e9) / 0.010
    assert REDUCERS["cross_decode_roofline"](m, args) == pytest.approx(want)
    assert 0 < want < 100
    m.trace = Trace(0)          # a program with no such scope: the parent's
    assert REDUCERS["cross_decode_roofline"](m, args) is None
    m.trace, m.config = Trace(10_000_000), {"num_hidden_layers": 32}
    assert REDUCERS["cross_decode_roofline"](m, args) is None


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("phi4_flash")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-phi4-flash.json").write_text(
        json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/reason-doc-b32.json"
                        ).read_text())
    # The shipped cycle's shape at the tiny geometry (chunk 32, window 16,
    # a ring of 10 pages of 8): every prompt past the window, one past the
    # ring.
    (root / "benchmark/traffic/tiny-reason-doc.json").write_text(
        json.dumps({
            **shape, "clients": 4, "stagger_s": 0.01,
            "prompt_tokens": {"kind": "cycle", "values": [32, 64, 160, 32]},
            "max_tokens": {"kind": "uniform", "min": 8, "max": 16,
                           "snap": 8}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"] if w["name"] == NAME)
    bench["configs"] = [{"name": "tiny-phi4-flash", "source": "none",
                         "file": "benchmark/configs/tiny-phi4-flash.json",
                         "reduced": [], "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL,
                           "config": "tiny-phi4-flash",
                           "traffic": "tiny-reason-doc"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if NAME in m["workloads"] else []
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 54), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"], eng["kv_quant"]) == (
                "tiny-phi4flash-test", 8, 4, 512, "")
    assert phases["programs"]["prefill_buckets"] == [32]
    ref = phases["reference"]
    assert ref["ok"] and ref["positions"] == 3 * 64
    assert (ref["tolerance"], ref["tolerance_p50"]) == (0.25, 0.05)
    cases = phases["kernel_parity"]["cases"]
    # The harness's own parity ran the SERVED fold (4 query heads over ONE
    # K/V head of 32) at the ring's window; the reference's adds the fold
    # over the whole context, the scan and the long request.
    assert [(c["kernel"], c.get("window")) for c in cases[:4]] == [
        ("paged_decode", 16), ("paged_prefill", 16),
        ("paged_decode_full", 0), ("paged_prefill_full", 0)]
    assert [c["kernel"] for c in cases[4:]] == [
        "cross_read_decode", "ssm_scan_chunked", "served_past_window"]
    assert all(c["ok"] for c in cases), cases
    assert cases[5]["max_abs_err"] < 1e-5 and cases[5]["tolerance"] == 1e-4
    # The long request, in set-up: 160 tokens in five chunks (past the
    # ring), every row but the prompt's last stopped at the full layer's
    # K/V, the ring recycled, the cross reads two layers' of every step's
    # context. These are the counters' readings over the LEAD-IN.
    past = cases[6]
    assert past["tokens"] == 160 and past["positions"] == 64
    assert past["prefill_rows_stopped_total"] == 159
    assert past["kv_ring_recycled_total"] > 0 and past["released"]
    steps = sum(160 + i for i in range(1, 64))
    assert 2 * steps <= past["cross_decode_keys_read_total"] \
        <= 2 * (steps + 8 * 256)
    assert 63 * 3 <= past["lin_decode_state_updates_total"] <= (63 + 8) * 3
    assert past["max_abs_err"] <= 0.25 and past["gap_p50"] <= 0.05
    win = phases["window"]
    assert win["compiles_in_window"] == 0 and win["drained"] is True
    # No device plane on a CPU: the shares and the roofline find nothing
    # and are left out; the counters are read (and may read 0 over a 2 s
    # window that held no dispatch).
    assert not any("_share" in k or "roofline" in k for k in last["metrics"])
    for name in ("lin.decode_state_updates", "xattn.decode_keys_read",
                 "prefill.rows_stopped", "cache.ring_pages_recycled",
                 "attn.decode_keys_global", "attn.decode_keys_window",
                 "kernel.prefill_pages_walked"):
        assert last["metrics"][f"cpu_rehearsal.{name}"]["value"] >= 0
    reads = last["metrics"]["cpu_rehearsal.xattn.decode_keys_read"]["value"]
    one = last["metrics"]["cpu_rehearsal.attn.decode_keys_global"]["value"]
    assert reads == 2 * one
