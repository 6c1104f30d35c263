"""Requirement F: cells, configurations, traffic mixes and per-layer
metrics are data. A directory with one more of each is picked up with no
code change; the same files drive the CPU rehearsal of the whole harness
(tiny widths, interpret-mode kernels), whose last line has the contract's
keys and no device metric name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import spec
from benchmark.reducers import REDUCERS

REPO = Path(__file__).resolve().parents[2]
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())

TINY_ENGINE = {
    "quant": "int8", "kv_quant": "int8", "mesh": {}, "max_batch_size": 4,
    "max_seq_len": 256, "kv_page_size": 8, "prefill_chunk": 32,
    "attention": "pallas", "prefill_batch": 2, "decode_burst": 4,
    "decode_burst_busy": 4, "prewarm_sampler_variants": False}


@pytest.fixture(scope="module")
def extended(tmp_path_factory) -> Path:
    """The repo's benchmark data plus ONE new configuration, traffic mix,
    per-layer metric (over an existing reducer) and cell."""
    root = tmp_path_factory.mktemp("extended")
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs/tiny-swa.json").write_text(json.dumps({
        "source": "none: CPU rehearsal of the harness",
        "preset": "tiny-mistral-test", "hidden_size": 64,
        "num_hidden_layers": 2, "sliding_window": 16, "reduced": {},
        "engine": TINY_ENGINE}))
    (root / "benchmark/traffic/tiny-closed.json").write_text(json.dumps({
        "loop": "closed", "clients": 6, "stagger_s": 0.01, "trace_seed": 1,
        "prompt_tokens": {"kind": "uniform", "min": 40, "max": 120,
                          "snap": 8},
        "max_tokens": {"kind": "uniform", "min": 8, "max": 24, "snap": 4}}))
    (root / "benchmark/traffic/tiny-open.json").write_text(json.dumps({
        "loop": "open", "rate_rps": 6.0, "lead_in_s": 1.5, "trace_seed": 1,
        "prompt_tokens": {"kind": "uniform", "min": 40, "max": 120,
                          "snap": 8},
        "max_tokens": {"kind": "uniform", "min": 8, "max": 24, "snap": 4}}))
    (root / "benchmark/layer_metrics/sched.prefill_wait_ms.json").write_text(
        json.dumps({"unit": "ms", "reducer": "request_interval_ms",
                    "args": {"from": "t_admitted", "to": "t_first_token"}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "tiny-swa", "source": "none",
        "file": "benchmark/configs/tiny-swa.json", "reduced": [],
        "why": "rehearsal"})
    bench["workloads"] += [
        {"name": "tiny-swa-closed", "config": "tiny-swa",
         "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-swa-open", "config": "tiny-swa",
         "traffic": "tiny-open", "chips": 1, "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny-swa-closed", "tiny-swa-open"]
    bench["per_layer"].append({
        "name": "sched.prefill_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "ttft_p50_ms",
        "workloads": ["tiny-swa-closed", "tiny-swa-open"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_every_cell_of_the_repo_finds_its_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"] and cell.traffic.name == w["traffic"]
        assert cell.config["engine"]["max_batch_size"] == 8
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for lm in cell.per_layer:
            assert lm.reducer in REDUCERS, lm
    with pytest.raises(KeyError, match="no workload"):
        spec.load_cell("no-such-cell")


def test_new_files_and_one_entry_are_picked_up_with_no_code_change(extended):
    cell = spec.load_cell("tiny-swa-closed", extended)
    assert cell.config["preset"] == "tiny-mistral-test"
    assert cell.traffic.clients == 6 and cell.traffic.loop == "closed"
    names = [lm.name for lm in cell.per_layer]
    assert "sched.prefill_wait_ms" in names and "step.decode_ms" in names
    # The cells that were there are untouched by the addition.
    assert spec.load_cell("mistral-7b-longdoc", extended).traffic == \
        spec.load_cell("mistral-7b-longdoc").traffic


def test_a_depth_cut_registers_a_derived_preset():
    from benchmark.gateway import resolve_preset
    from llmapigateway_tpu.models.config import PRESETS
    config = {"preset": "mixtral-8x7b", "hidden_size": 4096,
              "num_hidden_layers": 6, "num_local_experts": 8,
              "reduced": {"num_hidden_layers": 6}}
    try:
        assert resolve_preset("mixtral-8x7b-w8-d6", config) == \
            "mixtral-8x7b-w8-d6"
        derived = PRESETS["mixtral-8x7b-w8-d6"]
        assert derived.n_layers == 6 and derived.d_model == 4096
        assert derived.n_experts == 8 and PRESETS["mixtral-8x7b"].n_layers == 32
    finally:
        PRESETS.pop("mixtral-8x7b-w8-d6", None)
    with pytest.raises(ValueError, match="only depth may be cut"):
        resolve_preset("x", {**config, "reduced": {"hidden_size": 64}})
    with pytest.raises(ValueError, match="hidden_size=64"):
        resolve_preset("x", {"preset": "mixtral-8x7b", "hidden_size": 64})
    for c in BENCH["configs"]:       # the shipped files are the presets
        cfg = json.loads((REPO / c["file"]).read_text())
        assert resolve_preset(c["name"], cfg) == cfg["preset"]


def run_benchmark(*args: str) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "17",
           "JAX_ENABLE_COMPILATION_CACHE": "0"}
    env.pop("XLA_FLAGS", None)      # one CPU device, as a machine has one chip
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)


def test_without_a_chip_a_cell_fails_and_prints_no_result():
    done = run_benchmark("--workload", "mistral-7b-chat-sat", "--seed",
                         str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert done.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["tiny-swa-closed", "tiny-swa-open"])
def test_cpu_rehearsal_runs_the_whole_harness_and_names_no_device_metric(
        extended, workload):
    done = run_benchmark(
        "--workload", workload, "--seed", str(2**31 + 5),
        "--seconds", "2", "--trace", "1", "--root", str(extended),
        "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    assert set(last) == {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 6
    assert last["device"]["platform"] == "cpu"
    assert last["metrics"] and all(
        k.startswith("cpu_rehearsal.") for k in last["metrics"])
    assert "cpu_rehearsal.sched.prefill_wait_ms" in last["metrics"]
    assert last["metrics"]["cpu_rehearsal.engine.compiles_in_window"][
        "value"] == 0
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert {"start", "engine", "programs", "kernel_parity", "reference",
            "setup", "window", "trace"} <= set(phases)
    # Counters and trace open with the window, in the open loop too (its
    # lead-in is 1.5 s): the snapshots are the window apart, the traced
    # span begins inside it, is found by its markers and lasts what the
    # harness asked for, by the profiler's clock as by the host's.
    assert phases["window"]["counters_span_s"] == pytest.approx(2.0, abs=0.3)
    assert 0 <= phases["window"]["loop_stall_ms"] < 1500
    assert phases["window"]["loop_stall_cpu_s"] >= 0
    # Nothing is traced, lowered or compiled inside the window: the
    # harness listens to JAX's own events, whatever the program counts.
    assert phases["window"]["jax_events"] == {
        "count": 0, "seconds": 0.0, "longest": []}
    assert phases["setup"]["lead_in_s"] >= (
        1.5 if workload == "tiny-swa-open" else 0.0)
    assert 0 <= last["device"]["trace_offset_s"] < 1.5
    tr = phases["trace"]
    assert tr["marked"] is True and "problem" not in tr
    assert tr["profiler_clock_s"] == pytest.approx(tr["host_clock_s"],
                                                   abs=2e-3)
    assert 4.0 <= tr["profiler_clock_s"] < 4.2
    # No TPU plane in a CPU's trace: no device facts, no breakdown.
    assert not {"busy_s", "window_s"} & set(last["device"])
    assert phases["reference"]["ok"] and phases["reference"]["positions"] > 30
    assert all(c["ok"] for c in phases["kernel_parity"]["cases"])
    assert phases["window"]["compiles_in_window"] == 0
    assert phases["window"]["samples"]["out_tok_s"] > 0
    assert set(phases["setup"]) >= {"engine_build_s", "programs_s",
                                    "correctness_s", "lead_in_s", "setup_s"}
    records = REPO / "bench_out" / workload / "requests.jsonl"
    assert len(records.read_text().splitlines()) >= last["attempted"]
