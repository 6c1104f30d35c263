"""Requirement F: cells, configurations, traffic mixes and per-layer
metrics are data. A directory with one more of each is picked up with no
code change; the same files drive the CPU rehearsal of the whole harness
(tiny widths, interpret-mode kernels), whose last line has the contract's
keys and no device metric name.

Since PR 28 an ARCHITECTURE is files too: ``tiny-hybrid`` below names a
reference module of its own (with a kernel check), cuts depth and
vocabulary, states a layer-kind count and a scope, and brings a reducer in
a new file — and runs through the same rehearsal, with no module of
``benchmark/`` copied or patched."""
import dataclasses
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


FIXTURES = Path(__file__).parent / "fixtures" / "tiny_hybrid"

# A second architecture, by files alone. On the program's ``tiny-moe-test``
# (sparse experts): 4 layers of a published 8 in periods of 2, 256 of 512
# vocabulary rows as one of 2 chips that share each layer. The paged-layer
# count is a statement the rehearsal only carries (nothing reads a device
# trace on a CPU); ``tests`` of the reducers read it.
TINY_HYBRID = {
    "source": "none: CPU rehearsal of an architecture added by files",
    "preset": "tiny-moe-test", "reference": "tiny_hybrid",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "vocab_size": 256, "num_local_experts": 4, "num_experts_per_tok": 2,
    "head_dim": 16,
    "preset_fields": {"head_dim": "head_dim"},
    "reduced": {"num_hidden_layers": {"published": 8},
                "vocab_size": {"published": 512}},
    "chips_sharing_a_layer": 2,
    "deployment": "one of 2 chips that share each layer: half the "
                  "vocabulary rows; 4 of 8 layers, the rest a further stage",
    "layer_kinds": {"period": 2, "paged_attention": 2},
    "scopes": ["decode.experts"],
    "correctness": {
        "exact_up_to_tokens": 32,
        "logit_gap_tol": {"value": 0.2, "why": "rehearsal: a file may "
                                               "state a tighter bound"}},
    "engine": TINY_ENGINE}


@pytest.fixture(scope="module")
def extended(tmp_path_factory) -> Path:
    """The repo's benchmark data plus ONE new configuration, traffic mix,
    per-layer metric (over an existing reducer) and cell — and one new
    ARCHITECTURE: configuration, reference module, reducer file, metric
    and cell. Data directories are copied and files added; no module."""
    root = tmp_path_factory.mktemp("extended")
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    for kind, name in (("reference", "reference.py"),
                       ("reducer_files", "reducers.py")):
        (root / "benchmark" / kind).mkdir()
        shutil.copy(FIXTURES / name,
                    root / "benchmark" / kind / "tiny_hybrid.py")
    (root / "benchmark/configs/tiny-hybrid.json").write_text(
        json.dumps(TINY_HYBRID))
    (root / "benchmark/layer_metrics/model.expert_bytes_per_token.json"
     ).write_text(json.dumps({
         "unit": "bytes", "reducer": "expert_bytes_per_token",
         "args": {"bytes_per_weight": 2}}))
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
    bench["configs"].append({
        "name": "tiny-hybrid", "source": "none",
        "file": "benchmark/configs/tiny-hybrid.json",
        "reduced": ["num_hidden_layers", "vocab_size"], "why": "rehearsal"})
    bench["workloads"] += [
        {"name": "tiny-swa-closed", "config": "tiny-swa",
         "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-swa-open", "config": "tiny-swa",
         "traffic": "tiny-open", "chips": 1, "why": "rehearsal"},
        {"name": "tiny-hybrid-closed", "config": "tiny-hybrid",
         "traffic": "tiny-closed", "chips": 1, "why": "rehearsal"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += ["tiny-swa-closed", "tiny-swa-open",
                               "tiny-hybrid-closed"]
    bench["per_layer"].append({
        "name": "model.expert_bytes_per_token", "unit": "bytes",
        "better": "lower", "source": "program_counter",
        "layer": "model block", "moves": "out_tok_s",
        "workloads": ["tiny-hybrid-closed"]})
    bench["per_layer"].append({
        "name": "sched.prefill_wait_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "ttft_p50_ms",
        "workloads": ["tiny-swa-closed", "tiny-swa-open",
                      "tiny-hybrid-closed"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_every_cell_of_the_repo_finds_its_files():
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert cell.chips == w["chips"] and cell.traffic.name == w["traffic"]
        assert isinstance(cell.config["engine"], dict) and cell.config[
            "engine"]["max_batch_size"] >= 1     # its geometry is its own
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


def test_a_new_architecture_is_picked_up_from_files_alone(extended):
    """Stops 1, 5, 6, 7 of ISSUE 28: the reference module, the layer-kind
    count, the scope and the reducer file are found from the cell's data
    directory; no ``*.py`` under ``benchmark/`` knows their names."""
    from benchmark import reference, xplane
    cell = spec.load_cell("tiny-hybrid-closed", extended)
    assert cell.data == extended / "benchmark"
    ref = reference.load(cell.config, cell.data)
    assert ref.__file__ == str(extended / "benchmark/reference/tiny_hybrid.py")
    assert callable(ref.kernel_checks)
    assert reference.load(cell.config, cell.data) is ref       # loaded once
    assert spec.paged_attention_layers(cell.config, 4) == 2
    assert spec.scopes(cell.config) == ("decode.experts", *xplane.SCOPES)
    lm = {m.name: m for m in cell.per_layer}[
        "model.expert_bytes_per_token"]
    assert REDUCERS[lm.reducer].__module__.endswith(
        "reducer_files.tiny_hybrid")
    # A cell of the repo, loaded from the same root, names the default
    # reference — the package's own module, not a second copy of it.
    from benchmark.reference import forward
    old = spec.load_cell("mistral-7b-chat", extended)
    assert reference.load(old.config, old.data) is forward
    grep = subprocess.run(
        ["grep", "-rl", "tiny_hybrid\\|tiny-hybrid\\|decode.experts",
         "--include=*.py", str(REPO / "benchmark")],
        capture_output=True, text=True)
    assert grep.stdout == ""


def test_what_a_configuration_file_does_not_state_is_as_before_pr_28():
    """``mistral-7b-w8`` states none of the new keys: the reference is
    ``forward``, the tolerances 0.25 / 0.05 on whole chunks served
    together, every layer calls the paged kernels, ``SCOPES`` and the
    benchmark's own reducers are what they were."""
    from benchmark import correctness, reference, xplane
    from benchmark.reference import forward
    cell = spec.load_cell("mistral-7b-chat-sat")
    new_keys = {"reference", "correctness", "layer_kinds", "scopes",
                "preset_fields", "chips_sharing_a_layer"}
    assert not new_keys & set(cell.config) and cell.config["reduced"] == {}
    assert reference.load(cell.config, cell.data) is forward
    assert correctness.sampling(cell.config_name, cell.config) == \
        correctness.Sampling(None, 0.25, 0.05)
    assert spec.paged_attention_layers(cell.config, 32) == 32
    assert spec.scopes(cell.config) == xplane.SCOPES == (
        "attention.paged_prefill", "attention.paged_decode",
        "attention.paged_verify", "prefill.attention", "prefill.mlp",
        "decode.attention", "decode.mlp", "sampling")
    own = {n for n, f in REDUCERS.items()
           if f.__module__ == "benchmark.reducers"}
    assert own == {
        "request_interval_ms", "flight_mean", "program_ms_per_step",
        "program_ms_per_event", "scope_share", "kernel_roofline",
        "client_metric", "exposed_collective_share", "device_idle_share",
        "device_peak_hbm_bytes", "counter_delta"}
    assert not list((REPO / "benchmark").glob("reducer_files/*.py"))

    class Engine:                   # whole chunks: two where they fit
        prefill_chunk, S = 512, 8192
    from benchmark.run import sample_prompt_tokens
    assert sample_prompt_tokens(cell, Engine) == 1024


@pytest.mark.parametrize("layer_kinds, n_layers, want", [
    ({}, 32, 32),
    ({"period": 4, "paged_attention": 2}, 8, 2),          # a count
    ({"period": 4, "paged_attention": [0]}, 8, 2),        # a pattern
    ({"period": 4, "paged_attention": [0]}, 48, 12),
    ({"period": 4, "paged_attention": [3], "leading_dense": 1}, 9, 3),
    ({"period": 4, "paged_attention": 9}, 8, "9 paged layers of 8"),
    ({"period": 4, "paged_attention": []}, 8, "0 paged layers of 8"),
])
def test_layer_kinds_give_the_layers_that_call_the_paged_kernels(
        layer_kinds, n_layers, want):
    config = {"layer_kinds": layer_kinds} if layer_kinds else {}
    if isinstance(want, str):
        with pytest.raises(ValueError, match=want):
            spec.paged_attention_layers(config, n_layers)
    else:
        assert spec.paged_attention_layers(config, n_layers) == want


def test_a_scope_may_not_repeat_one_of_the_benchmarks():
    with pytest.raises(ValueError, match="repeat"):
        spec.scopes({"scopes": ["decode.mlp"]})
    with pytest.raises(ValueError, match="repeat"):
        spec.scopes({"scopes": ["a.b", "a.b"]})


def test_a_reducer_is_registered_once_and_an_unknown_one_fails_at_load(
        extended, tmp_path):
    from benchmark.reducers import reducer
    with pytest.raises(ValueError, match="registered twice"):
        @reducer
        def counter_delta(m, a):        # the benchmark's own name
            return None
    assert REDUCERS["counter_delta"].__module__ == "benchmark.reducers"
    # The same directory loaded again imports nothing a second time.
    spec.load_reducer_files(extended / "benchmark")
    spec.load_reducer_files(extended / "benchmark")
    # A metric file that names a reducer nobody registered: an error where
    # the cell is loaded, not in the traced run that would have called it.
    # (Its reducer file stays behind: a second copy of one would register
    # its names a second time, which is the error above.)
    root = tmp_path / "unknown"
    shutil.copytree(extended, root,
                    ignore=shutil.ignore_patterns("reducer_files"))
    (root / "benchmark/layer_metrics/step.decode_ms.json").write_text(
        json.dumps({"unit": "ms", "reducer": "no_such_reducer"}))
    with pytest.raises(ValueError, match="no reducer 'no_such_reducer'"):
        spec.load_cell("tiny-swa-closed", root)
    with pytest.raises(FileNotFoundError, match="reference/absent.py"):
        spec.load_module(root / "benchmark", "reference", "absent")


def test_what_the_harness_asks_of_the_engine_is_written_down_once():
    """Item 7: every name the harness touches on the engine is in
    ``benchmark.ENGINE_INTERFACE``, and an engine that lacks one fails the
    warm-up with that sentence, not an ``AttributeError`` mid-run."""
    import re
    import benchmark
    from benchmark.run import warm_programs
    used = set()
    for module in ("run", "sweep", "correctness", "gateway"):
        text = (REPO / "benchmark" / f"{module}.py").read_text()
        used |= set(re.findall(r"\b(?:eng|engine)\.([A-Za-z_]\w*)", text))
    assert used - {"engine"} <= set(benchmark.ENGINE_INTERFACE)
    assert {"prefill_groups", "_exec_prefill", "_decode_burst",
            "_flush_pending", "decode_burst_busy", "stats", "flight",
            "params", "model_cfg"} <= used

    class RecurrentOnly:
        """An engine with a second kind of state and no paged prefill."""
        def __getattr__(self, name):
            if name in ("_exec_prefill", "prefill_groups"):
                raise AttributeError(name)
            return None
    with pytest.raises(TypeError, match=r"lacks \['prefill_groups', "
                       r"'_exec_prefill'\].*has to keep them"):
        warm_programs(RecurrentOnly(), {"prefill_buckets": [],
                                        "prefill_groups": [],
                                        "decode_depths": []})


@dataclasses.dataclass(frozen=True)
class StubPreset:
    """A preset table's entry with the field the program's ``ModelConfig``
    lacks until a ``model_config`` PR gives it one: the experts HELD."""
    d_model: int = 4096
    n_layers: int = 48
    vocab_size: int = 196608
    n_experts: int = 320
    n_experts_held: int = 320
    experts_per_token: int = 8
    d_ff_expert: int = 1280


STUB = {"hybrid-250b": StubPreset()}
HELD = {  # one v5e chip as one of 8 that share each layer
    "preset": "hybrid-250b", "hidden_size": 4096, "num_hidden_layers": 8,
    "vocab_size": 24576, "n_routed_experts": 40, "num_experts_per_tok": 8,
    "moe_intermediate_size": 1280,
    "preset_fields": {"n_routed_experts": "n_experts",
                      "moe_intermediate_size": "d_ff_expert"},
    "reduced": {"num_hidden_layers": {"published": 48},
                "vocab_size": {"published": 196608},
                "n_routed_experts": {"published": 320,
                                     "held_in": "n_experts_held"}},
    "chips_sharing_a_layer": 8,
    "deployment": "one of 8 chips that share each layer",
    "layer_kinds": {"period": 4, "paged_attention": [0]}}


def held(**changes):
    config = json.loads(json.dumps(HELD))
    for path, value in changes.items():
        *groups, key = path.split("__")
        at = config
        for g in groups:
            at = at[g]
        if value is None:
            at.pop(key)
        else:
            at[key] = value
    return config


def test_the_three_cuts_register_a_derived_preset_on_any_preset_table():
    from benchmark.gateway import resolve_preset
    table = dict(STUB)
    assert resolve_preset("hybrid-250b-e8", held(), table) == "hybrid-250b-e8"
    derived = table["hybrid-250b-e8"]
    assert (derived.n_layers, derived.vocab_size, derived.n_experts_held) \
        == (8, 24576, 40)
    # The router keeps its published width; no width moved.
    assert derived.n_experts == 320 and derived.d_ff_expert == 1280
    assert STUB["hybrid-250b"].n_layers == 48
    assert spec.paged_attention_layers(held(), 8) == 2


@pytest.mark.parametrize("changes, says", [
    ({"num_hidden_layers": 6}, "not whole periods of 4"),
    ({"num_hidden_layers": 4, "layer_kinds__period": 2,
      "layer_kinds__leading_dense": 1}, "4 or more, after 1 leading"),
    ({"n_routed_experts": 4, "chips_sharing_a_layer": 80,
      "vocab_size": 196608, "reduced__vocab_size": None}, "8 or more"),
    ({"n_routed_experts": 64}, "not one of 8 chips' share"),
    ({"vocab_size": 12288, "n_routed_experts": 20,
      "chips_sharing_a_layer": 16}, "an eighth or more"),
    ({"vocab_size": 49152}, "not one of 8 chips' share"),
    ({"reduced__vocab_size": 24576}, "states no published count"),
    ({"reduced__vocab_size__published": 24576}, "states no published count"),
    ({"chips_sharing_a_layer": None}, "needs its deployment beside it"),
    ({"deployment": None}, "needs its deployment beside it"),
    ({"reduced__n_routed_experts__held_in": "n_local"},
     "no field 'n_local' to be told how many experts it holds"),
    ({"reduced__n_routed_experts__held_in": None}, "no field None"),
    ({"reduced__n_routed_experts__published": 160,
      "chips_sharing_a_layer": 4, "vocab_size": 49152,
      }, "preset 'hybrid-250b' routes over n_experts=320"),
    ({"reduced__moe_intermediate_size": {"published": 2560}},
     "moe_intermediate_size is a width"),
    ({"reduced__hidden_size": {"published": 8192}}, "hidden_size is a width"),
    ({"reduced__num_experts_per_tok": {"published": 16}}, "is a width"),
    ({"reduced__num_linear_heads": {"published": 64},
      "num_linear_heads": 8, "preset_fields__num_linear_heads": "n_experts"},
     "only depth, the experts held and the vocabulary"),
    ({"reduced__num_key_value_heads": {"published": 8}},
     "does not give it or 'preset_fields' does not name its field"),
    ({"preset_fields__head_dim": "head_dim", "head_dim": 128},
     "has no field 'head_dim'"),
    ({"preset_fields__hidden_size": "n_layers"},
     "moves hidden_size from 'd_model' to 'n_layers'"),
    ({"moe_intermediate_size": 640}, "moe_intermediate_size=640 in the file"),
])
def test_a_cut_outside_the_guides_floors_is_refused(changes, says):
    from benchmark.gateway import resolve_preset
    table = dict(STUB)
    with pytest.raises(ValueError, match=says):
        resolve_preset("x", held(**changes), table)
    assert set(table) == set(STUB)


def test_a_depth_cut_registers_a_derived_preset():
    from benchmark.gateway import resolve_preset
    from llmapigateway_tpu.models.config import PRESETS
    config = {"preset": "mixtral-8x7b", "hidden_size": 4096,
              "num_hidden_layers": 6, "num_local_experts": 8,
              "reduced": {"num_hidden_layers": {"published": 32}},
              "chips_sharing_a_layer": 1,
              "deployment": "6 of 32 layers: the first of six stages"}
    try:
        assert resolve_preset("mixtral-8x7b-w8-d6", config) == \
            "mixtral-8x7b-w8-d6"
        derived = PRESETS["mixtral-8x7b-w8-d6"]
        assert derived.n_layers == 6 and derived.d_model == 4096
        assert derived.n_experts == 8 and PRESETS["mixtral-8x7b"].n_layers == 32
    finally:
        PRESETS.pop("mixtral-8x7b-w8-d6", None)
    with pytest.raises(ValueError, match="hidden_size is a width"):
        resolve_preset("x", {**config, "reduced": {
            **config["reduced"], "hidden_size": {"published": 8192}}})
    with pytest.raises(ValueError, match="states no published count"):
        resolve_preset("x", {**config, "reduced": {"num_hidden_layers": 6}})
    with pytest.raises(ValueError, match="hidden_size=64"):
        resolve_preset("x", {"preset": "mixtral-8x7b", "hidden_size": 64})
    for c in BENCH["configs"]:       # the shipped files are the presets
        cfg = json.loads((REPO / c["file"]).read_text())
        assert resolve_preset(c["name"], cfg) == cfg["preset"]


def run_benchmark(*args: str, python: tuple[str, ...] = (
        "-m", "benchmark.run")) -> subprocess.CompletedProcess:
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "17",
           "JAX_ENABLE_COMPILATION_CACHE": "0"}
    env.pop("XLA_FLAGS", None)      # one CPU device, as a machine has one chip
    return subprocess.run(
        [sys.executable, *python, *args], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=300)


def test_without_a_chip_a_cell_fails_and_prints_no_result():
    done = run_benchmark("--workload", "mistral-7b-chat-sat", "--seed",
                         str(2**31 + 5), "--seconds", "1", "--trace", "0")
    assert done.returncode != 0
    assert "needs 1 TPU chip" in done.stderr
    assert done.stdout.strip() == ""


@pytest.mark.parametrize("workload", ["tiny-swa-closed", "tiny-swa-open",
                                      "tiny-hybrid-closed"])
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
    kernels = [c["kernel"] for c in phases["kernel_parity"]["cases"]]
    if workload == "tiny-hybrid-closed":
        # The architecture added by files: its own reference judged the
        # served tokens, at the bound and the sample its file states; its
        # kernel check stands on the kernel_parity line; its cuts and its
        # layer-kind count reached the engine and the attention shape; its
        # reducer read the configuration's file.
        assert kernels == ["paged_decode", "paged_prefill", "moe_mlp_dense"]
        assert phases["reference"]["tolerance"] == 0.2
        assert phases["reference"]["tolerance_p50"] == 0.05
        assert phases["engine"]["preset"] == "tiny-hybrid"
        assert phases["engine"]["layers"] == 4
        assert phases["engine"]["paged_layers"] == 2
        assert phases["engine"]["vocabulary"] == 256
        assert phases["programs"]["prefill_buckets"][0] <= 32
        assert last["metrics"]["cpu_rehearsal.model.expert_bytes_per_token"
                               ] == {"value": 3 * 64 * 128 * 2 * 2 * 4.0,
                                     "unit": "bytes"}
    else:
        assert kernels == ["paged_decode", "paged_prefill"]
        assert phases["reference"]["tolerance"] == 0.25
        assert phases["engine"]["paged_layers"] == phases["engine"]["layers"]
    assert phases["window"]["compiles_in_window"] == 0
    assert phases["window"]["samples"]["out_tok_s"] > 0
    assert set(phases["setup"]) >= {"engine_build_s", "programs_s",
                                    "correctness_s", "lead_in_s", "setup_s"}
    records = REPO / "bench_out" / workload / "requests.jsonl"
    assert len(records.read_text().splitlines()) >= last["attempted"]


BROKEN_ENGINE = """
import asyncio, json, sys
from pathlib import Path
from benchmark import run, spec
from llmapigateway_tpu.providers.local import make_local_provider


def broken(name, details):
    provider = make_local_provider(name, details)
    engine = provider.engine
    emit = engine._emit_token

    def emit_altered(req):
        if not req.cancelled and req.generated:
            req.generated[-1] = (req.generated[-1] + 1) % \\
                engine.model_cfg.vocab_size
        emit(req)
    engine._emit_token = emit_altered
    return provider


cell = spec.load_cell("tiny-swa-closed", Path(sys.argv[1]))
print(json.dumps(asyncio.run(run.run_cell(
    cell, 2**31 + 11, 1.0, False, Path(sys.argv[2]), rehearsal=True,
    local_factory=broken))))
"""


def test_a_token_altered_where_it_is_produced_reads_not_correct(
        extended, tmp_path):
    """The rest of a run with the timed path broken underneath: a driver
    skips only the harness's look for a chip (``main``) and hands
    ``run_cell`` an engine whose every emitted token is the one AFTER the
    one it computed. Requests still finish and every response is well
    formed; the reference sees tokens far below its best, and ``correct``
    comes out false."""
    done = run_benchmark(str(extended), str(tmp_path),
                         python=("-c", BROKEN_ENGINE))
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert all(c["ok"] for c in phases["kernel_parity"]["cases"])
    ref = phases["reference"]
    assert not ref["ok"] and ref["problems"] == []
    assert ref["gap_max"] > 4 * ref["tolerance"] and ref["gap_p50"] > 0.25
    result = lines[-1]
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] >= 6
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
