"""The cell ``solar-open2-chat-sat`` rehearsed on a CPU at a tiny derived
configuration: the same reference module (``benchmark/reference/
solar_open2.py``, found from a data directory that does not hold it), its
``kernel_checks``, the three cuts through ``resolve_preset``, the layer
kinds, the scopes — and the two per-layer metric files over the scopes this
architecture adds, as data a ``benchmark`` PR can move into
``benchmark/layer_metrics/`` (PERF.md, Open questions, says why this PR
could not)."""
import json
import shutil
from pathlib import Path

import pytest

from benchmark import spec

from .test_spec_discovery import BENCH, REPO, TINY_ENGINE, run_benchmark

SHIPPED = json.loads(
    (REPO / "benchmark/configs/solar-open2-250b-ep8.json").read_text())

# The program's ``tiny-hybrid-test`` (two periods of 4, 16 experts, top-4,
# one shared) cut as the shipped file cuts the published model: one period
# of two, 8 of 16 experts and 256 of 512 vocabulary rows as one of 2 chips
# that share each layer.
TINY = {
    "source": "none: CPU rehearsal of solar-open2-250b-ep8",
    "preset": "tiny-hybrid-test", "reference": SHIPPED["reference"],
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "n_routed_experts": 8, "n_shared_experts": 1,
    "num_experts_per_tok": 4, "moe_intermediate_size": 32,
    "rms_norm_eps": 1e-05, "use_rope": False, "use_gqa_gate": True,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16,
                           "num_heads": 4, "num_kv_heads": None},
    "preset_fields": SHIPPED["preset_fields"],
    "reduced": {"num_hidden_layers": {"published": 8},
                "vocab_size": {"published": 512},
                "n_routed_experts": {"published": 16,
                                     "held_in": "n_experts_held"}},
    "chips_sharing_a_layer": 2,
    "deployment": "one of 2 chips that share each layer: 8 of 16 experts, "
                  "half the vocabulary; one period of two",
    "layer_kinds": SHIPPED["layer_kinds"], "scopes": SHIPPED["scopes"],
    "engine": {**TINY_ENGINE, "prefix_cache": False}}

SHARES = {"step.decode_kda_share": "decode.kda",
          "step.decode_experts_share": "moe.experts"}
CELL = "tiny-solar-closed"


@pytest.fixture(scope="module")
def root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("solar")
    for sub in ("traffic", "layer_metrics"):
        shutil.copytree(REPO / "benchmark" / sub, root / "benchmark" / sub)
    (root / "benchmark/configs").mkdir()
    (root / "benchmark/configs/tiny-solar.json").write_text(json.dumps(TINY))
    shape = json.loads((REPO / "benchmark/traffic/chat-sat-b32.json"
                        ).read_text())
    (root / "benchmark/traffic/tiny-closed.json").write_text(json.dumps({
        **shape, "clients": 6, "stagger_s": 0.01,
        "prompt_tokens": {"kind": "uniform", "min": 40, "max": 120,
                          "snap": 8},
        "max_tokens": {"kind": "uniform", "min": 8, "max": 24, "snap": 4}}))
    for name, scope in SHARES.items():
        (root / f"benchmark/layer_metrics/{name}.json").write_text(json.dumps(
            {"unit": "%", "reducer": "scope_share",
             "args": {"programs": ["decode_scan", "decode_step"],
                      "scope": scope}}))
    bench = json.loads(json.dumps(BENCH))
    shipped = next(w for w in bench["workloads"]
                   if w["name"] == "solar-open2-chat-sat")
    bench["configs"] = [{"name": "tiny-solar", "source": "none",
                         "file": "benchmark/configs/tiny-solar.json",
                         "reduced": sorted(TINY["reduced"]),
                         "why": "rehearsal"}]
    bench["workloads"] = [{**shipped, "name": CELL, "config": "tiny-solar",
                           "traffic": "tiny-closed"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:    # the lists the shipped cell was appended to
            m["workloads"] = [CELL] if (shipped["name"] in m["workloads"]
                                        or m["name"] == "tpot_p50_ms") else []
    bench["per_layer"] += [
        {"name": name, "unit": "%", "better": "lower",
         "source": "device_trace", "layer": "model block",
         "moves": "tpot_p50_ms", "workloads": [CELL]} for name in SHARES]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_the_cells_files_are_found_and_its_share_metrics_are_data(root):
    from benchmark import reference
    from benchmark.reference import solar_open2
    cell = spec.load_cell(CELL, root)
    assert reference.load(cell.config, cell.data) is solar_open2
    assert callable(solar_open2.kernel_checks)
    assert spec.paged_attention_layers(cell.config, 4) == 1
    assert spec.paged_attention_layers(SHIPPED, 8) == 2
    assert spec.scopes(cell.config)[:6] == (
        "kda.prefill_chunk", "kda.decode_update", "moe.experts",
        "moe.shared", "prefill.kda", "decode.kda")
    names = {lm.name: lm for lm in cell.per_layer}
    assert {"device.idle_share", "sched.batch_occupancy",
            "device.peak_hbm_bytes", *SHARES} <= set(names)
    assert all(names[n].reducer == "scope_share" for n in SHARES)
    # `tpot_p50_ms` spread 0.89% and 0.33% in two sets of six seeds on the
    # chip, one over half its bound (PERF.md section 4), so the shipped cell
    # reports `out_tok_s` alone beside `setup_s`; here the share metrics
    # still move it.
    assert {m["name"] for m in cell.end_to_end} == {
        "out_tok_s", "tpot_p50_ms", "setup_s"}
    assert {m["name"] for m in spec.load_cell(
        "solar-open2-chat-sat").end_to_end} == {"out_tok_s", "setup_s"}
    # The shipped cell, from the repo's own files: the lengths are
    # chat-sat's, the callers twice the slots of its engine block.
    shipped = spec.load_cell("solar-open2-chat-sat")
    sat = spec.load_cell("mistral-7b-chat-sat").traffic
    assert (shipped.traffic.prompt_tokens, shipped.traffic.max_tokens) == (
        sat.prompt_tokens, sat.max_tokens)
    assert shipped.traffic.clients == 2 * shipped.config["engine"][
        "max_batch_size"] == 64
    assert "correctness" not in shipped.config      # default bounds, whole chunks
    assert shipped.config["engine"]["prefix_cache"] is False


@pytest.mark.parametrize("block, ok", [("float32", True),
                                       ("bfloat16", False)])
def test_the_linear_forms_are_checked_through_the_engines_state_block(
        root, block, ok):
    """The program keeps the state in the engine's block between calls, so
    ``kernel_checks`` carries every state through that block's dtype: the
    float32 the file states changes nothing, a bfloat16 block (the
    nearest precision below) fails both cases by its limit."""
    import types

    import jax.numpy as jnp

    from benchmark.reference import solar_open2
    from llmapigateway_tpu.models.config import get_preset
    cell = spec.load_cell(CELL, root)
    engine = types.SimpleNamespace(
        model_cfg=get_preset("tiny-hybrid-test"),
        cache=types.SimpleNamespace(state=(jnp.zeros((1,), block),)))
    cases = solar_open2.kernel_checks(engine, cell.config, True)
    assert [c["kernel"] for c in cases] == ["kda_prefill_chunked",
                                            "kda_decode_update"]
    assert [c["ok"] for c in cases] == [ok, ok]
    if not ok:      # far past the limit, not at its edge
        assert all(c["max_abs_err"] > 3 * solar_open2.LINEAR_FORM_TOL
                   for c in cases)


def test_cpu_rehearsal_of_the_cell(root):
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "2",
        "--trace", "1", "--root", str(root), "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    lines = [json.loads(ln) for ln in done.stdout.splitlines()]
    last = lines[-1]
    phases = {ln["phase"]: ln for ln in lines[:-1]}
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 6
    # The cuts reached the engine; one layer in four calls the paged kernels.
    eng = phases["engine"]
    assert (eng["preset"], eng["layers"], eng["paged_layers"],
            eng["vocabulary"]) == ("tiny-solar", 4, 1, 256)
    # Two prompts' chunks in one call, then one: the state crossed a chunk
    # boundary inside a prefill group before the reference judged it.
    assert phases["programs"]["prefill_groups"] == [1, 2]
    ref = phases["reference"]
    assert ref["ok"] and ref["positions"] == 3 * 64
    assert (ref["tolerance"], ref["tolerance_p50"]) == (0.25, 0.05)
    cases = {c["kernel"]: c for c in phases["kernel_parity"]["cases"]}
    assert list(cases) == ["paged_decode", "paged_prefill",
                           "kda_prefill_chunked", "kda_decode_update"]
    assert all(c["ok"] for c in cases.values())
    assert cases["paged_decode"]["window"] == 0
    win = phases["window"]
    assert win["compiles_in_window"] == 0
    assert win["jax_events"] == {"count": 0, "seconds": 0.0, "longest": []}
    # No device plane on a CPU: the share metrics find nothing and are left
    # out; the counters of the scheduler are there.
    assert not any(name in k for k in last["metrics"] for name in SHARES)
    assert "cpu_rehearsal.sched.batch_occupancy" in last["metrics"]
