"""chip_smoke.py rehearsed without the chip.

The script's contract — fail without a TPU, build nothing first, end in
one exact JSON line — and its two serving functions, run here at tiny
size on CPU devices with interpret-mode kernels. That finds wrong paths,
arguments and control flow before any chip time is spent; what only the
chip can show (``check_device`` / ``check_sharded_on_device``) is left to
the chip. The steering — tiny presets, CPU devices — happens here, through
the functions' own parameters, not through options of the script.
"""
from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _factory(devices):
    """Local-provider factory building the engine on the given CPU devices
    (the app's default factory would take every visible device)."""
    from llmapigateway_tpu.engine.engine import InferenceEngine
    from llmapigateway_tpu.providers.local import LocalProvider

    def build(name, details):
        return LocalProvider(name, InferenceEngine(details.engine,
                                                   devices=devices))
    return build


def test_script_fails_fast_without_a_tpu_and_builds_nothing(tmp_path):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path, env=env)
    assert r.returncode != 0
    lines = r.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    # It stopped at the device check: no phase line, no engine log.
    assert len(lines) == 1, lines
    assert "engine build" not in r.stderr and "params ready" not in r.stderr


def test_last_line_has_exactly_the_three_device_keys(smoke):
    line = smoke.last_line(True, {"platform": "tpu", "kind": "TPU v5 lite",
                                  "count": 1, "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert list(json.loads(line)) == ["ok", "device"]
    assert list(json.loads(line)["device"]) == ["platform", "kind", "count"]
    assert json.loads(smoke.last_line(False, smoke.device_facts()))[
        "device"]["platform"] == "cpu"


def test_kernel_parity_phase_runs_interpreted_at_tiny_widths(smoke, capsys):
    recs = smoke.kernel_parity(interpret=True, H=2, KV=1, Dh=32, page=8,
                               pages_per_slot=4, window=12, T=8)
    assert [(r["kernel"], r["kv"]) for r in recs] == [
        ("paged_decode", "bf16"), ("paged_prefill", "bf16"),
        ("paged_decode", "int8"), ("paged_prefill", "int8")]
    assert all(r["max_abs_err"] <= smoke.KERNEL_TOL for r in recs)
    assert len(capsys.readouterr().out.strip().splitlines()) == len(recs)


async def test_serve_and_query_on_cpu_at_tiny_size(smoke, capsys):
    """The default phase's serving function on a tiny sliding-window model:
    same requests, same assertions, lengths scaled to its page (8), chunk
    (32) and window (16) so the page ring rotates here too."""
    engine = {"preset": "tiny-mistral-test", "quant": "int8",
              "kv_quant": "int8", "mesh": {}, "max_batch_size": 4,
              "max_seq_len": 256, "kv_page_size": 8, "prefill_chunk": 32,
              "attention": "pallas",
              # Fewer programs to compile than the defaults' 4 + 6.
              "prefill_batch": 2, "decode_burst": 4, "decode_burst_busy": 4}
    work = smoke.Workload(single=32, burst=(50, 64, 90, 120, 150, 160, 190,
                                            230),
                          max_tokens=9, burst_max_tokens=13)
    report = await smoke.serve_and_query(
        engine, work, local_factory=_factory([jax.devices("cpu")[0]]))
    assert len(report["records"]) == 12
    assert report["engine"]["ring_pages_per_slot"] > 0
    assert not report["engine"]["prefix_cache"]
    assert report["stats"]["attention"] == "pallas"
    assert {r["id"] for r in report["records"] if r["stream"]} == {
        "sse", "burst7"}
    # Off the chip the device checks must refuse: the kernels ran
    # interpreted, so no compiled program holds one.
    with pytest.raises(AssertionError):
        smoke.check_device(report)
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [o["phase"] for o in out].count("request") == 12


async def test_compare_tp_on_four_virtual_devices(smoke):
    """The four-chip phase on four virtual CPU devices: TP=2 x DP=2 is the
    widest mesh tiny-test's two KV heads divide, so the kernels go under
    shard_map here as they do at TP=4 on the chip."""
    cpus = jax.devices("cpu")
    engine = {"preset": "tiny-test", "quant": "int8", "kv_quant": "int8",
              "mesh": {}, "max_batch_size": 4, "max_seq_len": 256,
              "kv_page_size": 16, "prefill_chunk": 32, "attention": "pallas",
              "dtype": "float32", "decode_burst": 4, "decode_burst_busy": 4}
    report = await smoke.compare_tp(
        engine, {"model": 2, "data": 2}, lens=(64,), n_tokens=4,
        local_factory=_factory(cpus[:4]), one_chip_devices=[cpus[0]])
    assert report["tp"]["devices"] == 4
    assert report["tp"]["mesh"] == {"data": 2, "model": 2}
    # Half the weight bytes on each device at TP=2 (replicated over data).
    assert len(report["weight_share"]) == 4
    assert all(0.45 <= s <= 0.60 for s in report["weight_share"].values())
    for p in report["prompts"]:
        assert p["max_logit_diff"] <= smoke.LOGIT_TOL
        assert len(p["one_tokens"]) == len(p["tp_tokens"]) == 5
        assert p["first_divergence"] is None
    with pytest.raises(AssertionError):
        smoke.check_sharded_on_device(report, 4)
