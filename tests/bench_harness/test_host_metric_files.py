"""The thirteen per-layer metrics of ISSUE 56 are data over what the
program counts: the parts of the scheduler ledger's ``worker_other`` and
``hop`` (``llmapigateway_tpu/obs/phases.py``), the threads' own CPU beside
their wall, the process's involuntary context switches and the prefill
calls that ended without a read. Each is a file over ``counter_delta`` and
an entry of ``BENCHMARK.json`` with the four cells ``sched.fetch_first_ms``
lists. One case a metric, pinned by NAME (wherever a later PR's entries
stand): its counter is a key of ``stats()`` on the tiny preset on a CPU (a
renamed counter fails here, not as a silent ``null`` on the chip), the
parent's program gives ``None``. One that runs the harness on
``mistral-7b-chat-sat``'s tiny preset and holds all thirteen to a number on
the result line, the parts inside their parents."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import pytest

from benchmark.reducers import REDUCERS

from .test_sched_metric_files import LONG, SAT, _measured
from .test_spec_discovery import BENCH, REPO, run_benchmark
from .test_spec_discovery import extended  # noqa: F401  (this module's copy)

CELLS = [SAT, LONG, "solar-open2-chat-sat", "command-a-plus-rag"]
PARTS = ["state", "tables", "rng", "args", "mirrors"]

# metric -> (engine counter, unit)
TABLE = {
    "sched.worker_other_ms": ("sched_worker_other_ms_total", "ms"),
    **{f"sched.worker_{p}_ms": (f"sched_worker_{p}_ms_total", "ms")
       for p in PARTS},
    "sched.hop_out_ms": ("sched_hop_out_ms_total", "ms"),
    "sched.hop_back_ms": ("sched_hop_back_ms_total", "ms"),
    "sched.loop_cpu_ms": ("sched_loop_cpu_ms_total", "ms"),
    "sched.dispatch_cpu_ms": ("sched_dispatch_cpu_ms_total", "ms"),
    "sched.worker_other_cpu_ms": ("sched_worker_other_cpu_ms_total", "ms"),
    "host.invol_ctx_switches": ("proc_invol_ctx_switches_total", "count"),
    "sched.prefill_calls_unread": ("prefill_calls_unread_total", "count"),
}


@pytest.fixture(scope="module")
def stats_keys(stop_engine) -> set[str]:
    """``stats()``'s keys of an engine that serves nothing: the counters
    are there from the start."""
    from llmapigateway_tpu.config.schemas import LocalEngineConfig
    from llmapigateway_tpu.engine.engine import InferenceEngine
    engine = InferenceEngine(LocalEngineConfig(
        preset="tiny-mistral-test", max_batch_size=2, max_seq_len=128,
        kv_layout="paged", kv_page_size=8, prefill_chunk=32,
        prefix_cache=False), devices=[jax.devices("cpu")[0]])
    keys = set(engine.stats())
    stop_engine(engine)
    return keys


def test_there_are_thirteen():
    assert len(TABLE) == 13


@pytest.mark.parametrize("metric", list(TABLE))
def test_a_host_metric_is_a_file_over_a_counter_the_program_keeps(
        metric, stats_keys):
    counter, unit = TABLE[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["unit"] == unit and raw["what"]
    assert raw["reducer"] == "counter_delta"
    assert raw["args"] == {"counter": counter}
    assert counter in stats_keys
    reduce = REDUCERS["counter_delta"]
    both = _measured({counter: 12.5, "xla_compile_total": 25},
                     {counter: 112.75, "xla_compile_total": 25})
    assert reduce(both, raw["args"]) == pytest.approx(100.25)
    # The parent's program keeps no such counter: nothing to read, on
    # either side alone or on both, and no exception.
    for o, c in (({}, {}), ({counter: 1.0}, {}), ({}, {counter: 1.0})):
        assert reduce(_measured(o, c), raw["args"]) is None
    # Its BENCHMARK.json entry, by name: the four cells first, each of
    # which reports the end-to-end metric it moves.
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry == {"name": metric, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": "out_tok_s", "workloads": entry["workloads"]}
    assert entry["workloads"][:len(CELLS)] == CELLS
    moved = next(m for m in BENCH["end_to_end"]
                 if m["name"] == "out_tok_s")
    assert set(entry["workloads"]) <= set(moved["workloads"])
    first = next(m for m in BENCH["per_layer"]
                 if m["name"] == "sched.fetch_first_ms")
    assert entry["workloads"][:len(CELLS)] == first["workloads"][:len(CELLS)]


CELL = "tiny-host-closed"


@pytest.fixture(scope="module")
def root(extended) -> Path:  # noqa: F811
    """``test_spec_discovery``'s rehearsal root (this module's own copy of
    it) with a cell of this module's own name (a run writes under
    ``bench_out/<cell>/``): ``tiny-swa-closed``'s twin, the tiny preset of
    ``mistral-7b-chat-sat``'s model under a closed loop."""
    path = extended / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    twin = next(w for w in bench["workloads"]
                if w["name"] == "tiny-swa-closed")
    bench["workloads"].append({**twin, "name": CELL})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tiny-swa-closed" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    path.write_text(json.dumps(bench))
    return extended


def test_all_thirteen_read_a_number_in_a_rehearsed_run(root):
    """The closed loop, 6 callers of 40-120 token prompts on 4 slots in
    chunks of 32: prompts of several chunks (calls that end without a
    read) beside requests decoding throughout."""
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 56),
        "--seconds", "2", "--trace", "1", "--root", str(root),
        "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    value = {}
    for metric, (_, unit) in TABLE.items():
        got = last["metrics"][f"cpu_rehearsal.{metric}"]
        assert got["unit"] == unit
        assert isinstance(got["value"], float) and got["value"] >= 0, metric
        value[metric] = got["value"]
    # The parts lie inside their parents (to the counters' rounding, three
    # decimals each), and the parents are the accepted metrics' counters.
    parts = sum(value[f"sched.worker_{p}_ms"] for p in PARTS)
    assert 0 < parts <= value["sched.worker_other_ms"] + 0.01
    hop = last["metrics"]["cpu_rehearsal.sched.hop_ms"]["value"]
    assert value["sched.hop_out_ms"] > 0 and value["sched.hop_back_ms"] > 0
    assert value["sched.hop_out_ms"] + value["sched.hop_back_ms"] \
        <= hop + 0.01
    # Threads computed, and no longer than they held the wall (a clock's
    # grain of room: the two clocks are read a moment apart).
    assert 0 < value["sched.dispatch_cpu_ms"] <= 1.05 * last["metrics"][
        "cpu_rehearsal.sched.dispatch_ms"]["value"] + 1.0
    assert 0 < value["sched.worker_other_cpu_ms"] \
        <= 1.05 * value["sched.worker_other_ms"] + 1.0
    assert 0 < value["sched.loop_cpu_ms"] <= 1.05 * 2000 + 50
    assert value["sched.prefill_calls_unread"] >= 1
