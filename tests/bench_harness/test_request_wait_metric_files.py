"""The nine per-layer metrics of ISSUE 41 are data over what the program
counts: the requests' wait ledger (``llmapigateway_tpu/obs/phases.py``:
seven ``req_*_ms_total`` totals, two counts) and the first-token fetch's
sub-counter. Eight are files over ONE new reducer, ``counter_ratio`` (a
file of its own under ``benchmark/reducer_files/``), one over
``counter_delta``; each has an entry APPENDED to ``BENCHMARK.json`` behind
PR 40's twelve. One case a metric: its counters are keys of ``stats()`` on
the tiny preset on a CPU (a renamed counter fails here, not as a silent
``null`` on the chip), its reducer is registered, its entry stands at the
end in the issue's order. One case for the reducer's ``None``s — the
parent's program, which counts none of this. One that runs the harness and
holds all nine to a number on the result line."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import pytest

from benchmark.reducers import REDUCERS

from .test_sched_metric_files import CHAT, LONG, SAT, _measured
from .test_spec_discovery import BENCH, REPO, run_benchmark
from .test_spec_discovery import extended  # noqa: F401  (this module's copy)

FIVE = [SAT, LONG, "solar-open2-chat-sat", "smallthinker-21b-mixed",
        "mistral-small4-longctx"]
FIRST, TOKENS = ["req_first_tokens_total"], ["req_decode_tokens_total"]
DECODE = ["req_decode_in_decode_ms_total",
          "req_decode_behind_prefill_ms_total", "req_decode_loop_ms_total"]

# metric -> (numerator counters, denominator counters, unit, the end-to-end
# metric it moves, cells), in the order the entries were appended.
TABLE = {
    "sched.ttft_own_prefill_ms": (
        ["req_ttft_own_prefill_ms_total"], FIRST, "ms", "ttft_p50_ms",
        [LONG, CHAT]),
    "sched.ttft_behind_prefill_ms": (
        ["req_ttft_behind_prefill_ms_total"], FIRST, "ms", "ttft_p50_ms",
        [LONG, CHAT]),
    "sched.ttft_behind_decode_ms": (
        ["req_ttft_behind_decode_ms_total"], FIRST, "ms", "ttft_p50_ms",
        [LONG, CHAT]),
    "sched.ttft_loop_ms": (
        ["req_ttft_loop_ms_total"], FIRST, "ms", "ttft_p50_ms",
        [LONG, CHAT]),
    "sched.tpot_in_decode_ms": (
        ["req_decode_in_decode_ms_total"], TOKENS, "ms", "tpot_p50_ms",
        [SAT, CHAT]),
    "sched.tpot_behind_prefill_ms": (
        ["req_decode_behind_prefill_ms_total"], TOKENS, "ms", "tpot_p50_ms",
        [SAT, CHAT]),
    "sched.tpot_loop_ms": (
        ["req_decode_loop_ms_total"], TOKENS, "ms", "tpot_p50_ms",
        [SAT, CHAT]),
    "sched.decode_behind_prefill_pct": (
        ["req_decode_behind_prefill_ms_total"], DECODE, "%", "out_tok_s",
        FIVE),
    "sched.fetch_first_ms": (
        ["sched_fetch_first_ms_total"], None, "ms", "out_tok_s",
        [SAT, LONG, "solar-open2-chat-sat"]),
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


@pytest.mark.parametrize("metric", list(TABLE))
def test_a_request_wait_metric_is_a_file_over_the_ledgers_counters(
        metric, stats_keys):
    num, den, unit, moves, cells = TABLE[metric]
    raw = json.loads(
        (REPO / f"benchmark/layer_metrics/{metric}.json").read_text())
    assert raw["unit"] == unit and raw["what"]
    assert raw["reducer"] in REDUCERS
    if den is None:
        assert raw["reducer"] == "counter_delta"
        assert raw["args"] == {"counter": num[0]}
    else:
        assert raw["reducer"] == "counter_ratio"
        assert REDUCERS["counter_ratio"].__module__ \
            == "benchmark.reducer_files.counter_ratio"
        scale = {"scale": 100} if unit == "%" else {}
        assert raw["args"] == {"num": num, "den": den, **scale}
    assert set(num) | set(den or ()) <= stats_keys
    # Its BENCHMARK.json entry, as the issue's table has it: appended, in
    # the issue's order, directly behind PR 40's twelve; a later PR's
    # entries stand behind these, a later cell behind these cells.
    names = [m["name"] for m in BENCH["per_layer"]]
    first = names.index("step.decode_kda_update_share") + 1
    assert names[first:first + len(TABLE)] == list(TABLE)
    entry = BENCH["per_layer"][first + list(TABLE).index(metric)]
    assert entry == {"name": metric, "unit": unit, "better": "lower",
                     "source": "program_counter", "layer": "scheduler",
                     "moves": moves, "workloads": entry["workloads"]}
    assert entry["workloads"][:len(cells)] == cells
    moved = next(m for m in BENCH["end_to_end"] if m["name"] == moves)
    assert set(entry["workloads"]) <= set(moved["workloads"])


def test_counter_ratio_reads_growth_over_growth_and_nothing_from_the_parent():
    ratio = REDUCERS["counter_ratio"]
    a = {"num": ["n1", "n2"], "den": ["d"]}
    both = _measured({"n1": 10.0, "n2": 1.5, "d": 4, "x": 1},
                     {"n1": 70.0, "n2": 4.0, "d": 29, "x": 2})
    assert ratio(both, a) == pytest.approx((60.0 + 2.5) / 25)
    assert ratio(both, {**a, "scale": 100}) == pytest.approx(250.0)
    assert ratio(both, {"num": ["n2"], "den": ["n1", "n2"], "scale": 100}
                 ) == pytest.approx(100 * 2.5 / 62.5)
    # The parent's program counts none of it: a counter missing from either
    # snapshot, numerator or denominator, and no exception.
    for gone in ("n1", "n2", "d"):
        for side in (0, 1):
            snaps = [{"n1": 1.0, "n2": 1.0, "d": 1},
                     {"n1": 2.0, "n2": 2.0, "d": 2}]
            del snaps[side][gone]
            assert ratio(_measured(*snaps), a) is None, (gone, side)
    assert ratio(_measured({}, {}), a) is None
    # A window in which nothing it is read against happened: no mean.
    still = _measured({"n1": 1.0, "n2": 1.0, "d": 7},
                      {"n1": 5.0, "n2": 1.0, "d": 7})
    assert ratio(still, a) is None


CELL = "tiny-waits-closed"


@pytest.fixture(scope="module")
def root(extended) -> Path:  # noqa: F811
    """``test_spec_discovery``'s rehearsal root (this module's own copy of
    it) with a cell of this module's own name: a run writes under
    ``bench_out/<cell>/``, and another file's run of the same cell may be
    going on beside this one. The root lists its tiny cells under every
    metric that lists cells; this one stands wherever ``tiny-swa-closed``
    does."""
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


def test_all_nine_read_a_number_in_a_rehearsed_run(root):
    """The closed loop, 6 callers of 40-120 token prompts on 4 slots in
    chunks of 32, has requests prefilling beside requests decoding
    throughout."""
    done = run_benchmark(
        "--workload", CELL, "--seed", str(2**31 + 41),
        "--seconds", "2", "--trace", "1", "--root", str(root),
        "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-3000:]
    last = json.loads(done.stdout.splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    value = {}
    for metric, (_, _, unit, _, _) in TABLE.items():
        got = last["metrics"][f"cpu_rehearsal.{metric}"]
        assert got["unit"] == unit
        assert isinstance(got["value"], float) and got["value"] >= 0, metric
        value[metric] = got["value"]
    # A request's own chunks and its own tokens took time; a percentage of
    # a whole is one.
    assert value["sched.ttft_own_prefill_ms"] > 0
    assert value["sched.tpot_in_decode_ms"] > 0
    assert 0 <= value["sched.decode_behind_prefill_pct"] <= 100
    tpot = sum(value[k] for k in ("sched.tpot_in_decode_ms",
                                  "sched.tpot_behind_prefill_ms",
                                  "sched.tpot_loop_ms"))
    assert value["sched.decode_behind_prefill_pct"] == pytest.approx(
        100 * value["sched.tpot_behind_prefill_ms"] / tpot, rel=1e-6)
    # The first-token read is a part of every read the worker blocked on.
    assert value["sched.fetch_first_ms"] <= last["metrics"][
        "cpu_rehearsal.sched.fetch_ms"]["value"]
