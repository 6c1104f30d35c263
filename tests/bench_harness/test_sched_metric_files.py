"""The ten per-layer metrics of ISSUE 26 are data: a file each under
``benchmark/layer_metrics/`` over the existing ``counter_delta`` reducer,
and an entry each in ``BENCHMARK.json``. One case per file: the reducer
gives the counter's delta when both ``stats()`` snapshots hold the key,
and ``None`` when either lacks it — the parent commit's program, whose
result line then leaves the metric out."""
from __future__ import annotations

import json

import pytest

from benchmark import roofline
from benchmark.reducers import REDUCERS, Measured
from benchmark.spec import REPO_ROOT, load_cell

CHAT, SAT, LONG = ("mistral-7b-chat", "mistral-7b-chat-sat",
                   "mistral-7b-longdoc")

# metric -> (engine counter, end-to-end metric it moves, cells or None)
TABLE = {
    "sched.parked_ms": ("sched_parked_ms_total", "ttft_p50_ms",
                        [CHAT, LONG]),
    "sched.admit_ms": ("sched_admit_ms_total", "ttft_p50_ms", [CHAT, LONG]),
    "sched.prefill_wall_ms": ("sched_prefill_wait_ms_total", "ttft_p50_ms",
                              [CHAT, LONG]),
    "sched.decode_wall_ms": ("sched_decode_wait_ms_total", "tpot_p50_ms",
                             [CHAT, SAT]),
    "sched.emit_ms": ("sched_emit_ms_total", "tpot_p50_ms", [CHAT, SAT]),
    "sched.fetch_ms": ("sched_fetch_ms_total", "tpot_p50_ms", [CHAT, SAT]),
    "sched.hop_ms": ("sched_hop_ms_total", "out_tok_s", [SAT, LONG]),
    "sched.dispatch_ms": ("sched_dispatch_ms_total", "out_tok_s",
                          [SAT, LONG]),
    "sched.other_ms": ("sched_other_ms_total", "out_tok_s", [SAT, LONG]),
    "engine.trace_ms_in_window": ("xla_trace_ms_total", "setup_s", None),
}


def _measured(counters_open: dict, counters_close: dict) -> Measured:
    return Measured(
        logs=[], t_open=0.0, t_close=40.0, trace=None, t_trace=(0.0, 0.0),
        flight=[], counters_open=counters_open,
        counters_close=counters_close, slots=8,
        shape=roofline.AttnShape(32, 32, 8, 128, 4096, 1, 4), peaks={},
        peak_hbm_bytes=None)


@pytest.mark.parametrize("name", sorted(TABLE))
def test_metric_file_reads_its_counter_and_nothing_from_the_parent(name):
    counter, moves, cells = TABLE[name]
    raw = json.loads((REPO_ROOT / "benchmark" / "layer_metrics"
                      / f"{name}.json").read_text())
    assert raw["unit"] == "ms" and raw["reducer"] == "counter_delta"
    assert raw["args"] == {"counter": counter}
    assert raw["what"]
    reduce = REDUCERS[raw["reducer"]]
    # Both snapshots hold the key: the delta, in milliseconds.
    both = _measured({counter: 1250.5, "xla_compile_total": 25},
                     {counter: 31250.75, "xla_compile_total": 25})
    assert reduce(both, raw["args"]) == pytest.approx(30000.25)
    # The parent's program writes no such counter: nothing to read, on
    # either side alone or on both, and no exception.
    for o, c in (({}, {}), ({counter: 1.0}, {}), ({}, {counter: 1.0})):
        assert reduce(_measured({"xla_compile_total": 25, **o},
                                {"xla_compile_total": 25, **c}),
                      raw["args"]) is None
    # Its BENCHMARK.json entry, as the issue's table has it.
    bench = json.loads((REPO_ROOT / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert entry["unit"] == "ms" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == moves
    assert entry.get("workloads") == cells
    assert entry["layer"] == ("compiled programs" if cells is None
                              else "scheduler")


def test_the_new_entries_stand_at_the_end_and_each_cell_loads_its_own():
    """The old list stands whole and first, PR 26's ten stand together
    directly after it, and what is newer follows (since PR 32: entries
    are only ever appended, so a later PR's stand behind these)."""
    bench = json.loads((REPO_ROOT / "BENCHMARK.json").read_text())
    names = [m["name"] for m in bench["per_layer"]]
    assert names[10] == "engine.compiles_in_window"   # the old list, whole
    assert sorted(names[11:21]) == sorted(TABLE)
    assert not set(names[:11] + names[21:]) & set(TABLE)
    assert "client.ttft_mid_ms" in names[21:]
    for cell in (CHAT, SAT, LONG):
        loaded = {lm.name for lm in load_cell(cell).per_layer}
        want = {n for n, (_, _, cells) in TABLE.items()
                if cells is None or cell in cells}
        assert want <= loaded
        assert not (set(TABLE) - want) & loaded
