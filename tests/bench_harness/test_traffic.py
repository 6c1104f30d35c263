"""Repair A.1: a traffic mix is a fixed trace. ``--seed`` changes what is
in the prompts, never how long they are or when they arrive."""
import json
import statistics
from pathlib import Path

import numpy as np
import pytest

from benchmark.traffic import (Traffic, make_trace, prompt_ids, quantile,
                               radical_inverse, support)

TRAFFIC = Path(__file__).resolve().parents[2] / "benchmark" / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))


@pytest.mark.parametrize("mix", MIXES)
def test_trace_is_the_same_in_every_run_and_seed_only_changes_content(mix):
    t = Traffic.load(TRAFFIC / f"{mix}.json")
    a, b = make_trace(t, 300), make_trace(t, 300)
    assert a == b
    # A longer trace starts with the same entries.
    assert make_trace(t, 700)[:300] == a
    ids = {seed: [prompt_ids(e, seed, 32000, e.prompt_tokens - 25, t.sessions)
                  for e in a[:8]] for seed in (3, 2**31 + 11)}
    for x, y in zip(ids[3], ids[2**31 + 11]):
        assert len(x) == len(y)
        assert not np.array_equal(x, y)
    again = prompt_ids(a[0], 3, 32000, a[0].prompt_tokens - 25, t.sessions)
    assert np.array_equal(again, ids[3][0])
    assert ids[3][0].min() >= 3 and ids[3][0].max() < 32000


@pytest.mark.parametrize("mix", MIXES)
def test_any_prefix_of_the_trace_offers_the_same_work(mix):
    t = Traffic.load(TRAFFIC / f"{mix}.json")
    trace = make_trace(t, 1024)
    whole_p = statistics.mean(e.prompt_tokens for e in trace)
    whole_m = statistics.mean(e.max_tokens for e in trace)
    for start in (0, 100, 517):
        part = trace[start:start + 96]
        assert statistics.mean(e.prompt_tokens for e in part) == \
            pytest.approx(whole_p, rel=0.06)
        assert statistics.mean(e.max_tokens for e in part) == \
            pytest.approx(whole_m, rel=0.06)


def test_lengths_stay_inside_their_spec_and_its_support():
    for mix in MIXES:
        t = Traffic.load(TRAFFIC / f"{mix}.json")
        trace = make_trace(t, 500)
        assert {e.prompt_tokens for e in trace} <= set(support(t.prompt_tokens))
        assert {e.max_tokens for e in trace} <= set(support(t.max_tokens))


def test_low_discrepancy_order_and_quantiles():
    assert [radical_inverse(i, 2) for i in (1, 2, 3, 4)] == [
        0.5, 0.25, 0.75, 0.125]
    spec = {"kind": "lognormal", "median": 256, "sigma": 0.7, "min": 64,
            "max": 1024, "snap": 64}
    assert quantile(spec, 0.5, 0) == 256
    assert quantile(spec, 1e-9, 0) == 64 and quantile(spec, 1 - 1e-9, 0) == 1024
    assert quantile({"kind": "cycle", "values": [5, 7]}, 0.9, 3) == 7
    assert quantile({"kind": "uniform", "min": 96, "max": 160, "snap": 8},
                    0.5, 0) == 128


def test_open_loop_schedule_keeps_its_rate_and_its_bursts(tmp_path):
    path = tmp_path / "open.json"
    path.write_text(json.dumps({
        "loop": "open", "rate_rps": 2.0, "lead_in_s": 5.0, "trace_seed": 9,
        "burst": {"size": 4, "every_s": 10.0},
        "prompt_tokens": {"kind": "cycle", "values": [100]},
        "max_tokens": {"kind": "cycle", "values": [10]}}))
    t = Traffic.load(path)
    due = [e.due_s for e in make_trace(t, 400)]
    assert due == sorted(due)
    assert len(due) / due[-1] == pytest.approx(2.0, rel=0.03)
    assert due.count(10.0) == 4 and due.count(20.0) == 4
    # Every 20-second stretch carries its share of the arrivals.
    for lo in (0, 40, 120):
        n = sum(lo <= d < lo + 20 for d in due)
        assert n == pytest.approx(40, abs=5)


def test_sessions_share_their_prefix_and_nothing_else(tmp_path):
    path = tmp_path / "sess.json"
    path.write_text(json.dumps({
        "loop": "closed", "clients": 2, "trace_seed": 1,
        "sessions": {"count": 3, "shared_prefix_tokens": 40},
        "prompt_tokens": {"kind": "cycle", "values": [100]},
        "max_tokens": {"kind": "cycle", "values": [10]}}))
    t = Traffic.load(path)
    tr = make_trace(t, 6)
    ids = [prompt_ids(e, 7, 32000, 75, t.sessions) for e in tr]
    assert tr[0].session == tr[3].session == 0
    assert np.array_equal(ids[0][:40], ids[3][:40])
    assert not np.array_equal(ids[0][40:], ids[3][40:])
    assert not np.array_equal(ids[0][:40], ids[1][:40])


def test_a_traffic_file_with_an_unknown_key_is_refused(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text(json.dumps({"loop": "closed", "clients": 1,
                                "trace_seed": 1, "prompt_tokens": {},
                                "max_tokens": {}, "ramp": 3}))
    with pytest.raises(ValueError, match="unknown traffic keys"):
        Traffic.load(path)


def test_the_open_loop_rate_is_four_fifths_of_its_recorded_knee(tmp_path):
    """``chat-rate``'s ``rate_rps`` is arithmetic on the sweep its
    ``calibration`` block records, which is for the reader: ``load`` drops
    it, and still refuses a key it does not know beside it."""
    raw = json.loads((TRAFFIC / "chat-rate.json").read_text())
    cal = raw["calibration"]
    assert raw["rate_rps"] == round(0.8 * cal["knee_rps"], 2)
    assert cal["knee_rps"] in cal["rates"] and cal["step_seconds"] == 30
    assert len(cal["commit"]) >= 7
    t = Traffic.load(TRAFFIC / "chat-rate.json")
    assert t.rate_rps == raw["rate_rps"] and not hasattr(t, "calibration")
    path = tmp_path / "chat-rate.json"
    path.write_text(json.dumps({**raw, "calibrated": cal}))
    with pytest.raises(ValueError,
                       match=r"unknown traffic keys \['calibrated'\]"):
        Traffic.load(path)


@pytest.mark.parametrize("mix", MIXES)
def test_a_mix_names_its_source_and_what_it_assumed(mix):
    """A mix says which public trace its numbers come from, with the
    quantiles taken from it, and lists every number that is the
    benchmark's own choice; the parameters agree with the quantiles."""
    raw = json.loads((TRAFFIC / f"{mix}.json").read_text())
    assert raw["why"] and raw["assumed"]
    src = raw["source"]
    assert src["name"] and src["read"] and "quantiles" in src
    q = src["quantiles"]
    if "prompt_tokens_p50" in q:
        assert raw["prompt_tokens"]["median"] == q["prompt_tokens_p50"]
        assert raw["max_tokens"]["median"] == q["output_tokens_p50"]
        t = Traffic.load(TRAFFIC / f"{mix}.json")
        trace = make_trace(t, 1024)
        snap = raw["prompt_tokens"]["snap"]
        assert abs(statistics.median(e.prompt_tokens for e in trace)
                   - q["prompt_tokens_p50"]) <= snap
        assert abs(statistics.median(e.max_tokens for e in trace)
                   - q["output_tokens_p50"]) <= raw["max_tokens"]["snap"]
    else:
        assert src["name"].startswith("none")
