"""Repairs A.2 and A.3: tokens are counted when they are streamed, and a
request contributes the tokens it streamed, however it ended."""
import math

import pytest

from benchmark.metrics import (BURST_S, RequestLog, bursts, end_to_end,
                               frames_in_window, mid_mean, overlapping,
                               percentile, streamed_by, tokens_in_window,
                               tpot_samples, ttft_samples)


def req(i, send, frames, end, ok=True, due=None, **kw):
    return RequestLog(index=i, rid=f"r{i}", prompt_tokens=10, max_tokens=8,
                      t_due=due, t_send=send, frames=frames, t_end=end,
                      status=200 if ok else 500, done=ok,
                      usage={"completion_tokens": sum(n for _, n in frames)}
                      if ok else None, finish_reason="length", **kw)


def test_tokens_count_when_streamed_across_both_edges():
    logs = [
        # straddles the opening edge: the frames at 100, 101 and 102 are
        # inside; the token that arrived at 100.0 was made before it and
        # the one that arrives at 110.0 (below) inside, so 16 either way
        req(0, 90.0, [(98.0, 1), (99.5, 1), (100.0, 1), (101.0, 1),
                      (102.0, 1)], 102.1),
        # wholly inside, one frame carrying a burst of 8
        req(1, 103.0, [(104.0, 1), (105.0, 8)], 105.1),
        # straddles the closing edge: 2 of 4 inside (110.0 is outside)
        req(2, 107.0, [(108.0, 1), (109.9, 1), (110.0, 1), (111.0, 1)],
            111.5),
        # still streaming when the run ended: no end, counted all the same
        req(3, 108.5, [(109.0, 2)], None),
    ]
    assert frames_in_window(logs, 100.0, 110.0) == 3 + 9 + 2 + 2
    assert tokens_in_window(logs, 100.0, 110.0) == 2 + 9 + 3 + 2
    values, counts = end_to_end(logs, 100.0, 110.0)
    assert values["out_tok_s"] == pytest.approx(1.6)
    assert counts["out_tok_s"] == 16
    assert len(overlapping(logs, 100.0, 110.0)) == 4


def test_tpot_is_over_requests_finished_inside_the_window():
    logs = [req(0, 0.0, [(1.0, 1), (1.5, 4)], 1.6),       # (0.5 s)/4
            req(1, 0.0, [(1.0, 1), (3.0, 1), (5.0, 1)], 5.1),
            req(2, 0.0, [(1.0, 1), (1.1, 1)], 12.0),       # ended outside
            req(3, 0.0, [(2.0, 1)], 2.1)]                  # one token: none
    assert sorted(tpot_samples(logs, 0.0, 10.0)) == pytest.approx(
        [125.0, 2000.0])


def test_failed_requests_miss_every_percentile():
    ok = [req(i, 10.0 + i, [(10.5 + i, 1), (10.6 + i, 1)], 10.7 + i)
          for i in range(8)]
    bad = [req(8, 18.0, [], 18.2, ok=False),
           req(9, 19.0, [(19.5, 1)], 19.6, ok=False)]
    sample = ttft_samples(ok + bad, 10.0, 30.0)
    assert len(sample) == 10 and sample.count(math.inf) == 2
    assert percentile(sample, 50) == pytest.approx(500.0)
    assert percentile(sample, 90) == math.inf
    values, counts = end_to_end(ok + bad, 10.0, 30.0)
    assert values["ttft_p50_ms"] == pytest.approx(500.0)
    assert "ttft_p90_ms" not in values         # it landed on a failure
    assert counts["ttft_p90_ms"] == 10
    assert [r.failed for r in ok + bad] == [False] * 8 + [True] * 2


def test_open_loop_ttft_is_from_the_due_time_and_hang_ups_are_no_failures():
    late = req(0, 5.3, [(5.8, 1)], 6.0, due=5.0)           # sent 0.3 s late
    hung = req(1, 6.0, [(6.4, 1)], 7.0, due=6.0, cancelled=True)
    hung.done, hung.usage = False, None
    unborn = req(2, 6.5, [], 7.0, due=6.5, cancelled=True)
    assert ttft_samples([late, hung, unborn], 0.0, 10.0) == pytest.approx(
        [800.0, 400.0])
    assert not hung.failed and not unborn.failed


def test_percentile_is_nearest_rank():
    xs = list(range(1, 101))
    assert percentile(xs, 50) == 50 and percentile(xs, 90) == 90
    assert percentile([7.0], 90) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50)


@pytest.mark.parametrize("ttfts_ms, failures, want", [
    # even count: ranks 2..6 of 8 (nearest rank: ceil(n/4), ceil(3n/4))
    ([100, 200, 300, 400, 500, 600, 700, 800], 0, 400.0),
    # odd count: ranks 2..6 of 7
    ([700, 100, 600, 200, 500, 300, 400], 0, 400.0),
    # one sample, and two: the quartiles fall on what there is
    ([250], 0, 250.0),
    ([100, 300], 0, 200.0),
    # a failure past the third quartile: ranks 3..8 of 10 are all served
    ([100, 200, 300, 400, 500, 600, 700, 800, 900], 1, 550.0),
    # failures inside the range (ranks 2..6 of 8, two of them +inf): left out
    ([100, 200, 300, 400, 500], 3, None),
    # nothing due in the window
    ([], 0, None),
], ids=["even", "odd", "one", "two", "failure-outside", "failure-inside",
        "empty"])
def test_ttft_mid_is_the_mean_between_the_nearest_rank_quartiles(
        ttfts_ms, failures, want):
    logs = [req(i, 10.0 + i, [(10.0 + i + ms / 1000.0, 1)], 30.0)
            for i, ms in enumerate(ttfts_ms)]
    logs += [req(100 + k, 10.5 + k, [], 11.0 + k, ok=False)
             for k in range(failures)]
    sample = ttft_samples(logs, 0.0, 50.0)
    values, counts = end_to_end(logs, 0.0, 50.0)
    assert counts["ttft_mid_ms"] == len(sample) == len(ttfts_ms) + failures
    if want is None:
        assert "ttft_mid_ms" not in values
        if not sample:
            with pytest.raises(ValueError):
                mid_mean(sample)
    else:
        assert values["ttft_mid_ms"] == pytest.approx(want)
        assert mid_mean(sample) == pytest.approx(want)
        # Between the two quartiles it is the mean of.
        assert (percentile(sample, 25) <= values["ttft_mid_ms"]
                <= percentile(sample, 75))
    # The median beside it is as it was.
    if sample and math.isfinite(percentile(sample, 50)):
        assert values["ttft_p50_ms"] == percentile(sample, 50)


def burst_stream(period, per_slot, slots, until):
    """Every slot gets ``per_slot`` tokens each ``period``, stamped within
    a few milliseconds of each other, as a decode scan serves them."""
    logs = [req(k, 0.0, [], None) for k in range(slots)]
    t = period
    while t < until:
        for k, r in enumerate(logs):
            r.frames += [(t + 0.0004 * k + 0.0001 * j, 1)
                         for j in range(per_slot)]
        t += period
    return logs


@pytest.mark.parametrize("phase", [0.0, 0.004, 0.1, 0.296, 0.2999, 0.31])
def test_a_burst_astride_an_edge_gives_the_share_of_its_interval_inside(
        phase):
    """28 tokens every 0.3 s: the rate is 93.3 tokens/s wherever the edges
    fall against the bursts. Counting frames, a 4.0 s window holds 13 or
    14 bursts: 7% apart by where its close fell (longdoc: 0.97% of 40 s,
    the refused check of PR 25)."""
    logs = burst_stream(0.3, 4, 7, until=12.0)
    t_open, t_close = 3.05 + phase, 7.05 + phase
    values, counts = end_to_end(logs, t_open, t_close)
    assert values["out_tok_s"] == pytest.approx(28 / 0.3, rel=2e-3)
    assert counts["out_tok_s"] in (13 * 28, 14 * 28)
    # Whole bursts inside count whole, once: only the two at the edges
    # are shared out.
    assert abs(tokens_in_window(logs, t_open, t_close)
               - frames_in_window(logs, t_open, t_close)) <= 28


def test_bursts_are_told_by_their_first_frame_and_accrue_evenly():
    logs = [req(0, 0.0, [(1.0, 1), (1.003, 1), (2.0, 1), (2.015, 1)], 2.1),
            req(1, 0.0, [(1.001, 1), (2.001, 2), (2.0 + BURST_S, 1)], 2.1)]
    stream = bursts(logs)
    assert stream == [(1.0, 3), (2.0, 4), (2.0 + BURST_S, 1)]
    assert streamed_by(stream, 0.5) == 0            # nothing before the first
    assert streamed_by(stream, 1.0) == 0 and streamed_by(stream, 1.0001) > 3
    assert streamed_by(stream, 1.5) == pytest.approx(3 + 2.0)
    assert streamed_by(stream, 2.0) == pytest.approx(7.0)
    assert streamed_by(stream, 9.0) == 8            # all of it, after the last
    # Monotone and continuous between bursts.
    ts = [1.0 + 0.01 * i for i in range(1, 100)]
    vals = [streamed_by(stream, t) for t in ts]
    assert all(0 < b - a < 0.05 for a, b in zip(vals, vals[1:]))


def test_a_stream_without_bursts_reads_as_its_frames_do():
    """One token every 7 ms: the bins of ``BURST_S`` move no more than a
    bin's tokens across an edge."""
    logs = [req(0, 0.0, [(1.0 + 0.007 * i, 1) for i in range(2000)], None)]
    for t_open, t_close in ((2.0, 9.0), (2.0031, 9.0155), (3.3, 4.3)):
        assert tokens_in_window(logs, t_open, t_close) == pytest.approx(
            frames_in_window(logs, t_open, t_close), abs=3.0)
