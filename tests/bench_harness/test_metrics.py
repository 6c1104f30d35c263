"""Repairs A.2 and A.3: tokens are counted when they are streamed, and a
request contributes the tokens it streamed, however it ended."""
import math

import pytest

from benchmark.metrics import (RequestLog, end_to_end, overlapping,
                               percentile, tokens_in_window, tpot_samples,
                               ttft_samples)


def req(i, send, frames, end, ok=True, due=None, **kw):
    return RequestLog(index=i, rid=f"r{i}", prompt_tokens=10, max_tokens=8,
                      t_due=due, t_send=send, frames=frames, t_end=end,
                      status=200 if ok else 500, done=ok,
                      usage={"completion_tokens": sum(n for _, n in frames)}
                      if ok else None, finish_reason="length", **kw)


def test_tokens_count_when_streamed_across_both_edges():
    logs = [
        # straddles the opening edge: 3 of its 5 tokens fall inside
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
    assert tokens_in_window(logs, 100.0, 110.0) == 3 + 9 + 2 + 2
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
