"""``counter_ratio``: a mean over the window read from two sets of the
program's monotone counters (ISSUE 41). The requests' wait ledger
(``llmapigateway_tpu/obs/phases.py``) counts slot-milliseconds a bucket
and the events they are read against; their growth over the window,
divided, is the mean milliseconds a first token (or a later token) spent
in that bucket. A counter can give no median: that needs the buckets on
each request's record."""
from __future__ import annotations

from typing import Any

from ..reducers import Measured, reducer


def _growth(m: Measured, counters: list[str]) -> float | None:
    if any(k not in m.counters_open or k not in m.counters_close
           for k in counters):
        return None
    return float(sum(m.counters_close[k] - m.counters_open[k]
                     for k in counters))


@reducer
def counter_ratio(m: Measured, a: dict[str, Any]) -> float | None:
    """The window's growth of the sum of the ``num`` counters over that of
    the ``den`` counters, times ``scale`` (1 if not given). ``None`` where
    a counter is missing from either ``stats()`` snapshot (a program that
    does not count it) or the denominator did not grow."""
    num, den = _growth(m, a["num"]), _growth(m, a["den"])
    if num is None or den is None or den <= 0:
        return None
    return a.get("scale", 1) * num / den
