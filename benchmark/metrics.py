"""From per-request frame logs to the end-to-end metrics. Pure arithmetic
on the client's records: no clock is read here.

A request's record (``RequestLog``) holds the time it was due (open loop)
and sent, and one ``(time, tokens)`` pair per content frame that reached
the client. The window is ``[t_open, t_close)`` on the same clock.

* ``out_tok_s``: content tokens whose frame arrived inside the window,
  over the window's length. A request that straddles an edge contributes
  what it streamed inside. The engine streams in bursts (one decode scan
  serves every slot several tokens, and the client gets them within
  milliseconds of each other); a burst's tokens were made over the time
  since the burst before it, so a burst whose interval straddles an edge
  gives the window the share of that interval that lies inside. Every
  other token counts whole, once. Without this a window counts its last
  burst in or out by milliseconds of stamping: 1% of a long-document
  window, run by run (PERF.md, the refused check of PR 25).
* ``tpot_ms``: per request finished inside the window,
  (last frame - first frame) / (tokens - 1); the metric is the median.
* ``ttft_ms``: first content frame minus the due time (open loop) or the
  send time (closed loop), over requests due/sent inside the window.
  ``ttft_mid_ms`` is the mean of that sample from its first quartile to
  its third (``mid_mean``): the median of a few dozen requests is ONE
  request's time, and which request stands in the middle changes from run
  to run; the mean of the middle half moves with all of them.

A failed request (non-200, error frame, no ``[DONE]``, no usage frame)
counts in ``failed`` and as missing every percentile it would have been
in: it enters the sample as +infinity.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import math
from typing import Any, Iterable


@dataclasses.dataclass
class RequestLog:
    index: int
    rid: str
    prompt_tokens: int
    max_tokens: int
    t_due: float | None = None        # open loop
    t_send: float = 0.0
    frames: list[tuple[float, int]] = dataclasses.field(default_factory=list)
    t_end: float | None = None        # the stream closed (either way)
    status: int | None = None
    done: bool = False                # "[DONE]" arrived
    usage: dict[str, Any] | None = None
    finish_reason: str | None = None
    error: str | None = None
    cancelled: bool = False           # the harness hung up at teardown
    # The engine's own stamps for the same request (same process, same
    # monotonic clock), when the submit hook saw it.
    t_submit: float | None = None
    t_admitted: float | None = None
    t_first_token: float | None = None

    @property
    def t_ref(self) -> float:
        return self.t_send if self.t_due is None else self.t_due

    @property
    def t_first(self) -> float | None:
        return self.frames[0][0] if self.frames else None

    @property
    def tokens(self) -> int:
        return sum(n for _, n in self.frames)

    @property
    def failed(self) -> bool:
        if self.cancelled:
            return False
        return self.t_end is not None and not (
            self.status == 200 and self.done and self.error is None
            and self.usage is not None)

    @property
    def finished(self) -> bool:
        return self.t_end is not None and not self.cancelled


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile (q in 0..100); +inf entries are failures
    and sort last. Raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def mid_mean(values: Iterable[float]) -> float:
    """Mean of the sample from its nearest-rank first quartile to its
    third, both included; +inf (a failure inside that range) makes it
    +inf. Raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("mid_mean of an empty sample")
    lo, hi = (max(1, math.ceil(q * len(xs))) for q in (0.25, 0.75))
    mid = xs[lo - 1:hi]
    return sum(mid) / len(mid)


BURST_S = 0.02      # frames this close to a burst's first frame are of it


def bursts(logs: Iterable[RequestLog]) -> list[tuple[float, int]]:
    """The whole stream as ``(arrival of its first frame, tokens)``: the
    frames of every request stamped within ``BURST_S`` of a burst's first
    frame belong to it. (A decode burst reaches the client within 10 ms;
    the next is 130 ms or more behind. A stream without bursts falls into
    bins of ``BURST_S``, which changes nothing that can be seen.)"""
    out: list[list[float]] = []
    for t, n in sorted((t, n) for r in logs for t, n in r.frames):
        if out and t - out[-1][0] < BURST_S:
            out[-1][1] += n
        else:
            out.append([t, n])
    return [(t, int(n)) for t, n in out]


def streamed_by(stream: list[tuple[float, int]], t: float) -> float:
    """Tokens streamed before ``t``: whole bursts that arrived before it,
    and of the burst then on its way the share of its interval (from the
    arrival of the burst before it to its own) that lies before ``t``."""
    times = [b for b, _ in stream]
    i = bisect.bisect_left(times, t)            # bursts arrived before t
    whole = sum(n for _, n in stream[:i])
    if i == 0 or i == len(stream):
        return float(whole)
    before, (arrival, n) = times[i - 1], stream[i]
    return whole + n * (t - before) / (arrival - before)


def tokens_in_window(logs: Iterable[RequestLog], t_open: float,
                     t_close: float) -> float:
    stream = bursts(logs)
    return streamed_by(stream, t_close) - streamed_by(stream, t_open)


def frames_in_window(logs: Iterable[RequestLog], t_open: float,
                     t_close: float) -> int:
    return sum(n for r in logs for t, n in r.frames if t_open <= t < t_close)


def tpot_samples(logs: Iterable[RequestLog], t_open: float,
                 t_close: float) -> list[float]:
    """Milliseconds per output token after the first, for requests whose
    stream ended inside the window."""
    out = []
    for r in logs:
        if not r.finished or not t_open <= r.t_end < t_close:
            continue
        if r.failed:
            out.append(math.inf)
        elif r.tokens >= 2:
            out.append(1000.0 * (r.frames[-1][0] - r.frames[0][0])
                       / (r.tokens - 1))
    return out


def ttft_samples(logs: Iterable[RequestLog], t_open: float,
                 t_close: float) -> list[float]:
    """Milliseconds to the first content frame, for requests due (open
    loop) or sent (closed loop) inside the window. One that failed, or
    never streamed a token, is +inf."""
    out = []
    for r in logs:
        if not t_open <= r.t_ref < t_close or r.cancelled and not r.frames:
            continue
        if r.failed or r.t_first is None:
            out.append(math.inf)
        else:
            out.append(1000.0 * (r.t_first - r.t_ref))
    return out


def overlapping(logs: Iterable[RequestLog], t_open: float,
                t_close: float) -> list[RequestLog]:
    """Requests with any part of their life inside the window."""
    return [r for r in logs if r.t_send < t_close
            and (r.t_end is None or r.t_end >= t_open)]


def end_to_end(logs: list[RequestLog], t_open: float, t_close: float
               ) -> tuple[dict[str, float], dict[str, int]]:
    """Every end-to-end quantity this module knows, by metric name, with
    the sample count behind each. A metric whose sample is empty, or whose
    percentile lands on a failed request, is left out."""
    n_tokens = tokens_in_window(logs, t_open, t_close)
    values: dict[str, float] = {"out_tok_s": n_tokens / (t_close - t_open)}
    counts = {"out_tok_s": frames_in_window(logs, t_open, t_close)}
    tpot = tpot_samples(logs, t_open, t_close)
    ttft = ttft_samples(logs, t_open, t_close)
    p50, p90 = (functools.partial(percentile, q=q) for q in (50, 90))
    for name, sample, stat in (("tpot_p50_ms", tpot, p50),
                               ("ttft_p50_ms", ttft, p50),
                               ("ttft_p90_ms", ttft, p90),
                               ("ttft_mid_ms", ttft, mid_mean)):
        counts[name] = len(sample)
        if sample:
            v = stat(sample)
            if math.isfinite(v):
                values[name] = v
    return values, counts
