"""The general reducers the per-layer metric files call. A metric's file
(``layer_metrics/<name>.json``) names one reducer and its arguments — a
scope or kernel name, a flight field, a pair of request timestamps — so a
new per-layer metric over an existing reducer is a file, not code.

A reducer takes what one traced run measured (``Measured``) and its
arguments, and returns a number, or ``None`` where it finds nothing to
read: the harness then leaves the metric out of the line.

A new KIND of reducer is a new file too: every module of
``reducer_files/`` is imported when this one is (and those of a cell's
own data directory when the cell is loaded, ``spec.load_reducer_files``)
and registers with ``@reducer``. A new kernel's roofline share is such a
file: its operation and byte counts from shapes (``Measured.config`` has
the configuration's file, so its widths), over
``roofline.least_seconds`` and ``Measured.peaks``.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Any, Callable

from . import roofline, spec
from .metrics import RequestLog
from .xplane import Reduced


@dataclasses.dataclass
class Measured:
    logs: list[RequestLog]
    t_open: float
    t_close: float
    trace: Reduced | None            # the profiler's trace, cut to its markers
    t_trace: tuple[float, float]     # host clock read inside the two markers
    flight: list[dict[str, Any]]     # flight-ring snapshot (whole run)
    counters_open: dict[str, Any]    # engine stats() at window open
    counters_close: dict[str, Any]   # ... and at close
    slots: int
    shape: roofline.AttnShape        # per-chip attention shape
    peaks: dict[str, Any]
    peak_hbm_bytes: int | None
    config: dict[str, Any] = dataclasses.field(default_factory=dict)
    #                                  the configuration's file, as loaded

    def in_window(self) -> list[RequestLog]:
        return [r for r in self.logs if r.t_first is not None
                and self.t_open <= r.t_first < self.t_close]


REDUCERS: dict[str, Callable[[Measured, dict[str, Any]], float | None]] = {}


def reducer(fn):
    if fn.__name__ in REDUCERS:
        raise ValueError(
            f"reducer {fn.__name__!r} is registered twice: by "
            f"{REDUCERS[fn.__name__].__module__} and by {fn.__module__}")
    REDUCERS[fn.__name__] = fn
    return fn


def _stamp(r: RequestLog, name: str) -> float | None:
    return r.t_first if name == "t_first" else getattr(r, name)


@reducer
def request_interval_ms(m: Measured, a: dict[str, Any]) -> float | None:
    """Median over the window's requests of ``to - from`` (two of the
    request's stamps: t_send, t_first on the client; t_submit, t_admitted,
    t_first_token in the engine), minus ``minus_to - minus_from`` if given."""
    out = []
    for r in m.in_window():
        ts = [_stamp(r, a[k]) for k in ("from", "to")]
        inner = [_stamp(r, a[k]) for k in ("minus_from", "minus_to")
                 if k in a]
        if None in ts or None in inner:
            continue
        v = ts[1] - ts[0]
        if inner:
            v -= inner[1] - inner[0]
        out.append(1000.0 * v)
    return statistics.median(out) if out else None


@reducer
def flight_mean(m: Measured, a: dict[str, Any]) -> float | None:
    """Mean of a flight-ring step field over the window's steps of one
    kind (``decode``: a decode burst ran in it; anything else: none did),
    times ``scale`` (``per_slot``: as a percentage of the slots)."""
    decode = a.get("steps", "decode") == "decode"
    vals = [rec[a["field"]] for rec in m.flight
            if rec["kind"] == "step" and ("burst_depth" in rec) == decode
            and m.t_open <= rec["t"] < m.t_close and a["field"] in rec]
    if not vals:
        return None
    scale = 100.0 / m.slots if a.get("per_slot") else a.get("scale", 1.0)
    return scale * sum(vals) / len(vals)


def _kernel_events(m: Measured, scope: str) -> int:
    dev = m.trace.devices[0]
    return sum(1 for op in dev.ops if op.scope == scope
               and op.category == "custom-call")


@reducer
def program_ms_per_step(m: Measured, a: dict[str, Any]) -> float | None:
    """Device time of the named programs over the decode steps they ran.
    A step is counted in the trace itself: one kernel event of
    ``step_scope`` per step for every layer that calls the paged kernels
    (``AttnShape.n_layers``)."""
    if m.trace is None or not m.trace.devices:
        return None
    steps = _kernel_events(m, a["step_scope"]) / m.shape.n_layers
    ns = sum(m.trace.self_ns(program=p) for p in a["programs"])
    return ns / 1e6 / steps if steps else None


@reducer
def program_ms_per_event(m: Measured, a: dict[str, Any]) -> float | None:
    """Device time of a program per execution."""
    if m.trace is None or not m.trace.devices:
        return None
    n = sum(m.trace.program_events(p) for p in a["programs"])
    ns = sum(m.trace.self_ns(program=p) for p in a["programs"])
    return ns / 1e6 / n if n else None


@reducer
def scope_share(m: Measured, a: dict[str, Any]) -> float | None:
    """Device time under a named scope as a percentage of the device time
    of the programs it lives in."""
    if m.trace is None or not m.trace.devices:
        return None
    total = sum(m.trace.self_ns(program=p) for p in a["programs"])
    part = sum(m.trace.self_ns(program=p, scope=a["scope"])
               for p in a["programs"])
    return 100.0 * part / total if total else None


@reducer
def kernel_roofline(m: Measured, a: dict[str, Any]) -> float | None:
    """The decode kernel's roofline share: the least time the chip could
    take for the calls of the traced span, over their device time. The
    calls are counted from the client's frame log: one call per layer for
    every token after a request's first whose frame arrived inside the
    span, at its exact context (prompt + tokens before it). Frames arrive
    a burst at a time (up to 8 tokens a slot), so the span's edges are
    good to one burst in the hundred-odd steps of the span.

    Only ``paged_decode``: a prefill call's position and length are known
    to no stamp, counter or trace event the program emits today, and a
    numerator guessed from host time is no device metric (PERF.md, Open
    questions: ``kernel.paged_prefill_roofline``)."""
    if m.trace is None or not m.trace.devices:
        return None
    if a["kernel"] != "paged_decode":
        raise ValueError(f"no call count for kernel {a['kernel']!r}")
    t0, t1 = m.t_trace
    s = m.shape
    least = 0.0
    for r in m.logs:
        seen = 0
        for t, n in r.frames:
            for j in range(n):
                if seen and t0 <= t < t1:
                    f, b = roofline.paged_decode_cost(
                        [r.prompt_tokens + seen - 1], s)
                    least += roofline.least_seconds(f, b, m.peaks)[0]
                seen += 1
    ns = m.trace.self_ns(scope=a["scope"], category="custom-call")
    return 100.0 * least * s.n_layers / (ns / 1e9) if ns else None


@reducer
def client_metric(m: Measured, a: dict[str, Any]) -> float | None:
    """An end-to-end quantity (``metrics.end_to_end``) reported as a
    per-layer metric of the client, in a cell where it does not repeat
    well enough between runs to be held to a bound."""
    from .metrics import end_to_end
    return end_to_end(m.logs, m.t_open, m.t_close)[0].get(a["metric"])


@reducer
def exposed_collective_share(m: Measured, a: dict[str, Any]) -> float | None:
    if m.trace is None or not m.trace.devices:
        return None
    return 100.0 * m.trace.exposed_collective_ns() / m.trace.window_ns


@reducer
def device_idle_share(m: Measured, a: dict[str, Any]) -> float | None:
    if m.trace is None or not m.trace.devices:
        return None
    return 100.0 * m.trace.idle_share()


@reducer
def device_peak_hbm_bytes(m: Measured, a: dict[str, Any]) -> float | None:
    return None if m.peak_hbm_bytes is None else float(m.peak_hbm_bytes)


@reducer
def counter_delta(m: Measured, a: dict[str, Any]) -> float | None:
    """An engine counter at window close minus at window open."""
    k = a["counter"]
    if k not in m.counters_open or k not in m.counters_close:
        return None
    return float(m.counters_close[k] - m.counters_open[k])


spec.load_reducer_files(spec.PACKAGE_DIR)      # last: they import this module
