"""Reduction of the JAX profiler's trace (``*.xplane.pb``) to what the
per-layer metrics read: device busy and idle time, time per program,
per named scope and per kernel, exposed collective time, and the idle
gaps by what the host was doing.

Reads the events through ``jax.profiler.ProfileData`` (planes -> lines ->
events with ``start_ns``, ``duration_ns`` and ``stats``). What an op IS —
its ``hlo_category`` and its ``tf_op`` (the ``jax.named_scope`` path) —
the profiler keeps once per distinct op in the plane's event metadata,
which ``ProfileData`` does not show; ``metadata_stats`` reads those few
records straight from the file's protobuf wire format (field numbers of
tsl's ``xplane.proto``). Nothing else is needed, so a small recorded trace
in the profiler's text form is enough to test the reduction
(``tests/bench_harness/fixtures``).

How a device plane is read:

* lines named ``XLA Ops`` hold one event per executed HLO op. Control-flow
  ops (``while``, ``call``, ``conditional``) enclose their bodies' events
  on the same line, so every sum here is over SELF time — an event's
  duration minus its children's — and busy time is the union of all
  intervals.
* the line ``XLA Modules`` holds one event per program execution
  (``jit_decode_scan(...)``); an op belongs to the module event that
  encloses its start.
* an op's scope is the innermost ``jax.named_scope`` of ``SCOPES`` found
  in its metadata (a configuration may put names of its own before them:
  ``spec.scopes``, handed to ``reduce``); its category is the
  ``hlo_category`` stat (or the op name without its number).

An op's key in the breakdown is ``<program>/<scope or ->:<category>``,
for instance ``prefill_step/attention.paged_prefill:custom-call``.

The traced span. The harness writes two marker spans into the trace, one
right after the profiler starts (``MARK_OPEN``) and one right before it
stops (``MARK_CLOSE``). They land on the host plane, on the line of the
thread that made the profiler calls. The traced span is ``[open.start,
close.start]`` ON THE PROFILER'S CLOCK, the one the device events are on;
``window_ns`` is its length, and every device op, program execution and
host span is clipped to it before anything is summed: an op that straddles
an edge contributes the part inside, and a program execution counts as the
part of itself that lies inside. So busy time cannot exceed the window,
whatever the host's threads were doing around the profiler calls. A trace
without both markers falls back to the span from the first to the last
device event (``Reduced.marked`` is false; the harness then prints no
device metric from it).

An idle gap's key is ``host.<program span>/<runtime span>``: the innermost
span that covers the gap's middle among those the PROGRAM writes
(``PROGRAM_SPANS``), or ``-``; then the innermost other span, or ``other``.
The time before the first op and after the last op of the span is idle and
is named like any other gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from collections import defaultdict
from typing import Any, Iterable

# Innermost first: attention.paged_* sits inside {prefill,decode}.attention.
SCOPES = ("attention.paged_prefill", "attention.paged_decode",
          "attention.paged_verify", "prefill.attention", "prefill.mlp",
          "decode.attention", "decode.mlp", "sampling")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective", re.I)
_CONTROL = re.compile(r"^(while|call|conditional)([.\d]*)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
MIN_GAP_NS = 20_000
MARK_OPEN, MARK_CLOSE = "bench.trace_open", "bench.trace_close"
# Prefixes of the host spans the program writes around its own phases
# (``obs/device.py`` ``phase``); everything else on the host plane is the
# runtime's. The benchmark's own ``bench.`` spans never name a gap.
PROGRAM_SPANS = ("prefill", "decode", "spec.", "sched.", "engine.")
_BENCH_SPANS = "bench."


@dataclasses.dataclass
class Op:
    start: int
    end: int
    self_ns: int
    program: str
    scope: str
    category: str
    collective: bool

    @property
    def key(self) -> str:
        return f"{self.program}/{self.scope or '-'}:{self.category}"


@dataclasses.dataclass
class DeviceTrace:
    name: str
    ops: list[Op]
    modules: list[tuple[int, int, str]]        # (start, end, program)
    module_parts: list[float]                  # share of each inside the span


@dataclasses.dataclass
class Reduced:
    devices: list[DeviceTrace]
    host: list[tuple[int, int, str]]           # (start, end, name)
    span: tuple[int, int]                      # traced span, profiler's clock
    marked: bool                               # span taken from the markers

    @property
    def window_ns(self) -> int:
        return self.span[1] - self.span[0]

    # -- busy and idle ------------------------------------------------------
    def busy_ns(self) -> float:
        """Union of device-op intervals, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(_union_len(_intervals(d.ops)) for d in self.devices) \
            / len(self.devices)

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns() / self.window_ns

    # -- sums of self time, averaged over chips -----------------------------
    def self_ns(self, *, program: str | None = None, scope: str | None = None,
                category: str | None = None) -> float:
        total = 0
        for d in self.devices:
            for op in d.ops:
                if program is not None and op.program != program:
                    continue
                if scope is not None and op.scope != scope:
                    continue
                if category is not None and op.category != category:
                    continue
                total += op.self_ns
        return total / max(1, len(self.devices))

    def program_events(self, program: str) -> float:
        """Executions of ``program`` on the first chip; one that straddles
        an edge of the span counts as the part of it that lies inside."""
        if not self.devices:
            return 0
        d = self.devices[0]
        return sum(part for (_, _, p), part in zip(d.modules, d.module_parts)
                   if p == program)

    def exposed_collective_ns(self) -> float:
        """Collective time during which no other op ran on that chip."""
        total = 0
        for d in self.devices:
            coll = _merge(_intervals(o for o in d.ops if o.collective
                                     and o.self_ns > 0))
            other = _merge(_intervals(o for o in d.ops if not o.collective
                                      and o.self_ns > 0
                                      and not _CONTROL.match(o.category)))
            total += _union_len(coll) - _overlap_len(coll, other)
        return total / max(1, len(self.devices))

    # -- the breakdown the ledger keeps -------------------------------------
    def top_ops(self, n: int = 10) -> list[list[Any]]:
        sums: dict[str, int] = defaultdict(int)
        for d in self.devices:
            for op in d.ops:
                sums[op.key] += op.self_ns
        k = max(1, len(self.devices))
        rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / k / 1e9] for name, ns in rows]

    def idle_gaps(self, n: int = 10) -> list[list[Any]]:
        """Idle time of the first chip by what the host was doing at the
        middle of each gap (``_host_at``), the span's two edges included."""
        if not self.devices:
            return []
        sums: dict[str, int] = defaultdict(int)
        for s, e in self.gaps():
            sums[self._host_at((s + e) // 2)] += e - s
        rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in rows]

    def gaps(self) -> list[tuple[int, int]]:
        """The first chip's idle stretches of ``MIN_GAP_NS`` or more, from
        the span's opening to its close."""
        prev_end, out = self.span[0], []
        for s, e in _merge(_intervals(self.devices[0].ops)) + [
                (self.span[1], self.span[1])]:
            if s - prev_end >= MIN_GAP_NS:
                out.append((prev_end, s))
            prev_end = max(prev_end, e)
        return out

    def _host_at(self, t: int) -> str:
        """``host.<program span>/<runtime span>`` over the moment ``t``:
        of each kind the innermost (shortest) span that covers it."""
        best: dict[bool, tuple[int, str]] = {}
        for s, e, name in self.host:
            if s <= t < e and not name.startswith(_BENCH_SPANS):
                kind = name.startswith(PROGRAM_SPANS)
                if kind not in best or e - s < best[kind][0]:
                    best[kind] = (e - s, name)
        return "host.%s/%s" % (best.get(True, (0, "-"))[1],
                               best.get(False, (0, "other"))[1])


@dataclasses.dataclass
class Trace:
    """One recorded trace: the events, and the per-op metadata stats by
    plane name and event name."""
    profile: Any
    meta: dict[str, dict[str, dict[str, Any]]]

    @classmethod
    def from_bytes(cls, data: bytes) -> "Trace":
        from jax.profiler import ProfileData
        return cls(ProfileData.from_serialized_xspace(data),
                   metadata_stats(data))

    @classmethod
    def from_text_proto(cls, text: str) -> "Trace":
        from jax.profiler import ProfileData
        return cls.from_bytes(ProfileData.text_proto_to_serialized_xspace(
            text))


def _varint(buf: memoryview, i: int) -> tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _wire(buf: memoryview):
    """(field number, wire type, value) of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
            yield field, wt, v
        elif wt == 2:
            ln, i = _varint(buf, i)
            yield field, wt, buf[i:i + ln]
            i += ln
        elif wt == 1:
            yield field, wt, bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            yield field, wt, bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")


def metadata_stats(data: bytes) -> dict[str, dict[str, dict[str, Any]]]:
    """``{plane name: {event name: {stat name: value}}}`` from the event
    metadata of an ``XSpace``: XSpace.planes=1; XPlane.name=2,
    .event_metadata=4, .stat_metadata=5 (maps: key=1, value=2);
    XEventMetadata.name=2, .stats=5; XStatMetadata.name=2;
    XStat.metadata_id=1, double=2, uint64=3, int64=4, str=5, ref=7."""
    import struct
    out: dict[str, dict[str, dict[str, Any]]] = {}
    for f, _, plane in _wire(memoryview(data)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, _, v in _wire(plane):
            if pf == 2:
                name = bytes(v).decode()
            elif pf == 5:
                entry = dict((k, x) for k, _, x in _wire(v))
                sm = dict((k, x) for k, _, x in _wire(entry.get(2, b"")))
                stat_names[entry.get(1, sm.get(1, 0))] = bytes(
                    sm.get(2, b"")).decode()
            elif pf == 4:
                entry = dict((k, x) for k, _, x in _wire(v))
                events.append(entry.get(2, b""))
        table: dict[str, dict[str, Any]] = {}
        for ev in events:
            ev_name, stats = "", {}
            for ef, _, v in _wire(ev):
                if ef == 2:
                    ev_name = bytes(v).decode(errors="replace")
                elif ef == 5:
                    key, val = None, None
                    for sf, wt, x in _wire(v):
                        if sf == 1:
                            key = x
                        elif sf == 2:
                            val = struct.unpack("<d", x)[0]
                        elif sf in (3, 4):
                            val = x
                        elif sf == 5:
                            val = bytes(x).decode(errors="replace")
                        elif sf == 7:
                            val = ("ref", x)
                    stats[key] = val
            table[ev_name] = {
                stat_names.get(k, str(k)): (stat_names.get(v[1], "")
                                            if isinstance(v, tuple) else v)
                for k, v in stats.items()}
        out[name] = table
    return out


def program_name(module_event: str) -> str:
    """``jit_decode_scan(1234)`` -> ``decode_scan``."""
    name = module_event.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def reduce(trace: Trace, scopes: tuple[str, ...] = SCOPES) -> Reduced:
    """The traced span is the one between the two markers, and everything
    is clipped to it. Without both markers: the span from the first to the
    last device event, nothing clipped. An op is filed under the first of
    ``scopes`` (innermost first) that its metadata names."""
    host, marks = [], {}
    for plane in trace.profile.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                continue              # runtime worker pools, not the program
            for ev in line.events:
                if ev.name in (MARK_OPEN, MARK_CLOSE):
                    marks.setdefault(ev.name, int(ev.start_ns))
                if ev.duration_ns > 0 and "::" not in ev.name:
                    host.append((int(ev.start_ns),
                                 int(ev.start_ns + ev.duration_ns),
                                 _host_name(ev.name)))
    span = (marks.get(MARK_OPEN), marks.get(MARK_CLOSE))
    marked = None not in span and span[0] < span[1]
    devices = []
    for plane in trace.profile.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name.upper() \
                and "core" not in name.lower():
            dev = _device(plane, trace.meta.get(name, {}),
                          span if marked else None, scopes)
            if dev.ops:
                devices.append(dev)
    if marked:
        host = _cut(host, span)
    else:
        span = (min((d.ops[0].start for d in devices), default=0),
                max((o.end for d in devices for o in d.ops), default=0))
    return Reduced(devices=devices, host=host, span=span, marked=marked)


def _host_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.()-]", "_", name.split(" ", 1)[0])[:48]


def _hlo_name(text: str) -> str:
    """``%fusion.235 = bf16[...] fusion(...)`` -> ``fusion.235``."""
    return text.split(" ", 1)[0].lstrip("%")


def _device(plane: Any, meta: dict[str, dict[str, Any]],
            span: tuple[int, int] | None = None,
            scopes: tuple[str, ...] = SCOPES) -> DeviceTrace:
    """One chip's ops and program executions, cut to ``span``. An op's
    program is found before the cut (by where the op really started); self
    times are taken after it, on what is left of each op."""
    whole: list[tuple[int, int, str]] = []
    raw: list[tuple[int, int, str, dict]] = []
    for line in plane.lines:
        if line.name == _MODULES_LINE:
            for ev in line.events:
                s = int(ev.start_ns)
                whole.append((s, s + int(ev.duration_ns),
                              program_name(ev.name)))
        elif line.name == _OPS_LINE:
            for ev in line.events:
                s = int(ev.start_ns)
                raw.append((s, s + int(ev.duration_ns), ev.name,
                            meta.get(ev.name) or {}))
    whole.sort()
    starts = [m[0] for m in whole]
    raw.sort(key=lambda r: (r[0], -r[1]))
    programs = []
    for s, _, _, _ in raw:
        i = bisect.bisect_right(starts, s) - 1
        programs.append(whole[i][2] if i >= 0 and s < whole[i][1]
                        else "unknown")
    modules = _cut([(s, e, p, e - s) for s, e, p in whole], span)
    module_parts = [(e - s) / n if n else 1.0 for s, e, _, n in modules]
    # Stable: an enclosing op cut to the same start stays before its body.
    cut = sorted(_cut([(*r, p) for r, p in zip(raw, programs)], span),
                 key=lambda r: (r[0], -r[1]))
    ops: list[Op] = []
    stack: list[Op] = []
    for s, e, name, stats, program in cut:
        short = _hlo_name(name)
        category = str(stats.get("hlo_category") or "").strip().replace(
            " ", "_") or ("custom-call" if " custom-call(" in name
                          else re.sub(r"[.\d]+$", "", short))
        op = Op(start=s, end=e, self_ns=e - s, program=program,
                scope=_scope(stats, short, scopes), category=category,
                collective=bool(_COLLECTIVE.search(category)
                                or _COLLECTIVE.search(short)))
        while stack and stack[-1].end <= s:
            stack.pop()
        if stack:                       # nested: the parent loses this time
            stack[-1].self_ns -= min(e, stack[-1].end) - s
        stack.append(op)
        ops.append(op)
    for op in ops:
        op.self_ns = max(0, op.self_ns)
    return DeviceTrace(name=plane.name, ops=ops,
                       modules=[m[:3] for m in modules],
                       module_parts=module_parts)


def _cut(rows: list[tuple], span: tuple[int, int] | None) -> list[tuple]:
    """``(start, end, ...)`` rows cut to ``span``; what lies outside goes."""
    if span is None:
        return rows
    lo, hi = span
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in rows
            if s < hi and e > lo]


def _scope(stats: dict, name: str, scopes: tuple[str, ...] = SCOPES) -> str:
    """From the op's ``tf_op`` (its named-scope path), else from its HLO
    name (a kernel is named after its scope)."""
    for text in (stats.get("tf_op"), name):
        if isinstance(text, str):
            for scope in scopes:
                if scope in text:
                    return scope
    return ""


def _intervals(ops: Iterable[Op]) -> list[tuple[int, int]]:
    return sorted((o.start, o.end) for o in ops if o.end > o.start)


def _merge(iv: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[tuple[int, int]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _union_len(iv: list[tuple[int, int]]) -> int:
    return sum(e - s for s, e in _merge(iv))


def _overlap_len(a: list[tuple[int, int]], b: list[tuple[int, int]]) -> int:
    """Length of the intersection of two merged interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def load(trace_dir: str) -> Trace:
    """The newest ``*.xplane.pb`` under a ``start_trace`` directory."""
    import glob
    import os
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    with open(paths[-1], "rb") as f:
        return Trace.from_bytes(f.read())


def describe(trace: Trace, n: int = 6) -> list[dict]:
    """Planes, lines and the first ``n`` events of each with their own and
    their metadata's stats, and wherever on a line the harness's marker
    spans are — for looking at one trace by hand before trusting the
    reduction."""
    out = []
    for plane in trace.profile.planes:
        meta = trace.meta.get(plane.name, {})
        for line in plane.lines:
            evs, marks = [], []
            count = 0
            for ev in line.events:
                if ev.name in (MARK_OPEN, MARK_CLOSE):
                    marks.append({"name": ev.name, "start_ns": ev.start_ns,
                                  "duration_ns": ev.duration_ns})
                if count < n:
                    evs.append({"name": ev.name, "start_ns": ev.start_ns,
                                "duration_ns": ev.duration_ns,
                                "stats": {k: (v if isinstance(
                                    v, (int, float)) else str(v)[:300])
                                    for k, v in dict(ev.stats).items()},
                                "meta": {k: (v if isinstance(
                                    v, (int, float)) else str(v)[:300])
                                    for k, v in (meta.get(ev.name) or {}
                                                 ).items()}})
                count += 1
            out.append({"plane": plane.name, "line": line.name,
                        "events": count, "first": evs, "marks": marks})
    return out
