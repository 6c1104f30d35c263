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
  in its metadata; its category is the ``hlo_category`` stat (or the op
  name without its number).

An op's key in the breakdown is ``<program>/<scope or ->:<category>``,
for instance ``prefill_step/attention.paged_prefill:custom-call``.
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


@dataclasses.dataclass
class Reduced:
    window_ns: int
    devices: list[DeviceTrace]
    host: list[tuple[int, int, str]]           # (start, end, name)

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

    def program_events(self, program: str) -> int:
        """Executions of ``program`` on the first chip."""
        if not self.devices:
            return 0
        return sum(1 for _, _, p in self.devices[0].modules if p == program)

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
        middle of each gap: the innermost host span that covers it."""
        if not self.devices:
            return []
        busy = _merge(_intervals(self.devices[0].ops))
        sums: dict[str, int] = defaultdict(int)
        prev_end = None
        for s, e in busy:
            if prev_end is not None and s - prev_end >= MIN_GAP_NS:
                sums[self._host_at((prev_end + s) // 2)] += s - prev_end
            prev_end = e
        rows = sorted(sums.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in rows]

    def _host_at(self, t: int) -> str:
        best = None
        for s, e, name in self.host:
            if s <= t < e and (best is None or e - s < best[0]):
                best = (e - s, name)
        return f"host.{best[1]}" if best else "host.other"


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


def reduce(trace: Trace, window_ns: int | None = None) -> Reduced:
    """``window_ns``: the traced window's length by the host clock;
    without it, the span from the first to the last device event."""
    devices, host = [], []
    lo, hi = None, None
    for plane in trace.profile.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name.upper() \
                and "core" not in name.lower():
            dev = _device(plane, trace.meta.get(name, {}))
            if dev.ops:
                devices.append(dev)
                lo = dev.ops[0].start if lo is None else min(
                    lo, dev.ops[0].start)
                hi = max(hi or 0, max(o.end for o in dev.ops))
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith("tf_XLA"):
                    continue          # runtime worker pools, not the program
                for ev in line.events:
                    if ev.duration_ns > 0 and "::" not in ev.name:
                        host.append((int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns),
                                     _host_name(ev.name)))
    if window_ns is None:
        window_ns = (hi - lo) if devices else 0
    return Reduced(window_ns=int(window_ns), devices=devices, host=host)


def _host_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.()-]", "_", name.split(" ", 1)[0])[:48]


def _hlo_name(text: str) -> str:
    """``%fusion.235 = bf16[...] fusion(...)`` -> ``fusion.235``."""
    return text.split(" ", 1)[0].lstrip("%")


def _device(plane: Any, meta: dict[str, dict[str, Any]]) -> DeviceTrace:
    modules: list[tuple[int, int, str]] = []
    raw: list[tuple[int, int, str, dict]] = []
    for line in plane.lines:
        if line.name == _MODULES_LINE:
            for ev in line.events:
                s = int(ev.start_ns)
                modules.append((s, s + int(ev.duration_ns),
                                program_name(ev.name)))
        elif line.name == _OPS_LINE:
            for ev in line.events:
                s = int(ev.start_ns)
                raw.append((s, s + int(ev.duration_ns), ev.name,
                            meta.get(ev.name) or {}))
    modules.sort()
    starts = [m[0] for m in modules]
    raw.sort(key=lambda r: (r[0], -r[1]))
    ops: list[Op] = []
    stack: list[Op] = []
    for s, e, name, stats in raw:
        i = bisect.bisect_right(starts, s) - 1
        program = modules[i][2] if i >= 0 and s < modules[i][1] else "unknown"
        short = _hlo_name(name)
        category = str(stats.get("hlo_category") or "").strip().replace(
            " ", "_") or ("custom-call" if " custom-call(" in name
                          else re.sub(r"[.\d]+$", "", short))
        op = Op(start=s, end=e, self_ns=e - s, program=program,
                scope=_scope(stats, short), category=category,
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
    return DeviceTrace(name=plane.name, ops=ops, modules=modules)


def _scope(stats: dict, name: str) -> str:
    """From the op's ``tf_op`` (its named-scope path), else from its HLO
    name (a kernel is named after its scope)."""
    for text in (stats.get("tf_op"), name):
        if isinstance(text, str):
            for scope in SCOPES:
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
    their metadata's stats — for looking at one trace by hand before
    trusting the reduction."""
    out = []
    for plane in trace.profile.planes:
        meta = trace.meta.get(plane.name, {})
        for line in plane.lines:
            evs = []
            count = 0
            for ev in line.events:
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
                        "events": count, "first": evs})
    return out
