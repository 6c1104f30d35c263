"""Chrome trace-event export for the scheduler flight recorder (ISSUE 7).

``GET /v1/api/flight`` returns the engine's resident per-step and
lifecycle records; this tool converts them into Chrome trace-event JSON
(the format Perfetto / ``chrome://tracing`` load natively), so "what did
the scheduler decide, step by step" becomes a zoomable timeline instead
of a table:

    curl -s localhost:9100/v1/api/flight > flight.json
    python tools/flight_report.py flight.json > flight.trace.json
    # open ui.perfetto.dev and load flight.trace.json

Tracks per engine (one trace-event process):

* ``scheduler`` — one duration slice per step record, named by its
  composition (``decode[8]``, ``prefill``, ``mixed``…), with the full
  record (burst depth, tokens, queue depth, fitted vs measured step
  time, clamp engagement) in ``args`` for the detail pane. A
  disaggregated engine (ISSUE 13) tags its step records with a ``pool``
  name and each pool gets its OWN lane (``scheduler:prefill`` /
  ``scheduler:decode``) so pool interference — the thing disaggregation
  exists to remove — is visible as lane overlap; pool-less records keep
  the single ``scheduler`` lane, byte-identical to pre-pool traces;
* ``lifecycle`` — instant events for admissions, sheds, and prefix-cache
  evictions (request ids attached, linking back to
  ``/v1/api/trace/{id}`` via the records' ``seq`` numbers); engine
  supervisor transitions (ISSUE 14) render as global instants named by
  the state entered (``supervisor:restarting``, ``supervisor:draining``)
  so an incident's RESTART/DRAIN edges bracket the steps they
  interrupted;
* ``prefill dispatch`` — one slice per compiled prefill call (ISSUE 26's
  PREFILL record), named ``prefill[rows x bucket]@lowest-highest start``;
* ``slot N`` — one slice per request's residency in a slot, from its
  admit record to its finish record, named by request id.

Timestamps are the recorder's monotonic clock mapped to microseconds
with the earliest resident record at 0.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any

TID_SCHED = 0
TID_LIFECYCLE = 1
TID_SLOT_BASE = 2
# Per-pool scheduler lanes (ISSUE 13): far above any real slot index so
# slot tracks and pool tracks can never collide in one process.
TID_POOL_BASE = 10000
# Compiled prefill dispatches on the worker thread (ISSUE 26).
TID_PREFILL = 9999
POOL_LANE_ORDER = ("prefill", "decode", "unified")


def _step_name(rec: dict[str, Any]) -> str:
    kind = rec.get("step_kind", "step")
    depth = rec.get("burst_depth")
    name = f"{kind}[{depth}]" if depth else kind
    # Spec steps carry their accepted-draft yield (ISSUE 10): surface it
    # in the slice name so acceptance is readable from the timeline
    # without opening each slice's detail pane.
    acc = rec.get("spec_accepted")
    if kind == "spec" and isinstance(acc, int):
        name += f" +{acc}acc"
    return name


def _meta(pid: int, tid: int | None, name: str, value: str) -> dict:
    ev: dict[str, Any] = {"ph": "M", "pid": pid, "name": name,
                          "args": {"name": value}, "ts": 0}
    if tid is not None:
        ev["tid"] = tid
    return ev


def engine_events(engine: str, records: list[dict[str, Any]],
                  pid: int, epoch: float) -> list[dict[str, Any]]:
    """Trace events for one engine's record list (seq order preserved)."""
    events: list[dict[str, Any]] = [
        _meta(pid, None, "process_name", f"engine:{engine}"),
        _meta(pid, TID_SCHED, "thread_name", "scheduler"),
        _meta(pid, TID_LIFECYCLE, "thread_name", "lifecycle"),
    ]

    def us(t: float) -> int:
        return int(round((t - epoch) * 1e6))

    admits: dict[str, dict[str, Any]] = {}      # rid -> admit record
    slots_seen: set[int] = set()
    pools_seen: set[str] = set()
    prefill_seen = False
    for rec in records:
        kind = rec.get("kind")
        dur_us = int(round(float(rec.get("dur_ms", 0.0)) * 1000.0))
        if kind == "step":
            pool = rec.get("pool")
            if pool:
                tid = TID_POOL_BASE + (
                    POOL_LANE_ORDER.index(pool)
                    if pool in POOL_LANE_ORDER else len(POOL_LANE_ORDER))
                pools_seen.add(str(pool))
            else:
                tid = TID_SCHED
            events.append({
                "ph": "X", "pid": pid, "tid": tid,
                "name": _step_name(rec), "cat": "step",
                "ts": us(rec["t"]) - dur_us, "dur": dur_us,
                "args": {k: v for k, v in rec.items() if k != "t"},
            })
            continue
        if kind == "prefill":
            # One compiled prefill dispatch (ISSUE 26): a slice over the
            # jitted call, named by its shape and start positions.
            prefill_seen = True
            events.append({
                "ph": "X", "pid": pid, "tid": TID_PREFILL,
                "name": "prefill[%sx%s]@%s-%s" % (
                    rec.get("rows"), rec.get("bucket"), rec.get("pos_lo"),
                    rec.get("pos_hi")),
                "cat": "prefill", "ts": us(rec["t"]) - dur_us,
                "dur": dur_us,
                "args": {k: v for k, v in rec.items() if k != "t"},
            })
            continue
        if kind == "supervisor":
            # Engine lifecycle transition (ISSUE 14): a global instant
            # named by the state entered (supervisor:restarting,
            # supervisor:draining, …) so an incident's RESTART/DRAIN
            # edges bracket the steps they interrupted.
            events.append({
                "ph": "i", "s": "g", "pid": pid, "tid": TID_LIFECYCLE,
                "name": f"supervisor:{rec.get('state', '?')}",
                "cat": "supervisor", "ts": us(rec["t"]),
                "args": {k: v for k, v in rec.items() if k != "t"},
            })
            continue
        if kind == "profile":
            # Profiler capture boundary (ISSUE 8): named instant so a
            # flight timeline visually brackets the XLA capture window —
            # the request_id carries the capture's trace directory.
            events.append({
                "ph": "i", "s": "g", "pid": pid, "tid": TID_LIFECYCLE,
                "name": f"profile:{rec.get('phase', '?')}",
                "cat": "profiler", "ts": us(rec["t"]),
                "args": {k: v for k, v in rec.items() if k != "t"},
            })
            continue
        rid = rec.get("request_id", "")
        if kind == "admit":
            if rid:
                admits[rid] = rec
            slots_seen.add(int(rec.get("slot", -1)))
        if kind == "finish" and rid and rid in admits:
            adm = admits.pop(rid)
            slot = int(rec.get("slot", -1))
            start = us(adm["t"])
            events.append({
                "ph": "X", "pid": pid, "tid": TID_SLOT_BASE + slot,
                "name": rid, "cat": "request",
                "ts": start, "dur": max(0, us(rec["t"]) - start),
                "args": {"admit_seq": adm["seq"], "finish_seq": rec["seq"],
                         "reason": rec.get("reason"),
                         "tokens": rec.get("tokens"),
                         "queue_wait_ms": adm.get("queue_wait_ms"),
                         "cached_tokens": adm.get("cached_tokens")},
            })
            slots_seen.add(slot)
        events.append({
            "ph": "i", "s": "p", "pid": pid, "tid": TID_LIFECYCLE,
            "name": str(kind), "cat": "lifecycle", "ts": us(rec["t"]),
            "args": {k: v for k, v in rec.items() if k != "t"},
        })
    for slot in sorted(slots_seen):
        if slot >= 0:
            events.append(_meta(pid, TID_SLOT_BASE + slot, "thread_name",
                                f"slot {slot}"))
    if prefill_seen:
        events.append(_meta(pid, TID_PREFILL, "thread_name",
                            "prefill dispatch"))
    for pool in sorted(pools_seen):
        tid = TID_POOL_BASE + (POOL_LANE_ORDER.index(pool)
                               if pool in POOL_LANE_ORDER
                               else len(POOL_LANE_ORDER))
        events.append(_meta(pid, tid, "thread_name", f"scheduler:{pool}"))
    return events


def convert(doc: dict[str, Any]) -> dict[str, Any]:
    """The /v1/api/flight response (or a bare ``{"records": [...]}``) as a
    Chrome trace-event document."""
    engines = doc.get("engines")
    if engines is None:
        if "records" not in doc:
            raise ValueError("not a flight document (no 'engines' or "
                             "'records' key — expected the /v1/api/flight "
                             "response)")
        engines = {"engine": doc}
    # Epoch = the earliest slice START (a duration record's window begins
    # dur_ms before its timestamp), so no event lands at a negative ts.
    all_ts = [rec["t"] - float(rec.get("dur_ms", 0.0)) / 1000.0
              for block in engines.values()
              for rec in block.get("records", ())]
    epoch = min(all_ts) if all_ts else 0.0
    events: list[dict[str, Any]] = []
    for pid, name in enumerate(sorted(engines), start=1):
        events.extend(engine_events(
            name, engines[name].get("records", []), pid, epoch))
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Convert /v1/api/flight JSON into Chrome trace-event "
                    "JSON (load in ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("file", type=Path,
                    help="flight JSON file, or '-' for stdin")
    ap.add_argument("--indent", type=int, default=None,
                    help="pretty-print with this indent")
    args = ap.parse_args(argv)
    raw = (sys.stdin.read() if str(args.file) == "-"
           else args.file.read_text())
    out = convert(json.loads(raw))
    print(json.dumps(out, indent=args.indent, sort_keys=True))
    return 0 if out["traceEvents"] else 1


if __name__ == "__main__":
    sys.exit(main())
