"""Waterfall table from a request-trace span tree (ISSUE 4 tooling).

``GET /v1/api/trace/{request_id}`` returns one request's span tree —
gateway root → router attempt N → provider call → engine phases. This tool
flattens that JSON into an indented waterfall so "where did request X
spend its 742 ms" is a table you read top to bottom:

    curl -s localhost:9100/v1/api/trace/<id> > trace.json
    python tools/trace_report.py trace.json
    python tools/trace_report.py --json trace.json   # machine-readable

Columns: start offset from the request's arrival (ms), duration (ms —
``open`` for a span that never closed, which the chaos tests assert never
happens), the owning layer, and the span name indented by tree depth.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

COLUMNS = ("start_ms", "dur_ms", "layer", "span")


def flatten(span: dict, depth: int = 0) -> list[dict]:
    """Depth-first rows: one per span, children in recorded order."""
    dur = span.get("duration_ms")
    row = {
        "start_ms": round(float(span.get("start_ms", 0.0)), 3),
        "dur_ms": round(float(dur), 3) if dur is not None else None,
        "layer": str(span.get("layer", "")),
        "span": "  " * depth + str(span.get("name", "?")),
        "depth": depth,
    }
    attrs = span.get("attrs")
    if isinstance(attrs, dict) and attrs:
        row["attrs"] = attrs
    rows = [row]
    for child in span.get("children", ()):
        rows.extend(flatten(child, depth + 1))
    return rows


def load_trace(path: Path) -> dict:
    doc = json.loads(path.read_text())
    if "spans" not in doc:
        raise ValueError(f"{path}: not a trace document (no 'spans' key — "
                         f"expected the /v1/api/trace/{{id}} response)")
    return doc


def report(paths: list[Path]) -> list[dict]:
    rows = []
    for p in paths:
        doc = load_trace(p)
        for row in flatten(doc["spans"]):
            row["file"] = p.name
            row["request_id"] = doc.get("request_id", "")
            rows.append(row)
    return rows


def format_table(rows: list[dict]) -> str:
    if not rows:
        return "(no spans found)"
    display = []
    for r in rows:
        d = {"start_ms": f"{r['start_ms']:.1f}",
             "dur_ms": ("open" if r["dur_ms"] is None
                        else f"{r['dur_ms']:.1f}"),
             "layer": r["layer"], "span": r["span"]}
        if "attrs" in r:
            d["span"] += "  " + " ".join(
                f"{k}={v}" for k, v in sorted(r["attrs"].items()))
        display.append(d)
    widths = {c: max(len(c), *(len(d[c]) for d in display)) for c in COLUMNS}
    lines = ["  ".join(c.rjust(widths[c]) if c.endswith("_ms") else
                       c.ljust(widths[c]) for c in COLUMNS),
             "  ".join("-" * widths[c] for c in COLUMNS)]
    for d in display:
        lines.append("  ".join(
            d[c].rjust(widths[c]) if c.endswith("_ms") else
            d[c].ljust(widths[c]) for c in COLUMNS))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Flatten /v1/api/trace/{id} JSON into an indented "
                    "waterfall table")
    ap.add_argument("files", nargs="+", type=Path)
    ap.add_argument("--json", action="store_true",
                    help="emit rows as JSON instead of a table")
    args = ap.parse_args(argv)
    rows = report(args.files)
    if args.json:
        print(json.dumps(rows, indent=2))
    else:
        for rid in {r["request_id"] for r in rows}:
            if rid:
                print(f"request {rid}")
        print(format_table(rows))
    return 0 if rows else 1


if __name__ == "__main__":
    sys.exit(main())
