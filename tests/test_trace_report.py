"""tools/trace_report.py: span-tree JSON → indented waterfall table."""
import json
import subprocess
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "tools"))
import trace_report  # noqa: E402

TRACE_DOC = {
    "request_id": "req-42",
    "complete": True,
    "spans": {
        "name": "gateway", "layer": "gateway",
        "start_ms": 0.0, "duration_ms": 742.1,
        "attrs": {"method": "POST", "status": 200},
        "children": [
            {"name": "router.attempt", "layer": "router",
             "start_ms": 1.2, "duration_ms": 120.0,
             "attrs": {"provider": "dead", "error": "[503] down"},
             "children": [
                 {"name": "provider.call", "layer": "provider",
                  "start_ms": 1.5, "duration_ms": 119.0}]},
            {"name": "router.attempt", "layer": "router",
             "start_ms": 122.0, "duration_ms": 618.0,
             "children": [
                 {"name": "provider.call", "layer": "provider",
                  "start_ms": 122.2, "duration_ms": 610.0,
                  "children": [
                      {"name": "engine.prefill", "layer": "engine",
                       "start_ms": 130.0, "duration_ms": 80.0},
                      {"name": "engine.decode", "layer": "engine",
                       "start_ms": 210.0, "duration_ms": None}]}]},
        ],
    },
}


def write_doc(tmp_path, doc=TRACE_DOC, name="trace.json") -> Path:
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return p


def test_flatten_depth_first_with_indent():
    rows = trace_report.flatten(TRACE_DOC["spans"])
    names = [r["span"] for r in rows]
    assert names == [
        "gateway",
        "  router.attempt", "    provider.call",
        "  router.attempt", "    provider.call",
        "      engine.prefill", "      engine.decode"]
    assert [r["depth"] for r in rows] == [0, 1, 2, 1, 2, 3, 3]
    # Start offsets and layers ride along.
    assert rows[1]["start_ms"] == 1.2 and rows[1]["layer"] == "router"
    # An unclosed span keeps a None duration (rendered as "open").
    assert rows[-1]["dur_ms"] is None


def test_report_and_table(tmp_path):
    rows = trace_report.report([write_doc(tmp_path)])
    assert all(r["request_id"] == "req-42" for r in rows)
    table = trace_report.format_table(rows)
    lines = table.splitlines()
    assert lines[0].split() == ["start_ms", "dur_ms", "layer", "span"]
    assert "742.1" in table and "engine.prefill" in table
    assert "open" in table          # the unclosed decode span
    # Attrs surface inline on the span column.
    assert "provider=dead" in table
    # Waterfall rows are in tree order: root first.
    assert lines[2].rstrip().endswith("method=POST status=200")


def test_cli_json_and_exit_codes(tmp_path):
    doc = write_doc(tmp_path)
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", "--json", str(doc)],
        capture_output=True, text=True, timeout=120,
        cwd=Path(__file__).resolve().parent.parent)
    assert proc.returncode == 0
    rows = json.loads(proc.stdout)
    assert len(rows) == 7
    assert rows[0]["span"] == "gateway"

    bad = tmp_path / "not_a_trace.json"
    bad.write_text(json.dumps({"value": 1}))
    proc = subprocess.run(
        [sys.executable, "tools/trace_report.py", str(bad)],
        capture_output=True, text=True, timeout=120,
        cwd=Path(__file__).resolve().parent.parent)
    assert proc.returncode != 0
