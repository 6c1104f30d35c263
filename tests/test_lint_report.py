"""tools/lint_report.py: SARIF → grouped text/markdown tables with call
chains, fed by the real renderer (analysis/reporter.render_sarif) so the
two ends of the pipe can never drift apart."""
from __future__ import annotations

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

from llmapigateway_tpu.analysis import ALL_RULES, analyze_program
from llmapigateway_tpu.analysis.reporter import render_sarif

TOOL = Path(__file__).parent.parent / "tools" / "lint_report.py"
FIXTURES = Path(__file__).parent / "fixtures" / "graftlint"

spec = importlib.util.spec_from_file_location("lint_report", TOOL)
lint_report = importlib.util.module_from_spec(spec)
spec.loader.exec_module(lint_report)


def _sarif_doc() -> dict:
    findings = analyze_program([FIXTURES / "transitive_bad"])
    return json.loads(render_sarif(findings, checked_files=6,
                                   rules=ALL_RULES))


def test_group_results_by_rule_sorted_by_location():
    grouped = lint_report.group_results(_sarif_doc())
    assert set(grouped) == {"async-blocking", "lock-discipline",
                            "timeout-discipline"}
    rows = grouped["async-blocking"]
    assert [r["uri"] for r in rows] == ["server/handlers.py"] * len(rows)
    assert rows == sorted(rows, key=lambda r: (r["uri"], r["line"], r["col"]))
    # Chains survive the SARIF round-trip.
    deep = [r for r in rows if len(r["chain"]) >= 3]
    assert deep and deep[0]["chain"][-1][0] == "util/helpers.py"


def test_text_render_groups_and_chains():
    grouped = lint_report.group_results(_sarif_doc())
    out = lint_report.render_text(grouped, 6)
    assert "== async-blocking" in out
    assert "== lock-discipline" in out
    assert "  server/handlers.py:" in out
    assert "      1. " in out                 # indented chain hops
    assert "across 6 file(s)" in out


def test_markdown_render_has_tables():
    grouped = lint_report.group_results(_sarif_doc())
    out = lint_report.render_markdown(grouped, 6)
    assert out.startswith("# graftlint report")
    assert "## `timeout-discipline` (1)" in out
    assert "| location | message |" in out
    assert "call chain" in out


def test_cli_exit_codes_and_stdin(tmp_path):
    doc = _sarif_doc()
    sarif_file = tmp_path / "r.sarif"
    sarif_file.write_text(json.dumps(doc))
    proc = subprocess.run([sys.executable, str(TOOL), str(sarif_file)],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1               # findings present
    assert "finding(s)" in proc.stdout

    clean = {"runs": [{"tool": {"driver": {"name": "graftlint"}},
                       "properties": {"checkedFiles": 3}, "results": []}]}
    proc = subprocess.run([sys.executable, str(TOOL), "-"],
                          input=json.dumps(clean),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0
    assert "clean" in proc.stdout

    proc = subprocess.run([sys.executable, str(TOOL), "/no/such.sarif"],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
