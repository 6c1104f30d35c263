"""graftlint v2 whole-program pass: cross-module transitive findings
(positive + negative fixture mini-packages per upgraded rule), call-chain
payloads, suppressions, SARIF output, the incremental cache, and the CLI
modes (--format sarif, --changed, self-run speed via the cache)."""
from __future__ import annotations

import json
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import llmapigateway_tpu
from llmapigateway_tpu.analysis import (ALL_RULES, analyze_program,
                                        summarize_source)
from llmapigateway_tpu.analysis.cache import LintCache
from llmapigateway_tpu.analysis.program import Program
from llmapigateway_tpu.analysis.reporter import render_sarif

PACKAGE_DIR = Path(llmapigateway_tpu.__file__).parent
FIXTURES = Path(__file__).parent / "fixtures" / "graftlint"


def _by_rule(findings):
    out = {}
    for f in findings:
        out.setdefault(f.rule, []).append(f)
    return out


# -- fixture mini-packages ----------------------------------------------------

def test_transitive_bad_package_fires_all_three_rules():
    findings = analyze_program([FIXTURES / "transitive_bad"])
    rules = _by_rule(findings)
    assert set(rules) == {"async-blocking", "lock-discipline",
                          "timeout-discipline"}

    # async-blocking: both handlers, chains with every file:line hop.
    ab = rules["async-blocking"]
    entries = {f.path for f in ab}
    assert entries == {"server/handlers.py"}
    one_hop = [f for f in ab if "get_config()" in f.message]
    two_hop = [f for f in ab if "get_config_deep()" in f.message]
    assert one_hop and two_hop
    assert any("time.sleep" in f.message for f in one_hop)
    # The chain carries the full hop list, terminal site included.
    deep = next(f for f in two_hop if "time.sleep" in f.message)
    assert len(deep.chain) == 3
    assert deep.chain[0].path == "server/handlers.py"
    assert deep.chain[1].path == "util/helpers.py"
    assert deep.chain[-1].note.startswith("time.sleep()")

    # lock-discipline: external mutate + external read + thread-reachable
    # loop-guarded access, with the dispatch chain.
    ld = rules["lock-discipline"]
    msgs = " | ".join(f.message for f in ld)
    assert "evict() mutates store._table" in msgs
    assert "snapshot() reads store._table" in msgs
    loop_f = next(f for f in ld if "guarded-by: loop" in f.message)
    assert "worker-thread dispatch" in loop_f.message
    assert any("dispatches" in h.note for h in loop_f.chain)

    # timeout-discipline: the helper outside providers/ is flagged, chain
    # rooted at the providers/ call site.
    td = rules["timeout-discipline"]
    assert [f.path for f in td] == ["util/httpio.py"]
    assert td[0].chain[0].path == "providers/flow.py"


def test_transitive_good_package_is_clean():
    assert analyze_program([FIXTURES / "transitive_good"]) == []


def test_device_sync_bad_package_fires_with_chains():
    findings = analyze_program([FIXTURES / "device_sync_bad"])
    rules = _by_rule(findings)
    ds = rules["device-sync-discipline"]
    # Every entry anchors at the serving layer.
    assert {f.path for f in ds} == {"server/handlers.py"}
    # The transitive case: handler -> state/device.py fetch, full chain.
    hop = next(f for f in ds if "call hop" in f.message)
    assert "state/device.py" in hop.message
    assert hop.chain[-1].path == "state/device.py"
    # The lexical cases rode along (np.asarray + .block_until_ready).
    msgs = " | ".join(f.message for f in ds)
    assert "np.asarray()" in msgs or "float()" in msgs
    assert ".block_until_ready()" in msgs
    # async-blocking overlaps only on its own float()-of-jax subset.
    assert set(rules) <= {"device-sync-discipline", "async-blocking"}


def test_device_sync_good_package_is_clean():
    """to_thread dispatch creates no edge, and the `# device-sync: ok`
    marker exempts the documented helper from BOTH transitive passes
    (a marked helper's vetted fetch must not resurface as
    async-blocking)."""
    assert analyze_program([FIXTURES / "device_sync_good"]) == []


def test_program_findings_respect_suppressions(tmp_path):
    pkg = tmp_path / "server"
    pkg.mkdir()
    (tmp_path / "util").mkdir()
    (pkg / "h.py").write_text(textwrap.dedent("""\
        from ..util.io import slow
        async def handler(request):
            return slow()  # graftlint: disable=async-blocking — startup only
    """))
    (tmp_path / "util" / "io.py").write_text(
        "import time\ndef slow():\n    time.sleep(1)\n")
    assert analyze_program([tmp_path]) == []
    # Remove the suppression: the finding appears.
    (pkg / "h.py").write_text(textwrap.dedent("""\
        from ..util.io import slow
        async def handler(request):
            return slow()
    """))
    findings = analyze_program([tmp_path])
    assert [f.rule for f in findings] == ["async-blocking"]


def test_report_only_filters_without_shrinking_the_world(tmp_path):
    (tmp_path / "server").mkdir()
    (tmp_path / "util").mkdir()
    (tmp_path / "server" / "h.py").write_text(
        "from ..util.io import slow\n"
        "async def handler(request):\n    return slow()\n")
    (tmp_path / "util" / "io.py").write_text(
        "import time\ndef slow():\n    time.sleep(1)\n")
    # Only the helper "changed": the finding's primary location is the
    # handler file, so nothing is reported — but analysis still resolved
    # the cross-module chain (reporting for the handler file shows it).
    assert analyze_program([tmp_path],
                           report_only={"util/io.py"}) == []
    assert len(analyze_program([tmp_path],
                               report_only={"server/h.py"})) == 1


# -- resolution unit checks ---------------------------------------------------

def test_devirtualization_is_unique_name_only(tmp_path):
    # Two classes defining the same method name: no resolution, no finding.
    (tmp_path / "server").mkdir()
    (tmp_path / "a.py").write_text(textwrap.dedent("""\
        import time
        class A:
            def helper_op(self):
                time.sleep(1)
        class B:
            def helper_op(self):
                return 1
    """))
    (tmp_path / "server" / "h.py").write_text(textwrap.dedent("""\
        async def handler(request, svc):
            return svc.helper_op()
    """))
    assert analyze_program([tmp_path]) == []
    # Make the name unique: the chain resolves.
    (tmp_path / "a.py").write_text(textwrap.dedent("""\
        import time
        class A:
            def helper_op(self):
                time.sleep(1)
    """))
    findings = analyze_program([tmp_path])
    assert [f.rule for f in findings] == ["async-blocking"]
    assert "A.helper_op" in findings[0].chain[0].note


def test_to_thread_reference_creates_no_edge():
    src = textwrap.dedent("""\
        import asyncio, time
        def blocking():
            time.sleep(1)
        async def handler(request):
            return await asyncio.to_thread(blocking)
    """)
    summ = summarize_source(src, "server/h.py")
    program = Program({"server/h.py": summ})
    assert program.findings() == []
    # ...and thread_refs recorded the dispatch for the reachability pass.
    assert summ["functions"]["handler"]["thread_refs"] == [["blocking", 5]]


def test_nested_sync_def_called_inline_is_an_edge():
    src = textwrap.dedent("""\
        import time
        async def handler(request):
            def fmt():
                time.sleep(1)
            return fmt()
    """)
    summ = summarize_source(src, "server/h.py")
    program = Program({"server/h.py": summ})
    findings = program.findings()
    assert [f.rule for f in findings] == ["async-blocking"]
    assert "handler.fmt" in findings[0].chain[0].note


# -- SARIF --------------------------------------------------------------------

def test_sarif_carries_chains_as_related_locations_and_codeflows():
    findings = analyze_program([FIXTURES / "transitive_bad"])
    doc = json.loads(render_sarif(findings, checked_files=6,
                                  rules=ALL_RULES))
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "graftlint"
    assert run["properties"]["checkedFiles"] == 6
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {"async-blocking", "lock-discipline",
            "timeout-discipline"} <= rule_ids
    chained = [r for r in run["results"] if "codeFlows" in r]
    assert chained, "interprocedural results must carry codeFlows"
    for res in chained:
        related = res["relatedLocations"]
        flow = res["codeFlows"][0]["threadFlows"][0]["locations"]
        assert len(flow) == len(related) >= 1
        for loc in related:
            phys = loc["physicalLocation"]
            assert phys["artifactLocation"]["uri"]
            assert phys["region"]["startLine"] >= 1
    # Multi-hop chains exist (the deep async-blocking fixture).
    assert any(len(r["relatedLocations"]) >= 3 for r in chained)


# -- the incremental cache ----------------------------------------------------

def test_cache_hit_skips_reanalysis_and_survives_touch(tmp_path):
    f = tmp_path / "server"
    f.mkdir()
    target = f / "h.py"
    target.write_text("import time\nasync def h(r):\n    time.sleep(1)\n")
    cache_path = tmp_path / "cache.json"

    cache = LintCache(cache_path, rule_names=("async-blocking",))
    assert cache.lookup(target, "server/h.py") is None
    from llmapigateway_tpu.analysis import RULES_BY_NAME, analyze_source
    src = target.read_text()
    findings = analyze_source(src, target,
                              [RULES_BY_NAME["async-blocking"]], f.parent)
    cache.store(target, "server/h.py", src, findings,
                summarize_source(src, target, f.parent))
    cache.save()

    # Fresh instance: mtime hit, findings round-trip exactly.
    cache2 = LintCache(cache_path, rule_names=("async-blocking",))
    hit = cache2.lookup(target, "server/h.py")
    assert hit is not None
    assert [x.to_dict() for x in hit[0]] == [x.to_dict() for x in findings]
    assert hit[1]["functions"]["h"]["blocking"]

    # touch(1): mtime differs, sha256 matches — still a hit.
    time.sleep(0.01)
    target.touch()
    cache3 = LintCache(cache_path, rule_names=("async-blocking",))
    assert cache3.lookup(target, "server/h.py") is not None

    # Content change: miss.
    target.write_text("import asyncio\nasync def h(r):\n    await asyncio.sleep(1)\n")
    cache4 = LintCache(cache_path, rule_names=("async-blocking",))
    assert cache4.lookup(target, "server/h.py") is None


def test_cache_key_invalidates_on_rule_set_change(tmp_path):
    target = tmp_path / "x.py"
    target.write_text("x = 1\n")
    cache_path = tmp_path / "cache.json"
    c1 = LintCache(cache_path, rule_names=("a", "b"))
    c1.store(target, "x.py", "x = 1\n", [], None)
    c1.save()
    assert LintCache(cache_path, rule_names=("a", "b")).lookup(
        target, "x.py") is not None
    assert LintCache(cache_path, rule_names=("a",)).lookup(
        target, "x.py") is None


# -- CLI ----------------------------------------------------------------------

def _cli(*args, cwd=None):
    return subprocess.run(
        [sys.executable, "-m", "llmapigateway_tpu.analysis", *args],
        capture_output=True, text=True, cwd=cwd, timeout=120)


def test_cli_sarif_output(tmp_path):
    bad = tmp_path / "server"
    bad.mkdir()
    (bad / "h.py").write_text(
        "import time\nasync def h(r):\n    time.sleep(1)\n")
    proc = _cli(str(tmp_path), "--format", "sarif")
    assert proc.returncode == 1
    doc = json.loads(proc.stdout)
    assert doc["runs"][0]["results"][0]["ruleId"] == "async-blocking"


def test_cli_program_pass_reports_chains(tmp_path):
    (tmp_path / "server").mkdir()
    (tmp_path / "util").mkdir()
    (tmp_path / "server" / "h.py").write_text(
        "from ..util.io import slow\n"
        "async def handler(request):\n    return slow()\n")
    (tmp_path / "util" / "io.py").write_text(
        "import time\ndef slow():\n    time.sleep(1)\n")
    proc = _cli(str(tmp_path))
    assert proc.returncode == 1
    assert "1 call hop(s)" in proc.stdout
    assert "util/io.py:3" in proc.stdout
    # --no-program drops the interprocedural finding.
    proc = _cli(str(tmp_path), "--no-program")
    assert proc.returncode == 0


def test_cli_changed_mode_with_shared_cache(tmp_path):
    """--changed lints only files differing from the ref (sharing the
    cache), for pre-commit use. Exercised against a scratch git repo."""
    repo = tmp_path / "repo"
    pkg = repo / "llmapigateway_tpu" / "server"
    pkg.mkdir(parents=True)
    git = ["git", "-C", str(repo)]
    subprocess.run(["git", "init", "-q", str(repo)], check=True, timeout=60)
    subprocess.run([*git, "config", "user.email", "t@t"], check=True,
                   timeout=60)
    subprocess.run([*git, "config", "user.name", "t"], check=True,
                   timeout=60)
    clean = pkg / "clean.py"
    clean.write_text("import asyncio\nasync def ok(r):\n"
                     "    await asyncio.sleep(0)\n")
    subprocess.run([*git, "add", "-A"], check=True, timeout=60)
    subprocess.run([*git, "commit", "-qm", "seed"], check=True, timeout=60)
    # New (untracked) file with a violation + an unchanged clean file.
    bad = pkg / "bad.py"
    bad.write_text("import time\nasync def h(r):\n    time.sleep(1)\n")

    # Point --changed's repo discovery at the scratch repo by running the
    # module from inside it is not possible (the module resolves its own
    # package dir), so drive the helper directly instead.
    from llmapigateway_tpu.analysis.__main__ import (_changed_files,
                                                     _repo_root)
    changed = _changed_files("HEAD", repo)
    assert changed == [bad]

    # The full CLI --changed path runs against THIS repo: it must at
    # minimum exit cleanly (0/1) and honor the shared cache file (a clean
    # checkout of a commit has nothing to lint, and then nothing to cache).
    cache = tmp_path / "gl-cache.json"
    proc = _cli("--changed", "HEAD", "--cache", str(cache))
    assert proc.returncode in (0, 1), proc.stderr
    assert cache.exists() or not _changed_files(
        "HEAD", _repo_root(PACKAGE_DIR))


def test_self_run_is_fast_via_incremental_cache(tmp_path):
    """The tier-1 gate's budget: a warm self-run over the whole package
    must finish in well under 10 s thanks to the cache."""
    cache = tmp_path / "selfrun-cache.json"
    proc = _cli(str(PACKAGE_DIR), "--cache", str(cache))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    t0 = time.monotonic()
    proc = _cli(str(PACKAGE_DIR), "--cache", str(cache))
    warm_s = time.monotonic() - t0
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert warm_s < 10.0, f"warm self-run took {warm_s:.1f}s"
