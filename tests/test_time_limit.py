"""No wait under tests/ outlasts the suite (tests/conftest.py,
``PER_TEST_LIMIT_S``): the per-test limit fails a test that runs past it,
by name, and every child process or awaited result a test waits for has a
timeout no longer than that limit."""
from __future__ import annotations

import ast
import os
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent

# A conftest of its own for a child run: tests/conftest.py itself is loaded
# by path, as pytest loads it, for what it sets at import.
LOAD_SUITE_CONFTEST = f"""\
import importlib.util
import pytest
spec = importlib.util.spec_from_file_location(
    "suite_conftest", {str(TESTS / "conftest.py")!r})
suite = importlib.util.module_from_spec(spec)
spec.loader.exec_module(suite)
"""
# ... and the suite's hook, with its limit argument set to one second.
CHILD_CONFTEST = LOAD_SUITE_CONFTEST + """
@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from suite.pytest_runtest_call(item, limit_s=1))
"""
CHILD_TESTS = """\
import time
def test_sleeps_past_the_limit(): time.sleep(30)
def test_stays_inside_it(): time.sleep(0.01)
"""


def test_a_test_past_the_limit_fails_by_name_and_the_next_one_runs(tmp_path):
    (tmp_path / "conftest.py").write_text(CHILD_CONFTEST)
    (tmp_path / "test_two.py").write_text(CHILD_TESTS)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_two.py", "-q", "-rf",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    out = run.stdout + run.stderr
    assert run.returncode == 1, out
    assert "1 failed, 1 passed" in out
    assert ("test_two.py::test_sleeps_past_the_limit ran past the per-test "
            "limit of 1 s") in out
    assert "time.sleep(30)" in out                  # the stack it stood in


PLAIN_MODULE = """\
import jax
def test_a_plain_module_compiles_at_low_effort():
    assert jax.config.values["jax_disable_most_optimizations"] is True
    assert jax.config.jax_enable_compilation_cache is True
"""
AOT_MODULE = """\
import jax
from test_aot_tpu_compile import full_effort_uncached
def test_under_the_aot_fixture_the_compiler_works_in_full():
    assert jax.config.values["jax_disable_most_optimizations"] is False
    assert jax.config.jax_enable_compilation_cache is False
"""


def test_tier1_compiles_at_low_effort_except_under_the_aot_fixture(tmp_path):
    """tests/conftest.py turns most of the CPU backend's optimisations off
    for every worker and child, whatever the environment said; the fixture
    the ``test_aot_tpu_*`` files share gives its module the compiler a served
    program gets, and hands it back (the plain module runs second)."""
    (tmp_path / "conftest.py").write_text(LOAD_SUITE_CONFTEST)
    (tmp_path / "test_a_aot.py").write_text(AOT_MODULE)
    (tmp_path / "test_b_plain.py").write_text(PLAIN_MODULE)
    run = subprocess.run(
        [sys.executable, "-m", "pytest", "test_a_aot.py", "test_b_plain.py",
         "-q", "-p", "no:cacheprovider", "-p", "no:xdist",
         "-p", "no:randomly"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_DISABLE_MOST_OPTIMIZATIONS": "0",
             "PYTHONPATH": os.pathsep.join([str(TESTS.parent), str(TESTS)])})
    out = run.stdout + run.stderr
    assert run.returncode == 0, out
    assert "2 passed" in out


def _limit() -> int:
    tree = ast.parse((TESTS / "conftest.py").read_text())
    return _constants(tree)["PER_TEST_LIMIT_S"]


def _constants(tree: ast.Module) -> dict:
    """A module's ``NAME = number`` lines."""
    return {t.id: node.value.value for node in tree.body
            if isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Constant)
            and isinstance(node.value.value, (int, float))
            for t in node.targets if isinstance(t, ast.Name)}


def test_the_limit_is_shorter_than_the_suite_and_longer_than_a_rehearsal():
    assert 400 <= _limit() <= 600


def _waits(path: Path):
    """(line, seconds or None) of every ``subprocess.run`` / ``check_*`` /
    ``.communicate`` / ``wait_for`` call in a file: the ``timeout`` it
    carries, a literal or a module constant; None where it carries none
    or one that cannot be read."""
    tree = ast.parse(path.read_text())
    named = _constants(tree)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        owner, name = node.func.value, node.func.attr
        of_subprocess = (isinstance(owner, ast.Name)
                         and owner.id == "subprocess"
                         and name in ("run", "call", "check_call",
                                      "check_output"))
        if not (of_subprocess or name in ("communicate", "wait_for")):
            continue
        given = [k.value for k in node.keywords if k.arg == "timeout"]
        if not given and name == "wait_for" and len(node.args) == 2:
            given = [node.args[1]]
        seconds = None
        if given and isinstance(given[0], ast.Constant):
            seconds = given[0].value
        elif given and isinstance(given[0], ast.Name):
            seconds = named.get(given[0].id)
        yield node.lineno, seconds


FILES = sorted(p for p in TESTS.rglob("*.py")
               if "bench_harness" not in p.parts and "fixtures" not in p.parts)


def test_every_wait_has_a_timeout_within_the_limit():
    """``tests/bench_harness/`` keeps its own waits (only a benchmark PR
    may edit it); everywhere else a child process or an awaited result is
    given up on before the per-test limit would have to."""
    limit = _limit()
    found = [(str(p.relative_to(TESTS)), line, seconds)
             for p in FILES for line, seconds in _waits(p)]
    assert len(found) > 20                  # the walk sees the calls
    # ... in both forms: an awaited result, and a child process.
    assert {("test_stop_and_cancel.py", 30), ("test_compilation_cache.py", 120)
            } <= {(f, s) for f, _, s in found}
    over = [w for w in found if w[2] is None or not 0 < w[2] <= limit]
    assert not over, over


@pytest.mark.parametrize("line, seconds", [
    ("subprocess.run(['x'])", None),
    ("subprocess.run(['x'], timeout=900)", 900),
    ("subprocess.run(['x'], timeout=LONG)", 1800),
    ("subprocess.check_output(['x'], timeout=n)", None),
    ("proc.communicate()", None),
    ("asyncio.wait_for(q.get(), 2)", 2),
    ("await asyncio.wait_for(q.get(), timeout=30)", 30)])
def test_the_walk_reads_a_call_as_written(tmp_path, line, seconds):
    src = tmp_path / "sample.py"
    src.write_text(f"LONG = 1800\nasync def f(n):\n    {line}\n")
    assert list(_waits(src)) == [(3, seconds)]
