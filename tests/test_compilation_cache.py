"""Where the engine puts the persistent XLA compilation cache.

The path is part of what a cache hit depends on, so the program chooses
exactly one: a fixed, git-ignored directory at the root of the checkout —
unless ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside, in
which case the engine sets no directory at all. These tests pin that
contract (engine/engine.py ``_enable_compilation_cache``); they read the
jax config, compile nothing, and restore what they touch.
"""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from llmapigateway_tpu.engine.engine import (_CACHE_DIR,
                                             _enable_compilation_cache)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_config(monkeypatch):
    """Run with no directory configured and none in the environment;
    restore the process-global jax setting afterwards."""
    saved = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", saved)


def test_env_var_set_engine_sets_no_directory(cache_config, monkeypatch,
                                              tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "outer"))
    _enable_compilation_cache("")
    _enable_compilation_cache(str(tmp_path / "explicit"))
    # Neither the default nor an explicit engine setting competes with it.
    assert jax.config.jax_compilation_cache_dir is None
    assert not (tmp_path / "explicit").exists()


def test_unset_uses_fixed_ignored_path_in_checkout(cache_config):
    _enable_compilation_cache("")
    assert jax.config.jax_compilation_cache_dir == str(_CACHE_DIR)
    assert _CACHE_DIR == REPO / ".xla_cache"
    assert f"{_CACHE_DIR.name}/" in (REPO / ".gitignore").read_text().split()


def test_fixed_path_is_the_same_in_another_process(tmp_path):
    """No pid, time, temp name, home directory or machine fingerprint in
    it: a second process, started elsewhere with another HOME, resolves
    the identical directory."""
    env = {**os.environ, "HOME": str(tmp_path), "PYTHONPATH": str(REPO)}
    out = subprocess.run(
        [sys.executable, "-c",
         "from llmapigateway_tpu.engine.engine import _CACHE_DIR; "
         "print(_CACHE_DIR)"],
        capture_output=True, text=True, timeout=120, cwd=tmp_path, env=env,
        check=True)
    assert out.stdout.strip().splitlines()[-1] == str(_CACHE_DIR)


def test_explicit_directory_is_used_when_env_unset(cache_config, tmp_path):
    _enable_compilation_cache(str(tmp_path / "explicit"))
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "explicit")
    assert (tmp_path / "explicit").is_dir()


def test_off_leaves_jax_settings_alone(cache_config):
    _enable_compilation_cache("off")
    assert jax.config.jax_compilation_cache_dir is None


def test_unwritable_directory_raises(cache_config, tmp_path):
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("x")
    with pytest.raises(OSError):
        _enable_compilation_cache(str(blocker / "cache"))
    assert jax.config.jax_compilation_cache_dir is None
