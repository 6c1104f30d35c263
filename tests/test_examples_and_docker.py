"""The shipped example configs must load through the real ConfigLoader, and
the Docker assets must stay coherent (entrypoint checks, healthcheck
contract)."""
import shutil
from pathlib import Path

from llmapigateway_tpu.config.loader import ConfigLoader

REPO = Path(__file__).resolve().parent.parent


def test_example_configs_validate(tmp_path):
    shutil.copy(REPO / "providers.json.example", tmp_path / "providers.json")
    shutil.copy(REPO / "models_fallback_rules.json.example",
                tmp_path / "models_fallback_rules.json")
    loader = ConfigLoader(tmp_path, fallback_provider="openrouter")
    providers = loader.providers
    assert {"openrouter", "openai", "nebius", "local_tpu",
            "local_tiny"} <= set(providers)
    assert providers["local_tpu"].type == "local"
    assert providers["local_tpu"].engine.mesh == {"data": 1, "model": 8}
    assert providers["local_tpu"].engine.kv_layout == "paged"   # old files
    rules = loader.rules
    assert rules["free-rotation"].rotate_models is True
    chain = rules["llama-3-8b"].fallback_models
    assert chain[0].provider == "local_tpu"
    assert chain[1].retry_count == 1
    tuned = rules["tuned-qwen"].fallback_models[0]
    assert tuned.use_provider_order_as_fallback is True
    assert tuned.providers_order == ["Cerebras", "DeepInfra", "Fireworks"]


def test_env_example_keys_are_real_settings():
    """Every key in .env.example must actually be consumed by Settings (or
    be a provider key name) — no dead knobs."""
    from llmapigateway_tpu.config import settings as settings_mod

    src = (REPO / "llmapigateway_tpu" / "config" / "settings.py").read_text()
    keys = [line.split("=")[0].strip()
            for line in (REPO / ".env.example").read_text().splitlines()
            if line and not line.startswith("#") and "=" in line]
    provider_keys = {"OPENROUTER_API_KEY", "OPENAI_API_KEY", "NEBIUS_API_KEY"}
    for key in keys:
        assert key in src or key in provider_keys, f"dead .env key {key}"


def test_healthcheck_exit_codes(tmp_path, monkeypatch):
    """healthcheck.py: 0 against a live /health, 1 against a dead port."""
    import subprocess
    import sys

    import aiohttp.test_utils

    from tests.test_server_integration import Gateway

    hc = REPO / "docker" / "healthcheck.py"

    async def run():
        import asyncio as aio
        async with Gateway(tmp_path) as g:
            port = g.client.server.port
            # to_thread: subprocess.run would block the loop serving /health.
            ok = await aio.to_thread(
                subprocess.run, [sys.executable, str(hc)],
                env={"GATEWAY_PORT": str(port), "PATH": "/usr/bin:/bin",
                     # Generous budget: on a CI box saturated by a
                     # concurrent test run the loop serving /health can
                     # stall past the probe's default 3x4s window.
                     "HEALTHCHECK_ATTEMPTS": "8",
                     "HEALTHCHECK_TIMEOUT_S": "10"},
                capture_output=True, timeout=120)
            assert ok.returncode == 0, ok.stderr
        dead = aiohttp.test_utils.unused_port()
        bad = subprocess.run([sys.executable, str(hc)],
                             env={"GATEWAY_PORT": str(dead), "PATH": "/usr/bin:/bin"},
                             capture_output=True, timeout=60)
        assert bad.returncode == 1

    import asyncio
    # A loop of its own: whether the thread still HAS a current loop depends
    # on which test files this worker ran before (an `asyncio.run` leaves
    # none), and `get_event_loop()` then raises.
    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(run())
    finally:
        loop.close()


def test_entrypoint_checks_all_three_preconditions():
    sh = (REPO / "docker" / "entrypoint.sh").read_text()
    for needle in ("GATEWAY_API_KEY", "providers.json",
                   "models_fallback_rules.json", "exec python main.py"):
        assert needle in sh


def test_dockerfile_excludes_local_secrets():
    df = (REPO / "Dockerfile").read_text()
    assert "rm -f .env providers.json models_fallback_rules.json" in df
    assert "USER gateway" in df
    assert "HEALTHCHECK" in df


def test_dockerfile_copies_only_files_that_exist():
    """Every source a ``COPY`` line names (not ``--from=`` another stage)
    is in the tree: a deleted file left on a COPY line fails the build."""
    named = []
    for line in (REPO / "Dockerfile").read_text().splitlines():
        if line.startswith("COPY ") and "--from=" not in line:
            *sources, _dest = [w for w in line.split()[1:]
                               if not w.startswith("--")]
            named += sources
    assert "main.py" in named and "llmapigateway_tpu" in named
    assert not [src for src in named if not (REPO / src).exists()]
