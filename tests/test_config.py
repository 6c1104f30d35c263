"""Config loader/schema tests: parsing, validation, hot reload semantics."""
import pytest

from llmapigateway_tpu.config.loader import (
    ConfigLoader, parse_providers, parse_rules, cross_validate, resolve_api_key)
from llmapigateway_tpu.config.schemas import ConfigError, ProviderDetails
from llmapigateway_tpu.config.settings import Settings


def test_settings_from_env(tmp_path, monkeypatch):
    (tmp_path / ".env").write_text(
        'GATEWAY_API_KEY="dotenv-key"\nGATEWAY_PORT=9999\n# comment\n')
    monkeypatch.setenv("GATEWAY_PORT", "9200")   # env wins over .env
    monkeypatch.setenv("ALLOWED_ORIGINS", "http://a.com, http://b.com")
    s = Settings.from_env(base_dir=tmp_path)
    assert s.gateway_api_key == "dotenv-key"
    assert s.gateway_port == 9200
    assert s.allowed_origins == ["http://a.com", "http://b.com"]
    assert s.db_dir == tmp_path / "db"


def test_loader_parses_reference_shape(config_dir):
    loader = ConfigLoader(config_dir, fallback_provider="openrouter")
    assert set(loader.providers) == {"fakeup", "openrouter"}
    assert loader.providers["fakeup"].type == "remote_http"
    rule = loader.rules["gw/test-model"]
    assert [fm.model for fm in rule.fallback_models] == ["real-model-a", "real-model-b"]
    assert rule.rotate_models is False           # "false" string coerced
    assert loader.rules["gw/rotating"].rotate_models is True


def test_local_provider_entry():
    providers = parse_providers([
        {"local_tpu": {"type": "local",
                       "engine": {"preset": "tinyllama-1.1b",
                                  "mesh": {"data": 1, "model": 8}}}}])
    assert providers["local_tpu"].engine.preset == "tinyllama-1.1b"
    assert providers["local_tpu"].engine.mesh == {"data": 1, "model": 8}


@pytest.mark.parametrize("engine,message", [
    ({"mesh": {"pipe": 2, "model": 4}},
     "unknown mesh axis 'pipe': the axes are data, expert, model"),
    ({"mesh": {"seq": 8}},
     "unknown mesh axis 'seq': the axes are data, expert, model"),
    ({"mesh": {"tensor": 8}},
     "unknown mesh axis 'tensor': the axes are data, expert, model"),
    ({"seq_attention": "ulysses"}, "seq_attention"),
    ({"kv_layout": "contiguous"},
     "'paged' is the only value; the contiguous layout was removed"),
])
def test_local_provider_refuses_a_mode_the_engine_no_longer_has(engine,
                                                                message):
    """A ``providers.json`` written for pipeline or sequence parallelism
    is refused when it is loaded — by axis name, with the axes that exist
    — and does not serve on fewer chips than it says; an option that is
    gone is an unknown field (``extra="forbid"``)."""
    with pytest.raises(ConfigError, match=message) as err:
        parse_providers([{"local_tpu": {"type": "local", "engine": {
            "preset": "tinyllama-1.1b", **engine}}}])
    assert "provider 'local_tpu' invalid" in str(err.value)


def test_kv_layout_is_accepted_for_old_files_with_its_one_value():
    providers = parse_providers([{"local_tpu": {"type": "local", "engine": {
        "preset": "tinyllama-1.1b", "kv_layout": "paged"}}}])
    assert providers["local_tpu"].engine.kv_layout == "paged"


def test_local_provider_requires_engine():
    with pytest.raises(ConfigError, match="requires 'engine'"):
        parse_providers([{"bad": {"type": "local"}}])


def test_remote_requires_baseurl():
    with pytest.raises(ConfigError, match="baseUrl"):
        parse_providers([{"bad": {"apikey": "X"}}])


def test_unknown_provider_in_rule_rejected():
    providers = parse_providers([{"p1": {"baseUrl": "http://x"}}])
    rules = parse_rules([{"gateway_model_name": "m",
                          "fallback_models": [{"provider": "nope", "model": "x"}]}])
    with pytest.raises(ConfigError, match="unknown provider"):
        cross_validate(providers, rules)


def test_hot_reload_swap_and_reject(config_dir):
    loader = ConfigLoader(config_dir, fallback_provider="openrouter")
    v0 = loader.version
    # Valid edit → swap.
    (config_dir / "models_fallback_rules.json").write_text(
        '[{"gateway_model_name": "gw/new", '
        '"fallback_models": [{"provider": "fakeup", "model": "m"}]}]')
    ok, err = loader.reload_rules()
    assert ok and err is None
    assert set(loader.rules) == {"gw/new"} and loader.version == v0 + 1
    # Invalid edit → rejected, old config retained.
    (config_dir / "models_fallback_rules.json").write_text('{"not": "a list"}')
    ok, err = loader.reload_rules()
    assert not ok and "list" in err
    assert set(loader.rules) == {"gw/new"}


def test_write_raw_validates_before_writing(config_dir):
    loader = ConfigLoader(config_dir, fallback_provider="openrouter")
    original = (config_dir / "models_fallback_rules.json").read_text()
    with pytest.raises(ConfigError):
        loader.write_raw("rules", '[{"gateway_model_name": "x", '
                                  '"fallback_models": [{"provider": "ghost", "model": "m"}]}]')
    # File untouched on validation failure (stricter than the reference).
    assert (config_dir / "models_fallback_rules.json").read_text() == original
    # Comments survive a valid save.
    text = '[\n  // keep me\n  {"gateway_model_name": "gw/ok", ' \
           '"fallback_models": [{"provider": "fakeup", "model": "m"}]}\n]'
    loader.write_raw("rules", text)
    assert "// keep me" in (config_dir / "models_fallback_rules.json").read_text()
    assert "gw/ok" in loader.rules


def test_resolve_api_key_env_vs_literal(monkeypatch):
    monkeypatch.setenv("MY_KEY_ENV", "resolved-secret")
    assert resolve_api_key(ProviderDetails(baseUrl="http://x", apikey="MY_KEY_ENV")) \
        == "resolved-secret"
    assert resolve_api_key(ProviderDetails(baseUrl="http://x", apikey="sk-literal-123")) \
        == "sk-literal-123"
    assert resolve_api_key(ProviderDetails(baseUrl="http://x")) is None


def test_duplicate_provider_rejected():
    with pytest.raises(ConfigError, match="duplicate"):
        parse_providers([{"a": {"baseUrl": "http://x"}},
                         {"a": {"baseUrl": "http://y"}}])
