"""repro.config facade: override > env > default precedence contract.

These tests pin the documented resolution order for every knob the env
sprawl (REPRO_TUNE_*, REPRO_OBS*) migrated into ``repro.configure``, and
that the tune consumers (cache path, cache-only mode, device forcing)
actually re-read the facade per call.  No jax needed for the precedence
core; the consumer tests import tune lazily.
"""
import os

import pytest

import repro
from repro import config


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    for env_var, _ in config.KNOWN_SETTINGS.values():
        monkeypatch.delenv(env_var, raising=False)
    config.reset()
    yield
    config.reset()


def test_facade_is_the_top_level_surface():
    assert repro.configure is config.configure
    assert repro.config is config


def test_default_then_env_then_override_precedence(monkeypatch):
    assert config.get("tune_cache") is None            # built-in default
    monkeypatch.setenv("REPRO_TUNE_CACHE", "/env/plans.json")
    assert config.get("tune_cache") == "/env/plans.json"
    repro.configure(tune_cache="/override/plans.json")  # facade wins
    assert config.get("tune_cache") == "/override/plans.json"
    repro.configure(tune_cache=None)                   # clear → env again
    assert config.get("tune_cache") == "/env/plans.json"
    monkeypatch.delenv("REPRO_TUNE_CACHE")
    assert config.get("tune_cache") is None


def test_unknown_setting_fails_loudly():
    with pytest.raises(KeyError):
        repro.configure(tune_cash="/tmp/x")
    with pytest.raises(KeyError):
        config.get("tune_cash")


def test_get_bool_flag_semantics(monkeypatch):
    assert config.get_bool("tune_cache_only") is False   # unset
    for falsy in ("", "0"):
        monkeypatch.setenv("REPRO_TUNE_CACHE_ONLY", falsy)
        assert config.get_bool("tune_cache_only") is False
    monkeypatch.setenv("REPRO_TUNE_CACHE_ONLY", "1")
    assert config.get_bool("tune_cache_only") is True
    repro.configure(tune_cache_only=False)               # override beats env
    assert config.get_bool("tune_cache_only") is False
    repro.configure(tune_cache_only=True)
    assert config.get_bool("tune_cache_only") is True


def test_reset_restores_env_bootstrap(monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_DEVICE", "tpu-v4")
    repro.configure(device="tpu-v5e")
    assert config.get("device") == "tpu-v5e"
    config.reset()
    assert config.get("device") == "tpu-v4"


def test_device_override_validated_eagerly_and_consumed():
    with pytest.raises(KeyError):
        repro.configure(device="tpu-v99")                # typo fails NOW
    from repro.tune.device import detect_device
    repro.configure(device="tpu-v6e")
    assert detect_device().kind == "tpu-v6e"
    repro.configure(device="gpu-a100")                   # re-read per call
    assert detect_device().kind == "gpu-a100"
    repro.configure(device=None)
    assert detect_device().kind == "cpu-interpret"       # back to detection


def test_tune_cache_consumers_read_facade(tmp_path, monkeypatch):
    from repro.tune import search
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "env.json"))
    assert search.cache_path() == str(tmp_path / "env.json")
    repro.configure(tune_cache=str(tmp_path / "facade.json"))
    assert search.cache_path() == str(tmp_path / "facade.json")
    assert search.cache_only() is False
    repro.configure(tune_cache_only=True)
    assert search.cache_only() is True


def test_obs_configure_is_eager(tmp_path):
    from repro import obs
    was_enabled = obs.is_enabled()
    try:
        repro.configure(obs=True)
        assert obs.is_enabled()
        repro.configure(obs=False)
        assert not obs.is_enabled()
        trace = tmp_path / "trace.jsonl"
        repro.configure(obs_trace=str(trace))
        assert obs.is_enabled()
        obs.event("cfg.test", "serve", ok=1)
        repro.configure(obs_trace=None, obs=False)       # close the tracer
        assert not obs.is_enabled()
        assert trace.exists() and "cfg.test" in trace.read_text()
    finally:
        config.reset()
        obs.configure(enabled=was_enabled)


def test_env_bootstrap_untouched_by_facade(monkeypatch):
    # configure() must never write to os.environ — env vars stay what the
    # shell set, so child processes inherit the bootstrap, not overrides
    monkeypatch.setenv("REPRO_TUNE_CACHE", "/env/plans.json")
    repro.configure(tune_cache="/override.json")
    assert os.environ["REPRO_TUNE_CACHE"] == "/env/plans.json"


def test_compile_cache_follows_env_var(tmp_path, monkeypatch):
    """JAX reads JAX_COMPILATION_CACHE_DIR itself: nothing is set in code."""
    import jax
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert config.use_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_checkout(monkeypatch):
    import jax
    updates = []
    monkeypatch.setattr(jax.config, "update", lambda *a: updates.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(root, ".jax_cache")
    assert config.use_compile_cache() == want
    assert updates == [("jax_compilation_cache_dir", want)]
