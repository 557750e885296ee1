"""repro.config — process-global settings facade.

The toolkit grew one environment variable per subsystem knob
(``REPRO_TUNE_CACHE``, ``REPRO_TUNE_CACHE_ONLY``, ``REPRO_TUNE_DEVICE``,
``REPRO_OBS``, ``REPRO_OBS_TRACE``).  Env vars are the right *bootstrap*
mechanism — CI lanes and shell one-liners flip them without code — but a
library embedding repro should not have to mutate ``os.environ``.  This
module is the one idiomatic entry point::

    import repro
    repro.configure(device="tpu-v6e", tune_cache="/tmp/plans.json",
                    obs_trace="run.jsonl")
    ...
    repro.configure(obs=False)        # selective teardown
    repro.config.reset()              # back to env/default bootstrap

Precedence (highest wins), documented here and enforced by tests:

1. values set through :func:`configure` (process-local overrides),
2. the corresponding environment variable,
3. the built-in default.

The consumers (``tune.search.cache_path``/``cache_only``,
``tune.device.detect_device``) re-read settings on every call, so a
``configure`` between two dispatches takes effect immediately — same
contract the env vars always had.  ``obs``/``obs_trace`` are *eager*: the
tracer is (re)installed at configure time, mirroring the import-time env
bootstrap in :mod:`repro.obs`.

This module imports only the stdlib at import time: ``import repro``
stays jax-free (tests force the platform before jax loads), and the
tune/obs consumers can import it without cycles.
"""
from __future__ import annotations

import os
from typing import Any, Optional

__all__ = ["KNOWN_SETTINGS", "configure", "get", "get_bool", "reset",
           "use_compile_cache"]

#: JAX's persistent compile cache when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: a fixed directory of the checkout (the path is part of what the
#: cache keys on, so it must not move between runs)
CHECKOUT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")

#: setting name -> (environment variable, default)
KNOWN_SETTINGS: dict[str, tuple[str, Optional[str]]] = {
    "device": ("REPRO_TUNE_DEVICE", None),
    "tune_cache": ("REPRO_TUNE_CACHE", None),
    "tune_cache_only": ("REPRO_TUNE_CACHE_ONLY", None),
    "obs": ("REPRO_OBS", None),
    "obs_trace": ("REPRO_OBS_TRACE", None),
}

_UNSET = object()

#: process-local overrides (highest precedence); value None = "explicitly
#: cleared" — falls through to the env var like an unset override would.
_overrides: dict[str, Any] = {}


def configure(**settings) -> None:
    """Set process-global repro settings; see module docstring.

    Unknown names raise ``KeyError`` (listing the valid ones) — typos
    should fail loudly, not silently configure nothing.  Passing ``None``
    clears that override, restoring env/default precedence.  Booleans are
    accepted for the flag-like settings (``tune_cache_only``, ``obs``).
    """
    unknown = set(settings) - set(KNOWN_SETTINGS)
    if unknown:
        raise KeyError(
            f"unknown setting(s) {sorted(unknown)}; "
            f"known: {sorted(KNOWN_SETTINGS)}")
    if "device" in settings and settings["device"] is not None:
        # validate eagerly — a bad device key should fail at configure
        # time, not at the first dispatch three layers deep
        from repro.tune.device import DEVICE_TABLE
        dev = settings["device"]
        if dev not in DEVICE_TABLE:
            raise KeyError(f"device={dev!r} not in device table "
                           f"{sorted(DEVICE_TABLE)}")
    for name, value in settings.items():
        if value is None:
            _overrides.pop(name, None)
        else:
            _overrides[name] = value
    if "obs" in settings or "obs_trace" in settings:
        _apply_obs()


def get(name: str, default: Any = _UNSET) -> Any:
    """Resolved value of ``name``: override > env var > default."""
    if name not in KNOWN_SETTINGS:
        raise KeyError(f"unknown setting {name!r}; "
                       f"known: {sorted(KNOWN_SETTINGS)}")
    if name in _overrides:
        return _overrides[name]
    env_var, builtin = KNOWN_SETTINGS[name]
    env = os.environ.get(env_var)
    if env is not None:
        return env
    return builtin if default is _UNSET else default


def get_bool(name: str) -> bool:
    """Flag-style resolution: False for unset/""/"0"/False, else True."""
    value = get(name)
    if value is None or value is False:
        return False
    if value is True:
        return True
    return str(value) not in ("", "0")


def reset() -> None:
    """Drop every override and re-bootstrap obs from the environment."""
    had_obs = "obs" in _overrides or "obs_trace" in _overrides
    _overrides.clear()
    if had_obs:
        _apply_obs()


def _apply_obs() -> None:
    """(Re)install the tracer from the resolved obs/obs_trace settings.

    Imported lazily: obs is stdlib-only but this keeps config importable
    from anywhere in the package without cycles."""
    from repro import obs
    trace_path = get("obs_trace")
    if trace_path:
        obs.configure(enabled=True, trace_path=str(trace_path))
    elif get_bool("obs"):
        obs.configure(enabled=True)
    else:
        obs.configure(enabled=False)


def use_compile_cache() -> str:
    """Place JAX's persistent compile cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and nothing is set in
    code (JAX reads the variable itself); otherwise the cache lives in
    :data:`CHECKOUT_COMPILE_CACHE`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_COMPILE_CACHE)
    return CHECKOUT_COMPILE_CACHE
