"""Shared test fixtures.

Multi-device policy: this conftest forces 4 host CPU devices through
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (jax reads the flag
lazily, at first backend initialization) so the distributed SUMMA tests can
run in-process on 2×2 / 1×4 / 4×1 grids.  Single-device semantics are
unchanged — unsharded ops still run on device 0 — and subprocess-based
tests (checkpoint, legacy summa) set their own flags.  When the flag cannot
take effect (the backend was already initialized with fewer devices, or an
explicit XLA_FLAGS pinned another count), multi-device tests auto-skip via
the ``host_grid_devices`` fixture; ``launch.mesh`` raises a descriptive
error instead of jax's opaque one.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

HOST_DEVICES = 4
_FLAG = f"--xla_force_host_platform_device_count={HOST_DEVICES}"


def _force_host_devices() -> None:
    if "xla_force_host_platform_device_count" in os.environ.get(
            "XLA_FLAGS", ""):
        return  # respect an explicit setting (e.g. the CI multi-device lane)
    from jax._src import xla_bridge as xb
    if not xb.backends_are_initialized():
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + _FLAG).strip()


_force_host_devices()

import jax  # noqa: E402


@pytest.fixture(scope="session")
def host_grid_devices() -> int:
    """≥ 4 host devices, else skip (the force flag must land before jax's
    backend initializes; it cannot be applied retroactively)."""
    if jax.device_count() < HOST_DEVICES:
        pytest.skip(
            f"needs {HOST_DEVICES} host devices — run with XLA_FLAGS="
            f"{_FLAG} set before jax initializes")
    return jax.device_count()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def assert_tree_allclose(a, b, rtol=1e-5, atol=1e-5):
    la = jax.tree.leaves(a)
    lb = jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32),
                                   rtol=rtol, atol=atol)
