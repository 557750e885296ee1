"""Device meshes: the P×Q grid of distributed SUMMA and a small host mesh.

Functions, not module constants: importing this module never touches jax
device state (a launcher sets XLA_FLAGS before first jax init).
"""
from __future__ import annotations

import jax


def _require_devices(need: int, what: str) -> None:
    """Descriptive failure instead of jax's opaque reshape error when the
    process has fewer devices than the requested mesh."""
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"{what} needs {need} devices but this process has {have}; "
            f"force host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need} *before* jax "
            f"initializes (or pass --devices {need} to the launcher)")


def make_host_mesh(data: int = 2, model: int = 2):
    """Small mesh over host CPU devices (tests)."""
    _require_devices(data * model, f"make_host_mesh({data}x{model})")
    return jax.make_mesh((data, model), ("data", "model"))


def make_grid_mesh(rows: int = 2, cols: int = 2,
                   axes: tuple[str, str] = ("row", "col")):
    """P×Q device grid for distributed SUMMA (core.summa)."""
    _require_devices(rows * cols, f"make_grid_mesh({rows}x{cols})")
    return jax.make_mesh((rows, cols), tuple(axes))
