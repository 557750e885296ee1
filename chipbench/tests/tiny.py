"""Every cell of BENCHMARK.json at a size the CPU holds, driven through the
harness with its look for a chip skipped.  The tiny sizes are the ``tiny``
blocks of each cell's configuration and traffic files, so a new cell
brings its own."""
from __future__ import annotations

import argparse
import copy
import json
import os

from chipbench import run as harness

ROOT = harness.ROOT


def workloads() -> list:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


_CELL_SPEC = harness.cell_spec


def _tiny(full: dict, tiny: dict) -> dict:
    """``full`` with the entries of its ``tiny`` block put in: a group
    (a dict) takes the tiny group's keys over its own, anything else is
    replaced."""
    out = {k: v for k, v in full.items() if k != "tiny"}
    for k, v in copy.deepcopy(tiny).items():
        out[k] = ({**out[k], **v} if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def spec(workload: str, seed: int) -> dict:
    """The cell's spec with its configuration and traffic made tiny by the
    ``tiny`` blocks of their files, and its limits set for the tiny size
    where the configuration's ``tiny`` block gives ``limits`` (a GEMM's
    limits hold at the tiny size as they stand)."""
    s = _CELL_SPEC(workload, seed)
    tiny = dict(s["config"].get("tiny", {}))
    limits = tiny.pop("limits", {})
    s["config"] = _tiny(s["config"], tiny)
    s["traffic"] = _tiny(s["traffic"], s["traffic"].get("tiny", {}))
    s["limits"] = {k: limits.get(k, v) for k, v in s["limits"].items()}
    return s


def patch(monkeypatch, tmp_path) -> None:
    """Skip the look for a chip, keep run state in ``tmp_path`` and make
    every cell tiny."""
    monkeypatch.setattr(harness, "STATE", str(tmp_path / "state"))
    for var in ("JAX_COMPILATION_CACHE_DIR", "REPRO_TUNE_CACHE",
                "REPRO_TUNE_CACHE_ONLY"):
        monkeypatch.setenv(var, "")
    prepare = harness.prepare_jax

    def prepare_without_cache(workload):
        jax = prepare(workload)
        jax.config.update("jax_enable_compilation_cache", False)
        return jax

    monkeypatch.setattr(harness, "prepare_jax", prepare_without_cache)
    monkeypatch.setattr(harness, "check_device",
                        lambda jax, chips: jax.devices())
    monkeypatch.setattr(harness, "cell_spec", spec)


def run(workload: str, seed: int = 2 ** 31 + 11, seconds: float = 0.3,
        trace: int = 0) -> dict:
    return harness.run(argparse.Namespace(workload=workload, seed=seed,
                                          seconds=seconds, trace=trace))
