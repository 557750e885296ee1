"""Rehearsals on the CPU: every cell end to end at a tiny size, and the
harness's refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as harness
from chipbench.tests import tiny


@pytest.mark.parametrize("workload", tiny.workloads())
def test_cell_runs_end_to_end_at_a_tiny_size(workload, monkeypatch,
                                             tmp_path):
    tiny.patch(monkeypatch, tmp_path)
    result = tiny.run(workload)
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    spec = harness.cell_spec(workload, 0)
    assert set(result["metrics"]) == {m["name"]
                                      for m in spec["end_to_end"]}
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "checks"
    json.dumps(result)


def _run_script(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gemm16k-50D50S",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_tpu():
    p = _run_script(tiny.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_refuses_in_a_directory_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(tiny.ROOT, "chipbench"),
                    tmp_path / "chipbench")
    p = _run_script(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no src/repro" in p.stderr


def _jax_with(kind: str):
    class Dev:
        platform = "tpu"
        device_kind = kind

    class Jax:
        @staticmethod
        def devices():
            return [Dev()]
    return Jax


def test_refuses_a_device_kind_missing_from_the_peaks():
    with pytest.raises(harness.Refused, match="peaks.json"):
        harness.check_device(_jax_with("TPU v9 imaginary"), 1)


def test_refuses_fewer_chips_than_the_cell_asks_for():
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.check_device(_jax_with("TPU v5 lite"), 4)


def test_refuses_a_traffic_loop_it_has_no_file_for():
    with pytest.raises(harness.Refused, match="no loop file"):
        harness.load_module("loops", "no_such_loop")


@pytest.mark.parametrize("workload", tiny.workloads())
def test_each_cell_names_files_that_exist(workload):
    spec = harness.cell_spec(workload, 0)
    for kind, name in (("drivers", spec["config"]["driver"]),
                       ("loops", spec["traffic"]["loop"])):
        assert os.path.exists(os.path.join(harness.HERE, kind,
                                           f"{name}.py")), (kind, name)
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           f"{m['name']}.py")), m["name"]
