"""The control (the reference one format below the configuration's
precision, standing in for the program) fails the limits; the program
passes them.  At the tiny size on the CPU; the readings at each cell's own
size come from ``chipbench/calibrate.py`` on the chip (PERF.md)."""
import pytest

from chipbench import calibrate
from chipbench.tests import tiny


@pytest.mark.parametrize("workload", tiny.workloads())
def test_control_fails_and_program_passes(workload, monkeypatch, tmp_path):
    tiny.patch(monkeypatch, tmp_path)
    tiny.harness.prepare_jax(workload)
    limits = tiny.spec(workload, 0)["limits"]
    for seed in (3, 2 ** 33 + 1):
        r = calibrate.readings(workload, seed, 0.2)
        assert all(v <= limits[k] for k, v in r["program"].items()), r
        assert any(v > limits[k] for k, v in r["control"].items()), r
