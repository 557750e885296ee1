"""chip_smoke.py refuses to run anywhere but on a TPU, from a checkout.

The phases themselves need the chip; here only the refusals are checked:
each must exit non-zero before any phase and print no result line.
"""
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _run(script, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _assert_refused(r, why):
    assert r.returncode != 0
    assert why in r.stderr
    assert "=== phase" not in r.stdout
    assert '"ok"' not in r.stdout


def test_refuses_cpu_backend(tmp_path):
    _assert_refused(_run(SCRIPT, tmp_path), "no TPU")


def test_refuses_without_checkout(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, lone)
    _assert_refused(_run(str(lone), tmp_path), "checkout")
