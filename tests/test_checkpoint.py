"""Checkpoint save/restore/async + elastic re-mesh."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt


def _tree():
    k = jax.random.PRNGKey(0)
    return {"a": jax.random.normal(k, (8, 16)),
            "b": {"c": jnp.arange(10, dtype=jnp.int32),
                  "d": jnp.ones((4,), jnp.bfloat16)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    path = str(tmp_path / "ck")
    man = ckpt.save(path, t, step=7, extra={"note": "x"})
    assert man["step"] == 7
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), t)
    got, man2 = ckpt.restore(path, like)
    assert man2["step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_restore_detects_corruption(tmp_path):
    t = _tree()
    path = str(tmp_path / "ck")
    ckpt.save(path, t, step=0)
    import json
    with open(os.path.join(path, "manifest.json")) as f:
        man = json.load(f)
    man["hash"] = "0" * 64
    with open(os.path.join(path, "manifest.json"), "w") as f:
        json.dump(man, f)
    with pytest.raises(IOError):
        ckpt.restore(path, t)


def test_restore_shape_mismatch(tmp_path):
    t = _tree()
    path = str(tmp_path / "ck")
    ckpt.save(path, t, step=0)
    bad = dict(t, a=jnp.zeros((4, 4)))
    with pytest.raises(ValueError):
        ckpt.restore(path, bad)


def test_async_checkpointer_keeps_latest(tmp_path):
    saver = ckpt.AsyncCheckpointer(str(tmp_path), keep=2)
    t = _tree()
    for s in (1, 2, 3, 4):
        saver.submit(t, s)
        saver.wait()
    dirs = sorted(os.listdir(tmp_path))
    assert dirs == ["step_00000003", "step_00000004"]
    assert saver.latest().endswith("step_00000004")


_REMESH = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import ckpt

    path = sys.argv[1]
    # save from a 4-device (2x2) mesh
    mesh4 = jax.make_mesh((2, 2), ("data", "model"))
    x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                       NamedSharding(mesh4, P("data", "model")))
    ckpt.save(path, {"x": x}, step=1)
    # restore onto a *different* mesh (4x1) — elastic re-mesh
    mesh2 = jax.make_mesh((4,), ("data",))
    sh = NamedSharding(mesh2, P("data", None))
    got, _ = ckpt.restore(path, {"x": x}, sharding_tree=sh)
    assert got["x"].sharding == sh
    np.testing.assert_array_equal(np.asarray(got["x"]),
                                  np.arange(64.0).reshape(8, 8))
    print("REMESH_OK")
""")


@pytest.mark.slow
def test_elastic_remesh(tmp_path):
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", _REMESH, str(tmp_path / "ck")],
        env=env, capture_output=True, text=True, timeout=600)
    assert "REMESH_OK" in out.stdout, (out.stdout, out.stderr)
