"""A run whose timed path is broken underneath reads ``correct: false``.

Each test skips the harness's look for a chip, makes the cell tiny and
drives the rest of a run with one fault planted in the program: an answer
(a GEMM output tile, a served token) altered where it is produced."""
import pytest

from chipbench.tests import tiny


def _cells(driver):
    import json
    import os
    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    drivers = {}
    for c in bench["configs"]:
        with open(os.path.join(tiny.ROOT, c["file"])) as f:
            drivers[c["name"]] = json.load(f)["driver"]
    return [w["name"] for w in bench["workloads"]
            if drivers[w["config"]] == driver]


@pytest.mark.parametrize("workload", _cells("gemm"))
def test_gemm_output_tile_altered(workload, monkeypatch, tmp_path):
    import jax.numpy as jnp

    import repro.tune
    tiny.patch(monkeypatch, tmp_path)
    assert tiny.run(workload)["correct"]
    real = repro.tune.mp_matmul

    def altered(a, b, c=None, **kw):
        out = real(a, b, c, **kw)
        t = out.tile
        bufs = tuple(buf.at[t:2 * t, :t].set(0) for buf in out.bufs)
        return type(out)(bufs, out.cls, out.tile, out.shape, out.fset)

    monkeypatch.setattr(repro.tune, "mp_matmul", altered)
    result = tiny.run(workload)
    assert not result["correct"]
    assert jnp.isfinite(max(c["value"] for c in result["checks"].values()))


@pytest.mark.parametrize("workload", _cells("serve"))
def test_served_token_altered(workload, monkeypatch, tmp_path):
    import jax.numpy as jnp

    import repro.serve.engine as E
    tiny.patch(monkeypatch, tmp_path)
    real = E._sample_tokens

    def altered(logits, temps, keys, n):
        tok = real(logits, temps, keys, n)
        return jnp.where(n == 3, (tok + 1) % logits.shape[-1], tok)

    monkeypatch.setattr(E, "_sample_tokens", altered)
    result = tiny.run(workload)
    assert not result["correct"]
    assert result["checks"]["max_logit_gap"]["value"] > \
        result["checks"]["max_logit_gap"]["limit"]


@pytest.mark.parametrize("workload", _cells("serve"))
def test_unfinished_request(workload, monkeypatch, tmp_path):
    """A request the engine drops never comes back: not correct."""
    import repro.serve.engine as E
    tiny.patch(monkeypatch, tmp_path)
    real = E.Engine.submit
    dropped = []

    def drop_first(self, req):
        if not dropped:
            dropped.append(req)
            return None
        return real(self, req)

    monkeypatch.setattr(E.Engine, "submit", drop_first)
    result = tiny.run(workload)
    assert result["failed"] == 1 and not result["correct"]
