"""Refinement-solver battery: convergence, residual-driven escalation,
ladder prefetch (zero mid-solve retunes), and distributed parity.

The distributed tests reuse the conftest 4-host-device policy
(``host_grid_devices`` fixture).  Sizes are kept small — the 512×512
acceptance run lives in ``launch/solve.py``.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import MPMatrix, accuracy as ACC
from repro.core.formats import DEFAULT_FORMATS, format_set
from repro.solve import (SolveConfig, diag_dominant, graded_spd,
                         rhs_for_solution, solve)
from repro.solve import lu as LU
from repro.solve.refine import _balanced_map, _ladder
from repro.tune import dispatch as TD


@pytest.fixture(autouse=True)
def _hermetic_tune(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_TUNE_CACHE", str(tmp_path / "plans.json"))
    TD.clear_registry()
    TD.reset_resolution_counters()
    yield
    TD.clear_registry()
    TD.reset_resolution_counters()


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def test_blocked_lu_reconstructs_operator():
    """At a uniform-HIGH map the trailing updates are fp32-exact, so L·U
    must reconstruct the quantized operator to fp32 roundoff."""
    n, t = 64, 16
    a = diag_dominant(n, seed=0).astype(np.float32)
    pa = np.full((n // t, n // t), DEFAULT_FORMATS.high, np.int8)

    def trailing(l21, u12, step):
        return l21.astype(np.float32) @ u12.astype(np.float32)

    lu_, stats = LU.blocked_lu(a, pa, t, trailing)
    lo = np.tril(lu_, -1) + np.eye(n, dtype=np.float32)
    up = np.triu(lu_)
    err = np.abs(lo @ up - a).max() / np.abs(a).max()
    assert err < 1e-5
    assert 0.0 < stats["gemm_fraction"] < 1.0


def test_triangular_solves_invert_lu():
    n, t = 64, 16
    a = diag_dominant(n, seed=1).astype(np.float32)
    pa = np.full((n // t, n // t), DEFAULT_FORMATS.high, np.int8)
    lu_, _ = LU.blocked_lu(a, pa, t,
                           lambda l, u, k: l.astype(np.float32) @ u)
    b = np.linspace(-1, 1, n).astype(np.float32)[:, None]
    x = LU.solve_upper(lu_, LU.solve_unit_lower(lu_, b, t), t)
    assert np.abs(a @ x - b).max() < 1e-3


def test_unblocked_lu_rejects_zero_pivot():
    with pytest.raises(ZeroDivisionError, match="pivot"):
        LU.unblocked_lu(np.zeros((4, 4), np.float32))


def test_hpl_metric_zero_for_exact_solution():
    a = diag_dominant(32, seed=2)
    x, b = rhs_for_solution(a, nrhs=2, seed=3)
    assert ACC.hpl_mxp_metric(a, x, b) < 1e-3
    # a perturbed solution scores measurably worse
    assert ACC.hpl_mxp_metric(a, x + 0.1, b) > ACC.hpl_mxp_metric(a, x, b)


def test_promotion_mask_targets_loud_tiles():
    """Within a row whose scale is set by a loud tile, only the loud tile
    exceeds its share of the HIGH-format budget — the relatively quiet
    tiles of the same row are spared (that is what keeps the escalated map
    cheaper than uniform-HIGH)."""
    n, t = 64, 16
    fset = DEFAULT_FORMATS
    rng = np.random.default_rng(0)
    a = np.full((n, n), 1e-3)
    a[:t, :t] = 300.0 * (1.0 + rng.standard_normal((t, t)))   # loud tile
    pa = np.full((n // t, n // t), fset.low, np.int8)
    stored = np.asarray(MPMatrix.from_dense(
        jnp.asarray(a, jnp.float32), pa, t, fset).to_dense())
    x = np.ones((n, 1))
    mask = ACC.promotion_mask(a, stored, x, pa, t, fset)
    assert mask[0, 0]
    assert not mask[0, 1:].any()     # quiet tiles of the loud row spared
    contrib = ACC.tile_rounding_contribution(a, stored, x, t)
    assert contrib[0, 0] > 100 * contrib[0, 1]
    # already-HIGH tiles are never "promoted"
    pa_hi = np.full_like(pa, fset.high)
    stored_hi = np.asarray(MPMatrix.from_dense(
        jnp.asarray(a, jnp.float32), pa_hi, t, fset).to_dense())
    assert not ACC.promotion_mask(a, stored_hi, x, pa_hi, t, fset).any()


def test_promotion_mask_flags_nonfinite_storage():
    """fp8 saturation (NaN storage) counts as infinite rounding error."""
    n, t = 32, 16
    fset = DEFAULT_FORMATS
    a = np.full((n, n), 1.0)
    a[:t, :t] = 1e4            # overflows fp8 e4m3
    pa = np.full((2, 2), fset.low8, np.int8)
    stored = np.asarray(MPMatrix.from_dense(
        jnp.asarray(a, jnp.float32), pa, t, fset).to_dense())
    assert not np.all(np.isfinite(stored))
    mask = ACC.promotion_mask(a, stored, np.ones((n, 1)), pa, t, fset)
    assert mask[0, 0]


def test_requantize_recovers_precision_from_exact_source():
    n, t = 32, 16
    fset = DEFAULT_FORMATS
    rng = np.random.default_rng(0)
    a = rng.standard_normal((n, n)).astype(np.float32)
    lo_map = np.full((2, 2), fset.low, np.int8)
    hi_map = np.full((2, 2), fset.high, np.int8)
    m = MPMatrix.from_dense(jnp.asarray(a), lo_map, t, fset)
    rounded = np.asarray(m.to_dense())
    assert np.abs(rounded - a).max() > 0          # bf16 rounding happened
    # promotion with the exact source recovers the dropped bits
    promoted = m.requantize(hi_map, dense=jnp.asarray(a))
    np.testing.assert_array_equal(np.asarray(promoted.to_dense()), a)
    # without the source the rounded values are all that is left
    stale = m.requantize(hi_map)
    np.testing.assert_array_equal(np.asarray(stale.to_dense()), rounded)
    with pytest.raises(ValueError, match="tile grid"):
        m.requantize(np.full((4, 4), fset.high, np.int8))


# ---------------------------------------------------------------------------
# Plan prefetch
# ---------------------------------------------------------------------------

def test_resolve_solve_plans_covers_ladder_and_registry():
    fset = DEFAULT_FORMATS
    cfg = SolveConfig(tile=16, ratio_high=0.0)
    maps = _ladder(cfg, 4, 4, weights=np.ones((64, 64)))
    book = TD.resolve_solve_plans(maps, 16, fset, nrhs=16)
    for rung in range(len(maps)):
        assert ("residual", rung) in book
        for step in range(3):
            assert ("trail", step, rung) in book
    assert len(book["keys"]) == len(maps) * 4
    # every prefetched problem now resolves from the registry, not the model
    TD.reset_resolution_counters()
    prob = TD.solve_gemm_problem(maps[0], 16, 1, fset)
    _plan, source = TD.resolve_plan(prob)
    assert source == "registry"
    assert TD.fresh_resolutions() == 0


def test_resolve_solve_plans_rejects_bad_nrhs():
    with pytest.raises(ValueError, match="multiple of tile"):
        TD.resolve_solve_plans([np.zeros((2, 2), np.int8)], 16,
                               DEFAULT_FORMATS, nrhs=8)


def test_fresh_resolution_counters():
    TD.reset_resolution_counters()
    assert TD.fresh_resolutions() == 0
    prob = TD.solve_gemm_problem(
        np.full((2, 2), DEFAULT_FORMATS.low, np.int8), 16, 1,
        DEFAULT_FORMATS)
    TD.resolve_plan(prob)
    assert TD.fresh_resolutions() == 1          # cost-model resolution
    TD.resolve_plan(prob)
    assert TD.fresh_resolutions() == 1          # registry hit is not fresh


# ---------------------------------------------------------------------------
# End-to-end solves (single device)
# ---------------------------------------------------------------------------

def _check_converged(rep, xt, fwd_tol=0.05):
    assert rep.converged, rep.metric_history
    assert rep.metric <= 1.0
    assert rep.fresh_resolutions == 0
    err = float(np.abs(rep.x - xt).max() / np.abs(xt).max())
    assert err < fwd_tol, err


def test_solve_benign_operator_needs_no_escalation():
    """An operator whose entries are exactly LOW-representable has zero
    storage-rounding residual: refinement converges at 0D:100S with no
    escalation (the residual-driven loop only promotes when the map is
    actually the bottleneck)."""
    a = diag_dominant(64, seed=0)
    a = np.asarray(jnp.asarray(a, jnp.bfloat16), np.float64)  # bf16-exact
    xt, b = rhs_for_solution(a, seed=1)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.0, max_sweeps=20))
    _check_converged(rep, xt)
    assert rep.escalations == 0
    assert rep.final_ratio == "0D:100S"


def test_solve_escalates_ill_conditioned_and_stays_cheaper():
    """The acceptance shape in miniature: 0D:100S start, stall, promotion
    of the loud tiles, convergence with the map still cheaper than
    uniform-HIGH."""
    a = graded_spd(128, cond=1e4, rho=0.9, seed=0)
    xt, b = rhs_for_solution(a, seed=1)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.0, max_sweeps=30))
    _check_converged(rep, xt)
    assert rep.escalations >= 1
    assert rep.storage_bytes < rep.uniform_high_bytes
    assert rep.factorizations == rep.escalations + 1
    assert rep.ratio_history[0] == "0D:100S"
    hi_frac = float((rep.final_map == DEFAULT_FORMATS.high).mean())
    assert 0.0 < hi_frac < 1.0


def test_solve_q_start_keeps_quiet_tiles_low8():
    """0D:80S:20Q start: fp8 tiles sit on the quietest tiles (norm_topk)
    and a useful share of them survives escalation."""
    a = graded_spd(128, cond=1e4, rho=0.8, seed=0)
    xt, b = rhs_for_solution(a, seed=1)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.0, ratio_low8=0.2,
                                  max_sweeps=30))
    _check_converged(rep, xt)
    fset = DEFAULT_FORMATS
    q_frac = float((rep.final_map == fset.low8).mean())
    assert q_frac > 0.05
    assert rep.storage_bytes < rep.uniform_high_bytes


def test_solve_d_start_converges():
    """20D:80S start: a fifth of the tiles HIGH from the outset still
    converges on the ill-conditioned operator with no mid-solve retune."""
    a = graded_spd(128, cond=1e4, rho=0.8, seed=0)
    xt, b = rhs_for_solution(a, seed=1)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.2, max_sweeps=40))
    _check_converged(rep, xt)
    assert rep.ratio_history[0] == "20D:80S"


def test_solve_cg_spd():
    a = graded_spd(96, cond=1e3, rho=0.85, seed=2)
    xt, b = rhs_for_solution(a, seed=3)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.0, method="cg",
                                  max_sweeps=40))
    _check_converged(rep, xt)
    assert rep.method == "cg"


def test_solve_multiple_rhs():
    a = graded_spd(64, cond=1e3, rho=0.9, seed=4)
    xt, b = rhs_for_solution(a, nrhs=3, seed=5)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.0, max_sweeps=30))
    assert rep.x.shape == (64, 3)
    _check_converged(rep, xt)


def test_solve_fp16_format_set():
    fs = format_set("fp16", "fp32")
    a = graded_spd(64, cond=1e3, rho=0.9, seed=6)
    xt, b = rhs_for_solution(a, seed=7)
    rep = solve(a, b, SolveConfig(tile=16, fset=fs, ratio_high=0.0,
                                  max_sweeps=30))
    _check_converged(rep, xt)


def test_solve_rejects_bad_shapes_and_methods():
    a = diag_dominant(64, seed=0)
    _, b = rhs_for_solution(a, seed=0)
    with pytest.raises(ValueError, match="square"):
        solve(a[:, :32], b, SolveConfig(tile=16))
    with pytest.raises(ValueError, match="unknown method"):
        solve(a, b, SolveConfig(tile=16, method="qr"))
    with pytest.raises(ValueError, match="balanced"):
        solve(a, b, SolveConfig(tile=16, summa_grid=(2, 2),
                                escalation="tile"))
    with pytest.raises(ValueError, match="nrhs_pad"):
        solve(a, b, SolveConfig(tile=16, nrhs_pad=24))
    with pytest.raises(ValueError, match="divide the tile-row"):
        solve(a, b, SolveConfig(tile=16, escalation="balanced",
                                balance_groups=3))   # mt=4 % 3 != 0


def test_balanced_ladder_maps_are_sorted_balanced():
    from repro.core.summa import _check_sorted_balanced
    fset = DEFAULT_FORMATS
    m = _balanced_map(8, 8, 2, 1, 2, fset)
    counts = _check_sorted_balanced(m, axis=0, groups=2, fset=fset)
    assert counts == {fset.low8: 1, fset.low: 1, fset.high: 2}


# ---------------------------------------------------------------------------
# Distributed (SUMMA-backed) variant
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_distributed_solution_bitwise_vs_single_device(host_grid_devices):
    """Single-device and 2×2-SUMMA solves walk bit-identical trajectories:
    the grouped local update is bitwise-equal to the single-device grouped
    path, everything else is the same deterministic code."""
    n = 64
    a = graded_spd(n, cond=1e4, rho=0.9, seed=0)
    xt, b = rhs_for_solution(a, seed=1)
    common = dict(tile=8, ratio_high=0.0, escalation="balanced",
                  balance_groups=2, local_path="grouped", nrhs_pad=16,
                  max_sweeps=25)
    rep_s = solve(a, b, SolveConfig(residual_path="grouped", **common))
    rep_d = solve(a, b, SolveConfig(summa_grid=(2, 2), **common))
    assert rep_s.converged and rep_d.converged
    assert rep_s.fresh_resolutions == 0 and rep_d.fresh_resolutions == 0
    assert rep_d.summa_recompiles == 0       # ladder pre-traced
    np.testing.assert_array_equal(rep_s.final_map, rep_d.final_map)
    np.testing.assert_array_equal(rep_s.x, rep_d.x)
    _check_converged(rep_d, xt)


def test_distributed_ref_path_matches_single_device(host_grid_devices):
    """The default (ref local path) distributed solve agrees with the
    single-device solve to fp32 accumulation noise and issues zero fresh
    resolutions under the prefetched summa plan keys."""
    n = 64
    a = graded_spd(n, cond=1e3, rho=0.9, seed=3)
    xt, b = rhs_for_solution(a, seed=4)
    common = dict(tile=8, ratio_high=0.0, escalation="balanced",
                  balance_groups=2, nrhs_pad=16, max_sweeps=25)
    rep_s = solve(a, b, SolveConfig(**common))
    rep_d = solve(a, b, SolveConfig(summa_grid=(2, 2), warm=False,
                                    **common))
    assert rep_d.converged and rep_d.fresh_resolutions == 0
    np.testing.assert_array_equal(rep_s.final_map, rep_d.final_map)
    assert float(np.abs(rep_s.x - rep_d.x).max() /
                 max(np.abs(rep_s.x).max(), 1e-30)) < 1e-3
    _check_converged(rep_d, xt)


def test_summa_grid_shape_validation(host_grid_devices):
    a = graded_spd(48, cond=1e3, rho=0.9, seed=0)   # 48 % (2·16) != 0
    _, b = rhs_for_solution(a, seed=0)
    with pytest.raises(ValueError, match="incompatible"):
        solve(a, b, SolveConfig(tile=16, summa_grid=(2, 2),
                                escalation="balanced"))


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_cli_ratio_parser():
    from repro.launch.solve import _parse_ratio
    assert _parse_ratio("0D:100S") == (0.0, 0.0)
    assert _parse_ratio("20D:70S:10Q") == (0.2, 0.1)
    with pytest.raises(ValueError, match="bad ratio"):
        _parse_ratio("20X:80S")


@pytest.mark.slow
def test_cli_end_to_end(tmp_path):
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["REPRO_TUNE_CACHE"] = str(tmp_path / "plans.json")
    env["PYTHONPATH"] = (os.path.join(root, "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.solve", "--n", "256",
         "--ratio", "0D:100S"],
        capture_output=True, text=True, timeout=900, env=env, cwd=root)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "converged=True" in r.stdout
    assert "mid-solve fresh resolutions 0" in r.stdout


def test_solve_report_fields_round_trip():
    a = diag_dominant(32, seed=0)
    _, b = rhs_for_solution(a, seed=0)
    rep = solve(a, b, SolveConfig(tile=16, ratio_high=0.5, max_sweeps=10))
    d = dataclasses.asdict(rep)
    for k in ("converged", "metric_history", "final_ratio", "gemm_fraction",
              "storage_bytes", "plan_keys", "fresh_resolutions"):
        assert k in d
    assert rep.plan_keys > 0
    assert 0.0 <= rep.gemm_fraction <= 1.0
